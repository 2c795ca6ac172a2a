"""Spans time host work; stages name device work.

A span is a context manager that records a monotonic start time, a
duration, a nesting depth/parent, and free-form key/value attributes:

    with obs.span("engine.spkadd_auto", k=8, selected="vec") as sp:
        ...
        sp.set_attr("parts", geom.parts)

Spans are recorded **only while observability is enabled** (the
``SPKADD_OBS`` env var, overridable per-process via :func:`set_enabled`).
Disabled, :func:`span` returns a shared no-op context — no timestamp, no
allocation of note, and (critically) no jit-traced ops ever: spans live
entirely on the host, so enabling or disabling them cannot perturb lowered
HLO (``tests/test_obs.py`` pins this).

A span times the host work it wraps, and while a jax profiler is
importable it also enters a ``jax.profiler.TraceAnnotation`` of the same
name, so that work sits on the profiler's host timeline. Under
``jax.jit`` the wrapped Python runs only while jax traces the function:
such a span times that tracing, once per compile, and never fires in a
steady run of the compiled program. An eager caller's spans (a dispatch
decision, a stream flush) fire on every call.

A **stage** names device work instead: ``with obs.stage("spkadd.plan"):``
enters ``jax.named_scope`` and writes the XLA frontend attribute
``obs_stage`` on every op traced inside it. The attribute survives
compilation and appears in each op's HLO text in a device trace, so the
benchmark reads each stage's device time from the trace. A stage adds no
op — the compiled program differs only in attributes, metadata and
instruction names — so stages are always on. Nested stages join their
names with ``/`` (``summa.reduce/spkadd.plan``); names come from the
closed set :data:`STAGES`. Where XLA fuses ops of two stages into one,
the fused op carries the stage of its root; an op that a compiler pass
makes anew without copying the attribute carries none (on a TPU: the
cumsum's reduce-windows, and the sorts, gathers and dots that the
partitioner re-creates inside ``shard_map``).

Export: :func:`export_jsonl` writes one JSON object per finished span —
``{"name", "t_ns", "dur_ns", "depth", "parent", "attrs"}`` — the schema
:func:`read_jsonl` round-trips. Setting ``SPKADD_OBS_JSONL=<path>``
registers an atexit hook that exports whatever was recorded, which is how
CI captures a trace artifact from a benchmark subprocess without the
benchmark knowing about tracing.

Thread-safety note: the finished-span list is append-only under a lock;
the span and stage *nesting stacks* are thread-local, so spans and stages
opened on different threads get independent chains.
"""
from __future__ import annotations

import atexit
import contextlib
import json
import os
import threading
import time
from typing import Any, Dict, Iterator, List, Optional

#: Master switch: any value other than ""/"0"/"false"/"off" enables spans.
OBS_ENV = "SPKADD_OBS"

#: When set (and observability is enabled), finished spans are exported to
#: this path at interpreter exit.
OBS_JSONL_ENV = "SPKADD_OBS_JSONL"

#: The closed set of stage names. A name must never hold a word that the
#: benchmark's op classes match in HLO text (``all-gather``,
#: ``tpu_custom_call``, ...).
STAGES = (
    "spkadd.plan",        # concatenation, plan sort, permutation, step tables
    "spkadd.accumulate",  # the Pallas launch or scatter; sorted/tree whole
    "spkadd.output",      # canonical gather, hash compaction
    "summa.gather",       # the all-gathers of A's and B's stripes
    "summa.multiply",     # the local multiply of each stage
    "summa.from_dense",   # sparsifying each partial product
    "summa.reduce",       # SpKAdd of the partials and to_dense
)

#: The XLA frontend attribute a stage writes on its ops.
STAGE_ATTR = "obs_stage"

_override: Optional[bool] = None
_lock = threading.Lock()
_finished: List[Dict[str, Any]] = []
_tls = threading.local()


def enabled() -> bool:
    """Is span recording on? Process override beats the env var."""
    if _override is not None:
        return _override
    return os.environ.get(OBS_ENV, "").lower() not in ("", "0", "false", "off")


def set_enabled(on: Optional[bool]) -> None:
    """Force spans on/off for this process; ``None`` defers to the env."""
    global _override
    _override = on


def _stack(attr: str = "stack") -> list:
    st = getattr(_tls, attr, None)
    if st is None:
        st = []
        setattr(_tls, attr, st)
    return st


class Span:
    """A live span. ``set_attr`` adds/overwrites attributes until exit."""

    __slots__ = ("name", "attrs", "_t0", "_depth", "_parent", "_ann")

    def __init__(self, name: str, attrs: Dict[str, Any]):
        self.name = name
        self.attrs = attrs
        self._t0 = 0
        self._depth = 0
        self._parent: Optional[str] = None
        self._ann = None

    def set_attr(self, key: str, value: Any) -> None:
        self.attrs[key] = value

    def __enter__(self) -> "Span":
        st = _stack()
        self._depth = len(st)
        self._parent = st[-1].name if st else None
        st.append(self)
        self._t0 = time.monotonic_ns()
        ann = _trace_annotation(self.name)
        if ann is not None:
            ann.__enter__()
            self._ann = ann
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        dur = time.monotonic_ns() - self._t0
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
            self._ann = None
        st = _stack()
        if st and st[-1] is self:
            st.pop()
        with _lock:
            _finished.append({
                "name": self.name,
                "t_ns": self._t0,
                "dur_ns": dur,
                "depth": self._depth,
                "parent": self._parent,
                "attrs": dict(self.attrs),
            })


class _NullSpan:
    """Shared no-op span for the disabled path."""

    __slots__ = ()
    name = ""
    attrs: Dict[str, Any] = {}

    def set_attr(self, key: str, value: Any) -> None:
        pass

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        pass


_NULL = _NullSpan()


def _trace_annotation(name: str):
    """A ``jax.profiler.TraceAnnotation`` when jax is importable, else None.
    Lazy so obs stays importable without jax (ledger tooling, CI scripts)."""
    try:
        from jax.profiler import TraceAnnotation
    except Exception:
        return None
    return TraceAnnotation(name)


def span(name: str, **attrs: Any):
    """Open a span (context manager). No-op (shared instance) when disabled.

    Attribute values should be JSON-representable scalars; anything else is
    stringified at export.
    """
    if not enabled():
        return _NULL
    return Span(name, dict(attrs))


@contextlib.contextmanager
def stage(name: str) -> Iterator[None]:
    """Name the device work traced inside: ``jax.named_scope(name)`` and
    the frontend attribute ``obs_stage`` set to the path of the enclosing
    stages joined with ``/``. An unknown name raises ``ValueError``."""
    if name not in STAGES:
        raise ValueError(f"unknown stage {name!r}; known: {STAGES}")
    import jax
    from repro.compat import set_xla_metadata

    st = _stack("stages")
    st.append(name)
    try:
        with jax.named_scope(name), \
                set_xla_metadata(**{STAGE_ATTR: "/".join(st)}):
            yield
    finally:
        st.pop()


def spans() -> List[Dict[str, Any]]:
    """Copies of every finished span so far (record order)."""
    with _lock:
        return [dict(s) for s in _finished]


def clear() -> None:
    """Drop all finished spans (the nesting stack is untouched)."""
    with _lock:
        _finished.clear()


def _jsonable(v: Any) -> Any:
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    if isinstance(v, dict):
        return {str(k): _jsonable(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_jsonable(x) for x in v]
    try:  # numpy / jax scalars
        return v.item()
    except Exception:
        return str(v)


def export_jsonl(path: str) -> int:
    """Write finished spans as JSONL; returns the number written."""
    recs = spans()
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        for r in recs:
            f.write(json.dumps(_jsonable(r), sort_keys=True) + "\n")
    return len(recs)


def read_jsonl(path: str) -> List[Dict[str, Any]]:
    """Round-trip reader for :func:`export_jsonl` output."""
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                out.append(json.loads(line))
    return out


def _atexit_export() -> None:
    path = os.environ.get(OBS_JSONL_ENV)
    if path and enabled() and _finished:
        try:
            n = export_jsonl(path)
            print(f"[obs] exported {n} spans to {path}", flush=True)
        except OSError:
            pass


atexit.register(_atexit_export)
