"""Reduce a ``jax.profiler`` trace to the numbers the per-layer readers take.

A trace holds one plane per device (``/device:TPU:<i>``) and host planes.
On a device plane the ``XLA Modules`` line holds one event per program run
and the ``XLA Ops`` line one event per HLO op, named by its HLO text. The
``Async XLA Ops`` line holds one event per asynchronous op, from its start
to its done, overlapping the ops that run meanwhile: of those only the
collectives are read (``async_op``), so that an exchange counts whichever
way the compiler schedules it; async copies and slices are left out. Each
op is given:

- its module: the program whose run covers its start (the generator's or
  the engine's);
- its class: ``sort`` (HLO sort), ``mosaic`` (a Pallas kernel, a
  ``tpu_custom_call``), ``collective`` (all-gather and the other
  cross-chip ops) or ``other``.

Host spans are the benchmark's own ``TraceAnnotation`` names (``gen``,
``engine_call``, ``block``). The traced window runs from the first span's
start to the last span's end; device busy time is the union of the
``XLA Ops`` intervals inside it, and each idle gap is named by the
innermost host span that covers it. A collective's exposed time is the
part of its interval in which no op of another class runs on its device.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
import re

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")

#: host spans the benchmark records around each step
SPAN_NAMES = ("gen", "engine_call", "block")

_DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
_COLLECTIVE = re.compile(r"all-gather|all-reduce|reduce-scatter|"
                         r"collective-permute|all-to-all")


class UnknownDevice(KeyError):
    """The device kind has no entry in ``peaks.json``."""


def load_peaks(kind: str, path: str = PEAKS) -> dict:
    """The published peaks of ``kind`` (``device_kind`` as JAX reports it).
    A kind missing from the table is an error, never a default."""
    with open(path) as f:
        table = json.load(f)
    if kind not in table:
        raise UnknownDevice(f"no peaks for device kind {kind!r} in {path}; "
                            f"known: {sorted(table)}")
    return table[kind]


@dataclasses.dataclass(frozen=True)
class Op:
    device: str
    name: str
    module: str
    cls: str
    start: int
    end: int
    async_op: bool = False


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: int
    end: int


def parse_op(text: str) -> tuple[str, str]:
    """``(name, opcode)`` of an op event, whose name is its HLO text
    (``%sort.0 = (s32[..], ..) sort(..), ..``)."""
    m = re.match(r"%?([^\s=]+) = (.*)$", text, re.S)
    if not m:
        return text, ""
    name, rest = m.group(1), m.group(2)
    if rest.startswith("("):  # a tuple type: skip to its closing paren
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.split(" ", 1)[1] if " " in rest else ""
    op = re.match(r"\s*([\w\-]+)\(", rest)
    return name, op.group(1) if op else ""


def classify(text: str) -> str:
    """``sort``, ``mosaic``, ``collective`` or ``other``, from an op's HLO
    text: a Mosaic kernel is a ``tpu_custom_call``; a collective is an
    all-gather, all-reduce, reduce-scatter, collective-permute or
    all-to-all, started, finished or fused."""
    name, opcode = parse_op(text)
    if opcode == "custom-call" and "tpu_custom_call" in text:
        return "mosaic"
    if _COLLECTIVE.search(opcode) or _COLLECTIVE.search(name):
        return "collective"
    if opcode == "sort":
        return "sort"
    return "other"


def _merge(ivs) -> list[list[int]]:
    """A union of ``(start, end)`` intervals as sorted disjoint intervals."""
    merged: list[list[int]] = []
    for s, e in sorted(ivs):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _overlap(a, b) -> int:
    """Length of the intersection of two merged interval lists."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        total += max(0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _module_name(raw: str) -> str:
    """``jit_spkadd_auto(123)`` -> ``jit_spkadd_auto``."""
    return re.sub(r"\(\d+\)$", "", raw).strip()


class Trace:
    """Device ops and host spans of one trace, with the window they span."""

    def __init__(self, ops: list[Op], spans: list[Span],
                 devices: list[str]):
        if not devices:
            raise ValueError("the trace holds no device plane")
        self.ops = ops
        self.spans = spans
        self.devices = devices
        if spans:
            self.window = (min(s.start for s in spans),
                           max(s.end for s in spans))
        elif ops:
            self.window = (min(o.start for o in ops), max(o.end for o in ops))
        else:
            raise ValueError("the trace holds neither host spans nor "
                             "device ops")

    # -- sums ---------------------------------------------------------------

    def window_s(self) -> float:
        lo, hi = self.window
        return (hi - lo) / 1e9

    def _select(self, device=None, module=None, cls=None, async_ops=False):
        """Ops in the window; ``async_ops`` adds the asynchronous ones."""
        lo, hi = self.window
        for o in self.ops:
            if o.end <= lo or o.start >= hi:
                continue
            if o.async_op and not async_ops:
                continue
            if device is not None and o.device != device:
                continue
            if module is not None and o.module != module:
                continue
            if cls is not None and o.cls != cls:
                continue
            yield o

    def _busy_intervals(self, device, module=None, cls=None,
                        async_ops=False):
        lo, hi = self.window
        return _merge((max(o.start, lo), min(o.end, hi))
                      for o in self._select(device, module, cls, async_ops))

    def busy_s(self, module=None, cls=None, async_ops=False) -> float:
        """Union of op intervals in the window, averaged over devices."""
        total = sum(e - s for d in self.devices
                    for s, e in self._busy_intervals(d, module, cls,
                                                     async_ops))
        return total / len(self.devices) / 1e9

    def exposed_s(self, cls: str) -> float:
        """Of the union of ``cls`` ops' intervals, asynchronous ones
        included, the part in which no op of another class runs on the same
        device, averaged over devices."""
        lo, hi = self.window
        total = 0
        for d in self.devices:
            mine = self._busy_intervals(d, cls=cls, async_ops=True)
            others = _merge((max(o.start, lo), min(o.end, hi))
                            for o in self._select(d) if o.cls != cls)
            total += sum(e - s for s, e in mine) - _overlap(mine, others)
        return total / len(self.devices) / 1e9

    def op_s(self, module=None, cls=None) -> float:
        """Sum of op durations in the window, averaged over devices."""
        lo, hi = self.window
        total = sum(min(o.end, hi) - max(o.start, lo)
                    for o in self._select(None, module, cls))
        return total / len(self.devices) / 1e9

    def count(self, module=None, cls=None, async_ops=False) -> int:
        return sum(1 for _ in self._select(None, module, cls, async_ops))

    # -- breakdown ----------------------------------------------------------

    def gaps(self) -> list[tuple[str, float]]:
        """Every idle gap of every device in the window, as (name of the
        innermost host span covering its midpoint, seconds), longest
        first."""
        lo, hi = self.window
        out = []
        for d in self.devices:
            edge = lo
            for s, e in self._busy_intervals(d) + [[hi, hi]]:
                if s > edge:
                    out.append((self._host_at((edge + s) // 2),
                                (s - edge) / 1e9))
                edge = max(edge, e)
        out.sort(key=lambda g: -g[1])
        return out

    def _host_at(self, t: int) -> str:
        covering = [s for s in self.spans if s.start <= t < s.end]
        if not covering:
            return "host"
        return min(covering, key=lambda s: s.end - s.start).name

    def top_ops(self, n: int = 10) -> list[tuple[str, float]]:
        """Op names by total device time in the window (averaged over
        devices), as ``module/op``."""
        lo, hi = self.window
        by: dict[str, int] = {}
        for o in self._select():
            key = f"{o.module}/{parse_op(o.name)[0]}"
            by[key] = by.get(key, 0) + min(o.end, hi) - max(o.start, lo)
        top = sorted(by.items(), key=lambda kv: -kv[1])[:n]
        return [(k, v / len(self.devices) / 1e9) for k, v in top]

    def breakdown(self, n: int = 10) -> dict:
        return {"device_ops": [list(x) for x in self.top_ops(n)],
                "idle_gaps": [list(x) for x in self.gaps()[:n]]}


def read_xspace(path: str, devices: list[str] | None = None) -> Trace:
    """Parse one ``.xplane.pb`` file; ``devices`` names the device planes
    to read (all by default), so that chips a cell leaves idle do not
    count."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: list[Op] = []
    spans: list[Span] = []
    found: list[str] = []
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name) and (devices is None
                                                or plane.name in devices):
            found.append(plane.name)
            lines = {line.name: list(line.events) for line in plane.lines}
            modules = sorted((int(e.start_ns), int(e.end_ns),
                              _module_name(e.name))
                             for e in lines.get("XLA Modules", []))
            events = ([(e, False) for e in lines.get("XLA Ops", [])]
                      + [(e, True) for e in lines.get("Async XLA Ops", [])])
            for e, async_op in events:
                cls = classify(e.name)
                if async_op and cls != "collective":
                    continue
                start, end = int(e.start_ns), int(e.end_ns)
                module = next((m for s, t, m in modules if s <= start < t),
                              "")
                ops.append(Op(plane.name, e.name, module, cls, start, end,
                              async_op))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in SPAN_NAMES:
                        spans.append(Span(e.name, int(e.start_ns),
                                          int(e.end_ns)))
    return Trace(ops, spans, sorted(found))


def find_xspace(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under a ``jax.profiler`` trace directory."""
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]
