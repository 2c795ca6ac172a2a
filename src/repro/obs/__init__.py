"""repro.obs — zero-dependency observability: spans, metrics, perf ledger.

Three layers (DESIGN.md §9):

- :mod:`repro.obs.trace` — context-manager **spans** that time host work
  (monotonic timings and attributes, gated by the ``SPKADD_OBS`` env
  switch, no-op and HLO-invariant when off, JSONL-exportable, entering a
  ``jax.profiler.TraceAnnotation`` so the host work sits on the
  profiler's clock), and **stages** that name device work: an always-on
  XLA frontend attribute (``obs_stage``) on every op traced inside, which
  adds no op and which a device trace shows per op.
- :mod:`repro.obs.metrics` — always-on named **counters/gauges/histograms**
  with snapshot/reset semantics; the common surface that absorbed the old
  ad-hoc module globals (``sparse.sort_calls`` et al.).
- :mod:`repro.obs.ledger` — the committed **perf-history ledger** under
  ``results/history/`` keyed by (commit, backend, suite, geometry), plus
  the rolling-baseline regression gate CI runs
  (``scripts/perf_fleet.py`` / ``scripts/bench_report.py``).

The convenience re-exports below are the instrumentation API the rest of
the codebase uses: ``obs.span(...)``, ``obs.stage(...)``,
``obs.counter(...)``, etc.
"""
from repro.obs.trace import (OBS_ENV, OBS_JSONL_ENV, STAGES, enabled,
                             set_enabled, span, stage, spans, clear,
                             export_jsonl, read_jsonl)
from repro.obs.metrics import (counter, gauge, histogram, snapshot, reset)

__all__ = [
    "OBS_ENV", "OBS_JSONL_ENV", "STAGES", "enabled", "set_enabled", "span",
    "stage", "spans",
    "clear", "export_jsonl", "read_jsonl",
    "counter", "gauge", "histogram", "snapshot", "reset",
]
