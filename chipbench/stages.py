"""Device time by program stage, read from the ops' HLO text.

The program names its device work with ``obs.stage``: every op traced
inside a stage carries the XLA frontend attribute ``obs_stage`` with the
stage's path, the names of the enclosing stages joined with ``/``
(``summa.reduce/spkadd.plan``). The attribute is part of the op's HLO
text, which is the name of its event in the device trace
(``frontend_attributes={obs_stage="spkadd.plan"}``), so the stage of each
op is read from ``Op.name``. An op the compiler made without carrying the
attribute over has no stage; so has every op of a program that names no
stages.

Times are those of a module's synchronous ops (``XLA Ops``), clipped to
the trace window and averaged over the chips, as ``Trace.op_s`` sums
them.
"""
from __future__ import annotations

import re

_STAGE = re.compile(r'obs_stage="([^"]*)"')


def stage_of(text: str) -> str | None:
    """The stage path of an op, from its HLO text; None if it has none."""
    m = _STAGE.search(text)
    return m.group(1) if m else None


def _ops(trace, module: str):
    lo, hi = trace.window
    for o in trace.ops:
        if o.module == module and not o.async_op and o.end > lo \
                and o.start < hi:
            yield o, min(o.end, hi) - max(o.start, lo)


def staged(trace, module: str) -> bool:
    """Whether any op of ``module`` in the window carries a stage."""
    return any(stage_of(o.name) is not None for o, _ in _ops(trace, module))


def stage_s(trace, module: str, component: str | None) -> float:
    """Seconds of ``module``'s ops whose stage path has ``component``
    (``None``: the ops that carry no stage), averaged over the chips."""
    total = 0
    for o, dur in _ops(trace, module):
        path = stage_of(o.name)
        if (path is None if component is None
                else path is not None and component in path.split("/")):
            total += dur
    return total / len(trace.devices) / 1e9


def per_step_ms(trace, win, component: str) -> float | None:
    """Device time of ``component`` in the engine module, per step, in ms;
    None where no op of that module carries a stage."""
    module = win.modules["engine"]
    if not staged(trace, module):
        return None
    return stage_s(trace, module, component) / win.steps * 1e3


def unstaged_pct(trace, win) -> float | None:
    """Share of the engine module's op time in ops that carry no stage, in
    %; None where no op of that module carries one."""
    module = win.modules["engine"]
    if not staged(trace, module):
        return None
    return 100.0 * stage_s(trace, module, None) / trace.op_s(module=module)
