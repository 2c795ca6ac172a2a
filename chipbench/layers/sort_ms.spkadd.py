"""Device time of HLO sort ops in the engine's program, per call, in ms."""


def read(trace, win):
    if not trace.count(module=win.modules["engine"], cls="sort"):
        return None
    return trace.op_s(module=win.modules["engine"], cls="sort") / win.steps * 1e3
