"""Device time of Mosaic kernels (Pallas ``tpu_custom_call``) in the
engine's program, per call, in ms."""


def read(trace, win):
    if not trace.count(module=win.modules["engine"], cls="mosaic"):
        return None
    return trace.op_s(module=win.modules["engine"], cls="mosaic") / win.steps * 1e3
