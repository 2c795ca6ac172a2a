"""Share of the traced window in which no op ran on the device, in %
(averaged over the chips used)."""


def read(trace, win):
    return 100.0 * (1.0 - trace.busy_s() / trace.window_s())
