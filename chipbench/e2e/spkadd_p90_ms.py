"""90th percentile of the time of one SpKAdd call (call to result ready),
over every call in the window, in milliseconds. Every traffic asks for at
least three calls (``min_steps``)."""
import statistics


def read(win) -> float:
    return statistics.quantiles(win.call_s, n=10, method="inclusive")[8] * 1e3
