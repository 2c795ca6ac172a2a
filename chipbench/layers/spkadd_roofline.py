"""SpKAdd's share of its roofline, in %, defined by the problem whatever
implements it.

The least bytes of one call are 8 for each input nonzero read (int32 key,
f32 value) and 8 for each output nonzero written; the least time is those
bytes over the chip's peak HBM bandwidth (``peaks.json``). SpKAdd does
about one add per input, far under the bandwidth's worth of operations, so
bandwidth alone bounds it. The share is the least time over the engine
program's device busy time, both summed over the traced calls.
"""


def least_bytes(in_nnz: int, out_nnz: int) -> int:
    return 8 * in_nnz + 8 * out_nnz


def read(trace, win):
    busy = trace.busy_s(module=win.modules["engine"])
    if busy <= 0:
        return None
    total = sum(least_bytes(int(i), int(o)) for i, o in
                zip(win.counts["in_nnz"], win.counts["out_nnz"]))
    return 100.0 * total / win.peaks["hbm_bytes_per_s"] / busy
