"""SpKAdd's share of its roofline over two-word keys, in %, defined by the
problem whatever implements it.

As ``spkadd_roofline``, with a key past int32 held as two int32 words: the
least bytes of one call are 12 for each input nonzero read (column and row
words, f32 value) and 12 for each output nonzero written; the least time is
those bytes over the chip's peak HBM bandwidth (``peaks.json``), and the
share is that time over the engine program's device busy time, both summed
over the traced calls.
"""


def least_bytes(in_nnz: int, out_nnz: int) -> int:
    return 12 * in_nnz + 12 * out_nnz


def read(trace, win):
    busy = trace.busy_s(module=win.modules["engine"])
    if busy <= 0:
        return None
    total = sum(least_bytes(int(i), int(o)) for i, o in
                zip(win.counts["in_nnz"], win.counts["out_nnz"]))
    return 100.0 * total / win.peaks["hbm_bytes_per_s"] / busy
