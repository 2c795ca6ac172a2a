"""The part of the collectives' device time in which no other op runs on
the same chip, per multiply, in ms (averaged over the chips used): what the
exchange adds to a multiply, where ``collective_ms.summa`` also counts what
compute hides."""


def read(trace, win):
    if not trace.count(cls="collective", async_ops=True):
        return None
    return trace.exposed_s("collective") / win.steps * 1e3
