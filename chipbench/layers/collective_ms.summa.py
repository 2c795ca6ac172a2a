"""Device time of collectives (all-gather and the other cross-chip ops) per
multiply, in ms (averaged over the chips used): the union of their
intervals, asynchronous ones from start to done included."""


def read(trace, win):
    if not trace.count(cls="collective", async_ops=True):
        return None
    return trace.busy_s(cls="collective", async_ops=True) / win.steps * 1e3
