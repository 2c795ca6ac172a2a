"""Device time of the engine ops staged ``spkadd.accumulate`` (the Pallas
launch or the ``spa`` scatter; the whole ``sorted`` or ``tree`` call), per
call, in ms."""

from chipbench import stages


def read(trace, win):
    return stages.per_step_ms(trace, win, "spkadd.accumulate")
