"""Device time of the engine ops staged ``spkadd.plan`` (concatenation,
plan sort, permutation into plan order, step tables), per call, in ms."""

from chipbench import stages


def read(trace, win):
    return stages.per_step_ms(trace, win, "spkadd.plan")
