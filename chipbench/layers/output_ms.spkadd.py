"""Device time of the engine ops staged ``spkadd.output`` (the canonical
gather, the hash compaction), per call, in ms."""

from chipbench import stages


def read(trace, win):
    return stages.per_step_ms(trace, win, "spkadd.output")
