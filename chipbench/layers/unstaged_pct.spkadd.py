"""Share of the engine program's op time in ops that carry no stage, in %:
what the stage metrics of the engine cannot place."""

from chipbench import stages


def read(trace, win):
    return stages.unstaged_pct(trace, win)
