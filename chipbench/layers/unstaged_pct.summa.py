"""Share of the SUMMA program's op time in ops that carry no stage, in %:
the part of a multiply that no ``summa.*`` stage names in the trace."""

from chipbench import stages


def read(trace, win):
    return stages.unstaged_pct(trace, win)
