"""Offline builder: mean wall time of a submit that missed the schedule
cache, less the wave's host seconds inside the same pump (harness span and
the program's match_wave counter)."""

from harness.driver import CODE_NAMES
from harness.stats import mean

SUBMIT = CODE_NAMES.index("submit")


def read(run):
    return mean((s.wall_ns - s.wave_ns) * 1e-6 for s in run.steps
                if s.code == SUBMIT and s.miss)
