"""Virtual cluster seconds the served path advances per wall second, over
the whole window: at 1 or more the scheduler keeps real time with this
cluster (host clock)."""

from harness.stats import rate


def read(run):
    return rate(run.window_virtual_s, run.window_wall_s)
