"""90th percentile, over every submit in the window, of the wall time from
the client's submit to the return of the service pump that took it: the
job then has its schedule order (host clock)."""

from harness.driver import CODE_NAMES
from harness.stats import percentile

SUBMIT = CODE_NAMES.index("submit")


def read(run):
    return percentile((s.wall_ns * 1e-6 for s in run.steps
                       if s.code == SUBMIT), 90)
