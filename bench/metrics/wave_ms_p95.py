"""95th percentile, over every wave in the window, of the wall time from
the event whose message triggered the wave to the return of the pump that
sent its grants (host clock)."""

from harness.stats import percentile


def read(run):
    return percentile((s.wall_ns * 1e-6 for s in run.steps if s.waved), 95)
