"""Service front: mean wall time of a heartbeat event (the agent's beat
sent, the service pump that followed) that ran no wave and took no
submit, over the window (harness span)."""

from harness.driver import CODE_NAMES
from harness.stats import mean

BEAT = CODE_NAMES.index("beat")


def read(run):
    return mean(s.wall_ns * 1e-3 for s in run.steps
                if s.code == BEAT and not s.waved)
