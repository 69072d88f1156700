"""Offline builder: device scan launches of the jit placement backend per
build in the window (the program's jit.PROFILE["device_calls"])."""

from harness.driver import CODE_NAMES

SUBMIT = CODE_NAMES.index("submit")


def read(run):
    builds = sum(1 for s in run.steps if s.code == SUBMIT and s.miss)
    if not builds:
        return None
    return run.jit["device_calls"] / builds
