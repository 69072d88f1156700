"""Heartbeat ops: host wall milliseconds per call of the device
eligibility op (the program's kernels.PROFILE entry; the op hands back
host arrays, so the call includes its device round trip)."""

KEY = "machines_with_candidates.xla"


def read(run):
    calls, secs = run.prof.get(KEY, (0, 0.0))
    return secs / calls * 1e3 if calls else None
