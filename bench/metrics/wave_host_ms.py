"""Heartbeat ops: host wall milliseconds per numpy wave (pool refresh
excluded; eligibility calls included), from the program's kernels.PROFILE
entry."""

KEY = "match_wave.numpy"


def read(run):
    calls, secs = run.prof.get(KEY, (0, 0.0))
    return secs / calls * 1e3 if calls else None
