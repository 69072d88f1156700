"""Heartbeat ops: mean wall milliseconds of the task pool's refresh that
opens each wave (the candidate batch), over the traced part of the window
(the program's ``pool.refresh`` span)."""

KEY = "pool.refresh"


def read(run):
    calls, secs = run.prof.get(KEY, (0, 0.0))
    return secs / calls * 1e3 if calls else None
