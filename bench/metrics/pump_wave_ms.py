"""Heartbeat ops: mean wall milliseconds of a service pump that ran a
wave (drain, settle, silence scan, pool refresh, wave, pushes), over the
traced part of the window (the program's ``svc.pump.wave`` counter)."""

KEY = "svc.pump.wave"


def read(run):
    calls, secs = run.prof.get(KEY, (0, 0.0))
    return secs / calls * 1e3 if calls else None
