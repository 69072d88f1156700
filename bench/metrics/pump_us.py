"""Service front: mean wall microseconds of a service pump that ran no
wave, over the traced part of the window (the program's ``svc.pump``
counter, which counts only while the trace records)."""

KEY = "svc.pump"


def read(run):
    calls, secs = run.prof.get(KEY, (0, 0.0))
    return secs / calls * 1e6 if calls else None
