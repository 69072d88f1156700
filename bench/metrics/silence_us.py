"""Service front: mean wall microseconds of the silence scan that every
pump runs (suspicion and loss of silent machines), over the traced part
of the window (the program's ``svc.check_silence`` counter)."""

KEY = "svc.check_silence"


def read(run):
    calls, secs = run.prof.get(KEY, (0, 0.0))
    return secs / calls * 1e6 if calls else None
