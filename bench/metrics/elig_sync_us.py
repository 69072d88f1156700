"""Heartbeat ops: mean wall microseconds in which a shard's eligibility
call waits for its device result and copies it to the host, over the
traced part of the window (the program's
``machines_with_candidates.xla.sync`` span)."""

KEY = "machines_with_candidates.xla.sync"


def read(run):
    calls, secs = run.prof.get(KEY, (0, 0.0))
    return secs / calls * 1e6 if calls else None
