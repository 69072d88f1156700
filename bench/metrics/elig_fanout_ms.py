"""Heartbeat ops: mean wall milliseconds of a wave's eligibility fan-out,
one device launch per matcher shard on the shard threads, from the first
launch to the merged masks, over the traced part of the window (the
program's ``shard.eligibility`` span)."""

KEY = "shard.eligibility"


def read(run):
    calls, secs = run.prof.get(KEY, (0, 0.0))
    return secs / calls * 1e3 if calls else None
