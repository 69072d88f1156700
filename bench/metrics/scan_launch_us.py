"""Offline builder: mean wall microseconds of a device scan launch of the
jit placement backend (the grid slab's upload and the enqueue), over the
traced part of the window (the program's ``jit.scan.launch`` span)."""

KEY = "jit.scan.launch"


def read(run):
    calls, secs = run.prof.get(KEY, (0, 0.0))
    return secs / calls * 1e6 if calls else None
