"""Offline builder: wall milliseconds per build in which the builder
waits for device scan results (first use of each launch's rows), over
the traced part of the window: the program's ``jit.scan.sync`` seconds
over its ``svc.build`` calls, both recorded only while the trace
records."""

KEY = "jit.scan.sync"
BUILD = "svc.build"


def read(run):
    builds = run.prof.get(BUILD, (0, 0.0))[0]
    calls, secs = run.prof.get(KEY, (0, 0.0))
    return secs / builds * 1e3 if builds and calls else None
