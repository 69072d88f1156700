"""Offline builder: wall milliseconds per build spent making compiled
programs that the bounded bucket caches had dropped or never held
(tracing, then compiling or loading from the persistent cache), over the
traced part of the window: the program's ``xla.load.*`` seconds over its
``svc.build`` calls.  A traced part with builds and no load reads 0."""

PREFIX = "xla.load."
BUILD = "svc.build"


def read(run):
    builds = run.prof.get(BUILD, (0, 0.0))[0]
    if not builds:
        return None
    secs = sum(v[1] for k, v in run.prof.items() if k.startswith(PREFIX))
    return secs / builds * 1e3
