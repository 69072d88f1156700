"""Kernels: device microseconds per launch of the eligibility kernel (the
xla machines_with_candidates), from the trace's XLA modules.  MODULES maps
its module names to this kernel; none of them in the trace reads as no
value."""

MODULES = ("jit_elig",)


def read(run):
    if run.reduced is None:
        return None
    got = run.reduced.module_time(MODULES)
    if got is None or not got[0]:
        return None
    return got[1] / got[0] * 1e6
