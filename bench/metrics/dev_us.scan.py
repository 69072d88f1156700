"""Kernels: device microseconds per launch of the placement scan, from the
trace's XLA modules.  MODULES maps the module names the scan compiles to
(kernels.scan_fn_for) to this kernel; none of them in the trace reads as
no value."""

MODULES = ("jit_scan",)


def read(run):
    if run.reduced is None:
        return None
    got = run.reduced.module_time(MODULES)
    if got is None or not got[0]:
        return None
    return got[1] / got[0] * 1e6
