"""Record the small profiler trace that the trace-reduction test reads.

    python3 bench/tests/record_trace.py <out.xplane.pb>

Runs, on the chip and under ``jax.profiler``, a few launches of the two
device programs the benchmark names (the placement scan at one bucket,
the eligibility op at 2,048 machines) inside the harness's spans
(``bench.window`` around it all, ``bench.submit`` and ``bench.beat``
around the launches), and copies the ``.xplane.pb`` to the path given.
"""

from __future__ import annotations

import shutil
import sys
import tempfile
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from harness import trace as trace_mod
    from repro.core.engine import kernels

    m, d, gb, Lb, Wb, Tb = 4, 4, 16, 224, 192, 768
    scan = kernels.scan_fn_for(m, d, gb, Lb, Wb, Tb)
    buf = jnp.ones((m, Tb, d), dtype=jnp.float32)
    Vs = np.full((gb, d), 0.5, dtype=np.float32)
    ks = np.full(gb, 3, dtype=np.int32)
    avail = np.ones((2048, d))
    dem = np.full((24, d), 0.1)
    dims = np.arange(d)

    def elig():
        return kernels.machines_with_candidates(avail, dem, dims, dims[:2],
                                                dims[2:], 0.25, True)

    np.asarray(scan(buf, np.int32(0), np.int32(200), Vs, ks))
    elig()
    tdir = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(tdir, profiler_options=opts)
    ann = jax.profiler.TraceAnnotation
    with ann(trace_mod.WINDOW_SPAN):
        for _ in range(5):
            with ann("bench.submit"):
                np.asarray(scan(buf, np.int32(0), np.int32(200), Vs, ks))
            with ann("bench.beat"):
                elig()
    jax.profiler.stop_trace()
    shutil.copy(trace_mod.find_xplane(tdir), out)
    shutil.rmtree(tdir, ignore_errors=True)
    r = trace_mod.reduce(out)
    print({"window_s": r.window_s, "busy_s": r.busy_s,
           "modules": r.modules, "idle_by_host": r.idle_by_host,
           "n_devices": r.n_devices})
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
