"""Set-up: compile every device shape the cell can reach before the window.

The shapes come from the traffic mix's ``warm`` section, in the
traffic's own terms (candidate counts per wave, stage widths, scan
windows and durations in ticks, grid spans, slab lengths), and are
mapped to the program's compile buckets by the program's own bucket
functions, so that the warm-up follows the program's buckets.
"""

from __future__ import annotations

import numpy as np


def eligibility(cfg, n_max: int) -> int:
    """Every candidate count 1..n_max on every matcher shard size, through
    the program's eligibility op.  Returns the calls made."""
    from repro.core.engine import kernels
    from repro.core.shard import ShardPlan

    plan = ShardPlan(cfg.n_machines, cfg.matcher_shards)
    dims = np.arange(cfg.d)
    calls = 0
    for m in sorted({int(s) for s in plan.sizes}):
        avail = np.ones((m, cfg.d))
        for n in range(1, n_max + 1):
            kernels.machines_with_candidates(
                avail, np.full((n, cfg.d), 0.1), dims, dims[:2], dims[2:],
                0.25, True)
            calls += 1
    return calls


def scans(m: int, d: int, w: dict) -> int:
    """The placement scan and the device grid's slab writer at every
    bucket the ranges in ``w`` reach.  Returns the programs run."""
    import jax.numpy as jnp

    from repro.core.engine import jit, kernels

    tbs = sorted({jit.DeviceGrid.alloc_len(s)
                  for s in range(w["grid_span"][0], w["grid_span"][1] + 1)})
    keys = set()
    for g in range(w["stage_width"][0], w["stage_width"][1] + 1):
        gb = kernels.pad8(g)
        for W in w["scan_window"]:
            for kmax in w["scan_kmax"]:
                keys.add(kernels.scan_buckets(gb, W, kmax))
    runs = 0
    for Tb in tbs:
        buf = jnp.ones((m, Tb, d), dtype=jnp.float32)
        for gb, Lb, Wb in sorted(keys):
            if Lb > Tb:
                continue
            Vs = np.full((gb, d), 2.0, dtype=np.float32)
            ks = np.ones(gb, dtype=np.int32)
            np.asarray(kernels.scan_fn_for(m, d, gb, Lb, Wb, Tb)(
                buf, np.int32(0), np.int32(16), Vs, ks))
            runs += 1
        update_fn = getattr(jit, "_update_fn", None)
        if update_fn is None:
            continue
        sbs = sorted({kernels.bucket(s) for s in range(1, w["slab_max"] + 1)})
        for Sb in sbs:
            if Sb > Tb:
                continue
            slab = np.ones((m, Sb, d), dtype=np.float32)
            buf = update_fn(m, Sb, d, Tb)(buf, slab, np.int32(0))
            runs += 1
        buf.block_until_ready()
    return runs
