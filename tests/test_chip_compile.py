"""Compile rehearsals for one TPU v5e chip, without the chip.

The TPU compiler is installed with jaxlib and compiles for a described
v5e topology, so these tests catch what interpret mode hides — Mosaic's
tiling rules, programs the chip's compiler refuses — at no chip time.
Nothing runs; a pass says the kernels of the scheduler's device path
compile at real sizes, not that they are fast or right on the chip.

Only one process may load the TPU library at a time, and it keeps it
until it exits, so the topology is described inside a module fixture
(never at import), every rehearsal lives in this one file, and each
compile happens in the test process itself.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import jit as engine_jit
from repro.core.engine import kernels, wave
from repro.core.engine.batched import WINDOW0
from repro.kernels.placement_scan import kernel as ps_kernel

M_CLUSTER = 2048        # one matcher launch at the s10 cluster size
N_CANDIDATES = 512      # exposed candidates in one heartbeat wave
D = 4                   # resource dims of every workload generator
M_BUILD = 4             # build_machines of the s10 population


@pytest.fixture(scope="module")
def one_chip():
    """A SingleDeviceSharding on a described v5e chip (persistent
    compilation cache off: a compile for an absent chip is written to it
    but cannot be read back here)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    prev = jax.config.jax_enable_compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            try:
                topo = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except Exception as e:  # no TPU compiler in this install
                pytest.skip(f"no v5e:2x2 topology can be described: {e}")
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", prev)
            compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_pallas_scan_compiles_for_v5e(one_chip):
    """The Pallas feasibility scan at a builder-scale window goes through
    Mosaic (a ``tpu_custom_call``), not the interpreter."""
    g, W, kmax = 24, kernels.bucket(WINDOW0), kernels.LONG_K
    L = kernels.bucket(W + kmax)
    fn = jax.jit(lambda win, vs, ks, tl: ps_kernel.scan_bitmaps(
        win, vs, ks, tl, W, interpret=False))
    s = functools.partial(_spec, one_chip)
    compiled = fn.lower(s((M_BUILD, L, D), jnp.float32),
                        s((g, D), jnp.float32), s((g,), jnp.int32),
                        s((), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_pallas_heartbeat_eligible_compiles_for_v5e(one_chip):
    fn = jax.jit(functools.partial(ps_kernel.heartbeat_eligible,
                                   interpret=False))
    s = functools.partial(_spec, one_chip)
    thr = s((M_CLUSTER, D), jnp.float32)
    mask = s((D,), jnp.float32)
    compiled = fn.lower(s((N_CANDIDATES, D), jnp.float32), thr, thr,
                        mask, mask, mask).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_xla_scan_bucket_compiles_for_v5e(one_chip):
    """The jit backend's scan bucket for the first window of a session."""
    g = 24
    gb, Lb, Wb = kernels.scan_buckets(g, WINDOW0, kernels.LONG_K)
    Tb = engine_jit.DeviceGrid.alloc_len(256)
    fn = kernels.scan_fn_for(M_BUILD, D, gb, Lb, Wb, Tb)
    s = functools.partial(_spec, one_chip)
    compiled = fn.lower(s((M_BUILD, Tb, D), jnp.float32),
                        s((), jnp.int32), s((), jnp.int32),
                        s((gb, D), jnp.float32), s((gb,), jnp.int32)).compile()
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("g, W, kmax, span", [
    (24, WINDOW0, kernels.LONG_K, 256),     # a session's first window
    (48, 256, kernels.LONG_K, 3072),        # the widest ad-hoc build bucket
])
def test_resident_scan_program_code_is_small_on_v5e(one_chip, g, W, kmax,
                                                    span):
    """Every scan program a process reaches stays loaded on the chip, so
    its code must stay small: under 1 MiB (a per-row gather in the run
    ladder made 2-9 MB a program)."""
    gb, Lb, Wb = kernels.scan_buckets(g, W, kmax)
    Tb = engine_jit.DeviceGrid.alloc_len(span)
    fn = kernels.scan_fn_for(M_BUILD, D, gb, Lb, Wb, Tb)
    s = functools.partial(_spec, one_chip)
    compiled = fn.lower(s((M_BUILD, Tb, D), jnp.float32),
                        s((), jnp.int32), s((), jnp.int32),
                        s((gb, D), jnp.float32), s((gb,), jnp.int32)).compile()
    assert compiled.memory_analysis().generated_code_size_in_bytes < 1 << 20


def test_xla_machines_with_candidates_compiles_for_v5e(one_chip):
    fn = kernels._ELIG_FNS.get((D,))
    s = functools.partial(_spec, one_chip)
    thr = s((M_CLUSTER, D), jnp.float32)
    mask = s((D,), jnp.float32)
    compiled = fn.lower(s((N_CANDIDATES, D), jnp.float32), thr, thr,
                        mask, mask, mask).compile()
    assert compiled.memory_analysis() is not None


def test_xla_match_wave_compiles_for_v5e(one_chip):
    """The fused wave core in float64 (``jax.enable_x64``) at 2048
    machines: the chip's compiler takes the program; whether its emulated
    float64 reproduces the numpy picks bit for bit only a chip run says."""
    g_cap = 8
    s = functools.partial(_spec, one_chip)
    with jax.enable_x64(True):
        fn = wave._build_wave_fn(M_CLUSTER, D, N_CANDIDATES, g_cap, 4,
                                 True, True, True, True)
        f64, i32 = jnp.float64, jnp.int32
        n = N_CANDIDATES
        compiled = fn.lower(
            s((M_CLUSTER, D), f64), s((M_CLUSTER,), i32), s((n, D), f64),
            s((n,), f64), s((n,), f64), s((n,), i32), s((n,), i32),
            s((n,), np.bool_), s((2,), f64), s((g_cap,), f64),
            s((g_cap,), f64), s((D,), np.bool_), s((D,), np.bool_),
            s((D,), np.bool_), s((8,), f64)).compile()
    assert compiled.memory_analysis() is not None
