"""Residency of the placement scan's compiled programs.

The scan (``kernels.scan_fn_for``) and the device grid's slab writer
(``jit._update_fn``) keep every program they build: their keys sit on the
program's own bucket ladders, so no count cap applies and a key reached
once is never traced, compiled or loaded again.  The LRU cap
(``kernels.BUCKET_CAP``) still bounds the eligibility and wave caches,
whose bound is tested beside ``_BucketCache`` itself.
"""

import numpy as np
import pytest

from repro.core import build_schedule
from repro.core.engine import kernels

jax = pytest.importorskip("jax")

from repro.core.engine import jit as J  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class _CompileCount:
    """Backend compiles (or persistent-cache loads) seen by jax.monitoring."""

    def __init__(self):
        self.n = 0

    def __call__(self, event: str, _secs: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            self.n += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self)


def _assert_resident(get, keys, call_first):
    """Fetch every key, then re-fetch each: same function, no build, no
    eviction; the first key's compiled program runs again uncompiled."""
    fns = [get(*k) for k in keys[:1]]
    call_first(fns[0])                      # compile one program
    fns += [get(*k) for k in keys[1:]]
    c0 = kernels.XLA_STATS["compiles"]
    e0 = kernels.XLA_STATS["evictions"]
    assert all(get(*k) is fn for k, fn in zip(keys, fns))
    assert kernels.XLA_STATS["compiles"] == c0
    assert kernels.XLA_STATS["evictions"] == e0
    with _CompileCount() as cc:
        call_first(get(*keys[0]))
    assert cc.n == 0, "a resident program compiled again"


def test_scan_programs_stay_resident_past_the_cap():
    """More distinct scan buckets than ``BUCKET_CAP``: none is dropped."""
    m, d = 1, 1
    Tb = J.DeviceGrid.alloc_len(0)
    _gb, Lb, Wb = kernels.scan_buckets(8, 8, kernels.SHORT_K)
    keys = [(m, d, 8 * (i + 1), Lb, Wb, Tb)
            for i in range(kernels.BUCKET_CAP + 8)]
    buf = jax.numpy.ones((m, Tb, d), dtype=jax.numpy.float32)

    def call_first(fn):
        gb = keys[0][2]
        out = fn(buf, np.int32(0), np.int32(16),
                 np.full((gb, d), 0.5, dtype=np.float32),
                 np.ones(gb, dtype=np.int32))
        assert np.asarray(out).shape == (gb, Wb, m)

    _assert_resident(kernels.scan_fn_for, keys, call_first)


def test_slab_writers_stay_resident_past_the_cap():
    """More distinct slab-writer buckets than ``BUCKET_CAP``: none is
    dropped."""
    m, d, Tb = 1, 1, J.DeviceGrid.alloc_len(0)
    keys = [(m, Sb, d, Tb) for Sb in range(1, kernels.BUCKET_CAP + 9)]

    def call_first(fn):
        buf = jax.numpy.zeros((m, Tb, d), dtype=jax.numpy.float32)
        out = fn(buf, np.ones((m, 1, d), dtype=np.float32), np.int32(3))
        assert float(np.asarray(out)[0, 3, 0]) == 1.0

    _assert_resident(J._update_fn, keys, call_first)


def test_build_stream_past_the_cap_compiles_nothing_twice(monkeypatch):
    """A stream of jit builds reaching more scan buckets than
    ``BUCKET_CAP`` (three cluster sizes, several grid spans), replayed: the
    second pass makes no executable and drops no bucket, and every build
    equals the ``batched`` backend's bit for bit."""
    from repro.sim.workload import production_dag

    monkeypatch.setattr(J, "MIN_DEVICE_G", 2)
    stream = [(production_dag(np.random.default_rng(seed), scale=0.35,
                              share=m), m)
              for seed, m in ((2, 2), (0, 3), (2, 3), (2, 4))]
    reached = set()
    get = kernels._SCAN_FNS.get

    def recording_get(key):
        reached.add(key)
        return get(key)

    monkeypatch.setattr(kernels._SCAN_FNS, "get", recording_get)
    first = [build_schedule(dag, m, backend="jit") for dag, m in stream]
    assert len(reached) > kernels.BUCKET_CAP
    e0 = kernels.XLA_STATS["evictions"]
    with _CompileCount() as cc:
        again = [build_schedule(dag, m, backend="jit") for dag, m in stream]
    assert cc.n == 0, f"{cc.n} executables made on the replay"
    assert kernels.XLA_STATS["evictions"] == e0
    for (dag, m), a, b in zip(stream, first, again):
        ref = build_schedule(dag, m, backend="batched")
        for got in (a, b):
            assert got.makespan == ref.makespan
            assert np.array_equal(got.start, ref.start)
            assert np.array_equal(got.machine, ref.machine)
