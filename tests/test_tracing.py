"""Program spans (`kernels.span`) and what reads them.

A span is on exactly while a ``jax.profiler`` session records: it then
sits on the trace's host plane as ``repro.<key>`` (on the device ops'
clock) and counts (calls, seconds) under ``kernels.PROFILE[key]``; off,
it leaves ``PROFILE`` untouched.  The service pump and its silence scan,
thousands a second, are counted the same way (``kernels.clock`` /
``count``) without trace events of their own.  These tests record CPU
traces into ``tmp_path`` and read them back with
``jax.profiler.ProfileData``: the pump and the silence scan, the build
(tagged ``job=``), the two-shard eligibility fan-out (tagged ``wave=``,
its syncs on the shard threads), the builder's scan launches and syncs,
and one ``xla.load.<cache>`` per bucket-cache miss.  A traced served run
decides exactly as an untraced one.
"""

import contextlib
import glob
import json
import os
import sys

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

jax = pytest.importorskip("jax")

from repro.core import FaultPlan  # noqa: E402
from repro.core.dag import DAG  # noqa: E402
from repro.core.engine import jit as J  # noqa: E402
from repro.core.engine import kernels  # noqa: E402
from repro.sim import make_workload  # noqa: E402
from repro.sim.cluster import scheme  # noqa: E402
from repro.svc import (Client, ServiceConfig, connect,  # noqa: E402
                       run_service_workload)
from repro.svc.agent import VirtualAgent  # noqa: E402
from repro.svc.scheduler import SchedulerCore, SchedulerService  # noqa: E402

GOLDEN = os.path.join(os.path.dirname(__file__), "data",
                      "golden_service.json")
#: counted, no trace event of their own
COUNTED_KEYS = ("svc.pump", "svc.pump.wave", "svc.check_silence")
#: counted and on the trace as ``repro.<key>``
SPAN_KEYS = ("svc.build", "pool.refresh", "shard.eligibility",
             "machines_with_candidates.xla.sync", "jit.scan.launch",
             "jit.scan.sync")


@contextlib.contextmanager
def trace_into(path):
    """Record a CPU profiler trace, at the benchmark's tracer levels,
    inside one outer ``test.outer`` annotation."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("test.outer"):
            yield
    finally:
        jax.profiler.stop_trace()


def host_events(path) -> list[tuple]:
    """(line index, name, start ns, end ns, stats) of every host event."""
    from jax.profiler import ProfileData

    (xplane,) = glob.glob(os.path.join(str(path), "**", "*.xplane.pb"),
                          recursive=True)
    out = []
    for plane in ProfileData.from_file(xplane).planes:
        if not plane.name.startswith("/host:"):
            continue
        for li, line in enumerate(plane.lines):
            for ev in line.events:
                out.append((li, ev.name, ev.start_ns,
                            ev.start_ns + ev.duration_ns, dict(ev.stats)))
    return out


def profile_delta(before: dict) -> dict:
    after = kernels.profile_snapshot()
    return {k: (v[0] - before.get(k, (0, 0.0))[0],
                v[1] - before.get(k, (0, 0.0))[1])
            for k, v in after.items()
            if v[0] != before.get(k, (0, 0.0))[0]}


def inside(ev, outer) -> bool:
    return outer[2] <= ev[2] and ev[3] <= outer[3]


def parity_workload(n=6, seed=3, interarrival=25.0):
    """The population of ``tests/data/golden_service.json``."""
    dags = make_workload("production", n, seed=seed)
    rng = np.random.default_rng(0)
    arrivals, t = [], 0.0
    for i, dag in enumerate(dags):
        arrivals.append((t, dag, i % 2))
        t += float(rng.exponential(interarrival))
    return arrivals


@pytest.fixture
def device_path(monkeypatch):
    """Small clusters on the device paths the chip runs at 4,034
    machines: eligibility on xla, builder scans launched on the device."""
    monkeypatch.setenv(kernels.HEARTBEAT_MIN_M_ENV, "4")
    monkeypatch.setenv(kernels.KERNELS_ENV, "match_wave=numpy")
    monkeypatch.setattr(J, "MIN_DEVICE_G", 2)


def sharded_cfg() -> ServiceConfig:
    return ServiceConfig(n_machines=12, seed=0, matcher_shards=2,
                         placement_backend="jit")


# -- off -------------------------------------------------------------------

def test_span_off_leaves_profile_untouched():
    assert not kernels.recording()
    before = kernels.profile_snapshot()
    with kernels.span("test.off", job=1):
        pass
    assert kernels.span("a") is kernels.span("b")   # one shared no-op
    t0 = kernels.clock()
    assert t0 is None
    kernels.count("test.off", t0)
    assert kernels.profile_snapshot() == before


# -- on: counts, nesting, metadata, threads --------------------------------

def test_spans_count_nest_and_tag_on_the_trace(tmp_path, device_path):
    """A traced 2-shard served run on the device paths: every key
    counts, each span's ``repro.*`` event lies in the outer annotation
    (the pump and the silence scan leave none), scan spans nest in
    builds on the pump's thread, builds carry their job ids, eligibility
    fan-outs carry their wave index, and the per-shard syncs run on the
    shard threads inside their fan-out."""
    arrivals = parity_workload(n=3)
    before = kernels.profile_snapshot()
    with trace_into(tmp_path):
        run_service_workload(arrivals, sharded_cfg(), scheme("dagps"))
    prof = profile_delta(before)
    for key in COUNTED_KEYS + SPAN_KEYS:
        calls, secs = prof.get(key, (0, 0.0))
        assert calls > 0 and secs > 0.0, key
    # every pump scans for silence
    assert prof["svc.check_silence"][0] == \
        prof["svc.pump"][0] + prof["svc.pump.wave"][0]

    evs = host_events(tmp_path)
    outer = next(e for e in evs if e[1] == "test.outer")
    repro = [e for e in evs if e[1].startswith("repro.")]
    assert repro and all(inside(e, outer) for e in repro)
    by = {}
    for e in repro:
        by.setdefault(e[1], []).append(e)
    for key in COUNTED_KEYS:
        assert "repro." + key not in by, key
    for key in SPAN_KEYS:
        assert len(by["repro." + key]) == prof[key][0], key

    builds = by["repro.svc.build"]
    assert sorted(e[4]["job"] for e in builds) == list(range(len(arrivals)))
    for e in by["repro.jit.scan.launch"] + by["repro.jit.scan.sync"]:
        assert any(b[0] == e[0] and inside(e, b) for b in builds), e[1]

    fans = by["repro.shard.eligibility"]
    waves = [e[4]["wave"] for e in fans]
    assert waves == sorted(waves) and len(set(waves)) == len(waves)
    syncs = by["repro.machines_with_candidates.xla.sync"]
    assert len(syncs) == 2 * len(fans)
    fan_lines = {e[0] for e in fans}
    assert {e[0] for e in syncs}.isdisjoint(fan_lines)
    for e in syncs:
        assert any(inside(e, f) for f in fans)


def test_shard_threads_record_spans(tmp_path, monkeypatch):
    """Both shard-elig threads of one 2-shard wave record their sync."""
    from repro.core.online import MatcherConfig
    from repro.core.shard import ShardedMatcher

    monkeypatch.setenv(kernels.HEARTBEAT_MIN_M_ENV, "4")
    sm = ShardedMatcher(MatcherConfig(), 16, {0: 1.0}, n_shards=2)
    avail = np.ones((16, 4))
    dem = np.full((5, 4), 0.25)
    try:
        sm.eligibility(avail, dem)                   # compile untraced
        before = kernels.profile_snapshot()
        with trace_into(tmp_path):
            eligible, _any = sm.eligibility(avail, dem)
    finally:
        sm.close()
    assert eligible.shape == (5, 16) and eligible.all()
    prof = profile_delta(before)
    assert prof["shard.eligibility"][0] == 1
    assert prof["machines_with_candidates.xla.sync"][0] == 2
    assert prof["machines_with_candidates.xla"][0] == 2
    evs = host_events(tmp_path)
    (fan,) = [e for e in evs if e[1] == "repro.shard.eligibility"]
    assert fan[4] == {"wave": 0}
    syncs = [e for e in evs
             if e[1] == "repro.machines_with_candidates.xla.sync"]
    ops = [e for e in evs if e[1] == "repro.machines_with_candidates.xla"]
    assert len(syncs) == 2 and len(ops) == 2
    assert len({e[0] for e in syncs}) == 2 and fan[0] not in \
        {e[0] for e in syncs}
    for s in syncs:                      # each sync inside its op's span
        assert any(o[0] == s[0] and inside(s, o) for o in ops)


def test_pump_key_chosen_at_exit(tmp_path):
    """A pump that waved counts as ``svc.pump.wave``, any other as
    ``svc.pump`` (the key chosen as the pump ends)."""
    core = SchedulerCore(ServiceConfig(n_machines=4, seed=0),
                         scheme("dagps"))
    addr = "inproc://tracing-pump"
    svc = SchedulerService(core, addr, clock=lambda: 0.0)
    got = []
    try:
        for m in range(4):
            VirtualAgent(m, connect(addr), None,
                         clock=lambda: 0.0).register(0.0)
        client = Client(connect(addr), clock=lambda: 0.0)
        dag = DAG(duration=np.array([2.0, 3.0]),
                  demand=np.full((2, 4), 0.25),
                  stage_of=np.array([0, 1]),
                  parents=[np.empty(0, np.int64), np.array([0])])
        with trace_into(tmp_path):
            for act in ("register", "submit", "idle"):
                if act == "submit":
                    client.submit(dag, group=0, t=0.0)
                before = kernels.profile_snapshot()
                w0 = core.smatcher.waves
                svc.pump(0.0)
                got.append((core.smatcher.waves - w0,
                            sorted(k for k in profile_delta(before)
                                   if k.startswith("svc.pump"))))
    finally:
        svc.close()
    assert got == [(0, ["svc.pump"]), (1, ["svc.pump.wave"]),
                   (0, ["svc.pump"])]


# -- bucket-cache loads ----------------------------------------------------

def test_xla_load_span_once_per_miss(tmp_path):
    """One ``xla.load.<cache>`` per miss while a trace records, again
    after an eviction; hits and untraced misses record none, and the
    compile/eviction counters count as they did."""
    import jax.numpy as jnp

    built = []

    def build(n):
        built.append(n)
        return jax.jit(lambda x: x + n)

    cache = kernels._BucketCache(build, "test", cap=2)
    raw = cache.get((0,))
    assert raw is cache.get((0,)) and not kernels.recording()
    x = jnp.ones(3)
    c0, e0 = kernels.XLA_STATS["compiles"], kernels.XLA_STATS["evictions"]
    before = kernels.profile_snapshot()
    with trace_into(tmp_path):
        for n in (1, 1, 2, 3, 1):                  # miss hit miss miss* miss
            assert float(cache.get((n,))(x)[0]) == 1.0 + n
        fn = cache.get((1,))
    prof = profile_delta(before)
    assert built == [0, 1, 2, 3, 1]
    assert prof["xla.load.test"][0] == 4
    assert kernels.XLA_STATS["compiles"] - c0 == 4
    assert kernels.XLA_STATS["evictions"] - e0 == 3
    assert fn is cache.get((1,))                   # the raw function again
    assert float(fn(x)[0]) == 2.0
    evs = host_events(tmp_path)
    assert sum(e[1] == "repro.xla.load.test" for e in evs) == 4


# -- decisions -------------------------------------------------------------

def test_traced_golden_run_matches_the_golden(tmp_path):
    """The golden service population decides exactly the committed
    golden with a trace recording."""
    with trace_into(tmp_path):
        svc = run_service_workload(parity_workload(),
                                   ServiceConfig(n_machines=12, seed=0),
                                   scheme("dagps"), fault_plan=FaultPlan())
    golden = json.load(open(GOLDEN))
    assert [[repr(t), j, tk, m] for t, j, tk, m in svc.placements] == \
        golden["placements"]
    assert {str(j.job_id): repr(j.jct) for j in svc.jobs} == golden["jcts"]


def test_traced_sharded_device_run_decides_as_untraced(tmp_path,
                                                       device_path):
    arrivals = parity_workload(n=3)
    plain = run_service_workload(arrivals, sharded_cfg(), scheme("dagps"))
    with trace_into(tmp_path):
        traced = run_service_workload(arrivals, sharded_cfg(),
                                      scheme("dagps"))
    assert traced.placements == plain.placements
    assert sorted((j.job_id, repr(j.jct)) for j in traced.jobs) == \
        sorted((j.job_id, repr(j.jct)) for j in plain.jobs)
