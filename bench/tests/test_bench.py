"""Tests of the benchmark harness, on the CPU at tiny sizes.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests

Each cell is rehearsed end to end through `harness.cell.run` (the command
itself refuses a CPU, which one test checks), with the cluster cut to a
few dozen machines, the eligibility op on its device implementation and
the wave on numpy, as on the chip.  The fault tests break the timed path
underneath a run and see ``correct`` come out false.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# small clusters take the device eligibility op, as 2,048 machines do on
# the chip; the wave stays on numpy, as the program keeps it on a TPU
os.environ.setdefault("REPRO_HEARTBEAT_DEVICE_MIN_M", "8")
os.environ.setdefault("REPRO_KERNELS", "match_wave=numpy")

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

from harness import cell, check, stats, trace  # noqa: E402
from harness.driver import CODE_NAMES, Step  # noqa: E402

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
ADHOC, RECURRING = "m4034-prod.adhoc", "m4034-prod.recurring"
SEED = 3_000_000_017   # above 2**31, as the seeds the benchmark is given


def tiny(workload: str, machines: int = 48) -> tuple[dict, dict]:
    """The cell's configuration and mix cut for a CPU rehearsal: a few
    dozen machines, half-size jobs, a small warm range."""
    _, _, config, mix = cell.spec(workload)
    cfg = {"service": dict(config["service"], n_machines=machines),
           "guarantees": dict(config["guarantees"], n_machines=machines),
           "job_scale": 0.5}
    warm = dict(mix["warm"], candidates_max=4)
    if "grid_span" in warm:
        warm.update(stage_width=[1, 8], grid_span=[320, 330], slab_max=64)
    return cfg, {"warm": warm}


def run_tiny(workload: str, seconds: float = 2.0, trace_on: bool = False,
             **kw) -> dict:
    cfg, mix = tiny(workload)
    return cell.run(workload, SEED, seconds, trace_on, require_tpu=False,
                    config_override=cfg, mix_override=mix,
                    log=lambda _l: None, **kw)


@pytest.mark.parametrize("workload", [ADHOC, RECURRING])
def test_cell_rehearsal(workload):
    res = run_tiny(workload)
    assert set(res) == CONTRACT_KEYS | {"checks"}
    assert list(res)[-1] == "checks"
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    want = {m["name"] for m in cell.metrics_for(bench, workload, False)}
    assert set(res["metrics"]) == want
    for m in res["metrics"].values():
        assert m["value"] > 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= \
        set(res["device"])


def test_traced_rehearsal_keys():
    res = run_tiny(RECURRING, trace_on=True)
    assert set(res) == CONTRACT_KEYS | {"checks", "breakdown"}
    assert {"busy_s", "window_s"} <= set(res["device"])
    # the CPU has no device plane: the trace readers read nothing there
    assert "dev_us.mwc" not in res["metrics"]
    assert "device_idle" not in res["metrics"]
    assert {"beat_us", "mwc_ms", "wave_host_ms"} <= set(res["metrics"])


def test_command_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(BENCH / "run.py"), "--workload",
                        ADHOC, "--seed", "1", "--seconds", "1", "--trace",
                        "0"], cwd=ROOT, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


# -- faults planted in the timed path -------------------------------------

def _state_unchanged(orig):
    def wave(self, now):
        before = self.avail.copy()
        out = orig(self, now)
        self.avail[:] = before          # grants leave the ledger unchanged
        return out
    return wave


def _half_batch(orig):
    def refresh(self):
        batch = orig(self)
        if batch is None or len(batch) < 2:
            return batch
        return batch.take(np.arange(len(batch) // 2))
    return refresh


def _answer_altered(orig):
    calls = {"n": 0}

    def wave(self, now):
        out = orig(self, now)
        calls["n"] += bool(out)
        if out and calls["n"] == 3:         # the third wave that grants
            t, job, task, m = self.placements[-1]
            self.placements[-1] = (t, job, task, (m + 1) % len(self.avail))
        return out
    return wave


def _pri_altered(orig):
    def make_pri(self, dag):
        pri = orig(self, dag)
        return pri[::-1].copy()             # the schedule order reversed
    return make_pri


def _unsound_eligibility(orig):
    def mwc(avail, *a, **k):
        eligible, any_m = orig(avail, *a, **k)
        eligible, any_m = eligible.copy(), any_m.copy()
        eligible[:, ::2] = False        # every other machine dropped
        any_m[::2] = False
        return eligible, any_m
    return mwc


FAULTS = {"state_unchanged": ("scheduler.SchedulerCore", "wave",
                              _state_unchanged),
          "half_batch": ("online.TaskPool", "refresh", _half_batch),
          "answer_altered": ("scheduler.SchedulerCore", "wave",
                             _answer_altered),
          "builder_answer_altered": ("scheduler.SchedulerCore", "_make_pri",
                                     _pri_altered)}


@pytest.mark.parametrize("kind", sorted(FAULTS))
def test_fault_turns_correct_false(monkeypatch, kind):
    from repro.core import online
    from repro.svc import scheduler

    where, name, make = FAULTS[kind]
    mod, cls = where.split(".")
    target = getattr({"online": online, "scheduler": scheduler}[mod], cls)
    monkeypatch.setattr(target, name, make(getattr(target, name)))
    res = run_tiny(ADHOC)
    assert res["correct"] is False, res["checks"]


def test_unsound_device_eligibility_turns_correct_false():
    from repro.core.engine import kernels

    sound = kernels._machines_with_candidates_xla
    kernels.register("machines_with_candidates", "xla",
                     _unsound_eligibility(sound), kernels.have_jax)
    try:
        res = run_tiny(ADHOC)
    finally:
        kernels.register("machines_with_candidates", "xla", sound,
                         kernels.have_jax)
    assert res["correct"] is False, res["checks"]


@pytest.mark.parametrize("workload", [ADHOC, RECURRING])
def test_control_is_flagged(workload):
    res = run_tiny(workload, seconds=3.0, control=True)
    assert res["correct"] is True, res["checks"]
    assert res["control"]["correct"] is False, res["control"]


# -- the references against the program ------------------------------------

@pytest.mark.parametrize("key", range(4))
def test_reference_builder_matches_the_program(key):
    from repro.core.builder import build_schedule
    from repro.core.dag import DAG

    from harness import ref_build
    from harness.traffic import Arrival, Traffic

    _, _, config, mix = cell.spec(ADHOC)
    tk = Traffic(mix, SEED, 0.5).tasks(Arrival(0.0, key))
    dag = DAG(duration=tk.duration, demand=tk.demand, stage_of=tk.stage_of,
              parents=tk.parents)
    want = build_schedule(dag, 4, backend="reference").pri_score
    got = ref_build.pri_score(tk.duration, tk.demand, tk.stage_of,
                              tk.parents, ref_build.Knobs(
                                  m=4, **config["builder"]))
    assert np.array_equal(got, want)


def test_traffic_offers_every_seed_the_same_work():
    from harness.traffic import Traffic

    _, _, config, mix = cell.spec(ADHOC)
    a, b = (Traffic(mix, s, config["job_scale"]) for s in (SEED, SEED + 1))
    blk = a.block
    for t in (a, b):
        arr = t.arrivals()[:2 * blk]
        t.keys = sorted(x.key for x in arr)
        t.span = arr[blk].t
    assert a.keys == b.keys
    assert a.span == pytest.approx(b.span)
    assert [x.key for x in a.arrivals()[:blk]] != \
        [x.key for x in b.arrivals()[:blk]]


# -- arithmetic -----------------------------------------------------------

def _steps(walls_ms, code="submit", waved=False):
    c = CODE_NAMES.index(code)
    return [Step(c, int(w * 1e6), waved, True, 0) for w in walls_ms]


def test_percentile_takes_every_request():
    # 9 fast chunks' medians would read 1 ms; the tail is in one chunk
    walls = [1.0] * 90 + [100.0] * 10
    run = cell.Run(workload={}, setup_s=1.0, window_wall_s=10.0,
                   window_virtual_s=5.0, steps=_steps(walls), prof={},
                   jit={"device_calls": 50})
    got = cell.load_reader("ready_ms_p90")(run)
    assert got == pytest.approx(float(np.percentile(walls, 90)))
    assert got > 1.0
    waves = _steps(walls, code="done", waved=True)
    run.steps = waves
    assert cell.load_reader("wave_ms_p95")(run) == pytest.approx(100.0)


def test_rate_is_over_the_whole_window():
    run = cell.Run(workload={}, setup_s=1.0, window_wall_s=8.0,
                   window_virtual_s=2.0, steps=[], prof={}, jit={})
    assert cell.load_reader("cluster_s_per_s")(run) == 0.25
    assert stats.rate(3.0, 0.0) is None
    assert stats.percentile([], 90) is None


def test_counters_per_call_and_per_build():
    run = cell.Run(workload={}, setup_s=1.0, window_wall_s=1.0,
                   window_virtual_s=1.0, steps=_steps([5.0, 7.0]),
                   prof={"machines_with_candidates.xla": (4, 0.02),
                         "match_wave.numpy": (2, 0.5)},
                   jit={"device_calls": 30})
    assert cell.load_reader("mwc_ms")(run) == pytest.approx(5.0)
    assert cell.load_reader("wave_host_ms")(run) == pytest.approx(250.0)
    assert cell.load_reader("scan_calls_per_build")(run) == 15
    assert cell.load_reader("build_ms")(run) == pytest.approx(6.0)
    assert cell.load_reader("dev_us.scan")(run) is None


def test_union_of_intervals():
    assert stats.union_seconds([(0, 2), (1, 3), (5, 6)]) == 4
    assert stats.union_seconds([]) == 0


# -- trace reduction ------------------------------------------------------

TRACE = BENCH / "tests" / "data" / "v5e_scan_elig.xplane.pb"


@pytest.mark.skipif(not TRACE.exists(), reason="no recorded chip trace")
def test_trace_reduction_on_a_chip_trace():
    r = trace.reduce(str(TRACE))
    assert r.n_devices == 1
    assert 0 < r.busy_s < r.window_s
    scan = r.module_time(cell.load_reader_module("dev_us.scan").MODULES)
    elig = r.module_time(cell.load_reader_module("dev_us.mwc").MODULES)
    assert scan is not None and scan[0] == 5
    assert elig is not None and elig[0] == 5
    idle = sum(r.idle_by_host.values())
    assert idle == pytest.approx(r.window_s - r.busy_s, rel=1e-6)
    assert {"submit", "beat"} <= set(r.idle_by_host)


def test_benchmark_names_resolve_to_files():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        assert (ROOT / c["file"]).exists()
        cfg = json.loads((ROOT / c["file"]).read_text())
        for k in c["reduced"]:
            assert k in cfg and k in cfg["reduced"]
    for w in bench["workloads"]:
        assert (BENCH / "traffic" / f"{w['traffic']}.json").exists()
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(cell.load_reader(m["name"]))
