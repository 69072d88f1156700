"""Tests of the readers of the program's own spans, on the CPU.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests/test_span_metrics.py

Each reader on a synthetic `Run`: the mean of its span, no value where
the span has no calls (as on a program without spans), and the per-build
ratios over the spans' own build count.  Then a traced CPU rehearsal of
each cell, which must report every span metric listed for the cell.
"""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

from harness import cell  # noqa: E402

ADHOC, RECURRING = "m4034-prod.adhoc", "m4034-prod.recurring"
SEED = 3_000_000_019
#: reader -> (span key, scale to the metric's unit)
MEANS = {"pump_us": ("svc.pump", 1e6),
         "silence_us": ("svc.check_silence", 1e6),
         "pump_wave_ms": ("svc.pump.wave", 1e3),
         "refresh_ms": ("pool.refresh", 1e3),
         "elig_fanout_ms": ("shard.eligibility", 1e3),
         "elig_sync_us": ("machines_with_candidates.xla.sync", 1e6),
         "scan_launch_us": ("jit.scan.launch", 1e6)}
PER_BUILD = ("scan_sync_ms_per_build", "xla_load_ms_per_build")
SPAN_METRICS = tuple(MEANS) + PER_BUILD


def run_of(prof: dict) -> cell.Run:
    return cell.Run(workload={}, setup_s=1.0, window_wall_s=10.0,
                    window_virtual_s=5.0, steps=[], prof=prof,
                    jit={"device_calls": 0})


@pytest.mark.parametrize("name", sorted(MEANS))
def test_mean_of_the_span(name):
    key, scale = MEANS[name]
    read = cell.load_reader(name)
    prof = {key: (4, 0.002), "svc.build": (2, 1.0)}
    assert read(run_of(prof)) == pytest.approx(0.002 / 4 * scale)
    assert read(run_of({"svc.build": (2, 1.0)})) is None
    assert read(run_of({key: (0, 0.0)})) is None


def test_pump_kinds_are_apart():
    prof = {"svc.pump": (10, 0.001), "svc.pump.wave": (2, 0.02)}
    assert cell.load_reader("pump_us")(run_of(prof)) == pytest.approx(100.0)
    assert cell.load_reader("pump_wave_ms")(run_of(prof)) == \
        pytest.approx(10.0)


def test_per_build_ratios_divide_by_the_build_span():
    # 40 builds in the window, 4 of them while the trace recorded
    prof = {"svc.build": (4, 4.0), "jit.scan.sync": (600, 0.2),
            "xla.load.scan": (3, 0.09), "xla.load.slab": (1, 0.01),
            "xla.load.elig": (1, 0.02)}
    run = run_of(prof)
    run.jit = {"device_calls": 6000}
    assert cell.load_reader("scan_sync_ms_per_build")(run) == \
        pytest.approx(50.0)
    assert cell.load_reader("xla_load_ms_per_build")(run) == \
        pytest.approx(30.0)


def test_per_build_ratios_without_builds_or_loads():
    sync = cell.load_reader("scan_sync_ms_per_build")
    load = cell.load_reader("xla_load_ms_per_build")
    assert sync(run_of({"jit.scan.sync": (5, 0.1)})) is None
    assert load(run_of({"xla.load.scan": (5, 0.1)})) is None
    assert sync(run_of({"svc.build": (2, 1.0)})) is None
    # builds with every program cached: a true zero, not a missing value
    assert load(run_of({"svc.build": (2, 1.0)})) == 0.0


def test_a_program_without_spans_reads_nothing():
    """The parent's program counts none of the span keys: every reader
    gives no value and raises nothing."""
    prof = {"machines_with_candidates.xla": (8, 0.03),
            "match_wave.numpy": (4, 0.02), "scan.numpy": (90, 0.4)}
    for name in SPAN_METRICS:
        assert cell.load_reader(name)(run_of(prof)) is None, name


def test_benchmark_lists_the_span_metrics():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    by = {m["name"]: m for m in bench["per_layer"]}
    for name in SPAN_METRICS:
        assert by[name]["source"] == "program_counter"
    assert set(by["pump_us"]["workloads"]) == {ADHOC, RECURRING}


# -- traced rehearsals -----------------------------------------------------

def tiny(workload: str, machines: int = 48) -> tuple[dict, dict]:
    """The cell cut for a CPU rehearsal, as ``test_bench.tiny`` cuts it."""
    _, _, config, mix = cell.spec(workload)
    cfg = {"service": dict(config["service"], n_machines=machines),
           "guarantees": dict(config["guarantees"], n_machines=machines),
           "job_scale": 0.5}
    warm = dict(mix["warm"], candidates_max=4)
    if "grid_span" in warm:
        warm.update(stage_width=[1, 8], grid_span=[320, 330], slab_max=64)
    return cfg, {"warm": warm}


@pytest.mark.parametrize("workload", [ADHOC, RECURRING])
def test_traced_rehearsal_reports_the_span_metrics(workload, monkeypatch):
    from repro.core.engine import jit as jit_mod

    # the chip's device paths at a CPU size: eligibility on xla at 48
    # machines, the wave on numpy, scans of 4 rows and up launched
    monkeypatch.setenv("REPRO_HEARTBEAT_DEVICE_MIN_M", "8")
    monkeypatch.setenv("REPRO_KERNELS", "match_wave=numpy")
    monkeypatch.setattr(jit_mod, "MIN_DEVICE_G", 4)
    cfg, mix = tiny(workload)
    res = cell.run(workload, SEED, 3.0, True, require_tpu=False,
                   config_override=cfg, mix_override=mix,
                   log=lambda _l: None)
    assert res["correct"] is True, res["checks"]
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    listed = {m["name"] for m in cell.metrics_for(bench, workload, True)}
    want = listed & set(SPAN_METRICS)
    assert want
    assert want <= set(res["metrics"]), want - set(res["metrics"])
    for name in want:
        assert res["metrics"][name]["value"] >= 0.0
    # the accepted per-layer metrics the CPU can read stay reported
    assert "beat_us" in res["metrics"]
