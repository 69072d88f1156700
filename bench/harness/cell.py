"""One run of one cell: set-up, the measured window, the check, the line.

`run` is the whole run behind ``bench/run.py``; tests call it directly
(with ``require_tpu=False`` and a smaller configuration) to rehearse a
cell on the CPU.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

from . import check

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
#: seconds of the window that a ``--trace 1`` run records
TRACE_WINDOW_S = 6.0
#: the program's cap on its cache of compiled scan buckets; the benchmark
#: runs the program's default
BUCKET_CAP_ENV = "REPRO_JIT_BUCKET_CAP"


class NoDevice(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell needs."""


class CompileClock:
    """Counts XLA executables made (compiled or loaded from the persistent
    cache) and their seconds, from jax.monitoring's backend-compile event."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.secs = 0.0
        self.count = 0

    def __call__(self, event: str, secs: float, **_kw) -> None:
        if event == self.EVENT:
            self.secs += secs
            self.count += 1


@dataclasses.dataclass
class Run:
    """What the metric readers read."""

    workload: dict
    setup_s: float
    window_wall_s: float
    window_virtual_s: float
    steps: list               # driver.Step of every event in the window
    prof: dict                # kernels.PROFILE delta over the window
    jit: dict                 # jit.PROFILE delta over the window
    reduced: object = None    # trace.Reduced of a --trace 1 run


def spec(workload: str) -> tuple[dict, dict, dict, dict]:
    """(benchmark, workload entry, configuration, traffic mix) by name."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    wl = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if wl is None:
        raise SystemExit(f"unknown workload {workload!r}")
    cf = next(c for c in bench["configs"] if c["name"] == wl["config"])
    config = json.loads((ROOT / cf["file"]).read_text())
    mix = json.loads((BENCH / "traffic" / f"{wl['traffic']}.json").read_text())
    return bench, wl, config, mix


def load_reader_module(name: str):
    """The reader of one metric: ``bench/metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def load_reader(name: str):
    return load_reader_module(name).read


def metrics_for(bench: dict, workload: str, traced: bool) -> list[dict]:
    group = bench["per_layer"] if traced else bench["end_to_end"]
    return [m for m in group
            if "workloads" not in m or workload in m["workloads"]]


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_tpu:
        if info["platform"] != "tpu":
            raise NoDevice(f"no TPU: JAX found {info['platform']}")
        if info["count"] < chips:
            raise NoDevice(f"the cell needs {chips} chips, found "
                           f"{info['count']}")
        peaks = json.loads((BENCH / "peaks.json").read_text())["devices"]
        if info["kind"] not in peaks:
            raise NoDevice(f"device kind {info['kind']!r} is not in "
                           f"bench/peaks.json")
    return info


def service_config(config: dict, **over):
    """The service's configuration; its fairness groups are those of the
    configuration's ``fairness`` section, unless ``service`` names them."""
    from repro.svc import ServiceConfig

    kw = dict(config["service"])
    kw.setdefault("groups", tuple(check.fairness(config)["groups"]))
    kw.update(over)
    return ServiceConfig(**kw)


def _fairness_fn(name: str):
    """The program's fairness function ``f`` of a configuration's name."""
    from repro.core.online import drf_fairness, slot_fairness

    return {"slot": slot_fairness, "drf": drf_fairness}[name]


def program_scheme(config: dict):
    """The program's scheme as the configuration states it: its preset,
    with the configuration's unfairness bound and fairness function."""
    from repro.sim.cluster import scheme

    fair = check.fairness(config)
    return scheme(config["scheme"], kappa=fair["kappa"],
                  fairness=_fairness_fn(fair["f"]))


def _delta(after: dict, before: dict) -> dict:
    out = {}
    for k, v in after.items():
        b = before.get(k)
        if isinstance(v, tuple):
            b = b or (0, 0.0)
            out[k] = (v[0] - b[0], v[1] - b[1])
        else:
            out[k] = v - (b or 0)
    return out


def _window(drv, seconds: float, trace: bool, log):
    """Handle events for ``seconds`` of wall time; with ``trace``, record
    a profiler trace of the first `TRACE_WINDOW_S` of them inside the
    harness's spans.  Returns (steps, wall seconds of stepping, path of
    the trace directory or None)."""
    import jax

    from . import driver, trace as trace_mod

    steps = []
    tdir = None
    w0 = time.perf_counter()
    deadline = w0 + seconds
    paused = 0.0
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(tdir, profiler_options=opts)
        ann = jax.profiler.TraceAnnotation
        names = ["bench." + n for n in driver.CODE_NAMES]
        t_stop = w0 + min(TRACE_WINDOW_S, seconds)
        vt_a = drv.vt
        with ann(trace_mod.WINDOW_SPAN):
            while time.perf_counter() < t_stop and drv.events:
                with ann(names[drv.events[0][2]]):
                    steps.append(drv.step())
        traced_wall = time.perf_counter() - w0
        log(f"traced part: virtual_s={drv.vt - vt_a:.6f} "
            f"wall_s={traced_wall:.6f} cluster_s_per_s="
            f"{(drv.vt - vt_a) / traced_wall:.6f}")
        p_stop = time.perf_counter()
        jax.profiler.stop_trace()
        paused = time.perf_counter() - p_stop   # not part of the window
        deadline += paused
    while time.perf_counter() < deadline and drv.events:
        steps.append(drv.step())
    if not drv.events:
        raise RuntimeError("the traffic ran out inside the window; raise "
                           "horizon_blocks")
    return steps, time.perf_counter() - w0 - paused, tdir


def _check_program(core, config: dict) -> None:
    """The program runs as the configuration states: its dagps policy,
    builder knobs, unfairness bound, fairness function and the groups'
    normalised shares in the built scheduler are the configuration's."""
    import inspect

    from repro.core.builder import build_schedule

    mc = core.smatcher.matcher.cfg
    pol = config["policy"]
    got = {"eta_m": mc.eta_m, "max_overbook": mc.max_overbook,
           "bundle_limit": mc.bundle_limit}
    flags = (mc.use_priority, mc.use_packing, mc.use_srpt,
             mc.use_overbooking, tuple(mc.fit_dims) == tuple(range(4)))
    params = inspect.signature(build_schedule).parameters
    knobs = {k: params[k].default for k in config["builder"]}
    if got != pol or not all(flags) or knobs != config["builder"]:
        raise ValueError(f"the program's dagps scheme ({got}, {flags}, "
                         f"{knobs}) is not the configuration's")
    fair = check.fairness(config)
    if (mc.kappa != fair["kappa"]
            or mc.fairness is not _fairness_fn(fair["f"])):
        raise ValueError(f"the program's kappa {mc.kappa!r} and fairness "
                         f"{mc.fairness.__name__} are not the configuration's "
                         f"kappa {fair['kappa']!r} and f {fair['f']!r}")
    want = list(zip(fair["groups"], fair["shares"]))
    got_shares = list(core.smatcher.matcher.deficits.share.items())
    if got_shares != want:
        raise ValueError(f"the program's normalised group shares "
                         f"{dict(got_shares)} are not the configuration's "
                         f"{dict(want)}")


def run(workload: str, seed: int, seconds: float, trace: bool,
        require_tpu: bool = True, config_override: dict | None = None,
        mix_override: dict | None = None, control: bool = False, t_start: float | None = None,
        log=print) -> dict:
    """Run one cell; returns the result line's object.  ``log`` gets the
    lines for standard error.  ``control`` also reads the control's
    numbers (the reference one precision lower in the program's place,
    see `check`) into the object's ``control`` key."""
    t_start = time.perf_counter() if t_start is None else t_start
    bench, wl, config, mix = spec(workload)
    if config_override:
        config = {**config, **config_override}
    if mix_override:
        mix = {**mix, **mix_override}
    # the program's own default for its compiled-bucket cache
    os.environ.pop(BUCKET_CAP_ENV, None)
    sys.path.insert(0, str(ROOT / "src"))
    from repro.core.engine import compile_cache

    compile_cache.enable()
    import jax

    from repro.core.engine import jit as jit_mod
    from repro.core.engine import kernels

    from . import driver, trace as trace_mod, warm
    from .traffic import Traffic

    device = device_info(int(wl["chips"]), require_tpu)
    log(f"program: {BUCKET_CAP_ENV}={kernels.BUCKET_CAP} (its default)")
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    try:
        cfg = service_config(config)
        spec_ = program_scheme(config)
        w = mix["warm"]
        n_elig = warm.eligibility(cfg, int(w["candidates_max"]))
        n_scan = warm.scans(cfg.build_machines, cfg.d, w) \
            if "grid_span" in w else 0
        log(f"warm: eligibility calls={n_elig} scan programs={n_scan} "
            f"compiles={clock.count} compile_s={clock.secs:.3f}")
        traffic = Traffic(mix, seed, config["job_scale"])
        drv = driver.Driver(traffic, cfg, spec_)
        try:
            _check_program(drv.core, config)
            drv.run_until_virtual(traffic.warm_until())
            c0 = (clock.count, clock.secs)
            x0 = dict(kernels.XLA_STATS)
            p0 = kernels.profile_snapshot()
            j0 = dict(jit_mod.PROFILE)
            vt0 = drv.vt
            setup_s = time.perf_counter() - t_start
            steps, wall, tdir = _window(drv, seconds, trace, log)
            run_rec = Run(workload=wl, setup_s=setup_s, window_wall_s=wall,
                          window_virtual_s=drv.vt - vt0, steps=steps,
                          prof=_delta(kernels.profile_snapshot(), p0),
                          jit=_delta(dict(jit_mod.PROFILE), j0))
            chips = jax.devices()[:int(wl["chips"])]
            device["memory_peak_bytes"] = int(max(
                (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for d in chips))
            xs = kernels.XLA_STATS
            log(f"window: wall_s={wall:.6f} "
                f"virtual_s={run_rec.window_virtual_s:.6f} "
                f"events={len(steps)} compiles={clock.count - c0[0]} "
                f"compile_s={clock.secs - c0[1]:.6f} "
                f"bucket_compiles={xs['compiles'] - x0['compiles']} "
                f"bucket_evictions={xs['evictions'] - x0['evictions']} "
                f"setup_s={setup_s:.6f} "
                f"memory_peak_bytes={device['memory_peak_bytes']}")
            counts = {n: sum(1 for s in steps if s.code == i)
                      for i, n in enumerate(driver.CODE_NAMES)}
            log(f"window events: {json.dumps(counts)} waves="
                f"{sum(s.waved for s in steps)} builds="
                f"{sum(s.miss for s in steps)} submits_ready_ms_samples="
                f"{counts['submit']}")
            if tdir is not None:
                try:
                    run_rec.reduced = trace_mod.reduce(
                        trace_mod.find_xplane(tdir))
                finally:
                    shutil.rmtree(tdir, ignore_errors=True)
                device["busy_s"] = run_rec.reduced.busy_s
                device["window_s"] = run_rec.reduced.window_s
        finally:
            drv.close()
        t_ref = time.perf_counter()
        nums = check.numbers(drv, config, seed)
        ctl = check.numbers(drv, config, seed, control=True) \
            if control else None
        log(f"check: references took {time.perf_counter() - t_ref:.3f} s "
            f"over {drv.steps} events and {len(drv.job_tasks)} jobs; "
            f"must_serve_picks={nums['must_serve_picks']} "
            f"deficit_peak={nums['deficit_peak']!r} (of kappa*C)")
    finally:
        jax.monitoring.unregister_event_duration_listener(clock)
    return _result(bench, wl, trace, run_rec, nums, drv, device, ctl)


def _result(bench, wl, trace, run_rec, nums, drv, device, ctl=None) -> dict:
    ok, shown = check.verdict(nums)
    out_metrics = {}
    for m in metrics_for(bench, wl["name"], trace):
        v = load_reader(m["name"])(run_rec)
        if v is not None:
            out_metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    placements, jobs = drv.decisions()
    result = {"correct": ok, "attempted": len(placements) + len(jobs),
              "failed": int(nums["placement_diffs"] + nums["exactly_once"]
                            + nums["precedence"]),
              "metrics": out_metrics, "device": device}
    if run_rec.reduced is not None:
        r = run_rec.reduced
        result["breakdown"] = {
            "device_ops": sorted(([k, v[1]] for k, v in r.modules.items()),
                                 key=lambda kv: -kv[1])[:10],
            "idle_gaps": sorted(([k, v] for k, v in r.idle_by_host.items()),
                                key=lambda kv: -kv[1])[:10]}
    if ctl is not None:
        ok_c, shown_c = check.verdict(ctl)
        result["control"] = {"correct": ok_c, "checks": shown_c}
    result["checks"] = shown
    return result
