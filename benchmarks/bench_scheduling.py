"""Scheduler benchmarks reproducing the paper's tables/figures.

  jct           — Fig. 10: JCT improvement vs Tez across benchmarks
  makespan      — Table 3: makespan gap vs Tez
  fairness      — Table 4: 2-queue perf gap + Jain index over windows
  alternatives  — Fig. 12 / Table 5: constructed-schedule quality vs
                  BFS/CP/Tetris/Random/CG/StripPart
  lowerbound    — Fig. 13: DAGPS vs NewLB vs old max(CPLen, TWork)
  sensitivity   — Fig. 14/15: eta-m sweep, remote-penalty sweep, load sweep
  domains       — Fig. 16: build-system + request-response workflow DAGs
  construction  — §7: schedule-construction wall time
  online_large  — s8: cluster-scale online matching (500+ machines,
                  200+ mixed production/TPC-DS jobs, Poisson arrivals)
  online_churn  — s9: s8 population under failures + stragglers +
                  speculative re-execution
"""

from __future__ import annotations

import time

import numpy as np

from repro.core import all_bounds, build_schedule, new_lb
from repro.core.baselines import (bfs_order, cg_order, cp_order, random_order,
                                  simulate_execution, strip_levels)
from repro.sim import make_workload, online_mix_workload, run_workload
from repro.sim.workload import build_system_dag, production_dag, workflow_dag

from .common import emit, emit_phases, n_jobs


def _imp(base: np.ndarray, new: np.ndarray, q: float) -> float:
    """Paper's normalized gap at percentile q: 1 - new/base per job."""
    gaps = 1.0 - new / np.maximum(base, 1e-9)
    return float(np.percentile(gaps, q) * 100)


def _memo_counters() -> dict[str, int]:
    from repro.core.memo import counters_snapshot

    return counters_snapshot()


def _emit_memo_rows(prefix: str, before: dict[str, int]) -> None:
    """Construction-memo accounting rows for one bench group.

    Reports the offline builder's placements-evaluated (live backend
    searches) vs placements-memoized (cross-candidate memo hits) since
    ``before``, plus the derived hit rate — so the bench JSON attributes
    construction speedups to the memo, not just to the wall clock.
    us_per_call is 0: these are counter rows, not timings (the CI
    regression gate keys on s*_ timing rows).
    """
    after = _memo_counters()
    ev = after["places_evaluated"] - before["places_evaluated"]
    hit = after["places_memoized"] - before["places_memoized"]
    emit(f"{prefix}_placements_evaluated", 0.0, ev)
    emit(f"{prefix}_placements_memoized", 0.0, hit)
    emit(f"{prefix}_memo_hit_rate", 0.0, round(hit / max(ev + hit, 1), 3))
    # hits served across the partitioned sub-builds of one DAG (recurring
    # pipelines: identical partitions -> identical tick-space queries)
    emit(f"{prefix}_memo_xpart_hits", 0.0,
         after["places_memoized_xpart"] - before["places_memoized_xpart"])
    emit(f"{prefix}_passes_replayed", 0.0,
         after["passes_replayed"] - before["passes_replayed"])
    emit(f"{prefix}_variants_pruned", 0.0,
         (after["variants_bound_skipped"] - before["variants_bound_skipped"])
         + (after["candidates_lb_skipped"] - before["candidates_lb_skipped"]))


def bench_jct() -> None:
    """Fig. 10: per-benchmark JCT improvement of DAGPS over Tez."""
    from benchmarks import common

    memo_before = _memo_counters()
    # "periodic" (recurring pipelines, §2: >40% of production jobs recur)
    # is the cross-partition memo's home regime: identical barrier-split
    # phases make the sub-builds share tick-space placement queries
    for bench in ("tpch", "tpcds", "bigbench", "ehive", "production",
                  "periodic"):
        dags = make_workload(bench, n_jobs(12), seed=42)
        t0 = time.perf_counter()
        rs = {s: run_workload(dags, s, n_machines=16, interarrival=12.0,
                              seed=42, profile=common.PROFILE)
              for s in ("tez", "tez+cp", "tez+tetris", "dagps")}
        dt = (time.perf_counter() - t0) * 1e6 / (4 * len(dags))
        tez = np.array([j.jct for j in sorted(rs["tez"].jobs, key=lambda j: j.job_id)])
        for s in ("tez+cp", "tez+tetris", "dagps"):
            new = np.array([j.jct for j in sorted(rs[s].jobs, key=lambda j: j.job_id)])
            emit(f"fig10_jct_{bench}_{s}_p50", dt, round(_imp(tez, new, 50), 1))
            if s == "dagps":
                emit(f"fig10_jct_{bench}_{s}_p75", dt, round(_imp(tez, new, 75), 1))
        if common.PROFILE:
            for s in ("tez", "dagps"):
                emit_phases(f"s1_jct_{bench}_{s}", rs[s].phase_times)
    _emit_memo_rows("s1_jct", memo_before)


def bench_makespan() -> None:
    """Table 3: makespan; all jobs arrive at t~0."""
    memo_before = _memo_counters()
    for bench in ("tpcds", "tpch", "periodic"):
        dags = make_workload(bench, n_jobs(16), seed=7)
        t0 = time.perf_counter()
        out = {}
        for s in ("tez", "tez+cp", "tez+tetris", "dagps"):
            out[s] = run_workload(dags, s, n_machines=12, interarrival=0.5,
                                  seed=7).makespan
        dt = (time.perf_counter() - t0) * 1e6 / (4 * len(dags))
        for s in ("tez+cp", "tez+tetris", "dagps"):
            gain = 100 * (1 - out[s] / out["tez"])
            emit(f"table3_makespan_{bench}_{s}", dt, round(gain, 1))
    _emit_memo_rows("s2_makespan", memo_before)


def bench_fairness() -> None:
    """Table 4: two even queues vs one; perf gap and Jain's index."""
    dags = make_workload("tpcds", n_jobs(14), seed=11)
    shares = {0: 1.0, 1: 1.0}
    for s in ("tez", "tez+drf", "tez+tetris", "dagps"):
        t0 = time.perf_counter()
        one = run_workload(dags, s, n_machines=12, interarrival=10.0,
                           n_groups=1, seed=11)
        two = run_workload(dags, s, n_machines=12, interarrival=10.0,
                           n_groups=2, seed=11)
        dt = (time.perf_counter() - t0) * 1e6 / (2 * len(dags))
        gap = 100 * (np.median(two.jcts()) / np.median(one.jcts()) - 1.0)
        emit(f"table4_2q_perf_gap_{s}", dt, round(-gap, 1))
        for w in (10.0, 60.0, 240.0):
            emit(f"table4_jain_{s}_{int(w)}s", dt,
                 round(two.jain_index(w, shares), 3))


def bench_alternatives() -> None:
    """Fig. 12 / Table 5: constructed schedules vs best-of-breed baselines."""
    m = 4
    per: dict[str, list] = {k: [] for k in
                            ("dagps", "cp", "tetris", "random", "cg", "strippart")}
    base = []
    t_build = []
    N = n_jobs(24)
    for i in range(N):
        dag = production_dag(np.random.default_rng(1000 + i), share=m)
        bfs = simulate_execution(dag, m, order=bfs_order(dag))
        base.append(bfs)
        t0 = time.perf_counter()
        sched = build_schedule(dag, m)
        t_build.append(time.perf_counter() - t0)
        per["dagps"].append(min(
            simulate_execution(dag, m, policy="dagps", pri_score=sched.pri_score),
            sched.makespan))
        per["cp"].append(simulate_execution(dag, m, order=cp_order(dag)))
        per["tetris"].append(simulate_execution(dag, m, policy="tetris"))
        per["random"].append(simulate_execution(dag, m, order=random_order(dag, i)))
        per["cg"].append(simulate_execution(dag, m, order=cg_order(dag)))
        per["strippart"].append(simulate_execution(
            dag, m, policy="tetris", barrier_levels=strip_levels(dag)))
    base_a = np.array(base)
    dt = float(np.mean(t_build)) * 1e6
    for k, v in per.items():
        for q in (25, 50, 75, 90):
            emit(f"table5_vs_bfs_{k}_p{q}", dt, round(_imp(base_a, np.array(v), q), 1))


def bench_lowerbound() -> None:
    """Fig. 13: closeness to NewLB; NewLB vs the old max(CPLen, TWork)."""
    m = 4
    ratios, tighten = [], []
    N = n_jobs(24)
    t0 = time.perf_counter()
    for i in range(N):
        dag = production_dag(np.random.default_rng(2000 + i), share=m)
        b = all_bounds(dag, m)
        sched = build_schedule(dag, m)
        ms = min(simulate_execution(dag, m, policy="dagps",
                                    pri_score=sched.pri_score), sched.makespan)
        ratios.append(ms / b["newlb"])
        tighten.append(b["newlb"] / max(b["cplen"], b["twork"]))
    dt = (time.perf_counter() - t0) * 1e6 / N
    r = np.array(ratios)
    emit("fig13_dagps_over_newlb_p50", dt, round(float(np.percentile(r, 50)), 3))
    emit("fig13_dagps_over_newlb_p75", dt, round(float(np.percentile(r, 75)), 3))
    emit("fig13_dagps_over_newlb_max", dt, round(float(r.max()), 3))
    emit("fig13_frac_within_1.13", dt, round(float((r <= 1.13).mean()), 3))
    emit("fig13_newlb_tightening_p50", dt,
         round(float(np.percentile(tighten, 50)), 3))


def bench_sensitivity() -> None:
    """Fig. 14/15: eta multiplier, remote penalty, load scaling."""
    dags = make_workload("tpcds", n_jobs(10), seed=21)
    t0 = time.perf_counter()
    base = None
    for m_eta in (0.05, 0.2, 0.5):
        res = run_workload(dags, "dagps", n_machines=12, interarrival=8.0,
                           seed=21, eta_m=m_eta)
        v = float(np.mean(res.jcts()))
        base = base or v
        emit(f"fig14_eta_m_{m_eta}", 0.0, round(100 * (1 - v / base), 1))
    for rp in (0.5, 0.8, 1.0):
        res = run_workload(dags, "dagps", n_machines=12, interarrival=8.0,
                           seed=21, remote_penalty=rp)
        emit(f"fig14_rp_{rp}", 0.0, round(float(np.mean(res.jcts())), 1))
    # Fig 15: load = fewer machines, same workload
    for machines in (16, 8, 4):
        tez = run_workload(dags, "tez", n_machines=machines, interarrival=8.0, seed=21)
        dg = run_workload(dags, "dagps", n_machines=machines, interarrival=8.0, seed=21)
        gain = 100 * (1 - np.median(dg.jcts()) / np.median(tez.jcts()))
        emit(f"fig15_load_m{machines}", 0.0, round(float(gain), 1))
    _ = t0


def bench_domains() -> None:
    """Fig. 16: DAGs from distributed builds and request-response workflows."""
    m = 4
    for name, gen in (("build", build_system_dag), ("workflow", workflow_dag)):
        imps_t, imps_c = [], []
        N = n_jobs(12)
        t0 = time.perf_counter()
        for i in range(N):
            dag = gen(np.random.default_rng(3000 + i))
            sched = build_schedule(dag, m)
            dg = min(simulate_execution(dag, m, policy="dagps",
                                        pri_score=sched.pri_score), sched.makespan)
            tet = simulate_execution(dag, m, policy="tetris")
            cp = simulate_execution(dag, m, order=cp_order(dag))
            imps_t.append(1 - dg / tet)
            imps_c.append(1 - dg / cp)
        dt = (time.perf_counter() - t0) * 1e6 / N
        emit(f"fig16_{name}_vs_tetris_p50", dt,
             round(float(np.median(imps_t)) * 100, 1))
        emit(f"fig16_{name}_vs_cp_p50", dt,
             round(float(np.median(imps_c)) * 100, 1))


def bench_construction() -> None:
    """§7: BuildSchedule wall time across DAG sizes, per placement backend.

    Emits one row per (size, backend), the reference/batched speedup
    ratio, and — per backend — a scan-phase row (seconds inside the
    feasibility-scan kernels, via the dispatch-layer profile) plus jit
    retrace/device-call accounting, so jit-path regressions gate in CI
    like scenario regressions (benchmarks/check_regression.py keys on
    these s7_* rows).
    """
    from repro.core import available_backends, get_backend
    from repro.core.engine import jit as jit_mod, kernels
    from benchmarks import common

    sizes = ((0.5, "small"),) if common.QUICK else (
        (0.5, "small"), (1.0, "medium"), (2.0, "large"))
    backends = ["reference", "batched"]
    if "jit" in available_backends() and get_backend("jit").available():
        backends.append("jit")
    for scale, label in sizes:
        dag = production_dag(np.random.default_rng(99), scale=scale, share=8)
        times: dict[str, float] = {}
        for be in backends:
            if be == "jit":
                # untimed warm-up build: session start pre-warms the base
                # kernel bucket and this pass compiles the remaining shape
                # buckets, so the timed row measures placement, not XLA
                # compilation (ROADMAP follow-up)
                build_schedule(dag, 8, backend=be)
            memo_before = _memo_counters()
            kprof0 = kernels.profile_snapshot()
            jit_mod.reset_profile()
            retrace0 = kernels.XLA_STATS["compiles"]
            t0 = time.perf_counter()
            build_schedule(dag, 8, backend=be)
            times[be] = time.perf_counter() - t0
            emit(f"s7_construction_{label}_n{dag.n}_{be}",
                 times[be] * 1e6, round(times[be], 3))
            # scan-phase row: seconds inside the dispatched scan kernels
            # for this build (numpy/xla/pallas); the jit backend's device
            # launches are timed by the jit.scan.* spans of a traced run,
            # not here.  Gated like any s7 timing row
            kprof1 = kernels.profile_snapshot()
            scan_s = sum(sec - kprof0.get(key, (0, 0.0))[1]
                         for key, (_c, sec) in kprof1.items()
                         if key.startswith("scan."))
            emit(f"s7_scan_{label}_{be}", scan_s * 1e6, round(scan_s, 3))
            if be == "jit":
                emit(f"s7_construction_{label}_jit_retraces", 0.0,
                     kernels.XLA_STATS["compiles"] - retrace0)
                emit(f"s7_construction_{label}_jit_device_calls", 0.0,
                     jit_mod.PROFILE["device_calls"])
            _emit_memo_rows(f"s7_construction_{label}_{be}", memo_before)
        # legacy row: the default backend's wall time under the old name
        emit(f"s7_construction_{label}_n{dag.n}",
             times["batched"] * 1e6, round(times["batched"], 3))
        emit(f"s7_construction_{label}_speedup_ref_over_batched",
             times["batched"] * 1e6,
             round(times["reference"] / max(times["batched"], 1e-9), 2))


def bench_online_large() -> None:
    """s8: online matching at cluster scale (intractable pre-vectorization).

    >=500 machines (>=1k non-quick), >=200 mixed production + TPC-DS jobs,
    Poisson arrivals at a rate that keeps the cluster saturated — the
    §5/§7 regime where the matcher, not the per-job DAGs, is the
    bottleneck.  The pre-refactor object-list path took ~104 s for the
    tez+tetris leg alone; the SoA path runs it in seconds.  `derived` is
    the scheme's median JCT so the row doubles as an output-stability
    check.  Heartbeat eligibility runs through the kernel-dispatch layer
    (one batched launch per heartbeat); the `_phase_heartbeat` rows report
    time inside that op and the `_heartbeat_kernel` row names the
    implementation that served it.
    """
    import os

    from repro.core.dag import dag_digest
    from repro.core.engine import kernels
    from repro.sim import clear_schedule_cache
    from benchmarks import common

    n_m, n_j = (500, 200) if common.QUICK else (1024, 320)
    dags = online_mix_workload(n_j, seed=88)
    # dedup accounting through the canonical digest (the same bytes the
    # simulator cache and the build service key on)
    emit(f"s8_online_large_j{n_j}_unique_dags", 0.0,
         len({dag_digest(d) for d in dags}))
    res_dagps = None
    for sch in ("tez+tetris", "dagps"):
        t0 = time.perf_counter()
        res = run_workload(dags, sch, n_machines=n_m, interarrival=1.0,
                           seed=88, build_machines=4, profile=common.PROFILE)
        dt = time.perf_counter() - t0
        tag = sch.replace("+", "_")
        emit(f"s8_online_large_m{n_m}_j{n_j}_{tag}", dt * 1e6,
             round(float(np.median(res.jcts())), 1))
        if common.PROFILE:
            emit_phases(f"s8_online_large_{tag}", res.phase_times)
            emit(f"s8_online_large_{tag}_heartbeat_kernel", 0.0,
                 kernels.heartbeat_impl("machines_with_candidates", n_m))
        if sch == "dagps":
            res_dagps = res
    # build-service variant: identical scenario with per-arrival
    # construction overlapped across the worker pool (the tentpole
    # cross-job lever) — the schedule cache is cleared so construction is
    # honestly re-paid, and re-filled by this run for s9.  `derived`
    # (median JCT) must equal the serial row: decisions are bit-identical.
    # Pinned to 2 workers by default so the row NAME (and with it the
    # committed-baseline match + CI gate) is host-independent; crank
    # REPRO_BENCH_BUILD_WORKERS on bigger machines to see the scaling.
    workers = max(int(os.environ.get("REPRO_BENCH_BUILD_WORKERS", "2")), 2)
    clear_schedule_cache()
    t0 = time.perf_counter()
    res_w = run_workload(dags, "dagps", n_machines=n_m, interarrival=1.0,
                         seed=88, build_machines=4, build_workers=workers,
                         profile=common.PROFILE)
    dt = time.perf_counter() - t0
    emit(f"s8_online_large_m{n_m}_j{n_j}_dagps_w{workers}", dt * 1e6,
         round(float(np.median(res_w.jcts())), 1))
    if common.PROFILE:
        emit_phases(f"s8_online_large_dagps_w{workers}", res_w.phase_times)
        emit("s8_online_large_build_workers", 0.0, workers)
        b1 = res_dagps.phase_times["build"]
        bn = res_w.phase_times["build"]
        emit("s8_online_large_build_speedup", 0.0,
             round(b1 / max(bn, 1e-9), 2))


def bench_online_churn() -> None:
    """s9: s8's population under failures, stragglers and speculation.

    Same DAGs and seed as s8, so the offline builds come from the exact
    schedule cache when both scenarios run in one process; what this row
    times is the online machinery under churn (requeue on machine failure,
    straggler stretch, speculative copies and sibling kills) at scale.
    """
    from benchmarks import common

    n_m, n_j = (500, 200) if common.QUICK else (800, 320)
    dags = online_mix_workload(n_j, seed=88)
    t0 = time.perf_counter()
    res = run_workload(dags, "dagps", n_machines=n_m, interarrival=1.0,
                       seed=88, build_machines=4, profile=common.PROFILE,
                       straggle_prob=0.05, straggle_factor=(2.0, 5.0),
                       speculate=True, failure_rate=1 / 120.0,
                       repair_time=60.0)
    dt = time.perf_counter() - t0
    emit(f"s9_online_churn_m{n_m}_j{n_j}_dagps", dt * 1e6,
         round(float(np.median(res.jcts())), 1))
    # counter rows: us_per_call 0 so the CI regression gate (which keys on
    # s*_ timings) doesn't re-gate the same wall clock under three names
    emit("s9_online_churn_speculative_launches", 0.0,
         res.speculative_launches)
    emit("s9_online_churn_tasks_requeued", 0.0,
         res.failed_tasks_requeued)
    if common.PROFILE:
        emit_phases("s9_online_churn_dagps", res.phase_times)


def bench_online_sharded() -> None:
    """s10: sharded heartbeat matching at 2k-10k+ machines.

    Scaling ladder at fixed machines-per-shard (2048) over one fixed job
    population: shard count grows with the cluster, so each shard's
    batched eligibility launch covers a constant machine slice and
    per-heartbeat (wave) match latency must stay flat in m (within
    noise) — the `_match_us_per_wave` rows are the flatness evidence.
    Decisions are bit-identical across shard counts (the sharded wave
    pins pick order to one global matcher; tests/test_shard.py), so
    `derived` median JCTs double as an output-stability check.  Per-shard
    heartbeat-kernel seconds and the auto-selected impl (xla at >=
    `kernels.heartbeat_device_min_m()` machines per launch) are emitted
    as counter rows.  Quick mode runs one 2-shard 2048-machine row for
    the CI regression gate.
    """
    from repro.core.engine import kernels
    from benchmarks import common

    n_j = 120 if common.QUICK else 200
    dags = online_mix_workload(n_j, seed=88)
    sizes = ((2048, 2),) if common.QUICK else ((2048, 1), (4096, 2),
                                               (10240, 5))
    for n_m, n_shards in sizes:
        t0 = time.perf_counter()
        res = run_workload(dags, "dagps", n_machines=n_m, interarrival=0.5,
                           seed=88, build_machines=4,
                           matcher_shards=n_shards, profile=common.PROFILE)
        dt = time.perf_counter() - t0
        emit(f"s10_online_sharded_m{n_m}_s{n_shards}_dagps", dt * 1e6,
             round(float(np.median(res.jcts())), 1))
        ss = res.shard_stats
        emit(f"s10_online_sharded_m{n_m}_waves", 0.0, ss["waves"])
        emit(f"s10_online_sharded_m{n_m}_heartbeat_kernel", 0.0,
             kernels.heartbeat_impl("machines_with_candidates",
                                    (n_m + n_shards - 1) // n_shards))
        if common.PROFILE:
            emit_phases(f"s10_online_sharded_m{n_m}", res.phase_times)
            # flatness metrics, both per heartbeat wave.  `match_us_per_wave`
            # is raw matcher seconds / waves: on a single-core host it sums
            # the per-shard kernel launches serially.  `critical_wave_us`
            # removes that serialization artifact — non-kernel match time
            # plus the *slowest* shard's kernel time, i.e. the wave latency
            # with one core per shard (the launches release the GIL) — and
            # is the number that must stay flat as m grows at fixed
            # machines-per-shard.  Both sit far below the regression gate's
            # absolute floor, so they are informational (the wall row above
            # is the gated one).
            waves = max(ss["waves"], 1)
            per_wave = res.phase_times["match"] / waves * 1e6
            emit(f"s10_online_sharded_m{n_m}_match_us_per_wave", per_wave,
                 round(per_wave, 1))
            ksum, kmax = sum(ss["kernel_secs"]), max(ss["kernel_secs"])
            crit = (res.phase_times["match"] - ksum + kmax) / waves * 1e6
            emit(f"s10_online_sharded_m{n_m}_critical_wave_us", crit,
                 round(crit, 1))
            for k, sec in enumerate(ss["kernel_secs"]):
                emit(f"s10_online_sharded_m{n_m}_shard{k}_kernel_secs",
                     0.0, sec)


def bench_degraded() -> None:
    """s11: matching under a quarantined eligibility shard (core/faults.py).

    A raise-all plan on shard 0 fails its first launch, quarantines it
    (quarantine_after=1, probes off) and serves every later wave from the
    conservative all-eligible mask — the worst sustained degraded mode the
    recovery policy can park in.  The gated wall row is the degraded run;
    the healthy run rides along for the overhead ratio, and
    ``decisions_equal`` asserts the superset-soundness claim end-to-end:
    degraded decisions are bit-identical (backoff pinned to 0 so the row
    times extra mask work, not injected sleeps).
    """
    from repro.core import FaultPlan, RecoveryPolicy
    from benchmarks import common

    n_m, n_j = (1024, 80) if common.QUICK else (2048, 120)
    dags = online_mix_workload(n_j, seed=88)
    kw = dict(n_machines=n_m, interarrival=0.5, seed=88, build_machines=4,
              matcher_shards=2, profile=common.PROFILE)
    # warm the schedule cache so both timed legs pay zero construction
    run_workload(dags, "dagps", **kw)
    t0 = time.perf_counter()
    healthy = run_workload(dags, "dagps", **kw)
    dt_h = time.perf_counter() - t0
    emit(f"s11_degraded_healthy_m{n_m}_j{n_j}_dagps", dt_h * 1e6,
         round(float(np.median(healthy.jcts())), 1))
    plan = FaultPlan.parse("seed=1;shard_launch:raise@1,shard=0")
    rec = RecoveryPolicy(launch_timeout=None, launch_retries=0, backoff=0.0,
                         backoff_cap=0.0, quarantine_after=1,
                         probe_every=10 ** 9, probe_secs=None)
    t0 = time.perf_counter()
    degraded = run_workload(dags, "dagps", fault_plan=plan, recovery=rec,
                            **kw)
    dt_d = time.perf_counter() - t0
    emit(f"s11_degraded_m{n_m}_j{n_j}_dagps", dt_d * 1e6,
         round(float(np.median(degraded.jcts())), 1))
    # counter rows (us_per_call 0: not re-gated)
    emit("s11_degraded_overhead_ratio", 0.0,
         round(dt_d / max(dt_h, 1e-9), 2))
    emit("s11_degraded_decisions_equal", 0.0, int(
        [repr(j.jct) for j in sorted(degraded.jobs, key=lambda j: j.job_id)]
        == [repr(j.jct) for j in sorted(healthy.jobs, key=lambda j: j.job_id)]
        and repr(degraded.makespan) == repr(healthy.makespan)))
    fs = degraded.fault_stats
    emit("s11_degraded_injections", 0.0,
         fs["injections"].get("shard_launch.raise", 0))
    emit("s11_degraded_quarantines", 0.0, fs["shard"]["quarantines"])
    emit("s11_degraded_quarantined_launches", 0.0,
         fs["shard"]["quarantined_launches"])
    if common.PROFILE:
        emit_phases("s11_degraded_dagps", degraded.phase_times)
        emit("s11_degraded_recovery_secs", 0.0, fs["recovery_secs"])


def bench_dynamic() -> None:
    """s12: dynamic DAGs — recurring-pipeline edits with incremental repair.

    Micro rows first: one recurring-pipeline template is built, mutated
    (stage resize / stage append / deadline retarget), and re-planned both
    ways — ``rebuild_schedule`` (delta: untouched partitions replay from
    the previous build) vs a fresh ``build_schedule`` — with the bit-parity
    oracle asserting the two schedules are identical.  The ``_speedup``
    rows quantify what the replay saves; ``_reuse_pct`` rows report the
    placements replayed (the >=50% acceptance metric for resize/append).

    Scenario rows then run the three s12 arms end-to-end through the
    simulator (sim/workload.s12_dynamic): `resize` edits each later
    arrival of a recurring pipeline pre-arrival, `retime` pulls every
    deadline in (nothing replays — the contrast arm), `midrun` mutates a
    *running* job and edits a machine speed.  Counter rows surface
    SimResult.mutation_stats; us_per_call 0 keeps them ungated.
    """
    from repro.core.builder import assert_schedules_equal, rebuild_schedule
    from repro.sim.workload import (mut_append_stage, mut_resize_stage,
                                    mut_retarget, periodic_dag, s12_dynamic)
    from benchmarks import common

    m = 4
    template = periodic_dag(np.random.default_rng(5), name="recurring")
    base = build_schedule(template, m)
    for name, mut in (("resize", mut_resize_stage(stage=1, delta_q=1)),
                      ("append", mut_append_stage()),
                      ("retime", mut_retarget(0.8))):
        new_dag, _delta = mut(template)
        t0 = time.perf_counter()
        delta_s = rebuild_schedule(base, new_dag)
        t_delta = time.perf_counter() - t0
        t0 = time.perf_counter()
        full_s = build_schedule(new_dag, m)
        t_full = time.perf_counter() - t0
        assert_schedules_equal(delta_s, full_s)   # bit-parity oracle
        info = delta_s.build_info
        reuse = info.reused_tasks / max(new_dag.n, 1)
        emit(f"s12_dynamic_rebuild_{name}_delta", t_delta * 1e6,
             round(t_delta, 4))
        emit(f"s12_dynamic_rebuild_{name}_full", t_full * 1e6,
             round(t_full, 4))
        emit(f"s12_dynamic_rebuild_{name}_speedup", 0.0,
             round(t_full / max(t_delta, 1e-9), 2))
        emit(f"s12_dynamic_rebuild_{name}_reuse_pct", 0.0,
             round(100 * reuse, 1))

    n_j = 5 if common.QUICK else 8
    for kind in ("resize", "retime", "midrun"):
        dags, muts = s12_dynamic(kind, n_jobs=n_j, seed=5)
        t0 = time.perf_counter()
        res = run_workload(dags, "dagps", n_machines=16, interarrival=10.0,
                           seed=5, mutations=muts)
        dt = time.perf_counter() - t0
        emit(f"s12_dynamic_{kind}_j{n_j}_dagps", dt * 1e6,
             round(float(np.median(res.jcts())), 1))
        ms = res.mutation_stats
        emit(f"s12_dynamic_{kind}_placement_reuse_pct", 0.0,
             round(100 * ms["tasks_reused"] / max(ms["tasks_total"], 1), 1))
        emit(f"s12_dynamic_{kind}_delta_builds", 0.0, ms["delta_builds"])
        emit(f"s12_dynamic_{kind}_full_builds", 0.0, ms["full_builds"])
        emit(f"s12_dynamic_{kind}_mutations_applied", 0.0,
             ms["applied"] + ms["pre_arrival"] + ms["speed_changes"])


def bench_device_wave() -> None:
    """s13: device-resident fused heartbeat wave (core/engine/wave.py).

    Drives ``ShardedMatcher.match_wave`` directly at m=2048 in steady
    state — picked machines are refilled between waves, so the device
    mirror re-syncs through the dirty-row scatter, never a full upload.
    Two legs over identical waves: the numpy host loop fed by the PR 6
    batched eligibility launch, and the fused xla wave.  The gated wall
    row is the fused per-wave latency; counter rows assert the pick
    sequences are bit-identical and derive per-wave launches and
    host<->device transfer bytes for both legs — the >=10x traffic
    reduction the device-resident state buys.  A routed-vs-exact sim
    pair rides along quantifying the lossy preset's JCT/Jain gap.
    """
    import os

    from repro.core.engine import kernels
    from repro.core.online import CandidateBatch, MatcherConfig
    from repro.core.shard import ShardedMatcher
    from benchmarks import common

    m, d, n = 2048, 4, 512
    n_waves = 8 if common.QUICK else 32
    rng = np.random.default_rng(13)
    cfg = MatcherConfig()
    shares = {0: 1.0, 1: 1.0}
    batch = CandidateBatch(
        dem=rng.uniform(0.05, 0.3, (n, d)),
        pri=rng.uniform(0.5, 2.0, n),
        srpt=rng.uniform(1.0, 300.0, n),
        grp=rng.integers(0, 2, n),
        loc=np.where(rng.random(n) < 0.3, rng.integers(0, m, n), -1),
        job=np.arange(n), tid=np.arange(n))
    avail0 = rng.uniform(0.2, 1.0, (m, d))
    alive = np.ones(m, bool)

    def leg(impl: str) -> tuple[list, float]:
        """Run the fixed wave sequence under one forced impl."""
        os.environ[kernels.KERNELS_ENV] = f"match_wave={impl}"
        sm = ShardedMatcher(cfg, m, shares, n_shards=1, capacity=float(m))
        avail = avail0.copy()
        picks: list = []
        with sm:
            def one_wave():
                got = []

                def cb(gi, mm):
                    got.append((gi, int(mm)))
                    avail[mm] -= batch.dem[gi]

                sm.match_wave(avail, alive, batch, cb)
                for gi, mm in got:          # tasks complete: refill the
                    avail[mm] += batch.dem[gi]   # picked rows (dirty set)
                return got

            one_wave()                      # warm caches / compile
            kernels.reset_profile()         # count only the timed waves
            t0 = time.perf_counter()
            for _ in range(n_waves):
                picks.append(one_wave())
            dt = time.perf_counter() - t0
        return picks, dt / n_waves * 1e6

    saved = os.environ.get(kernels.KERNELS_ENV)
    try:
        np_picks, np_us = leg("numpy")
        prof = kernels.profile_snapshot()
        pr6_bytes = sum(prof.get(f"machines_with_candidates.xla.{k}",
                                 (0, 0))[0]
                        for k in ("bytes_h2d", "bytes_d2h"))
        dev_picks, dev_us = leg("xla")
        prof = kernels.profile_snapshot()
        dev_bytes = sum(prof.get(f"match_wave.xla.{k}", (0, 0))[0]
                        for k in ("bytes_h2d", "bytes_d2h"))
        launches = prof.get("match_wave.xla.launches", (0, 0))[0]
        waves = max(prof.get("match_wave.xla.waves", (0, 0))[0], 1)
    finally:
        kernels.reset_demotions()
        if saved is None:
            os.environ.pop(kernels.KERNELS_ENV, None)
        else:
            os.environ[kernels.KERNELS_ENV] = saved
    emit("s13_device_wave", dev_us, round(dev_us, 1))
    emit(f"s13_wave_numpy_us_per_wave_m{m}", np_us, round(np_us, 1))
    emit("s13_wave_decisions_equal", 0.0, int(np_picks == dev_picks))
    emit("s13_wave_launches_per_wave", 0.0, round(launches / waves, 2))
    emit(f"s13_wave_bytes_per_wave_pr6_m{m}", 0.0, pr6_bytes // n_waves)
    emit(f"s13_wave_bytes_per_wave_device_m{m}", 0.0, dev_bytes // n_waves)
    emit("s13_wave_transfer_reduction_x", 0.0,
         round(pr6_bytes / max(dev_bytes, 1), 1))

    # routed preset: distributed per-shard matching, explicitly lossy —
    # quantify what it costs against the decision-exact global wave
    n_j = 20 if common.QUICK else 60
    dags = online_mix_workload(n_j, seed=77)
    kw = dict(n_machines=64, interarrival=2.0, n_groups=2, seed=77,
              matcher_shards=4)
    exact = run_workload(dags, "dagps", **kw)
    routed = run_workload(dags, "dagps", matcher_mode="routed", **kw)
    gap = 100 * (float(np.median(routed.jcts())) /
                 max(float(np.median(exact.jcts())), 1e-9) - 1.0)
    emit("s13_routed_jct_gap_pct", 0.0, round(gap, 1))
    emit("s13_routed_jain_exact", 0.0,
         round(exact.jain_index(60.0, shares), 3))
    emit("s13_routed_jain_routed", 0.0,
         round(routed.jain_index(60.0, shares), 3))


def bench_service() -> None:
    """s14: the scheduler service (svc/) vs the in-process simulator.

    The same arrival list runs twice: through `ClusterSim`, and through a
    real inproc service — central scheduler, one message-comm agent per
    machine, a streaming client, acks/retransmit timers and all — driven
    in virtual time.  The gated wall row is the service run (what the
    comm + lease machinery costs over the bare event loop);
    ``decisions_equal`` asserts the healthy-path parity claim end-to-end
    (every placement and JCT bit-identical).  A chaos leg then re-runs
    the workload under a drop/dup/delay + crash + partition plan and
    reports the liveness accounting (all jobs done, exactly-once
    effective placements, lease reclaims)."""
    from repro.core import FaultPlan
    from repro.sim.cluster import ClusterSim, SimConfig, scheme
    from repro.svc import ServiceConfig, run_service_workload
    from benchmarks import common

    n_m, n_j = (12, 6) if common.QUICK else (24, 16)
    dags = make_workload("production", n_j, seed=3)
    rng = np.random.default_rng(0)
    arrivals, t = [], 0.0
    for i, dag in enumerate(dags):
        arrivals.append((t, dag, i % 2))
        t += float(rng.exponential(25.0))
    spec = scheme("dagps")

    t0 = time.perf_counter()
    sim = ClusterSim(SimConfig(n_machines=n_m, seed=0, speculate=False,
                               record_placements=True,
                               fault_plan=FaultPlan()), spec).run(arrivals)
    dt_sim = time.perf_counter() - t0
    emit(f"s14_service_sim_m{n_m}_j{n_j}_dagps", dt_sim * 1e6,
         round(float(np.median(sim.jcts())), 1))

    t0 = time.perf_counter()
    svc = run_service_workload(arrivals, ServiceConfig(n_machines=n_m,
                                                       seed=0),
                               spec, fault_plan=FaultPlan())
    dt_svc = time.perf_counter() - t0
    emit(f"s14_service_m{n_m}_j{n_j}_dagps", dt_svc * 1e6,
         round(float(np.median(svc.jcts())), 1))
    emit("s14_service_overhead_ratio", 0.0,
         round(dt_svc / max(dt_sim, 1e-9), 2))
    emit("s14_service_decisions_equal", 0.0, int(
        svc.placements == sim.placements
        and sorted((j.job_id, repr(j.jct)) for j in svc.jobs)
        == sorted((j.job_id, repr(j.jct)) for j in sim.jobs)
        and repr(svc.makespan) == repr(sim.makespan)))
    comm = svc.fault_stats["comm"]
    emit("s14_service_msgs_sent", 0.0, comm["sent"])
    emit("s14_service_placements", 0.0,
         svc.fault_stats["service"]["placements"])

    chaos_plan = ("seed=5;comm_send:drop@0.08;comm_send:dup@0.08;"
                  "comm_send:delay@0.05,delay=0.5;"
                  "agent:crash@1.0,machine=3,count=1;"
                  "agent:partition@0.03,delay=4.0;heartbeat:drop@0.08")
    t0 = time.perf_counter()
    chaos = run_service_workload(arrivals, ServiceConfig(n_machines=n_m,
                                                         seed=0),
                                 spec, fault_plan=chaos_plan)
    dt_chaos = time.perf_counter() - t0
    emit(f"s14_service_chaos_m{n_m}_j{n_j}_dagps", dt_chaos * 1e6,
         round(float(np.median(chaos.jcts())), 1))
    emit("s14_service_chaos_jobs_done", 0.0,
         int(len(chaos.jobs) == len(arrivals)))
    emit("s14_service_chaos_exactly_once", 0.0,
         int(all(v == 1 for v in chaos.effective.values())
             and len(chaos.effective) == sum(d.n for d in dags)))
    cfs = chaos.fault_stats
    emit("s14_service_chaos_lease_reclaims", 0.0,
         cfs["service"]["lease_reclaims"])
    emit("s14_service_chaos_stale_done", 0.0, cfs["service"]["stale_done"])
    emit("s14_service_chaos_retransmits", 0.0, cfs["comm"]["retransmits"])


ALL = [bench_jct, bench_makespan, bench_fairness, bench_alternatives,
       bench_lowerbound, bench_sensitivity, bench_domains, bench_construction,
       bench_online_large, bench_online_churn, bench_online_sharded,
       bench_degraded, bench_dynamic, bench_device_wave, bench_service]
