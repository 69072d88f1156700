"""DAG shapes for the benchmark's traffic: the yardstick's own copies.

The production DAG generator after the paper's §2.3 statistics of
production jobs (median depth ~7, median in-degree 7 and out-degree 1,
demands with a CoV of ~1 across tasks, durations over three orders of
magnitude, tasks grouped in stages), copied from the program's workload
module so that an edit there cannot move the benchmark.  A generator
returns a `StageGraph`; `expand` turns one into task arrays
(every task of a stage depends on every task of each parent stage), with
per-task jitter drawn from the caller's generator.  The task arrays are
the benchmark's own: the correctness reference reads them, and the
program is handed a DAG built from them.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class StageGraph:
    tasks: tuple[int, ...]
    durations: tuple[float, ...]
    demands: tuple[tuple[float, ...], ...]
    deps: tuple[tuple[int, ...], ...]
    duration_jitter: float = 0.0
    demand_jitter: float = 0.0


@dataclasses.dataclass
class Tasks:
    """One job's task arrays, in topological order."""

    duration: np.ndarray          # (n,) seconds
    demand: np.ndarray            # (n, 4) share of a machine
    stage_of: np.ndarray          # (n,)
    parents: list[np.ndarray]     # direct predecessors of each task
    name: str

    @property
    def n(self) -> int:
        return len(self.duration)


class _Builder:
    def __init__(self):
        self.tasks, self.durs, self.dems, self.deps = [], [], [], []

    def add(self, q, dur, dem, parents) -> int:
        self.tasks.append(max(int(q), 1))
        self.durs.append(float(dur))
        self.dems.append(tuple(float(x) for x in
                               np.clip(np.asarray(dem, float), 0.01, 0.9)))
        self.deps.append(tuple(sorted({int(p) for p in parents})))
        return len(self.tasks) - 1

    def graph(self, duration_jitter=0.0, demand_jitter=0.0) -> StageGraph:
        return StageGraph(tuple(self.tasks), tuple(self.durs),
                          tuple(self.dems), tuple(self.deps),
                          duration_jitter, demand_jitter)


def _lognormal(rng, median: float, sigma: float) -> float:
    return float(median * np.exp(sigma * rng.standard_normal()))


def production(rng, scale: float = 1.0, share: int = 4, **_kw) -> StageGraph:
    """Production DAG after §2.3: 2-4 overlap motifs (a wide cheap enabler,
    a long heavy stage and a medium chain stage each), a join tail over
    the long stages, and 2-5 unordered noise stages.  ``share`` is the
    machines the job is sized for."""
    m = max(int(share * scale), 2)
    b = _Builder()
    long_res = rng.permutation(4)
    prev_tail = None
    long_ids = []
    for g in range(int(rng.integers(2, 5))):
        r_long = int(long_res[g % 4])
        wide_dem = np.full(4, 0.05) * np.exp(0.4 * rng.standard_normal(4))
        wide_dem[r_long] = rng.uniform(0.35, 0.55)
        w = b.add(int(rng.integers(3 * m, 6 * m)),
                  max(1.0, _lognormal(rng, 3.0, 0.5)), wide_dem,
                  [prev_tail] if prev_tail is not None else [])
        long_dem = np.full(4, 0.05) * np.exp(0.4 * rng.standard_normal(4))
        long_dem[r_long] = rng.uniform(0.5, 0.75)
        long_ids.append(b.add(int(rng.integers(max(m // 2, 1), m + 1)),
                              _lognormal(rng, 50.0, 0.4), long_dem, [w]))
        mid_dem = np.full(4, 0.07) * np.exp(0.5 * rng.standard_normal(4))
        mid_dem[int(rng.integers(0, 4))] = rng.uniform(0.25, 0.5)
        prev_tail = b.add(int(rng.integers(2 * m, 4 * m)),
                          max(0.5, _lognormal(rng, 6.0, 0.8)), mid_dem, [w])
    agg_dem = np.full(4, 0.1) * np.exp(0.4 * rng.standard_normal(4))
    agg_dem[int(rng.integers(0, 4))] = rng.uniform(0.2, 0.4)
    b.add(int(rng.integers(1, 4)), max(1.0, _lognormal(rng, 8.0, 0.6)),
          agg_dem, long_ids + ([prev_tail] if prev_tail is not None else []))
    for _ in range(int(rng.integers(2, 6))):
        par = ([int(rng.integers(0, len(b.tasks) - 1))]
               if rng.random() < 0.5 else [])
        dd = np.full(4, 0.08) * np.exp(0.6 * rng.standard_normal(4))
        dd[int(rng.integers(0, 4))] = rng.uniform(0.2, 0.6)
        b.add(max(1, int(_lognormal(rng, 4.0, 0.9))),
              max(0.3, _lognormal(rng, 5.0, 1.1)), dd, par)
    return b.graph(duration_jitter=0.15, demand_jitter=0.1)


FAMILIES = {"production": production}


def _topo(deps, n: int) -> list[int]:
    state = [0] * n
    out: list[int] = []

    def visit(s: int) -> None:
        if state[s] == 2:
            return
        if state[s] == 1:
            raise ValueError("cycle in stage graph")
        state[s] = 1
        for p in deps[s]:
            visit(int(p))
        state[s] = 2
        out.append(s)

    for s in range(n):
        visit(s)
    return out


def expand(g: StageGraph, rng, name: str) -> Tasks:
    """Task arrays of one stage graph; jitter is drawn from ``rng``."""
    ids: list[np.ndarray] = [np.empty(0, np.int64)] * len(g.tasks)
    durations, demands, stage_of, parents = [], [], [], []
    nxt = 0
    for s in _topo(g.deps, len(g.tasks)):
        q = g.tasks[s]
        ids[s] = np.arange(nxt, nxt + q, dtype=np.int64)
        nxt += q
        par = (np.sort(np.concatenate([ids[p] for p in g.deps[s]]))
               if g.deps[s] else np.empty(0, np.int64))
        base_dem = np.asarray(g.demands[s], dtype=np.float64)
        for _ in range(q):
            dur = g.durations[s]
            if g.duration_jitter:
                dur *= 1.0 + g.duration_jitter * float(rng.standard_normal())
            dem = base_dem
            if g.demand_jitter:
                dem = base_dem * (1.0 + g.demand_jitter
                                  * rng.standard_normal(base_dem.shape))
            durations.append(max(dur, 1e-3))
            demands.append(np.clip(dem, 1e-4, 1.0))
            stage_of.append(s)
            parents.append(par)
    return Tasks(np.asarray(durations, dtype=np.float64),
                 np.asarray(demands, dtype=np.float64),
                 np.asarray(stage_of, dtype=np.int64), parents, name)
