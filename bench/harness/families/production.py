"""The production job family: DAGs after the paper's §2.3 statistics of
production jobs (median depth ~7, median in-degree 7 and out-degree 1,
demands with a CoV of ~1 across tasks, durations over three orders of
magnitude, tasks grouped in stages), copied from the program's workload
module so that an edit there cannot move the benchmark.
"""

from __future__ import annotations

import numpy as np

from ..dags import Builder, StageGraph


def _lognormal(rng, median: float, sigma: float) -> float:
    return float(median * np.exp(sigma * rng.standard_normal()))


def graph(rng, scale: float = 1.0, share: int = 4, **_kw) -> StageGraph:
    """Production DAG after §2.3: 2-4 overlap motifs (a wide cheap enabler,
    a long heavy stage and a medium chain stage each), a join tail over
    the long stages, and 2-5 unordered noise stages.  ``share`` is the
    machines the job is sized for."""
    m = max(int(share * scale), 2)
    b = Builder()
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
