"""The one traffic generator: a mix file of parameters in, arrivals out.

A mix (``bench/traffic/<name>.json``) names a job family, how jobs
recur, and the arrival process.  Arrivals come in blocks of ``block``
jobs.  Everything a block offers is drawn from the mix's own seeds, so
every ``--seed`` gets the same work:

  * its jobs: ``fresh`` — ``block`` new jobs per block, each job drawn
    (shape and per-task jitter) from the mix's job seed and its place;
    ``pool`` — the same ``block`` jobs in every block (a pool of
    recurring pipelines, each resubmitted once per block);
  * its gaps: ``block`` independent exponential draws of mean
    ``mean_gap_s`` (a Poisson process), from the mix's arrival seed.

``--seed`` draws the order of the jobs and of the gaps within each
block, so seeds differ in which job meets which gap and in what order,
not in what is offered.  ``job_scale``, from the configuration, sizes
the jobs (the family's task counts scale with it).

A job's shape comes from its family, ``jobs.family``: the file
``families/<family>.py`` (see `dags.family`), given the mix's other
``jobs`` keys as parameters.

A job's fairness group (its tenant) comes from the mix's optional
``jobs.tenants``: ``{"weights": [w0, w1, ...]}`` gives group ``g`` the
share ``w_g / sum(w)`` of the jobs, ``{"groups": N, "zipf": s}`` the
share ``(g + 1)**-s`` normalised over ``g < N``.  Each job key draws its
group from the job seed and the key, on a generator of its own beside
the shape's (``default_rng([job seed, key, 1])``), so ``--seed`` never
changes which tenant owns a job, a pool pipeline keeps its group on
every resubmission, and the shapes are those of a mix without tenants.
Without ``tenants`` every job is in group 0.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from . import dags


@dataclasses.dataclass
class Arrival:
    t: float
    key: int            # job identity: arrivals with one key are one job
    group: int = 0      # the job's fairness group (tenant)


class Traffic:
    def __init__(self, mix: dict, seed: int, job_scale: float):
        self.mix = mix
        self.seed = int(seed)
        self.job_scale = float(job_scale)
        jobs = mix["jobs"]
        self.mode = jobs["mode"]
        if self.mode not in ("fresh", "pool"):
            raise ValueError(f"unknown jobs.mode {self.mode!r}")
        self.family = jobs["family"]
        self.graph = dags.family(self.family)
        self.params = {k: v for k, v in jobs.items()
                       if k not in ("mode", "family", "seed", "tenants")}
        self.job_seed = int(jobs["seed"])
        self.tenant_p = _tenant_shares(jobs.get("tenants"))
        arr = mix["arrivals"]
        self.block = int(arr["block"])
        self.mean_gap = float(arr["mean_gap_s"])
        self.gap_seed = int(arr["seed"])
        self.warm_blocks = int(mix["warm_blocks"])
        self.n_blocks = int(mix["horizon_blocks"])
        self._tasks: dict[int, dags.Tasks] = {}
        self._groups: dict[int, int] = {}

    def gaps(self, b: int) -> np.ndarray:
        """Block ``b``'s gaps, in the mix's own order."""
        return np.random.default_rng([self.gap_seed, b]).exponential(
            self.mean_gap, self.block)

    def warm_until(self) -> float:
        """Virtual time at which the warm prefix ends: the first arrival
        of block ``warm_blocks``."""
        return self.arrivals()[self.warm_blocks * self.block].t

    def arrivals(self) -> list[Arrival]:
        out = []
        t = 0.0
        for b in range(self.n_blocks):
            rng = np.random.default_rng([self.seed, b])
            order = rng.permutation(self.block)
            gaps = self.gaps(b)[rng.permutation(self.block)]
            for i in range(self.block):
                j = int(order[i])
                key = j if self.mode == "pool" else b * self.block + j
                out.append(Arrival(t, key, self.group(key)))
                t += float(gaps[i])
        return out

    def group(self, key: int) -> int:
        """The fairness group of job ``key``."""
        if self.tenant_p is None:
            return 0
        got = self._groups.get(key)
        if got is None:
            rng = np.random.default_rng([self.job_seed, key, 1])
            got = int(rng.choice(len(self.tenant_p), p=self.tenant_p))
            self._groups[key] = got
        return got

    def tasks(self, a: Arrival) -> dags.Tasks:
        """Task arrays of one arrival's job (made once per job key)."""
        got = self._tasks.get(a.key)
        if got is None:
            rng = np.random.default_rng([self.job_seed, a.key])
            g = self.graph(rng, scale=self.job_scale, **self.params)
            got = dags.expand(g, rng, f"{self.family}-{a.key}")
            self._tasks[a.key] = got
        return got


def _tenant_shares(tenants: dict | None) -> np.ndarray | None:
    """Each group's share of the jobs, from a mix's ``jobs.tenants``."""
    if tenants is None:
        return None
    if set(tenants) == {"weights"}:
        w = np.asarray(tenants["weights"], dtype=np.float64)
    elif set(tenants) == {"groups", "zipf"}:
        w = np.arange(1, int(tenants["groups"]) + 1,
                      dtype=np.float64) ** -float(tenants["zipf"])
    else:
        raise ValueError(f"jobs.tenants takes weights, or groups and zipf: "
                         f"{tenants!r}")
    if len(w) == 0 or not np.all(w >= 0) or w.sum() <= 0:
        raise ValueError(f"jobs.tenants weighs no group: {tenants!r}")
    return w / w.sum()
