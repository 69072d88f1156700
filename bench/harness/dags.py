"""DAG shapes for the benchmark's traffic: the yardstick's own copies.

A job family is a file of its own, ``families/<family>.py``, whose
``graph(rng, scale, **params)`` draws one job as a `StageGraph` (tasks
grouped in stages, each stage depending on whole parent stages); a mix
names its family in ``jobs.family``, and `family` finds the file by that
name, so a family is added as a new file.  `expand` turns a stage graph
into task arrays (every task of a stage depends on every task of each
parent stage), with per-task jitter drawn from the caller's generator.
The task arrays are the benchmark's own: the correctness reference reads
them, and the program is handed a DAG built from them.
"""

from __future__ import annotations

import dataclasses
import importlib
import re
from pathlib import Path

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


class Builder:
    """Stages added one at a time, for a family's ``graph``."""

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


FAMILIES = Path(__file__).resolve().parent / "families"


def family(name: str):
    """The ``graph`` of job family ``name``: ``families/<name>.py``."""
    if not re.fullmatch(r"[A-Za-z0-9_]+", name) \
            or not (FAMILIES / f"{name}.py").is_file():
        known = sorted(p.stem for p in FAMILIES.glob("[!_]*.py"))
        raise ValueError(f"unknown job family {name!r}; known: {known}")
    return importlib.import_module(f".families.{name}", __package__).graph


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
