"""Plain reference of DAGPS's offline builder (the paper's §4, Figs. 5-7).

One job's task arrays in, its preferred schedule order out, as the
priScore the online matcher ranks tasks by (1 for the first task to
start, towards 0 for the last).  Imports nothing of the program and is
written for plainness, not speed: every candidate variant runs on its
own copy of the virtual space, and the only pruning is the exact one
(a variant whose span reaches the incumbent's is dropped; the search
stops once the incumbent meets the lower bound).

The search, as the paper and the configuration's ``builder`` knobs set
it:

  1. candidate troublesome sets T from sweeps of LongScore (duration over
     the longest) and FragScore (a stage's work over its greedy packing
     time) thresholds, each closed under "on a path between two T tasks";
  2. T placed first, forward or backward, whichever is more compact;
  3. the rest as P (T's ancestors), C (T's descendants) and O (others) in
     the four dead-end-free orders T-OPC, T-OCP, T-COP, T-POC;
  4. the most compact schedule wins; ties go to the earlier variant.

A job whose tasks split into totally ordered parts (every task of one
part precedes every task of the next) is built part by part, the parts
laid end to end.

Placement is on ``m`` unit-capacity machines in time ticks: forward puts
a task at the earliest (start, lowest machine) at or after its parents'
end that fits its demand for its whole duration, backward at the latest
(start, lowest machine) ending by its children's start.  The space is a
float32 grid of free capacity, read against the float64 demand.
"""

from __future__ import annotations

import dataclasses
import heapq
import math

import numpy as np


@dataclasses.dataclass(frozen=True)
class Knobs:
    m: int                 # machines of the virtual space
    ticks: int = 256       # time ticks over the job's lower-bound horizon
    n_long: int = 8        # LongScore threshold levels
    n_frag: int = 6        # FragScore threshold levels
    max_candidates: int = 24


class Graph:
    """One job: durations, demands, stages and direct parents, in
    topological order, with its reachability as a boolean matrix."""

    def __init__(self, duration, demand, stage_of, parents):
        self.duration = np.asarray(duration, dtype=np.float64)
        self.demand = np.asarray(demand, dtype=np.float64)
        self.stage_of = np.asarray(stage_of, dtype=np.int64)
        self.parents = [np.asarray(p, dtype=np.int64) for p in parents]
        n = self.n = len(self.duration)
        kids: list[list[int]] = [[] for _ in range(n)]
        for i, ps in enumerate(self.parents):
            for p in ps:
                kids[int(p)].append(i)
        self.children = [np.asarray(k, dtype=np.int64) for k in kids]
        n_stages = int(self.stage_of.max()) + 1 if n else 0
        self.stages = [np.nonzero(self.stage_of == s)[0]
                       for s in range(n_stages)]
        #: anc[i, j]: j is a strict ancestor of i
        self.anc = np.zeros((n, n), dtype=bool)
        for i in range(n):
            for p in self.parents[i]:
                self.anc[i] |= self.anc[p]
                self.anc[i, p] = True

    def split(self, t_mask):
        """(T, O, P, C) masks around a closed T."""
        ids = np.nonzero(t_mask)[0]
        up = self.anc[ids].any(axis=0)          # ancestors of T
        down = self.anc[:, ids].any(axis=1)     # descendants of T
        p = up & ~t_mask
        c = down & ~t_mask
        return t_mask, ~(t_mask | p | c), p, c

    def closure(self, mask):
        """T plus every task on a path between two T tasks."""
        ids = np.nonzero(mask)[0]
        if len(ids) == 0:
            return mask.copy()
        return mask | (self.anc[ids].any(axis=0)
                       & self.anc[:, ids].any(axis=1))

    def sub(self, ids):
        """The job restricted to ``ids`` (stages renumbered densely)."""
        where = {int(t): k for k, t in enumerate(ids)}
        parents = [np.asarray(sorted(where[int(p)] for p in self.parents[t]
                                     if int(p) in where), dtype=np.int64)
                   for t in ids]
        _, stage = np.unique(self.stage_of[ids], return_inverse=True)
        return Graph(self.duration[ids], self.demand[ids], stage, parents)


# ----------------------------------------------------------------------
# the virtual resource-time space
# ----------------------------------------------------------------------

class Space:
    """Free capacity of ``m`` machines over time ticks, grown at either
    end on demand; ticks are logical and may be negative."""

    def __init__(self, m: int, d: int, length: int, dtype=np.float32):
        self.m, self.d = m, d
        self.free = np.ones((m, max(length, 8), d), dtype=dtype)
        self.off = 0                  # physical index = logical + off
        self.lo: int | None = None    # earliest start placed
        self.hi: int | None = None    # latest end placed

    def copy(self) -> "Space":
        s = Space.__new__(Space)
        s.m, s.d, s.off, s.lo, s.hi = self.m, self.d, self.off, self.lo, self.hi
        s.free = self.free.copy()
        return s

    @property
    def start(self) -> int:
        return -self.off

    @property
    def end(self) -> int:
        return self.free.shape[1] - self.off

    @property
    def span(self) -> int:
        return 0 if self.lo is None else self.hi - self.lo

    def _grow_back(self) -> None:
        self.free = np.concatenate([self.free, np.ones_like(self.free)],
                                   axis=1)

    def _grow_front(self) -> None:
        self.off += self.free.shape[1]
        self.free = np.concatenate([np.ones_like(self.free), self.free],
                                   axis=1)

    def _starts(self, v, k: int, lo: int, hi: int):
        """(machines, logical starts) where ``v`` fits over [t, t + k) for
        lo <= t <= hi - k."""
        ok = (self.free[:, lo + self.off:hi + self.off, :] >= v).all(axis=2)
        if k > 1:
            if ok.shape[1] < k:
                return np.empty(0, np.int64), np.empty(0, np.int64)
            c = np.cumsum(ok, axis=1, dtype=np.int32)
            run = c[:, k - 1:].copy()
            run[:, 1:] -= c[:, :run.shape[1] - 1]
            ok = run == k
        ms, ts = np.nonzero(ok)
        return ms, ts + lo

    def earliest(self, v, k: int, ready: int) -> tuple[int, int]:
        while True:
            if ready + self.off < 0:
                self._grow_front()
            elif ready + self.off + k > self.free.shape[1]:
                self._grow_back()
            else:
                ms, ts = self._starts(v, k, ready, self.end)
                if len(ts):
                    j = int(np.argmin(ts))
                    return int(ms[j]), int(ts[j])
                self._grow_back()

    def latest(self, v, k: int, deadline: int) -> tuple[int, int]:
        while True:
            if deadline + self.off > self.free.shape[1]:
                self._grow_back()
            elif deadline - k < self.start:
                self._grow_front()
            else:
                ms, ts = self._starts(v, k, self.start, deadline)
                if len(ts):
                    j = int(np.argmax(ts))
                    return int(ms[j]), int(ts[j])
                self._grow_front()

    def take(self, machine: int, t0: int, k: int, v) -> None:
        p = t0 + self.off
        win = self.free[machine, p:p + k, :]
        win -= v
        self.lo = t0 if self.lo is None else min(self.lo, t0)
        self.hi = t0 + k if self.hi is None else max(self.hi, t0 + k)


# ----------------------------------------------------------------------
# one variant's placement state
# ----------------------------------------------------------------------

class Placer:
    def __init__(self, g: Graph, space: Space, k: np.ndarray):
        self.g, self.space, self.k = g, space, k
        self.start = np.zeros(g.n, dtype=np.int64)
        self.end = np.zeros(g.n, dtype=np.int64)
        self.machine = np.full(g.n, -1, dtype=np.int64)
        self.placed = np.zeros(g.n, dtype=bool)

    def branch(self) -> "Placer":
        p = Placer.__new__(Placer)
        p.g, p.k, p.space = self.g, self.k, self.space.copy()
        p.start, p.end = self.start.copy(), self.end.copy()
        p.machine, p.placed = self.machine.copy(), self.placed.copy()
        return p

    def _anchor(self, t: int, forward: bool) -> int:
        """Forward: the latest end of a placed parent, else the earliest
        start placed (0 in an empty space).  Backward: the earliest start
        of a placed child, else the latest end placed (the grid's end in
        an empty space)."""
        sp = self.space
        if forward:
            ends = [self.end[p] for p in self.g.parents[t] if self.placed[p]]
            if ends:
                return int(max(ends))
            return sp.lo if sp.lo is not None else 0
        starts = [self.start[c] for c in self.g.children[t] if self.placed[c]]
        if starts:
            return int(min(starts))
        return sp.hi if sp.hi is not None else sp.end

    def place_pass(self, ids, forward: bool, limit) -> bool:
        """Place ``ids`` in dependency order within the set (longest task
        first, then the one gating more of the set, then the lower id),
        each at its extreme feasible slot.  False once the span reaches
        ``limit``."""
        g, sp = self.g, self.space
        inside = np.zeros(g.n, dtype=bool)
        inside[ids] = True
        gate = g.parents if forward else g.children
        opens = g.children if forward else g.parents
        tie = np.array([len(x) for x in opens])
        pending = {int(i): int(inside[gate[i]].sum()) for i in ids}
        heap = [(-g.duration[i], -tie[i], i) for i in pending
                if pending[i] == 0]
        heapq.heapify(heap)
        left = len(pending)
        while left:
            if not heap:
                return False
            t = heapq.heappop(heap)[2]
            k = int(self.k[t])
            anchor = self._anchor(t, forward)
            if forward:
                m, t0 = sp.earliest(g.demand[t], k, anchor)
            else:
                m, t0 = sp.latest(g.demand[t], k, anchor)
            sp.take(m, t0, k, g.demand[t])
            self.start[t], self.end[t] = t0, t0 + k
            self.machine[t], self.placed[t] = m, True
            if limit is not None and sp.span >= limit:
                return False
            left -= 1
            for c in opens[t]:
                c = int(c)
                if inside[c]:
                    pending[c] -= 1
                    if pending[c] == 0:
                        heapq.heappush(heap, (-g.duration[c], -tie[c], c))
        return True


def _place(pl: Placer, op: str, ids, limit) -> Placer | None:
    """One segment of a variant on a copy of ``pl``: forward, backward,
    or both and the more compact (forward on a tie)."""
    if op != "best":
        out = pl.branch()
        return out if out.place_pass(ids, op == "fwd", limit) else None
    if len(ids) == 0:
        return pl.branch()
    f = pl.branch()
    ok_f = f.place_pass(ids, True, limit)
    blim = limit if not ok_f else (f.space.span if limit is None
                                   else min(limit, f.space.span))
    b = pl.branch()
    ok_b = b.place_pass(ids, False, blim)
    if ok_f and (not ok_b or f.space.span <= b.space.span):
        return f
    return b if ok_b else None


# ----------------------------------------------------------------------
# the search
# ----------------------------------------------------------------------

def frag_scores(g: Graph, m: int) -> np.ndarray:
    out = np.ones(len(g.stages), dtype=np.float64)
    for s, ids in enumerate(g.stages):
        if len(ids) == 0:
            continue
        dur = float(g.duration[ids].mean())
        peak = float(g.demand[ids].mean(axis=0).max())
        if peak <= 0 or dur <= 0:
            continue
        per_machine = max(int(1.0 / max(peak, 1e-9) + 1e-9), 1)
        exec_time = math.ceil(len(ids) / (m * per_machine)) * dur
        out[s] = min(len(ids) * dur * peak / m / exec_time, 1.0)
    return out


def candidates(g: Graph, kn: Knobs) -> list[np.ndarray]:
    """Closed troublesome sets, the empty set first, at most
    ``max_candidates`` (a spread of sizes when there are more)."""
    long_score = g.duration / max(float(g.duration.max()), 1e-12)
    frag = frag_scores(g, kn.m)[g.stage_of]

    def levels(vals, k):
        u = np.unique(vals)
        return u if len(u) <= k else np.unique(
            np.quantile(u, np.linspace(0, 1, k)))

    ls, fs = levels(long_score, kn.n_long), levels(frag, kn.n_frag)
    empty = np.zeros(g.n, dtype=bool)
    seen, out = {empty.tobytes()}, [empty]
    pairs = [(lv, fv) for lv in ls[::-1] for fv in fs]
    pairs += [(lv, -1.0) for lv in ls[::-1]] + [(2.0, fv) for fv in fs]
    for lv, fv in pairs:
        mask = (long_score >= lv) | (frag <= fv)
        if not mask.any():
            continue
        mask = g.closure(mask)
        if mask.tobytes() not in seen:
            seen.add(mask.tobytes())
            out.append(mask)
    if len(out) > kn.max_candidates:
        order = np.argsort([c.sum() for c in out], kind="stable")
        keep = order[np.unique(np.linspace(0, len(order) - 1,
                                           kn.max_candidates).astype(int))]
        out = [out[i] for i in sorted(keep)]
    return out


def _orders(o, p, c) -> list[list[tuple[str, np.ndarray]]]:
    """T-OPC, T-OCP, T-COP, T-POC (P only backward, C only forward, O
    either way), empty segments dropped, repeats removed."""
    seg = {"O": ("best", o), "P": ("bwd", p), "C": ("fwd", c),
           "Ob": ("bwd", o), "Of": ("fwd", o)}
    names = [["O", "P", "C"], ["O", "C", "P"], ["C", "Ob", "P"],
             ["P", "Of", "C"]]
    if len(p) == 0 and len(c) == 0:
        names = [["O"]]
    out, seen = [], []
    for seq in names:
        key = tuple(s for s in seq if len(seg[s][1]))
        if key not in seen:
            seen.append(key)
            out.append([seg[s] for s in key])
    return out


def _build_one(g: Graph, kn: Knobs, grid):
    """(start ticks, tick length) of the most compact variant."""
    cp = np.zeros(g.n)
    for i in range(g.n):
        ps = g.parents[i]
        cp[i] = (cp[ps].max() if len(ps) else 0.0) + g.duration[i]
    horizon = max(float(cp.max()), float(
        (g.duration[:, None] * g.demand).sum(axis=0).max() / kn.m))
    tick = max(horizon / kn.ticks, 1e-9)
    k = np.maximum(np.ceil(g.duration / tick - 1e-9).astype(np.int64), 1)
    length = int(k.sum() / max(kn.m, 1) + k.max()) + 4
    length = max(length, int(1.25 * horizon / tick) + 4)
    # lower bound on any schedule's span in ticks: the critical path, and
    # each resource's work over m machines
    fin = np.zeros(g.n, dtype=np.int64)
    for i in range(g.n):
        ps = g.parents[i]
        fin[i] = (fin[ps].max() if len(ps) else 0) + k[i]
    work = (k[:, None] * g.demand).sum(axis=0)
    lb = max(int(fin.max()), int(np.ceil(work.max() / max(kn.m, 1) - 1e-12)))
    best_span, best_start = None, None
    for t_mask in candidates(g, kn):
        if best_span is not None and best_span <= lb:
            break
        t_mask, o, p, c = g.split(t_mask)
        base = Placer(g, Space(kn.m, g.demand.shape[1], length, grid), k)
        base = _place(base, "best", np.nonzero(t_mask)[0], best_span)
        if base is None:
            continue
        for seq in _orders(np.nonzero(o)[0], np.nonzero(p)[0],
                           np.nonzero(c)[0]):
            if best_span is not None and best_span <= lb:
                break
            pl = base
            for op, ids in seq:
                pl = _place(pl, op, ids, best_span)
                if pl is None:
                    break
            if pl is not None and (best_span is None
                                   or pl.space.span < best_span):
                best_span, best_start = pl.space.span, pl.start.copy()
    return best_start, tick


def _parts(g: Graph) -> list[np.ndarray]:
    """Totally ordered parts: a cut after task i is valid when every task
    up to i is an ancestor of every later task."""
    cuts = [i for i in range(g.n - 1)
            if g.anc[i + 1:, :i + 1].all()]
    out, prev = [], 0
    for c in cuts:
        out.append(np.arange(prev, c + 1))
        prev = c + 1
    out.append(np.arange(prev, g.n))
    return out


def pri_score(duration, demand, stage_of, parents, kn: Knobs,
              grid=np.float32) -> np.ndarray:
    """The builder's priScore for one job; ``grid`` is the precision of
    the space's free capacity."""
    g = Graph(duration, demand, stage_of, parents)
    if g.n == 0:
        return np.empty(0)
    parts = _parts(g) if g.n > 1 else [np.arange(g.n)]
    start = np.zeros(g.n)
    offset = 0.0
    for ids in parts:
        sub = g.sub(ids) if len(parts) > 1 else g
        ticks, tick = _build_one(sub, kn, grid)
        s = (ticks - ticks.min()).astype(np.float64) * tick
        start[ids] = s + offset
        offset += float((s + sub.duration).max() - s.min())
    order = np.lexsort((np.arange(g.n), start))
    rank = np.empty(g.n, dtype=np.float64)
    rank[order] = np.arange(g.n)
    return 1.0 - rank / g.n
