"""Plain reference of the served scheduler's decisions (the paper's §5,
Fig. 8), independent of the program.

Replays the benchmark's cluster from the same traffic, event by event,
in the order the benchmark's driver offers them (arrivals, machine
heartbeats, lease completions; ties in time in the order they were
scheduled), and decides every heartbeat wave itself:

  * a wave runs after each event that changed the scheduler's state (a
    submit, or the completions that finished at one instant, settled in
    grant order), over every machine, most free capacity first;
  * each job exposes its ``expose`` runnable tasks of highest priScore;
  * on each machine, tasks are picked one at a time, up to
    ``bundle_limit``: among the tasks that fit (or, failing any, that fit
    on cores and memory and overbook network and disk by at most
    ``max_overbook`` - 1), the highest ``pri * packing - eta * srpt``,
    where packing is the dot product of demand and free capacity (times
    ``1 - overshoot`` for an overbooked task) and ``eta`` scales SRPT by
    the running averages of the picks' scores and srpt;
  * a granted task runs its duration stretched by its machine's largest
    network or disk load after the grant, if over 1.

Fairness follows the paper's bounded unfairness (§5): one deficit
counter per fairness group of the configuration, kept in the ledger's
precision and starting at 0.  After each pick every group ``g`` gains
``share_g * f(demand)`` and the picked task's group loses ``f(demand)``,
where ``f`` is 1 for slot fairness and the task's largest demand share
(of a machine of capacity 1) for DRF, and shares are normalised to sum
to 1.  Before each pick, if the most deprived group (the largest
deficit) is at least ``kappa * C`` behind (``C``: the cluster's
machines) and has a task that could be picked on this machine, only its
tasks can be; the fit-before-overbook rule then applies among them.  The
text leaves ties open: the most deprived group is the first of the
largest deficits in the configuration's order of groups, and when that
group has nothing to pick here, the others pick as usual (the next most
deprived group is not forced).  One counter serves the whole cluster,
whatever the number of matcher shards.

Each job's priScore is an input: the builder's answer, which
`ref_build` checks on its own.  Imports nothing of the program.
"""

from __future__ import annotations

import heapq

import numpy as np

_ARR, _DONE, _HB = range(3)
EPS = 1e-9


class Policy:
    def __init__(self, p: dict, fairness: dict):
        self.expose = int(p["expose_per_job"])
        self.eta_m = float(p["eta_m"])
        self.slack = float(p["max_overbook"]) - 1.0
        self.bundle = int(p["bundle_limit"])
        self.rigid = list(p["rigid_dims"])
        self.fungible = list(p["fungible_dims"])
        self.groups = list(fairness["groups"])
        self.shares = [float(s) for s in fairness["shares"]]   # sum to 1
        self.kappa = float(fairness["kappa"])
        self.drf = fairness["f"] == "drf"


class _Job:
    def __init__(self, tk, pri, t: float, group: int):
        self.tk, self.pri, self.arrival, self.group = tk, pri, t, group
        n = tk.n
        self.pending = np.array([len(p) for p in tk.parents])
        kids: list[list[int]] = [[] for _ in range(n)]
        for i, ps in enumerate(tk.parents):
            for p in ps:
                kids[int(p)].append(i)
        self.children = kids
        self.runnable = {i for i in range(n) if self.pending[i] == 0}
        self.work = tk.duration * np.abs(tk.demand).sum(axis=1)
        self.srpt = float(self.work.sum())
        self.n_done = 0


class Cluster:
    """The reference scheduler and its cluster, stepped like the driver."""

    def __init__(self, traffic, n_machines: int, d: int, period: float,
                 policy: Policy, pri: list, ledger=np.float64):
        self.traffic = traffic
        self.pol = policy
        self.pri = pri                # job id -> priScore
        self.period = period
        #: free capacity per machine, in the configuration's precision
        self.avail = np.ones((n_machines, d), dtype=ledger)
        self.arrivals = traffic.arrivals()
        self.events: list = []
        self._n = 0
        for k, a in enumerate(self.arrivals):
            self._push(a.t, _ARR, k)
        for m in range(n_machines):
            self._push(period * (m + 1) / n_machines, _HB, m)
        self.jobs: list[_Job] = []
        self.live: list[int] = []     # unfinished jobs, in submit order
        self.leases: dict[int, tuple[int, int, int]] = {}
        self.next_lease = 1
        self.ema_score = self.ema_srpt = 1.0
        #: the groups' shares and deficit counters, in the configuration's
        #: order and the ledger's precision (scalars: a group or a few)
        self.slot = {g: k for k, g in enumerate(policy.groups)}
        self.ledger = ledger
        self.share = [ledger(x) for x in policy.shares]
        self.deficit = [ledger(0.0)] * len(policy.groups)
        self.must_at = policy.kappa * float(n_machines)
        #: picks made while a group was owed service, and the largest
        #: deficit any group reached
        self.must_serve_picks = 0
        self.deficit_peak = 0.0
        self.overrun = False
        self.placements: list[tuple[float, int, int, int]] = []
        self.results: list[tuple[int, float, float]] = []
        #: (job, task) -> the time its machine finishes it
        self.ends: dict[tuple[int, int], float] = {}

    def _push(self, t, code, arg) -> None:
        heapq.heappush(self.events, (t, self._n, code, arg))
        self._n += 1

    def step(self) -> None:
        t, _, code, arg = heapq.heappop(self.events)
        dirty = False
        if code == _ARR:
            a = self.arrivals[arg]
            j = len(self.jobs)
            self.jobs.append(_Job(self.traffic.tasks(a), self.pri[j], t,
                                  self.slot[a.group]))
            self.live.append(j)
            dirty = True
        elif code == _DONE:
            done = [arg]
            while self.events and self.events[0][2] == _DONE \
                    and self.events[0][0] <= t + 1e-9:
                done.append(heapq.heappop(self.events)[3])
            for lease in sorted(done):
                self._settle(lease, t)
            dirty = True
        else:
            self._push(t + self.period, _HB, arg)
        if dirty:
            for t_done, m, lease in sorted(self._wave(t), key=lambda x: x[1:]):
                self._push(t_done, _DONE, lease)

    def run_steps(self, n: int) -> None:
        """Handle ``n`` events, or stop short at an arrival past the jobs
        whose priScore is known (``overrun``): the replay has then left
        the run's own order of events, so the decisions already differ."""
        for _ in range(n):
            if self.events[0][2] == _ARR and len(self.jobs) == len(self.pri):
                self.overrun = True
                return
            self.step()

    def _settle(self, lease: int, t: float) -> None:
        j, task, m = self.leases.pop(lease)
        job = self.jobs[j]
        self.avail[m] += job.tk.demand[task]
        job.n_done += 1
        job.srpt -= float(job.work[task])
        for c in job.children[task]:
            job.pending[c] -= 1
            if job.pending[c] == 0:
                job.runnable.add(c)
        if job.n_done == job.tk.n:
            self.live.remove(j)
            self.results.append((j, job.arrival, t))

    # -- one wave ----------------------------------------------------------

    def _candidates(self):
        rows = []
        for j in self.live:
            job = self.jobs[j]
            top = sorted(job.runnable, key=lambda i: -job.pri[i])
            rows += [(j, i) for i in top[:self.pol.expose]]
        return rows

    def _wave(self, now: float) -> list:
        rows = self._candidates()
        if not rows:
            return []
        pol = self.pol
        job = np.array([r[0] for r in rows])
        tid = np.array([r[1] for r in rows])
        dem = np.stack([self.jobs[j].tk.demand[i] for j, i in rows])
        pri = np.array([self.jobs[j].pri[i] for j, i in rows],
                       dtype=np.float64)
        srpt = np.array([self.jobs[j].srpt for j in job], dtype=np.float64)
        grp = np.array([self.jobs[j].group for j in job])
        av = self.avail
        # which machines could take a task as the wave starts (a machine's
        # free capacity changes only when the wave reaches it)
        fits = (dem[:, None, :] <= av[None] + EPS).all(axis=2)
        over = (~fits
                & (dem[:, None, pol.rigid] <= av[None][:, :, pol.rigid]
                   + EPS).all(axis=2)
                & (dem[:, None, pol.fungible] <= av[None][:, :, pol.fungible]
                   + pol.slack + EPS).all(axis=2))
        can = fits | over
        active = np.ones(len(rows), dtype=bool)
        order = np.argsort(-av.sum(axis=1))
        visit = order[(av[order] > 1e-9).any(axis=1) & can[:, order].any(axis=0)]
        granted = []
        for m in visit.tolist():
            if not active.any():
                break
            if not (can[:, m] & active).any():
                continue
            for i in self._match(av[m], dem, pri, srpt, grp, active):
                active[i] = False
                j, task = int(job[i]), int(tid[i])
                av[m] -= dem[i]
                load = 1.0 - av[m]
                expected = float(self.jobs[j].tk.duration[task]) \
                    * float(max(load[pol.fungible].max(), 1.0))
                lease = self.next_lease
                self.next_lease += 1
                self.leases[lease] = (j, task, m)
                self.jobs[j].runnable.discard(task)
                self.placements.append((now, j, task, m))
                self.ends[(j, task)] = now + expected
                granted.append((now + expected, m, lease))
        return granted

    def _match(self, row, dem, pri, srpt, grp, active) -> list[int]:
        """Tasks picked for one machine, in pick order."""
        pol = self.pol
        free = row.astype(np.float64).copy()
        taken = ~active
        rig, fun = pol.rigid, pol.fungible
        picked: list[int] = []
        while len(picked) < pol.bundle:
            fits = (dem <= free + EPS).all(axis=1)
            over = (~fits & (dem[:, rig] <= free[rig] + EPS).all(axis=1)
                    & (dem[:, fun] <= free[fun] + pol.slack + EPS).all(axis=1))
            ok = (fits | over) & ~taken
            most = max(self.deficit)
            if most >= self.must_at:
                mine = ok & (grp == self.deficit.index(most))
                if mine.any():
                    ok = mine
                    self.must_serve_picks += 1
            if not ok.any():
                break
            pos = np.clip(free, 0.0, None)
            dot = dem[:, 0] * pos[0]
            for k in range(1, dem.shape[1]):
                dot = dot + dem[:, k] * pos[k]
            shoot = np.clip((dem[:, fun] - free[fun]).max(axis=1), 0.0, None)
            base = np.where(fits, dot, dot * np.maximum(1.0 - shoot, 0.05))
            eta = self.eta_m_now()
            perf = pri * base - eta * srpt
            pool = ok & fits if (ok & fits).any() else ok
            score = np.where(pool, perf, -np.inf)
            i = int(np.argmax(score))
            if not np.isfinite(score[i]):
                break
            taken[i] = True
            picked.append(i)
            a = 0.05
            self.ema_score = (1 - a) * self.ema_score + a * float(pri[i] * base[i])
            self.ema_srpt = (1 - a) * self.ema_srpt + a * max(float(srpt[i]), 1e-12)
            free -= dem[i]
            np.clip(free, 0.0, None, out=free)
            self._allocated(int(grp[i]), dem[i])
        return picked

    def _allocated(self, g: int, demand: np.ndarray) -> None:
        """Every group gains its share of the pick's weight; the picked
        task's group pays the weight."""
        w = self.ledger(demand.max() if self.pol.drf else 1.0)
        d = self.deficit
        for k, share in enumerate(self.share):
            d[k] = d[k] + share * w
        d[g] = d[g] - w
        self.deficit_peak = max(self.deficit_peak, float(max(d)))

    def eta_m_now(self) -> float:
        return self.pol.eta_m * self.ema_score / max(self.ema_srpt, 1e-12)
