"""The benchmark's cluster and clock: a virtual-time driver of the service.

A copy of the virtual-time driver loop of the program's service client
(the event heap, arrivals, per-machine heartbeats every
``heartbeat_period`` and lease completions), kept here so that how the
traffic is offered cannot change with the program.  It calls only the
service's public API: `SchedulerService`, `SchedulerCore`,
`VirtualAgent`, `Client`, `connect`, `ServiceConfig` and
``scheme("dagps")``.

Events are replayed unpaced: each starts as soon as the service has
handled the one before.  The service decides on virtual time alone, so
the pace of the replay cannot change a decision, and a wall-clock window
over the replay measures the fastest pace at which the scheduler keeps
up with this cluster.

Each job is submitted in its fairness group, the arrival's ``group``:
the traffic draws it from the mix's ``jobs.tenants`` (group 0 where the
mix has none), and the configuration's ``fairness`` section lists the
groups the service is told of (`ServiceConfig.groups`).

Each handled event can be timed (`Driver.step` returns its record): the
wall time from taking the event off the heap to the return of the
service's pump that handled it, whether that pump ran a wave, and, for a
submit, the wall seconds the wave took inside it.
"""

from __future__ import annotations

import dataclasses
import functools
import heapq
import itertools
import time


_ARR, _DONE, _HB, _HBA = range(4)
CODE_NAMES = ("submit", "done", "beat", "beat_late")
_RUN_IDS = itertools.count()


@dataclasses.dataclass
class Step:
    code: int
    wall_ns: int        # event off the heap -> the service's pump returned
    waved: bool         # the pump ran a wave
    miss: bool          # a submit whose job the service had not seen
    wave_ns: int        # wall ns of the wave's host work inside a submit


class Driver:
    """One served cluster: a scheduler service, one agent per machine and
    one client, stepped by a single virtual-time event heap."""

    def __init__(self, traffic, cfg, spec):
        from repro.core.dag import DAG
        from repro.core.engine import kernels
        from repro.svc import (Client, SchedulerCore, SchedulerService,
                               connect)
        from repro.svc.agent import VirtualAgent

        self.arrivals = traffic.arrivals()
        unknown = {a.group for a in self.arrivals} - set(cfg.groups)
        if unknown:
            raise ValueError(f"the traffic submits to groups "
                             f"{sorted(unknown)} the configuration does not "
                             f"list ({list(cfg.groups)})")
        self._DAG = DAG
        self._kernels = kernels
        self.traffic = traffic
        self.cfg = cfg
        self.vt = 0.0
        addr = f"inproc://bench-{next(_RUN_IDS)}"
        self.core = SchedulerCore(cfg, spec)
        self.svc = SchedulerService(self.core, addr, clock=self._clock)
        try:
            self.agents = [VirtualAgent(m, connect(addr), cfg.recovery,
                                        clock=self._clock)
                           for m in range(cfg.n_machines)]
            self.busy: set[int] = set(range(cfg.n_machines))
            for a in self.agents:
                a.register(0.0)
                a.ch.comm.on_ready = functools.partial(self.busy.add,
                                                       a.machine)
            self.client = Client(connect(addr), recovery=cfg.recovery,
                                 clock=self._clock)
            self.svc.pump(0.0)
        except BaseException:
            self.svc.close()
            raise
        self._counter = itertools.count()
        self.events: list = []
        for k, a in enumerate(self.arrivals):
            heapq.heappush(self.events, (a.t, next(self._counter), _ARR, k))
        # NodeManagers beat every period at phases spread evenly over it
        period = cfg.heartbeat_period
        n = cfg.n_machines
        for m in range(n):
            heapq.heappush(self.events, (period * (m + 1) / n,
                                         next(self._counter), _HB, m))
        self.seen_jobs: set[int] = set()
        #: task arrays, traffic keys and fairness groups of the service's
        #: jobs, in submit order (= job id)
        self.job_tasks: list = []
        self.job_keys: list[int] = []
        self.job_groups: list[int] = []
        #: job results as the client receives them
        self.results: list = []
        #: (job, task) -> the time its agent finishes it
        self.ends: dict[tuple[int, int], float] = {}
        self.steps = 0

    def _clock(self) -> float:
        return self.vt

    def close(self) -> None:
        self.svc.close()

    # -- one event ---------------------------------------------------------

    def step(self) -> Step:
        perf = time.perf_counter_ns
        t0 = perf()
        t_now, _, code, arg = heapq.heappop(self.events)
        self.vt = t_now
        self.steps += 1
        miss = False
        wave_ns = 0
        if code == _ARR:
            a = self.arrivals[arg]
            miss = a.key not in self.seen_jobs
            self.seen_jobs.add(a.key)
            tk = self.traffic.tasks(a)
            self.job_tasks.append(tk)
            self.job_keys.append(a.key)
            self.job_groups.append(a.group)
            dag = self._DAG(duration=tk.duration, demand=tk.demand,
                            stage_of=tk.stage_of, parents=tk.parents,
                            name=tk.name)
            self.client.submit(dag, group=a.group, t=t_now)
        elif code == _DONE:
            m, lease = arg
            self.agents[m].complete(lease, t_now)
            self.busy.add(m)
            # simultaneous completions settle as one batch before the wave
            while self.events and self.events[0][2] == _DONE \
                    and self.events[0][0] <= t_now + 1e-9:
                _, _, _, (m2, lease2) = heapq.heappop(self.events)
                self.agents[m2].complete(lease2, t_now)
                self.busy.add(m2)
        elif code == _HB:
            delayed = self.agents[arg].heartbeat(t_now)
            self.busy.add(arg)
            if delayed is not None:
                heapq.heappush(self.events, (delayed[1], next(self._counter),
                                             _HBA, arg))
            heapq.heappush(self.events, (t_now + self.cfg.heartbeat_period,
                                         next(self._counter), _HB, arg))
        else:
            self.agents[arg].send_beat(t_now)
        waves0 = self.core.smatcher.waves
        if code == _ARR:
            w0 = self._wave_secs()
            self.svc.pump(t_now)
            wave_ns = int((self._wave_secs() - w0) * 1e9)
        else:
            self.svc.pump(t_now)
        t1 = perf()
        waved = self.core.smatcher.waves != waves0
        todo = sorted(self.busy)
        self.busy.clear()
        for m in todo:
            ag = self.agents[m]
            for t_done, lease in ag.poll(t_now):
                job, task, _ = ag.active[lease]
                self.ends[(job, task)] = t_done
                heapq.heappush(self.events, (t_done, next(self._counter),
                                             _DONE, (ag.machine, lease)))
            if not ag.idle():
                self.busy.add(m)
        for handle in self.client.poll(t_now):
            self.results.append(handle.result)
        return Step(code, t1 - t0, waved, miss, wave_ns)

    def _wave_secs(self) -> float:
        slot = self._kernels.profile_snapshot().get("match_wave.numpy")
        return slot[1] if slot else 0.0

    # -- phases ------------------------------------------------------------

    def run_until_virtual(self, t_end: float) -> None:
        """Handle every event before virtual time ``t_end``."""
        while self.events and self.events[0][0] < t_end:
            self.step()

    def run_steps(self, n: int) -> None:
        for _ in range(n):
            self.step()

    def decisions(self) -> tuple[list, list]:
        """(placements, job results) so far: the answers compared."""
        placements = [tuple(p) for p in self.core.placements]
        jobs = sorted((r.job_id, r.arrival, r.finish) for r in self.results)
        return placements, jobs
