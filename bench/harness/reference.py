"""Plain reference of the scheduler's guarantees, independent of the program.

Imports nothing of the program and takes nothing it made: it replays
the service's lease stream (every grant as ``(t, job, task, machine)``)
against the benchmark's own task arrays and its own float64 ledger, and
checks what the configuration states:

  * every task is granted at most once, and every task of a job the
    service reported finished was granted (``exactly_once``);
  * a task is granted only once each of its parents has finished
    (``precedence``);
  * at every grant the machine's committed demand on the rigid dims
    (cores, memory) stays within its capacity (``overcommit``: the
    largest excess seen); the fungible dims (network, disk) may be
    overbooked;
  * a task runs its duration stretched by the overload of its machine's
    fungible dims after the grant, and a job finishes when its last task
    does (``end_rel_err``: the largest gap between the time an agent
    finishes a task and the reference's, relative to the task's run
    time, and between a reported job completion and the reference's,
    relative to the job's JCT).
"""

from __future__ import annotations

import heapq

import numpy as np

#: a completion this close before a grant has happened before it (the
#: service settles completions before the wave they trigger)
TIME_TOL = 1e-9


def check(placements, job_tasks, results, ends, guarantees: dict) -> dict:
    """Numbers compared, from the lease stream of one run.

    ``placements``: grants in grant order; ``job_tasks[j]``: the task
    arrays of the service's job ``j``; ``results``: ``(job, arrival,
    finish)`` of every job the service reported finished; ``ends``:
    ``(job, task) -> time`` at which the agents finish each granted task.
    """
    cap = float(guarantees["capacity"])
    rigid = list(guarantees["rigid_dims"])
    fungible = list(guarantees["fungible_dims"])
    n_machines = int(guarantees["n_machines"])
    load = np.zeros((n_machines, len(rigid) + len(fungible)))
    running: list = []
    done_at: dict[tuple[int, int], float] = {}
    dup = prec = 0
    over = -np.inf
    rel = 0.0
    for seq, (t, job, task, m) in enumerate(placements):
        while running and running[0][0] <= t + TIME_TOL:
            _, _, m2, dem2 = heapq.heappop(running)
            load[m2] -= dem2
        key = (job, task)
        if key in done_at:
            dup += 1
            continue
        tk = job_tasks[job]
        for p in tk.parents[task]:
            tp = done_at.get((job, int(p)))
            if tp is None or tp > t + TIME_TOL:
                prec += 1
                break
        dem = tk.demand[task]
        load[m] += dem
        over = max(over, float(load[m][rigid].max()) - cap)
        factor = max(float(load[m][fungible].max()), 1.0)
        t_done = t + float(tk.duration[task]) * factor
        heapq.heappush(running, (t_done, seq, m, dem))
        done_at[key] = t_done
        got = ends.get(key)
        if got is not None:
            rel = max(rel, abs(got - t_done) / (t_done - t))
    missing = 0
    for job, arrival, finish in results:
        tk = job_tasks[job]
        ends = [done_at.get((job, i)) for i in range(tk.n)]
        if any(e is None for e in ends):
            missing += sum(e is None for e in ends)
            continue
        want = max(ends)
        rel = max(rel, abs(finish - want) / max(want - arrival, 1e-9))
    return {"exactly_once": dup + missing, "precedence": prec,
            "overcommit": over if placements else 0.0,
            "end_rel_err": rel}
