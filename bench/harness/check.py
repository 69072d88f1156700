"""How ``correct`` is decided: the numbers compared and their limits.

Every answer the run gave, from the first event to the window's close,
is compared with references that import nothing of the program:

  * `reference.check`: the guarantees the configuration states (exactly
    once, precedence, capacity, task and job end times);
  * `ref_serve`: the served decisions.  The reference scheduler replays
    the same traffic for as many events and decides every wave itself,
    its fairness groups' deficit counters and must-serve rule with it;
    ``placement_diffs`` counts the grants and job completions that differ
    from its own, by position.  Beside the numbers it counts
    ``must_serve_picks`` (picks made while a group was owed service) and
    ``deficit_peak`` (the largest deficit reached, over ``kappa * C``):
    what the fairness rule had to do, not compared with a limit;
  * `ref_build`: the builder's answers.  For a sample of the distinct jobs
    the run built (drawn from the seed, and always the largest), the
    reference builds the priScore; ``pri_diffs`` counts the tasks whose
    priScore differs.

The control (`numbers` with ``control=True``) puts the reference in the
program's place, computed one precision lower than the configuration
states: the scheduler's ledger and deficit counters in float32 instead of
float64, the builder's space in float16 instead of float32.

Each number has its limit in ``bench/limits.json``; a run is correct when
every number is at or under its limit.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

from . import ref_build, ref_serve, reference

LIMITS = Path(__file__).resolve().parents[1] / "limits.json"
#: distinct jobs whose priScore the reference builds in each run
SAMPLE = 8
#: a configuration's ``fairness`` where it states none, or states part:
#: one group, and the program's default unfairness bound and slot fairness
FAIRNESS = {"groups": [0], "shares": [1], "kappa": 0.1, "f": "slot"}


def fairness(config: dict) -> dict:
    """The configuration's fairness groups, their shares normalised to sum
    to 1 (summed in order, as a float64 sum), kappa and f."""
    fair = {**FAIRNESS, **config.get("fairness", {})}
    groups, shares = list(fair["groups"]), list(fair["shares"])
    if (set(fair) != set(FAIRNESS) or not groups
            or len(groups) != len(shares) or len(set(groups)) != len(groups)
            or fair["f"] not in ("slot", "drf") or not fair["kappa"] > 0
            or min(shares) < 0 or sum(shares) <= 0):
        raise ValueError(f"fairness wants distinct groups, a share for "
                         f"each, kappa above 0 and f slot or drf: {fair!r}")
    total = 0.0
    for x in shares:
        total += float(x)
    return dict(fair, shares=[float(x) / total for x in shares])


def limits() -> dict:
    return {k: v["limit"] for k, v in json.loads(LIMITS.read_text()).items()}


def decision_diffs(got: tuple[list, list], want: tuple[list, list]) -> int:
    """Answers that differ: placements by position, then job results."""
    n = 0
    for a, b in zip(got, want):
        n += sum(x != y for x, y in zip(a, b)) + abs(len(a) - len(b))
    return n


def sample_jobs(job_keys: list, sizes: list, seed: int, n: int) -> list[int]:
    """Job ids of up to ``n`` distinct jobs drawn from the seed, plus the
    largest (first submit of each distinct job)."""
    first: dict[int, int] = {}
    for j, k in enumerate(job_keys):
        first.setdefault(k, j)
    ids = sorted(first.values())
    if not ids:
        return []
    pick = np.random.default_rng([int(seed), 2]).choice(
        ids, size=min(n, len(ids)), replace=False)
    return sorted({int(j) for j in pick} | {max(ids, key=sizes.__getitem__)})


def serve_reference(drv, config: dict, ledger=np.float64):
    """The reference scheduler over the run's traffic and events, fed the
    priScore the program's builder gave each job."""
    svc = config["service"]
    policy = ref_serve.Policy(dict(config["policy"],
                                   expose_per_job=svc["expose_per_job"],
                                   **{k: config["guarantees"][k] for k in
                                      ("rigid_dims", "fungible_dims")}),
                              fairness(config))
    pri = [drv.core.jobs[j].pri for j in range(len(drv.job_tasks))]
    ref = ref_serve.Cluster(drv.traffic, svc["n_machines"], svc["d"],
                            svc["heartbeat_period"], policy, pri,
                            ledger=ledger)
    ref.run_steps(drv.steps)
    return ref


def numbers(drv, config: dict, seed: int, control: bool = False) -> dict:
    want_ref = serve_reference(drv, config)
    want = (want_ref.placements, sorted(want_ref.results))
    kn = ref_build.Knobs(m=config["service"]["build_machines"],
                         **config["builder"])
    sizes = [tk.n for tk in drv.job_tasks]
    sample = sample_jobs(drv.job_keys, sizes, seed, SAMPLE)
    if control:
        low = serve_reference(drv, config, ledger=np.float32)
        got, ends = (low.placements, sorted(low.results)), low.ends
    else:
        got, ends = drv.decisions(), drv.ends
    out = reference.check(got[0], drv.job_tasks, got[1], ends,
                          config["guarantees"])
    out["placement_diffs"] = decision_diffs(got, want) + int(want_ref.overrun)
    out["must_serve_picks"] = want_ref.must_serve_picks
    out["deficit_peak"] = want_ref.deficit_peak / want_ref.must_at
    diffs = 0
    for j in sample:
        tk = drv.job_tasks[j]
        args = (tk.duration, tk.demand, tk.stage_of, tk.parents, kn)
        want_pri = ref_build.pri_score(*args)
        got_pri = (ref_build.pri_score(*args, grid=np.float16) if control
                   else np.asarray(drv.core.jobs[j].pri))
        diffs += int((got_pri != want_pri).sum())
    out["pri_diffs"] = diffs
    return out


def verdict(nums: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) in a fixed order."""
    lim = limits()
    shown = {k: {"value": nums[k], "limit": lim[k]} for k in sorted(lim)}
    return all(nums[k] <= lim[k] for k in lim), shown
