"""Tests of fairness groups and job families in the harness, on the CPU.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests

A three-group rehearsal (a configuration with a ``fairness`` section and
a mix with ``jobs.tenants``) runs end to end through `harness.cell.run`
on a few dozen machines, where the must-serve rule fires, and the
reference decides every fairness pick itself.  The digest test pins the
existing mixes' arrivals and task arrays to what they were before the
traffic learnt tenants and the job families moved into files of their
own.
"""

from __future__ import annotations

import hashlib
import re

import numpy as np
import pytest

from test_bench import ADHOC, RECURRING, SEED, tiny

from harness import cell, dags
from harness.traffic import Traffic

THREE = {"groups": [0, 1, 2], "shares": [1, 1, 1], "kappa": 0.1}
#: skewed tenants: groups 1 and 2 wait long between jobs, so they fall
#: kappa * C behind and are owed service when their jobs come
TENANTS = {"groups": 3, "zipf": 1.5}


def run_groups(f: str = "slot", fairness: dict | None = None,
               tenants: dict | None = None, **kw) -> tuple[dict, dict]:
    """A three-group rehearsal of the ad-hoc cell; returns the result and
    the counts the check logs."""
    cfg, mix = tiny(ADHOC)
    _, _, _, mix0 = cell.spec(ADHOC)
    cfg["fairness"] = dict(fairness or THREE, f=f)
    mix["jobs"] = dict(mix0["jobs"], tenants=tenants or TENANTS)
    lines: list[str] = []
    res = cell.run(ADHOC, SEED, 2.0, False, require_tpu=False,
                   config_override=cfg, mix_override=mix, log=lines.append,
                   **kw)
    got = next(ln for ln in lines if ln.startswith("check: references"))
    counts = {k: float(v) for k, v in
              re.findall(r"(must_serve_picks|deficit_peak)=([0-9.e+-]+)",
                         got)}
    return res, counts


@pytest.mark.parametrize("f", ["slot", "drf"])
def test_three_groups_decided_exactly(f):
    res, counts = run_groups(f, control=f == "slot")
    assert counts["must_serve_picks"] > 0, counts
    assert counts["deficit_peak"] >= 1.0, counts
    assert res["checks"]["placement_diffs"]["value"] == 0, res["checks"]
    assert res["correct"] is True, res["checks"]
    if f == "slot":
        assert res["control"]["correct"] is False, res["control"]


def test_one_group_never_owes_service():
    res, counts = run_groups(fairness={"groups": [0], "shares": [1],
                                       "kappa": 0.1},
                             tenants={"weights": [1]})
    assert counts == {"must_serve_picks": 0.0, "deficit_peak": 0.0}
    assert res["correct"] is True, res["checks"]


def _submit_in_group_0(orig):
    def submit(self, dag, group=0, t=0.0):
        return orig(self, dag, group=0, t=t)
    return submit


def _kappa_raised(orig):
    # a lower kappa moves no pick here: the group owed service is one whose
    # deficit grew far past kappa * C while it had nothing to run
    def init(self, shares, capacity, kappa):
        orig(self, shares, capacity, kappa * 10)
    return init


@pytest.mark.parametrize("kind", ["every_job_in_group_0", "kappa_raised"])
def test_fairness_fault_turns_correct_false(monkeypatch, kind):
    from repro.core.online import DeficitCounters
    from repro.svc import Client

    if kind == "every_job_in_group_0":
        monkeypatch.setattr(Client, "submit", _submit_in_group_0(
            Client.submit))
    else:
        monkeypatch.setattr(DeficitCounters, "__init__", _kappa_raised(
            DeficitCounters.__init__))
    res, _ = run_groups()
    assert res["checks"]["placement_diffs"]["value"] > 0, res["checks"]
    assert res["correct"] is False, res["checks"]


@pytest.mark.parametrize("change, named", [
    ({"shares": [2, 1, 1]}, "shares"),
    ({"kappa": 0.05}, None),
])
def test_program_departing_from_the_configuration_is_refused(
        monkeypatch, change, named):
    if named is None:
        # the program's preset ignores the configuration's kappa
        from repro.sim import cluster

        orig = cluster.scheme
        monkeypatch.setattr(cluster, "scheme",
                            lambda name, **kw: orig(name, **dict(kw,
                                                                 kappa=0.1)))
        named = "kappa"
    with pytest.raises(ValueError, match=named) as err:
        run_groups(fairness=dict(THREE, **change))
    if "shares" in change:
        assert "0.5" in str(err.value) and "0.333" in str(err.value)


# -- traffic ----------------------------------------------------------------

#: sha256 of every arrival's (t, key), and of the task arrays of the first
#: 64 distinct jobs, at job_scale 1 and seed `SEED`: the values the mixes
#: gave before they could name tenants and before the production family
#: moved into ``families/production.py``
DIGESTS = {
    "adhoc": (
        "a96e90fded67e252a581b0abfc4943a2095cde2a4dc8808239699d4d0bdb6674",
        "457cccab5f744c54bceb371a62f9180c71faa9e189d96959a238b96adee91e11"),
    "recurring": (
        "9e6b026dbe4a8d7b109214a24961d80a6649981b490947b80e9627f9d62de5c1",
        "121ad68f536eadd4168d4cfaeeaded866548d6d327493fa2d77f3c69a7554ef0"),
}


def _digest(tr: Traffic) -> tuple[str, str]:
    arr = tr.arrivals()
    h = hashlib.sha256()
    h.update(np.asarray([a.t for a in arr], np.float64).tobytes())
    h.update(np.asarray([a.key for a in arr], np.int64).tobytes())
    first: dict = {}
    for a in arr:
        first.setdefault(a.key, a)
    ht = hashlib.sha256()
    for a in list(first.values())[:64]:
        tk = tr.tasks(a)
        for x in (tk.duration, tk.demand, tk.stage_of):
            ht.update(np.ascontiguousarray(x).tobytes())
        ht.update(np.asarray([len(p) for p in tk.parents], np.int64)
                  .tobytes())
        ht.update(np.concatenate(tk.parents).astype(np.int64).tobytes())
        ht.update(tk.name.encode())
    return h.hexdigest(), ht.hexdigest()


@pytest.mark.parametrize("workload", [ADHOC, RECURRING])
def test_mixes_offer_what_they_did(workload):
    _, wl, config, mix = cell.spec(workload)
    tr = Traffic(mix, SEED, config["job_scale"])
    assert _digest(tr) == DIGESTS[wl["traffic"]]
    assert {a.group for a in tr.arrivals()} == {0}
    # tenants draw on a generator of their own: the shapes stay
    jobs = dict(mix["jobs"], tenants=TENANTS)
    assert _digest(Traffic(dict(mix, jobs=jobs), SEED,
                           config["job_scale"])) == DIGESTS[wl["traffic"]]


@pytest.mark.parametrize("workload", [ADHOC, RECURRING])
def test_tenants_follow_the_job_not_the_seed(workload):
    _, _, config, mix = cell.spec(workload)
    mix = dict(mix, jobs=dict(mix["jobs"], tenants={"weights": [3, 1, 1]}))
    owner: dict = {}
    for seed in (SEED, SEED + 1):
        for a in Traffic(mix, seed, config["job_scale"]).arrivals():
            assert owner.setdefault(a.key, a.group) == a.group
    if len(owner) > 1000:     # the ad-hoc mix's 3,200 jobs; the pool has 32
        share = np.bincount(list(owner.values()), minlength=3) / len(owner)
        assert share == pytest.approx([0.6, 0.2, 0.2], abs=0.03)


def test_tenants_are_refused_unless_well_formed():
    _, _, config, mix = cell.spec(ADHOC)
    for bad in ({"weights": []}, {"weights": [0, 0]}, {"groups": 3},
                {"weights": [1], "zipf": 1}):
        jobs = dict(mix["jobs"], tenants=bad)
        with pytest.raises(ValueError, match="tenants"):
            Traffic(dict(mix, jobs=jobs), SEED, 1.0)


def test_job_families_are_files():
    assert dags.family("production").__module__.endswith(
        "families.production")
    for bad in ("tpcds", "../dags", "dags"):
        with pytest.raises(ValueError, match="production"):
            dags.family(bad)
