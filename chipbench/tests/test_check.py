"""The comparison itself, on rounds and packs made by hand."""
import json
import os
import types

import numpy as np
import pytest

from chipbench import check, reference as ref
from repro.core.cluster_types import TaskSet

CONFIG = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs",
                      "eva-alibaba-1k.json")


@pytest.fixture(scope="module")
def config():
    with open(CONFIG) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cat(config):
    return ref.Catalog(config["catalog"], config["families"])


def round_with_twins(plan_tail_type):
    """Tasks 10 and 12 are alike in workload and demand: 10 runs beside 11
    on a p3.8xlarge (type 1), 12 is pending and Partial packs it alone."""
    rows = np.array([[1.0, 14.0, 196.3], [1.0, 4.0, 20.0], [1.0, 14.0, 196.3]])
    demand = np.repeat(rows[:, None, :], 3, axis=1)
    ts = TaskSet.from_arrays(np.array([10, 11, 12]), np.array([1, 2, 3]),
                             np.zeros(3, np.int64), demand)
    view = types.SimpleNamespace(
        tasks=ts, live=[types.SimpleNamespace(type_index=1, task_ids=(10, 11))])
    repack = check.PackCall(demand[2:], np.zeros(1, np.int64), [(1, [0])],
                            np.ones(1, np.int64))
    plan = [(1, (10, 11)), (plan_tail_type, (12,))]
    return check.RoundRecord(view, {}, [repack], plan)


def test_a_pending_twin_of_a_kept_task_is_no_violation(cat, config):
    r = check.check_round(round_with_twins(1), cat, config)
    assert {k: r[k] for k in ("pack_cost_gap", "pack_misplaced",
                              "pack_mismatched", "plan_violations",
                              "rows_not_judged")} == {
        "pack_cost_gap": 0.0, "pack_misplaced": 0, "pack_mismatched": 0,
        "plan_violations": 0, "rows_not_judged": 0}


def test_a_plan_that_is_not_the_pack_is_a_violation(cat, config):
    r = check.check_round(round_with_twins(0), cat, config)
    assert r["plan_violations"] == 1


def round_with_jobs_of_two_sizes():
    """Task 10 is a one-task job; tasks 11-14 are one four-task job alike
    to it in workload and demand.  Partial repacks task 11 alone."""
    demand = np.repeat(np.array([[1.0, 14.0, 196.3]] * 5)[:, None, :], 3,
                       axis=1)
    ts = TaskSet.from_arrays(np.arange(10, 15), np.array([1, 2, 2, 2, 2]),
                             np.zeros(5, np.int64), demand)
    rp = np.full(1, 3.06)
    repack = check.PackCall(demand[1:2], np.zeros(1, np.int64), [(1, [0])],
                            check.job_tasks(rp, 4 * rp))
    return ts, repack


def test_rows_are_matched_to_jobs_of_their_size():
    ts, repack = round_with_jobs_of_two_sizes()
    demand, workloads = ts.demand_by_family, ts.workloads
    sizes = np.array([1, 4, 4, 4, 4])
    assert check._match_rows(repack, demand, workloads,
                             sizes).tolist() == [1]
    # content alone (every size 1) takes the one-task job's row, whose job
    # RP is a fourth
    alone = check.PackCall(repack.demand, repack.workloads, repack.out,
                           np.ones(1, np.int64))
    assert check._match_rows(alone, demand, workloads,
                             np.ones(5, np.int64)).tolist() == [0]
    # a pack given no job sums prices each task as its own job
    assert check.job_tasks(np.full(2, 3.06), None).tolist() == [1, 1]


@pytest.mark.parametrize("shortfall, close", [(5e-6, True), (1e-4, False)])
def test_break_even_within_the_float32_band_is_not_judged(cat, shortfall,
                                                          close):
    """Two tasks whose TNRP falls ``shortfall`` (relative) short of a
    c7i.large's cost: float64 does not keep them, and inside the float32
    band the pack says it is not to be judged."""
    k = cat.names.index("c7i.large")
    cost = cat.costs[k]
    demand = np.zeros((2, 3, 3))
    demand[:, :, 1] = 1.0
    rp = np.full(2, cost / 2)
    pairwise = np.array([[1.0 - shortfall]])
    only = ref.Catalog([{"name": "c7i.large", "family": "c7i",
                         "capacity": cat.caps[k].tolist(),
                         "hourly_cost": cost}], ["p3", "c7i", "r7i"])
    seen = []
    got = ref.pack(demand, np.zeros(2, np.int64), rp, rp, only, pairwise,
                   close=seen)
    assert got == []
    assert bool(seen) == close


def test_migration_cost_prices_moves_and_launches(cat):
    """Live: instance 7 (type 1) holds tasks 10 and 11.  The plan keeps 10
    there, moves 11 to a new type-0 instance and starts pending task 12 on
    a new type-2 instance."""
    live = [(7, 1, (10, 11))]
    plan = [(1, (10,)), (0, (11,)), (2, (12,))]
    delay = [0.0, 36.0, 72.0]
    got = ref.migration_cost(live, plan, {10: 1, 11: 2, 12: 1}, cat, delay,
                             360.0)
    c = cat.costs
    want = (0.1 * (c[0] + c[2])               # two launches
            + 0.02 * (c[0] + c[1])            # 11 moves from type 1
            + 0.01 * c[2])                    # 12 starts from pending
    assert got == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("values, want", [
    ((2.0, 1.5, 1.0, 0.0, 3600.0), False),  # Full saves 1 $/h, moves cost 1.5
    ((3.0, 1.0, 1.0, 0.0, 3600.0), True),
    ((2.0, 1.5, 1.0, 0.0, 7200.0), True),
    ((2.0, 2.0, 1.0, 1.0, 3600.0), None),   # a tie
])
def test_the_ensemble_adopts_full_when_it_is_worth_more(values, want):
    assert ref.adopt_full(*values) == want


def test_a_planner_given_other_prices_is_a_violation(cat, config):
    rec = round_with_twins(1)
    rec.prices = [cat.costs.copy()]
    assert check.check_round(rec, cat, config)["plan_violations"] == 0
    rec.prices = [cat.costs * (1 + 1e-6)]
    assert check.check_round(rec, cat, config)["plan_violations"] == 1


def forced_round(plan, packed, forced=True, revoked=(9,)):
    """Instance 7 (type 1) holds tasks 10 and 11 and is kept; instance 9
    (type 1) holds task 12 and is under a revocation notice.  ``packed``:
    Partial repacked task 12 onto a fresh instance."""
    rec = round_with_twins(1)
    rec.view = types.SimpleNamespace(
        tasks=rec.view.tasks, revoked=set(revoked),
        live=[types.SimpleNamespace(instance_id=7, type_index=1,
                                    task_ids=(10, 11)),
              types.SimpleNamespace(instance_id=9, type_index=1,
                                    task_ids=(12,))])
    if not packed:
        rec.packs = []
    rec.plan = rec.partial = plan
    rec.forced = forced
    return rec


@pytest.fixture(scope="module")
def spot_config(config):
    return dict(config, scheduler=dict(
        config["scheduler"], policies=[{"layer": "SpotLayer", "args": {}}]))


@pytest.mark.parametrize("rec, violations, judged", [
    (forced_round([(1, (10, 11)), (1, (12,))], packed=True), 0, 1),
    # the instance under notice kept (and the keep test's walk over the
    # survivors finds a slot too many), its task not repacked
    (forced_round([(1, (10, 11)), (1, (12,))], packed=False), 2, 1),
    # a notice the scheduler did not force a Partial for
    (forced_round([(1, (10, 11)), (1, (12,))], packed=True, forced=False),
     1, 0),
    # a forced Partial with no notice
    (forced_round([(1, (10, 11)), (1, (12,))], packed=True, revoked=()),
     1, 0),
])
def test_a_forced_round_is_partial_over_the_survivors(cat, spot_config, rec,
                                                      violations, judged):
    r = check.check_round(rec, cat, spot_config)
    assert r["plan_violations"] == violations
    assert r["forced_checked"] == judged
    assert r["choices_judged"] + r["choices_not_judged"] == 0


@pytest.mark.parametrize("repacked, exact", [
    # rounds kept twice (the largest also drawn to repack) leave fewer
    (lambda i: i % 3 == 0, False),
    # four repacking rounds, one of them the ``full`` stratum's: the
    # repacking reservoir holds it already, so it takes no slot
    (lambda i: i in (0, 50, 100, 133), True),
])
def test_the_strata_take_their_rounds_from_the_all_rounds_reservoir(
        repacked, exact):
    for seed in range(5):
        s = check.Sampler(8, seed)
        for i in range(200):
            strata = (["pressure"] if i == 57 else []) + (
                ["full"] if i == 133 else [])
            s.offer(i, 10 + (i == 90), repacked(i), lambda i=i: i, strata)
        got = s.records()
        assert {57, 133, 90} | {i for i, _ in s.rep} <= set(got)
        assert len(got) == 8 if exact else len(got) <= 8
