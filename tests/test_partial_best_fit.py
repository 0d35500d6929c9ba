"""Partial Reconfiguration's best fit into kept instances, against the
instance-by-instance loop it replaced.

``_loop_partial`` keeps that loop as written before the best fit held the
kept instances' loads in arrays: for every pending task it walks every kept
instance, re-sums the instance's used demand from its task rows and
evaluates each grown set that fits with prices computed afresh.  The array
form must choose the same instance for every task, so Partial returns the
same assignment list, instance for instance and in order.
"""
import numpy as np
import pytest

from repro.cluster.traces import alibaba_like_trace
from repro.core import (ThroughputTable, TaskSet, aws_catalog,
                        evaluate_assignments, full_reconfiguration,
                        make_task, partial_reconfiguration)
from repro.core.cluster_types import ClusterConfig
from repro.core.full_reconfig import EPS
from repro.core.reservation_price import job_rp_sums, reservation_prices
from repro.core.workloads import M_TRUE, NUM_WORKLOADS
from repro.obs.profiler import Profiler, activate

CAT = aws_catalog()


def _loop_partial(tasks, live_assignments, pending_ids, catalog, table, *,
                  interference_aware=True, multi_task_aware=True,
                  type_mask=None, keep_bonus=None):
    """The keep test, the per-instance best-fit loop and the repack."""
    system_ids = set(tasks.ids.tolist())
    trimmed = []
    for k, tids in live_assignments:
        alive = tuple(t for t in tids if t in system_ids)
        if alive:
            trimmed.append((k, alive))

    repack = set(pending_ids) & system_ids
    keep = []
    if trimmed:
        tnrps, costs = evaluate_assignments(trimmed, tasks, catalog,
                                            table, multi_task_aware,
                                            type_mask=type_mask)
        for (k, tids), s, c in zip(trimmed, tnrps, costs):
            slack = keep_bonus(k, tids) if keep_bonus is not None else 0.0
            if s >= c - slack - EPS:
                keep.append((k, tids))
            else:
                repack |= set(tids)

    if not repack:
        return ClusterConfig(keep)

    rp_all = reservation_prices(tasks, catalog, type_mask=type_mask)
    job_rp_all = job_rp_sums(tasks, rp_all) if multi_task_aware else None

    keep = [list(a) for a in keep]
    for tid in sorted(repack, key=lambda t: -rp_all[tasks.row(t)]):
        row = tasks.row(tid)
        best, best_left = -1, np.inf
        for i, (k, tids) in enumerate(keep):
            fam = catalog.family_ids[k]
            used = tasks.demand_by_family[
                [tasks.row(x) for x in tids], fam, :].sum(axis=0)
            d = tasks.demand_by_family[row, fam, :]
            if np.any(used + d > catalog.capacities[k] + EPS):
                continue
            grown = (k, tuple(tids) + (tid,))
            s, c = evaluate_assignments([grown], tasks, catalog, table,
                                        multi_task_aware,
                                        type_mask=type_mask)
            if s[0] < c[0] - EPS:
                continue
            left = float(((catalog.capacities[k] - used - d)
                          / np.maximum(catalog.capacities[k], 1.0)).sum())
            if left < best_left:
                best, best_left = i, left
        if best >= 0:
            keep[best][1] = tuple(keep[best][1]) + (tid,)
            repack.discard(tid)
    keep = [(k, tuple(tids)) for k, tids in keep]

    if not repack:
        return ClusterConfig(keep)
    sub = tasks.subset(sorted(repack))
    rows = np.array([tasks.row(t) for t in sub.ids.tolist()])
    packed = full_reconfiguration(
        sub, catalog, table, interference_aware=interference_aware,
        multi_task_aware=multi_task_aware, engine="numpy",
        rp=rp_all[rows],
        job_rp=job_rp_all[rows] if job_rp_all is not None else None,
        type_mask=type_mask)
    return ClusterConfig(keep + packed.assignments)


def _fleet(seed, n_live_jobs, n_done_jobs, n_new_jobs, multi_task_fraction):
    """A steady-state round: live instances from one interference-aware
    pack of the live and the since-completed jobs, the completed jobs'
    tasks gone from the task set (room on the kept instances), and the new
    jobs' tasks pending."""
    jobs = alibaba_like_trace(n_live_jobs + n_done_jobs + n_new_jobs,
                              seed=seed, mean_interarrival_s=33.0,
                              multi_task_fraction=multi_task_fraction)
    rng = np.random.default_rng(seed)
    old = [jobs[i] for i in rng.permutation(n_live_jobs + n_done_jobs)]
    live_jobs, new_jobs = old[:n_live_jobs], jobs[n_live_jobs + n_done_jobs:]
    packed = full_reconfiguration(
        TaskSet([t for j in old for t in j.tasks]), CAT, _table(seed),
        engine="numpy")
    tasks = TaskSet([t for j in live_jobs + new_jobs for t in j.tasks])
    pending = {t.task_id for j in new_jobs for t in j.tasks}
    return tasks, packed.assignments, pending


def _table(seed):
    """Interference as the simulator learns it: the ground truth's pairwise
    entries, and a few exact co-location sets."""
    rng = np.random.default_rng(seed)
    table = ThroughputTable(NUM_WORKLOADS, default=0.95)
    for w1 in range(NUM_WORKLOADS):
        for w2 in range(NUM_WORKLOADS):
            if rng.uniform() < 0.7:
                table.record(w1, (w2,), float(M_TRUE[w1, w2]))
    for w in range(NUM_WORKLOADS):
        table.record(w, (w, w), float(rng.uniform(0.5, 1.0)))
    return table


def _mask():
    """Every type but the two cheapest of the c7i family: the repack and the
    prices have to route around them, and every task still fits."""
    mask = np.ones(len(CAT.types), dtype=bool)
    mask[[CAT.index_of("c7i.large"), CAT.index_of("c7i.xlarge")]] = False
    return mask


def _tie(swapped):
    """Two kept c7i.4xlarge instances with three (4 vCPU, 8 GB) tasks each
    leave the same room for a fourth.  ``keep_bonus`` keeps the part-filled
    instances through the keep test; the grown set of four pays for the
    instance on its own."""
    def build(seed):
        n = 7
        demand = np.broadcast_to([0.0, 4.0, 8.0], (n, 3, 3))
        tasks = TaskSet.from_arrays(np.arange(n), np.arange(n),
                                    np.full(n, 7), demand)
        k = CAT.index_of("c7i.4xlarge")
        live = [(k, (0, 1, 2)), (k, (3, 4, 5))]
        if swapped:
            live.reverse()
        kw = dict(interference_aware=False, multi_task_aware=False,
                  keep_bonus=lambda k, tids: 1.0)
        return tasks, live, {6}, None, kw
    return build


def _case(table=None, multi_task_fraction=0.0, n_live_jobs=1000,
          n_done_jobs=60, n_new_jobs=20, **kw):
    def build(seed):
        tasks, live, pending = _fleet(seed, n_live_jobs, n_done_jobs,
                                      n_new_jobs, multi_task_fraction)
        return tasks, live, pending, table and table(seed), kw
    return build


# half the jobs of 2 or 4 tasks: about as many tasks from fewer jobs
MULTI_TASK_FLEET = dict(multi_task_fraction=0.5, n_live_jobs=550,
                        n_done_jobs=35, n_new_jobs=12)

CASES = {
    "plain": _case(),
    "type_mask": _case(type_mask=_mask()),
    "keep_bonus": _case(keep_bonus=lambda k, tids: 1.0 * len(tids)
                        - 0.5 * (k % 3)),
    "single_task_pricing": _case(multi_task_aware=False),
    "multi_task_jobs": _case(**MULTI_TASK_FLEET),
    "interference_table": _case(table=_table),
    "all_at_once": _case(**dict(MULTI_TASK_FLEET, n_new_jobs=20),
                         type_mask=_mask(), table=_table,
                         keep_bonus=lambda k, tids: 0.01),
    "tie_in_left": _tie(swapped=False),
    "tie_in_left_swapped": _tie(swapped=True),
}


def _run_case(name, seed, profiler=None):
    tasks, live, pending, table, kw = CASES[name](seed)
    want = _loop_partial(tasks, live, pending, CAT, table, **kw)
    activate(profiler)
    try:
        got = partial_reconfiguration(tasks, live, pending, CAT, table, **kw)
    finally:
        activate(None)
    return tasks, live, pending, want, got


@pytest.mark.parametrize("name", sorted(CASES))
def test_best_fit_places_as_the_instance_loop(name):
    p = Profiler()
    tasks, live, pending, want, got = _run_case(name, seed=7, profiler=p)
    assert got.assignments == want.assignments
    (fit,) = p.by_name("partial.best_fit")
    assert fit.tags["fits"] > 0 and fit.tags["evals"] > 0
    if name.startswith("tie_in_left"):
        # both fit with the same room left: the first in kept order wins
        assert fit.tags["fits"] == 2
        assert got.assignments == [(live[0][0], live[0][1] + (6,)), live[1]]
        return
    # the fleet is the size the test means, and the best fit placed tasks
    assert len(tasks) >= 1000 and fit.tags["kept"] >= 300
    assert 10 <= len(pending) <= 60
    grown = set(got.assignments) - set(live)
    assert any(set(tids) & pending for _, tids in grown)


@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("multi_task_fraction", [0.0, 0.5])
@pytest.mark.parametrize("multi_task_aware", [False, True])
def test_evaluate_assignments_takes_the_round_prices(masked,
                                                     multi_task_fraction,
                                                     multi_task_aware):
    jobs = alibaba_like_trace(120, seed=3,
                              multi_task_fraction=multi_task_fraction)
    tasks = TaskSet([t for j in jobs for t in j.tasks])
    mask = _mask() if masked else None
    plan = full_reconfiguration(tasks, CAT, None, engine="numpy",
                                type_mask=mask).assignments
    table = _table(3)
    rp = reservation_prices(tasks, CAT, type_mask=mask)
    job_rp = job_rp_sums(tasks, rp)
    base = evaluate_assignments(plan, tasks, CAT, table, multi_task_aware,
                                type_mask=mask)
    for kw in (dict(rp=rp, job_rp=job_rp), dict(rp=rp)):
        got = evaluate_assignments(plan, tasks, CAT, table,
                                   multi_task_aware, type_mask=mask, **kw)
        assert np.array_equal(got[0], base[0])
        assert np.array_equal(got[1], base[1])
    if multi_task_fraction and multi_task_aware:  # the job term is read
        assert not np.array_equal(job_rp, rp)


def test_best_fit_span_counts_each_stage():
    p = Profiler()
    _run_case("plain", seed=11, profiler=p)
    (fit,) = p.by_name("partial.best_fit")
    t = fit.tags
    assert t["scanned"] == t["pending"] * t["kept"]
    assert 0 < t["evals"] <= t["fits"] <= t["scanned"]

    # a kept instance with no room: a p3.2xlarge whose one GPU a resnet18
    # task holds, and a second resnet18 task pending
    k = CAT.index_of("p3.2xlarge")
    full_gpu = make_task(job_id=0, workload=0)     # resnet18, 1 GPU
    new = make_task(job_id=1, workload=0)
    tasks = TaskSet([full_gpu, new])
    p = Profiler()
    activate(p)
    try:
        out = partial_reconfiguration(tasks, [(k, (full_gpu.task_id,))],
                                      {new.task_id}, CAT, None,
                                      interference_aware=False,
                                      multi_task_aware=False)
    finally:
        activate(None)
    (fit,) = p.by_name("partial.best_fit")
    assert fit.tags["kept"] == 1 and fit.tags["pending"] == 1
    assert fit.tags["scanned"] == 1
    assert fit.tags["fits"] == 0 and fit.tags["evals"] == 0
    assert out.assignments[0] == (k, (full_gpu.task_id,))
