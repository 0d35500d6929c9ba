"""Equivalence of the three packing engines (paper-faithful python loop,
vectorized numpy, jitted JAX incremental formulation)."""
import numpy as np
import pytest

from repro.core import (Catalog, InstanceType, TaskSet, ThroughputTable,
                        aws_catalog, dispersed_demo_regions,
                        full_reconfiguration, make_task,
                        multi_region_catalog, table3_catalog)
from repro.core.catalog import AWS_CATALOG, FAMILIES
from repro.core.cluster_types import Task
from repro.core.workloads import NUM_WORKLOADS

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - exercised in minimal envs
    HAVE_HYPOTHESIS = False


def _random_tasks(n, seed):
    rng = np.random.default_rng(seed)
    return TaskSet([make_task(job_id=1000 * seed + i,
                              workload=int(rng.integers(NUM_WORKLOADS)))
                    for i in range(n)])


def _random_table(seed, default=0.95):
    rng = np.random.default_rng(seed)
    t = ThroughputTable(NUM_WORKLOADS, default=default)
    for _ in range(25):
        w1, w2 = rng.integers(NUM_WORKLOADS, size=2)
        t.record(int(w1), (int(w2),), float(rng.uniform(0.7, 1.0)))
    return t


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
@pytest.mark.parametrize("interference", [False, True])
def test_numpy_matches_python(seed, interference):
    tasks = _random_tasks(40, seed)
    cat = aws_catalog()
    table = _random_table(seed) if interference else None
    kw = dict(interference_aware=interference, multi_task_aware=False)
    c_py = full_reconfiguration(tasks, cat, table, engine="python", **kw)
    c_np = full_reconfiguration(tasks, cat, table, engine="numpy", **kw)
    assert sorted(c_py.assignments) == sorted(c_np.assignments)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_numpy_matches_python_multitask(seed):
    rng = np.random.default_rng(seed)
    tasks = []
    for j in range(12):
        w = int(rng.integers(NUM_WORKLOADS))
        for _ in range(int(rng.integers(1, 4))):
            tasks.append(make_task(job_id=j, workload=w))
    ts = TaskSet(tasks)
    cat = aws_catalog()
    table = _random_table(seed)
    kw = dict(interference_aware=True, multi_task_aware=True)
    c_py = full_reconfiguration(ts, cat, table, engine="python", **kw)
    c_np = full_reconfiguration(ts, cat, table, engine="numpy", **kw)
    assert sorted(c_py.assignments) == sorted(c_np.assignments)


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4])
@pytest.mark.parametrize("interference", [False, True])
def test_jax_matches_numpy(seed, interference):
    tasks = _random_tasks(50, seed)
    cat = aws_catalog()
    table = _random_table(seed, default=0.97) if interference else None
    kw = dict(interference_aware=interference, multi_task_aware=True)
    c_np = full_reconfiguration(tasks, cat, table, engine="numpy", **kw)
    c_jx = full_reconfiguration(tasks, cat, table, engine="jax", **kw)
    # same total cost (tie-breaks may differ by float association)
    assert c_jx.total_hourly_cost(cat) == pytest.approx(
        c_np.total_hourly_cost(cat), rel=1e-6)
    # every task assigned exactly once in both
    for c in (c_np, c_jx):
        tids = sorted(t for _, ts_ in c.assignments for t in ts_)
        assert tids == sorted(tasks.ids.tolist())


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("interference", [False, True])
def test_jax_matches_numpy_multitask(seed, interference):
    """Jobs of 1-3 tasks: job-RP sums differ between jobs of one workload,
    so the jax engine collapses classes on full rows, not workloads."""
    rng = np.random.default_rng(seed)
    tasks = []
    for j in range(30):
        w = int(rng.integers(NUM_WORKLOADS))
        for _ in range(int(rng.integers(1, 4))):
            tasks.append(make_task(job_id=j, workload=w))
    ts = TaskSet(tasks)
    cat = aws_catalog()
    table = _random_table(seed, default=0.97) if interference else None
    kw = dict(interference_aware=interference, multi_task_aware=True)
    c_np = full_reconfiguration(ts, cat, table, engine="numpy", **kw)
    c_jx = full_reconfiguration(ts, cat, table, engine="jax", **kw)
    assert c_jx.total_hourly_cost(cat) == pytest.approx(
        c_np.total_hourly_cost(cat), rel=1e-6)
    tids = sorted(t for _, ts_ in c_jx.assignments for t in ts_)
    assert tids == sorted(ts.ids.tolist())


def test_jax_exact_fit_with_decimal_demands():
    """Five tasks whose decimal RAM demands fill the big type exactly: the
    f32 remainder after four of them sits a few ulps below the fifth's
    demand, and the fit test must still admit it, as numpy's does."""
    cat = Catalog.from_types([
        InstanceType("big", "p3", (8, 64, 488), 24.48),
        InstanceType("mid", "p3", (4, 32, 244), 12.24),
        InstanceType("small", "p3", (1, 8, 61), 3.06),
    ])
    demands = [(1, 19, 173.6), (1, 22, 175.8), (1, 17, 94.7), (1, 3, 40.8),
               (1, 3, 3.1)]
    tasks = TaskSet([
        Task(task_id=i, job_id=i, workload=i,
             demands={fam: d if fam == "p3" else (99.0, 999.0, 9999.0)
                      for fam in FAMILIES})
        for i, d in enumerate(demands)])
    kw = dict(interference_aware=False, multi_task_aware=True)
    c_np = full_reconfiguration(tasks, cat, None, engine="numpy", **kw)
    c_jx = full_reconfiguration(tasks, cat, None, engine="jax", **kw)
    assert _canon(c_np) == [(0, (0, 1, 2, 3, 4))]
    assert _canon(c_jx) == _canon(c_np)


def _canon(cfg):
    """Partition-canonical view: the jax engine emits each instance's tasks
    grouped by collapsed class, numpy in pick order."""
    return sorted((k, tuple(sorted(t))) for k, t in cfg.assignments)


def _random_catalog(seed):
    """Random market: continuous costs (no reservation-price ties), random
    sizes, anchored by the three largest AWS types so every workload stays
    feasible on each family."""
    rng = np.random.default_rng(seed)
    types = [t for t in AWS_CATALOG
             if t.name in ("p3.16xlarge", "c7i.24xlarge", "r7i.24xlarge")]
    assert len(types) == 3
    for i in range(int(rng.integers(6, 12))):
        fam = FAMILIES[int(rng.integers(len(FAMILIES)))]
        if fam == "p3":
            gpu = float(rng.integers(1, 9))
            cap = (gpu, 8.0 * gpu, 61.0 * gpu)
        else:
            cpu = float(2 ** rng.integers(1, 7))
            cap = (0.0, cpu, cpu * (2.0 if fam == "c7i" else 8.0))
        types.append(InstanceType(f"rnd-{seed}-{i}", fam, cap,
                                  float(rng.uniform(0.05, 30.0))))
    return Catalog.from_types(types)


def _check_random_catalog(seed):
    cat = _random_catalog(seed)
    tasks = _random_tasks(45, seed)
    kw = dict(interference_aware=False, multi_task_aware=True)
    c_np = full_reconfiguration(tasks, cat, None, engine="numpy", **kw)
    c_jx = full_reconfiguration(tasks, cat, None, engine="jax", **kw)
    assert c_jx.total_hourly_cost(cat) == pytest.approx(
        c_np.total_hourly_cost(cat), rel=1e-6)
    for c in (c_np, c_jx):
        tids = sorted(t for _, ts_ in c.assignments for t in ts_)
        assert tids == sorted(tasks.ids.tolist())


@pytest.mark.parametrize("seed", [10, 11, 12, 13, 14, 15])
def test_jax_matches_numpy_random_catalog(seed):
    _check_random_catalog(seed)


if HAVE_HYPOTHESIS:
    @settings(deadline=None, max_examples=20)
    @given(seed=st.integers(0, 100_000))
    def test_jax_matches_numpy_random_catalog_property(seed):
        _check_random_catalog(seed)


def test_jax_x64_exact_partition_match():
    """Under x64 the engine's accept/score tolerances collapse below EPS,
    so the jitted plan is partition-identical to numpy, not just cost-equal."""
    import jax
    jax.config.update("jax_enable_x64", True)
    try:
        kw = dict(interference_aware=False, multi_task_aware=True)
        for seed, cat in ((0, aws_catalog()), (20, _random_catalog(20))):
            tasks = _random_tasks(60, seed)
            c_np = full_reconfiguration(tasks, cat, None, engine="numpy", **kw)
            c_jx = full_reconfiguration(tasks, cat, None, engine="jax", **kw)
            assert _canon(c_np) == _canon(c_jx)
    finally:
        jax.config.update("jax_enable_x64", False)


def test_jax_type_mask_matches_numpy():
    cat = aws_catalog()
    # forbid the GPU family: CPU-feasible packing must agree across engines
    mask = np.array([t.family != "p3" for t in cat.types])
    rng = np.random.default_rng(5)
    cpu_ok = [w for w in range(NUM_WORKLOADS) if _cpu_feasible(cat, mask, w)]
    tasks = TaskSet([make_task(job_id=7000 + i,
                               workload=int(rng.choice(cpu_ok)))
                     for i in range(30)])
    kw = dict(interference_aware=False, multi_task_aware=True,
              type_mask=mask)
    c_np = full_reconfiguration(tasks, cat, None, engine="numpy", **kw)
    c_jx = full_reconfiguration(tasks, cat, None, engine="jax", **kw)
    assert c_jx.total_hourly_cost(cat) == pytest.approx(
        c_np.total_hourly_cost(cat), rel=1e-6)
    for k, _ in c_jx.assignments:
        assert mask[k]


def _cpu_feasible(cat, mask, workload):
    from repro.core import reservation_prices
    ts = TaskSet([make_task(job_id=0, workload=workload, task_id=0)])
    try:
        return bool(np.isfinite(reservation_prices(ts, cat,
                                                   type_mask=mask)[0]))
    except ValueError:  # fits no unmasked type
        return False


def test_jax_region_caps_match_numpy():
    cat = multi_region_catalog(dispersed_demo_regions(3)).at(3600.0)
    rng = np.random.default_rng(9)
    tasks = TaskSet([make_task(job_id=8000 + i,
                               workload=int(rng.integers(NUM_WORKLOADS)))
                     for i in range(35)])
    kw = dict(interference_aware=False, multi_task_aware=True)
    plans = {}
    for eng in ("numpy", "jax"):
        caps = [3, None, 4]
        plans[eng] = full_reconfiguration(tasks, cat, None, engine=eng,
                                          region_caps=caps, **kw)
        per_region = np.bincount(
            [cat.region_of(k) for k, _ in plans[eng].assignments],
            minlength=3)
        assert per_region[0] <= 3 and per_region[2] <= 4
    assert plans["jax"].total_hourly_cost(cat) == pytest.approx(
        plans["numpy"].total_hourly_cost(cat), rel=1e-6)


def test_table3_walkthrough_jax_engine():
    specs = [(2, 8, 24), (1, 4, 10), (0, 6, 20), (0, 4, 12)]
    ts = TaskSet([Task(i, i, i, {"p3": tuple(map(float, s))})
                  for i, s in enumerate(specs)])
    cat = table3_catalog()
    cfg = full_reconfiguration(ts, cat, None, interference_aware=False,
                               multi_task_aware=False, engine="jax")
    assert cfg.total_hourly_cost(cat) == pytest.approx(12.8)


def _tie_fleet(seed, interference):
    """~1,000 task classes built for exact cross-class score ties.

    RPs and job-RP sums sit on a coarse dyadic grid shared by every
    workload, so many classes of one workload score exactly alike and the
    tie goes to the lowest current task row; demands are distinct eighths,
    so each task is its own class, except for multi-task classes of 2-12
    identical tasks whose rows are scattered among the others.  Their
    row pointers advance inside a fill, stay put across a rejected one and
    jump over replicated fills, so every path of the tie key is taken.
    Every value is exact in float32, as are its sums."""
    rng = np.random.default_rng(seed)
    n_single, n_multi = 900, 40
    W, F = NUM_WORKLOADS, len(FAMILIES)
    gpu = rng.integers(1, 3, n_single + n_multi)
    demand = np.zeros((n_single + n_multi, F, 3))
    demand[:, 0] = np.column_stack(
        [gpu, rng.integers(8, 97, gpu.size) / 8,
         rng.integers(32, 481, gpu.size) / 8])
    for f in (1, 2):  # CPU families: no GPU, the task's host share
        demand[:, f, 1] = rng.integers(8, 65, gpu.size) / 8
        demand[:, f, 2] = rng.integers(16, 513, gpu.size) / 8
    workloads = rng.integers(4, size=gpu.size)  # big same-workload ties
    rp = (2 + rng.integers(6, size=gpu.size)) / 4
    jr = rp * rng.integers(1, 3, size=gpu.size)
    copies = np.concatenate([np.ones(n_single, int),
                             rng.integers(2, 13, n_multi)])
    rows = list(rng.permutation(np.repeat(np.arange(gpu.size), copies)))
    # A replicated fill, then a tie across it, at fixed rows: X (RP 5.5, 7
    # tasks) and Z (RP 2.75, 9 tasks) outbid every other task, and two of
    # each fill the dearest type with no tie (Z2, Z's equal on 6 GPUs,
    # fits only beside one X), so the fill is emitted 3 times; the fourth
    # takes the last X, then Z2 (row 65) ties Z, whose lowest row left is
    # 70, not the 30 the fill's own picks reached: X and Z2 fill it.
    placed = []
    for rp_s, p3, at in ((5.5, (2, 8, 200), (5, 15, 25, 35, 45, 55, 75)),
                         (2.75, (2, 8, 50), (10, 20, 30, 40, 50, 60, 70, 80,
                                             90)),
                         (2.75, (6, 8, 50), (65,))):
        demand = np.concatenate([demand, [[p3, (0, 64, 512),
                                           (0, 64, 512)]]])
        workloads = np.append(workloads, 0)
        rp = np.append(rp, rp_s)
        jr = np.append(jr, rp_s)
        placed += [(r, len(rp) - 1) for r in at]
    for r, task in sorted(placed):
        rows.insert(r, task)
    cat = Catalog.from_types([
        InstanceType("g8", "p3", (8, 64, 512), 8.0),
        InstanceType("g4", "p3", (4, 32, 256), 4.0),
        InstanceType("g1", "p3", (1, 8, 64), 1.25),
        InstanceType("c32", "c7i", (0, 32, 64), 2.0),
        InstanceType("r32", "r7i", (0, 32, 256), 3.0),
        InstanceType("r8", "r7i", (0, 8, 64), 0.5),
    ])
    pairwise = np.ones((W, W))
    if interference:
        pairwise = rng.uniform(0.9, 1.0, (W, W))
        np.fill_diagonal(pairwise, 1.0)
    return (demand[rows], workloads[rows], rp[rows], jr[rows], cat,
            pairwise)


@pytest.mark.parametrize("interference,x64", [(False, False), (False, True),
                                              (True, True)],
                         ids=["off-f32", "off-x64", "on-x64"])
@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_jax_matches_numpy_pick_for_pick_at_fleet_width(seed, interference,
                                                        x64):
    """~1,000 classes, the device's 1,024-class bucket: the same instances
    in the same order with the same tasks as numpy's task-level argmax,
    through ties that cross accepted, rejected and replicated fills.
    Interference is compared under x64, where both engines sum alike;
    without it every score is exact in float32 too."""
    import jax

    from repro.core.engine_jax import pack_jax
    from repro.core.full_reconfig import _pack_numpy
    demand, workloads, rp, jr, cat, pairwise = _tie_fleet(seed, interference)
    assert 900 < len(np.unique(demand.reshape(len(rp), -1), axis=0)) <= 1024
    want = _pack_numpy(demand, workloads, rp, jr, cat, pairwise)
    jax.config.update("jax_enable_x64", x64)
    try:
        got = pack_jax(demand, workloads, rp, jr, cat, pairwise)
    finally:
        jax.config.update("jax_enable_x64", False)
    assert sum(len(r) for _, r in want) == len(rp)  # every task placed
    assert [(k, sorted(r)) for k, r in got] == \
        [(k, sorted(r)) for k, r in want]


def test_table3_walkthrough_counts_picks_and_fills(monkeypatch):
    """The device pack counts its greedy steps and fills.  Table 3 by hand:
    on it1, τ1, τ2 and τ4 in 4 steps (3 picks, then nothing fits) and τ3
    in a rejected fill of 2 steps (a pick, then no task left); on it2 one
    step, nothing fits; on it3 τ3 in 2 steps; it4 has no task left to
    fill.  9 steps in 4 fills, tagged on the ``jax_pack`` span and read
    back only where a profiler is active."""
    from repro.core import engine_jax
    from repro.obs import profiler as prof
    specs = [(2, 8, 24), (1, 4, 10), (0, 6, 20), (0, 4, 12)]
    ts = TaskSet([Task(i, i, i, {"p3": tuple(map(float, s))})
                  for i, s in enumerate(specs)])
    cat = table3_catalog()
    kw = dict(interference_aware=False, multi_task_aware=False,
              engine="jax")
    p = prof.Profiler()
    prof.activate(p)
    try:
        cfg = full_reconfiguration(ts, cat, None, **kw)
    finally:
        prof.activate(None)
    assert cfg.total_hourly_cost(cat) == pytest.approx(12.8)
    (sp,) = p.by_name("jax_pack")
    assert (sp.tags["picks"], sp.tags["fills"]) == (9, 4)

    class Unread:
        def __int__(self):
            raise AssertionError("a count read back without a profiler")

        __index__ = __bool__ = __int__

    jitted = engine_jax._pack_all_types

    def counts_unread(*a, **k):
        return jitted(*a, **k)[:6] + (Unread(), Unread())

    counts_unread._cache_size = jitted._cache_size
    monkeypatch.setattr(engine_jax, "_pack_all_types", counts_unread)
    assert full_reconfiguration(ts, cat, None, **kw).assignments == \
        cfg.assignments
