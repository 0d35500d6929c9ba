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
