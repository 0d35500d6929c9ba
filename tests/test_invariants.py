"""Property-test harness pinning the simulator's conservation laws across
randomly *composed* scenarios (spot × multi-region × burstable ×
deferrable × service).

Every billing and signalling pathway in the simulator must balance no
matter which scenario axes are stacked:

* **billing conservation** — on static catalogs the total cost equals the
  per-instance recompute (lifetime × hourly price, summed over every
  instance ever launched — commitment-pool instances excluded: they bill
  zero marginal) plus the standing pool bills (pool capacity-hours × the
  discounted rate: each pool-hour paid exactly once, used or idle) plus
  egress; on multi-region catalogs the per-region ledger sums to the
  total either way, and on multi-provider catalogs so does the
  per-provider ledger;
* **commitment accounting** — ``commitment_cost`` re-derives from the
  capacity integral, utilization stays in [0, 1], and idle waste is
  exactly the uncovered capacity-hours at the discounted rate;
* **egress exactly once** — each cross-region checkpoint move bills the
  egress fee exactly once (the instrumented charge log matches both the
  egress total and the migration counter);
* **no billing while pending** — a job held by an admission controller
  has no instances, so nothing accrues before its first admission;
* **bus exactly-once** — every pressure signal reaches every subscriber
  exactly once, including a second independent subscriber;
* **serving accounting** — served requests integrate the request profile
  exactly over the job's active window, and the SLO counters never exceed
  it.

The hypothesis sweep (bounded profile: few examples, no deadline — CI
installs the ``test`` extra) drives random axis combinations through the
laws; seeded fallback tests run the same checker without hypothesis so the
laws stay pinned even in a bare environment.
"""
import itertools

import pytest

from repro.autoscale import latest_start_s
from repro.cluster import traces
from repro.cluster import (SimConfig, Simulator, burstable_trace,
                           deferrable_trace, physical_trace, portfolio_trace)
from repro.core import cluster_types
from repro.core import (CommitmentModel, EvaScheduler, PriceModel, Provider,
                        RequestProfile, ServiceSpec, UtilityCurve,
                        aws_catalog, burstable_demo_catalog,
                        dispersed_demo_regions, make_job,
                        multi_provider_catalog, multi_region_catalog)
from repro.core.workloads import WORKLOAD_INDEX, checkpoint_size_gb
from repro.obs import FlightRecorder
from repro.policies import (AutoscaleLayer, CreditLayer, MultiRegionLayer,
                            PortfolioLayer, SLOLayer, SpotLayer)

EMBED = WORKLOAD_INDEX["embed-serve"]


class _Instrumented(Simulator):
    """Logs every cross-region egress charge and adds a second pressure-bus
    subscriber, so the conservation checker can audit both."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.egress_calls = []
        self.bus_copy = []
        self.pressure_bus.subscribe(self.bus_copy.append)

    def _cross_region_charge(self, workload, r_s, r_d):
        if r_s != r_d:
            self.egress_calls.append((workload, r_s, r_d))
        return super()._cross_region_charge(workload, r_s, r_d)


def _service_job(job_id, duration_s=2700.0):
    """Small embed-serve fleet with a stepped request profile (a breakpoint
    inside the window keeps the integral law non-trivial)."""
    spec = ServiceSpec(
        requests=RequestProfile((0.0, 600.0, 1500.0), (0.0, 80.0, 40.0)),
        utility=UtilityCurve(100.0), per_replica_rps=400.0,
        base_latency_ms=25.0)
    return make_job(job_id=job_id, workload=EMBED, arrival_time=0.0,
                    duration_s=duration_s, n_tasks=2, service=spec)


def _lambda_integral(prof, a, b):
    ts = (a,) + prof.breakpoints_between(a, b) + (b,)
    return sum(prof.rate_at(t0) * (t1 - t0) for t0, t1 in zip(ts, ts[1:]))


def _compose(catalog_kind, spot, deferrable, service, hazard, n_jobs, seed):
    """Build one composed scenario: catalog, jobs, stack, sim config."""
    pm = PriceModel.mean_reverting(discount=0.4, seed=seed + 1) if spot \
        else None
    if catalog_kind == "multiregion":
        cat = multi_region_catalog(dispersed_demo_regions(2))
        layers = [SpotLayer(), MultiRegionLayer()]
    elif catalog_kind == "provider":
        # two providers + a commitment pool: the full portfolio grid
        cm = CommitmentModel(instance_type="c7i.2xlarge", pool_size=2,
                             rate_fraction=0.5)
        pm2 = PriceModel.mean_reverting(discount=0.45, seed=seed + 2) \
            if spot else None
        cat = multi_provider_catalog([
            Provider(name="aws", price_model=pm, commitments=(cm,)),
            Provider(name="gcp", cost_scale=1.03, price_model=pm2)])
        layers = [SpotLayer(), MultiRegionLayer(), PortfolioLayer()]
    elif catalog_kind == "burstable":
        cat = burstable_demo_catalog(price_model=pm)
        layers = [SpotLayer(), CreditLayer()]
    else:
        cat = aws_catalog(price_model=pm)
        layers = [SpotLayer()]
    if deferrable:
        jobs = deferrable_trace(n_jobs=n_jobs, seed=seed)
        layers.append(AutoscaleLayer(strike=0.9))
    elif catalog_kind == "burstable":
        jobs = burstable_trace(n_jobs=n_jobs, seed=seed)
    elif catalog_kind == "provider":
        # steady base that can fill the pool + bursts that overflow it
        jobs = portfolio_trace(n_steady=2, n_burst=n_jobs, seed=seed,
                               horizon_h=2.0)
    else:
        jobs = physical_trace(n_jobs=n_jobs, seed=seed,
                              duration_range_h=(0.2, 0.5))
    layers.append(SLOLayer())
    if service:
        jobs = jobs + [_service_job(job_id=10_000 + seed)]
    cfg = SimConfig(seed=seed,
                    preemption_hazard_per_hour=hazard if spot else 0.0)
    return cat, jobs, layers, cfg


def _run_composed(catalog_kind, spot, deferrable, service, hazard, n_jobs,
                  seed):
    cat, jobs, layers, cfg = _compose(catalog_kind, spot, deferrable,
                                      service, hazard, n_jobs, seed)
    # a flight recorder rides along on every composed scenario: the
    # event-cost conservation law below audits its ledger against the
    # metrics, and recording must never perturb any of the other laws
    rec = FlightRecorder(meta={"catalog": catalog_kind, "seed": seed})
    sched = EvaScheduler(cat, policies=layers, recorder=rec)
    sim = _Instrumented(cat, jobs, sched, cfg, recorder=rec)
    m = sim.run()
    return sim, m, cat, jobs


def _pool_standing(sim):
    """Σ pool capacity-hours × discounted rate (the exactly-once pool bill)."""
    if not getattr(sim, "_commit", False):
        return 0.0
    return sum(sim._pool_capacity_s[ri] / 3600.0 * sim._pool_rate[ri]
               for ri, _cm in sim._pools)


def _check_conservation(sim, m, cat, jobs):
    # --- billing: every instance ever launched, lifetime × hourly price;
    # pool instances bill zero marginal (the standing pool bill — capacity-
    # hours × discounted rate, exactly once per pool-hour — covers them)
    assert m.total_cost >= 0.0
    pool_inst = lambda inst: (getattr(sim, "_commit", False)  # noqa: E731
                              and sim._pool_type[inst.type_index])
    if not sim._spot:
        recomputed = sum(
            (inst.terminated_t - inst.request_t) / 3600.0
            * cat.costs[inst.type_index]
            for inst in sim.instances.values() if not pool_inst(inst))
        assert m.total_cost == pytest.approx(
            recomputed + _pool_standing(sim) + m.egress_cost,
            rel=1e-9, abs=1e-9)
    for inst in sim.instances.values():  # nothing left accruing
        assert inst.terminated_t is not None
    # --- ledgers: always present (empty-safe dicts), gated by explicit
    # flags; each ledger sums to the total on its axis
    assert isinstance(m.cost_by_region, dict)
    assert isinstance(m.cost_by_provider, dict)
    assert isinstance(m.commitment_utilization, dict)
    assert m.has_regions == (cat.regions is not None)
    if m.has_regions:
        assert m.total_cost == pytest.approx(
            sum(m.cost_by_region.values()), rel=1e-9, abs=1e-9)
    else:
        assert m.cost_by_region == {}
    assert m.has_providers == (cat.regions is not None and any(
        r.provider is not None for r in cat.regions))
    if m.has_providers:
        assert m.total_cost == pytest.approx(
            sum(m.cost_by_provider.values()), rel=1e-9, abs=1e-9)
    else:
        assert m.cost_by_provider == {}
    # --- commitments: standing bill re-derived from the capacity integral,
    # utilization bounded, idle waste = uncovered capacity at the rate
    assert m.has_commitments == cat.has_commitments
    if m.has_commitments:
        assert m.commitment_cost == pytest.approx(_pool_standing(sim),
                                                  rel=1e-9, abs=1e-9)
        assert m.commitment_cost <= m.total_cost + 1e-9
        idle = 0.0
        for ri, _cm in sim._pools:
            name = cat.regions[ri].name
            util = m.commitment_utilization[name]
            assert 0.0 <= util <= 1.0 + 1e-12
            cap_s = sim._pool_capacity_s[ri]
            cov_s = sim._pool_covered_s[ri]
            assert 0.0 <= cov_s <= cap_s + 1e-9
            idle += (cap_s - cov_s) / 3600.0 * sim._pool_rate[ri]
        assert m.commitment_idle_cost == pytest.approx(idle, rel=1e-9,
                                                       abs=1e-9)
    else:
        assert m.commitment_cost == 0.0
        assert m.commitment_idle_cost == 0.0
        assert m.commitment_utilization == {}
    # --- egress: exactly once per cross-region move, fee re-derived
    assert len(sim.egress_calls) == m.cross_region_migrations
    if cat.transfer is not None:
        fees = sum(cat.transfer.egress_usd(r_s, r_d, checkpoint_size_gb(w))
                   for w, r_s, r_d in sim.egress_calls)
        assert m.egress_cost == pytest.approx(fees, rel=1e-9, abs=1e-9)
    else:
        assert m.egress_cost == 0.0
    # --- pressure bus: exactly once per subscriber, audited by the copy
    bus = sim.pressure_bus
    n_subs = len(bus._subscribers)
    assert n_subs >= 2  # scheduler + instrumented copy
    assert bus.delivered == bus.published * n_subs
    assert len(sim.bus_copy) == bus.published
    # --- serving: request accounting integrates the profile exactly
    service_jobs = [j for j in jobs if j.service is not None]
    assert m.has_service == bool(service_jobs)
    if service_jobs:
        expect = sum(
            _lambda_integral(j.service.requests, j.arrival_time,
                             j.arrival_time + j.duration_s)
            for j in service_jobs)
        assert m.slo_requests_total == pytest.approx(expect, rel=1e-9)
        assert m.slo_requests_ok <= m.slo_requests_total + 1e-9
        assert m.service_utility_sum <= m.slo_requests_total + 1e-9
        for j in service_jobs:  # wall-clock window, not iterations
            assert j.completion_time == pytest.approx(
                j.arrival_time + j.duration_s)
    # --- every job completes (deadline backstops, service windows, batch)
    for j in jobs:
        assert j.completion_time is not None
    # --- event-cost conservation: every dollar the simulator bills flows
    # through the flight recorder's ledger exactly once, so the aggregated
    # (category, key) cells sum back to the metrics totals on every axis
    log = m.events
    if log is not None:
        assert sum(log.costs.values()) == pytest.approx(m.total_cost,
                                                        rel=1e-9, abs=1e-9)
        by_cat = log.cost_by("category")
        assert by_cat.get("egress", 0.0) == pytest.approx(m.egress_cost,
                                                          rel=1e-9, abs=1e-9)
        assert by_cat.get("commitment", 0.0) == pytest.approx(
            m.commitment_cost, rel=1e-9, abs=1e-9)
        if m.has_regions:
            by_key = log.cost_by("key")
            for name, amt in m.cost_by_region.items():
                assert by_key.get(name, 0.0) == pytest.approx(amt, rel=1e-9,
                                                              abs=1e-9)
        # lifecycle sanity: one terminate per provision (the billing law
        # above already pinned that nothing is left accruing)
        counts = log.counts()
        assert counts.get("terminate", 0) == counts.get("provision", 0)


# --------------------------------------------------------- seeded fallback
SEEDED = [
    ("aws", True, False, True, 0.4, 4, 2),
    ("multiregion", False, False, True, 0.0, 3, 5),
    ("burstable", True, True, False, 0.3, 4, 8),
    ("provider", True, False, False, 0.3, 3, 11),
    ("provider", False, False, True, 0.0, 3, 21),
]


@pytest.mark.parametrize("kind,spot,defer,service,hazard,n,seed", SEEDED)
def test_conservation_seeded(kind, spot, defer, service, hazard, n, seed):
    _check_conservation(*_run_composed(kind, spot, defer, service, hazard,
                                       n, seed))


def test_no_billing_while_pending():
    """A never-admit strike controller holds every deferrable job until
    its latest-start deadline: no instance may even be *requested* (let
    alone billed) before the earliest latest-start in the trace."""
    cat = aws_catalog()  # static: billing is exactly instance lifetimes
    jobs = deferrable_trace(n_jobs=5, seed=3)
    assert all(j.deferrable for j in jobs)
    sched = EvaScheduler(cat, policies=[SpotLayer(),
                                        AutoscaleLayer(strike=1e-9),
                                        SLOLayer()])
    sim = _Instrumented(cat, jobs, sched, SimConfig(seed=5))
    m = sim.run()
    first_ls = min(latest_start_s(j.deadline_s, j.duration_s) for j in jobs)
    assert m.instances_launched > 0
    for inst in sim.instances.values():
        assert inst.request_t >= first_ls - 1e-6
    assert m.deadline_misses == 0
    _check_conservation(sim, m, cat, jobs)


def test_ledgers_always_present_and_gated():
    """Regression for the latent ledger gap: every ledger dict exists on
    every run (empty-safe — no AttributeError / KeyError probing), and
    ``summary()`` keys are gated by the explicit ``has_*`` flags, not dict
    truthiness (a multi-region run whose ledger happens to be all-zero
    must still report it)."""
    # single-region, commitment-free: flags off, ledgers empty, no keys
    sim, m, _, _ = _run_composed("aws", False, False, False, 0.0, 2, 3)
    assert (m.has_regions, m.has_providers, m.has_commitments) == \
        (False, False, False)
    assert m.cost_by_region == {} and m.cost_by_provider == {}
    assert m.commitment_utilization == {}
    s = m.summary()
    assert "egress_cost" not in s and "capacity_denied" not in s
    assert not any(k.startswith(("cost_provider_", "util_")) for k in s)
    assert "commitment_cost" not in s
    # multi-region without providers: region keys present even while the
    # provider axis stays silent
    sim, m, cat_mr, _ = _run_composed("multiregion", False, False, False,
                                      0.0, 2, 3)
    assert m.has_regions and not m.has_providers
    s = m.summary()
    assert "egress_cost" in s
    assert all(f"cost_{r.name}" in s for r in cat_mr.regions)
    assert not any(k.startswith("cost_provider_") for k in s)
    # full provider grid: all three axes report
    sim, m, cat, _ = _run_composed("provider", False, False, False, 0.0,
                                   2, 3)
    assert m.has_regions and m.has_providers and m.has_commitments
    s = m.summary()
    assert any(k.startswith("cost_provider_") for k in s)
    assert any(k.startswith("util_") for k in s)
    assert "commitment_cost" in s and "commitment_idle_cost" in s


# ---------------------------------------- vectorized vs scalar equality
def _run_mode(kind, spot, defer, service, hazard, n, seed, *, vectorized,
              recording):
    """One composed scenario in one simulator mode; fresh jobs per run
    (the simulator mutates Job objects)."""
    cat, jobs, layers, cfg = _compose(kind, spot, defer, service, hazard,
                                      n, seed)
    rec = FlightRecorder(meta={"mode": "vec" if vectorized else "scalar"}) \
        if recording else None
    sched = EvaScheduler(cat, policies=layers, recorder=rec)
    sim = Simulator(cat, jobs, sched, cfg, recorder=rec,
                    vectorized=vectorized)
    return sim.run()


def _dicts_close(ds, dv, label):
    assert set(ds) == set(dv), label
    for k in ds:
        assert dv[k] == pytest.approx(ds[k], rel=1e-9, abs=1e-9), \
            f"{label}[{k}]"


_ID_COUNTERS = ((cluster_types, "_task_counter"), (traces, "_job_ids"),
                (traces, "_task_ids"))


def _rewind_ids(state=None):
    """Take (no argument) or restore the next values of the global counters
    that fresh jobs and tasks draw their ids from.  Two runs of one scenario
    started from the same values get the same ids.  Plans may depend on the
    ids' values (Partial takes tasks of equal price in the iteration order
    of a set of ids), so runs whose ids differ may rightly part ways."""
    if state is None:
        state = [next(getattr(mod, name)) for mod, name in _ID_COUNTERS]
    for (mod, name), value in zip(_ID_COUNTERS, state):
        setattr(mod, name, itertools.count(value))
    return state


def _check_vec_scalar_equality(kind, spot, defer, service, hazard, n, seed):
    """``Simulator(..., vectorized=True)`` must replay the exact event
    trajectory of the scalar reference on the same jobs, ids included:
    identical counters, summaries, ledgers, and recorder cost cells within
    the documented <=1e-9 relative tolerance (float reassociation on the
    vectorized sums), with recording both off and on."""
    for recording in (False, True):
        ids = _rewind_ids()
        mv = _run_mode(kind, spot, defer, service, hazard, n, seed,
                       vectorized=True, recording=recording)
        _rewind_ids(ids)
        ms = _run_mode(kind, spot, defer, service, hazard, n, seed,
                       vectorized=False, recording=recording)
        ss, sv = ms.summary(), mv.summary()
        assert set(ss) == set(sv)
        for k, a in ss.items():
            b = sv[k]
            if isinstance(a, float) or isinstance(b, float):
                assert b == pytest.approx(a, rel=1e-9, abs=1e-9), k
            else:
                assert a == b, k  # counters are decisions: exact
        _dicts_close(ms.cost_by_region, mv.cost_by_region, "cost_by_region")
        _dicts_close(ms.cost_by_provider, mv.cost_by_provider,
                     "cost_by_provider")
        _dicts_close(ms.commitment_utilization, mv.commitment_utilization,
                     "commitment_utilization")
        if recording:
            # event-cost conservation holds in both modes, and the
            # aggregated ledger cells agree cell-by-cell
            for m in (ms, mv):
                assert sum(m.events.costs.values()) == pytest.approx(
                    m.total_cost, rel=1e-9, abs=1e-9)
            _dicts_close(ms.events.cost_by("category"),
                         mv.events.cost_by("category"), "cost_by_category")
            _dicts_close(ms.events.cost_by("key"), mv.events.cost_by("key"),
                         "cost_by_key")
            assert ms.events.counts() == mv.events.counts()


@pytest.mark.parametrize("kind,spot,defer,service,hazard,n,seed", SEEDED)
def test_vectorized_matches_scalar_seeded(kind, spot, defer, service,
                                          hazard, n, seed):
    _check_vec_scalar_equality(kind, spot, defer, service, hazard, n, seed)


# ------------------------------------------------------- hypothesis sweep
@pytest.fixture(scope="module")
def _hyp():
    return pytest.importorskip("hypothesis")


def test_conservation_random_compositions(_hyp):
    """Random axis compositions through the same conservation checker.

    Bounded profile (few examples, no deadline): each example is a full
    simulator run, so the sweep stays CI-sized; the seeded tests above
    keep the laws pinned when hypothesis is absent.
    """
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st

    @settings(max_examples=8, deadline=None, derandomize=True,
              suppress_health_check=[HealthCheck.too_slow])
    @given(
        kind=st.sampled_from(["aws", "multiregion", "burstable",
                              "provider"]),
        spot=st.booleans(),
        deferrable=st.booleans(),
        service=st.booleans(),
        hazard=st.sampled_from([0.0, 0.3, 0.6]),
        n_jobs=st.integers(2, 5),
        seed=st.integers(0, 50),
    )
    def inner(kind, spot, deferrable, service, hazard, n_jobs, seed):
        _check_conservation(*_run_composed(kind, spot, deferrable, service,
                                           hazard, n_jobs, seed))

    inner()
