"""The spot-market path of the harness, on a test-only deployment
(``data/spot-rehearsal.json``: ``eva-alibaba-1k``'s trace under
mean-reverting spot prices, a 0.3/h preemption hazard with 120 s notices,
and ``SpotLayer``), driven through ``run.simulate`` and ``run.judge`` at a
tiny fleet on the CPU (the harness's look for a chip is skipped)."""
import json
import os

import numpy as np
import pytest

import run
from chipbench import check, reference as ref
from chipbench.faults import FAULTS, SPOT_FAULTS

DATA = os.path.join(os.path.dirname(__file__), "data", "spot-rehearsal.json")
NAME = "spot-rehearsal"
SEEDS = [2**35 + 21, 2**35 + 22, 2**35 + 23]


@pytest.fixture
def config():
    with open(DATA) as f:
        return json.load(f)


@pytest.fixture
def spot(monkeypatch, config):
    """``run.load_cell`` serves the deployment under ``fleet1k-steady``'s
    traffic and harness settings."""
    bench, _, _, traffic, harness = run.load_cell("fleet1k-steady")
    cell = {"name": NAME, "config": config["name"], "traffic": "steady",
            "chips": 1}
    monkeypatch.setattr(run, "load_cell",
                        lambda name: (bench, cell, config, traffic, harness))
    return NAME


def judged(seed):
    r = run.simulate(NAME, seed, 1.5, traced=False,
                     rehearse=run.REHEARSAL_JOBS)
    numbers = run.judge(r)
    ok, _ = check.verdicts(numbers, r["harness"]["limits"])
    return ok, numbers, r


@pytest.mark.parametrize("seed", SEEDS)
def test_spot_run_is_correct(spot, seed):
    ok, numbers, r = judged(seed)
    assert ok, numbers
    assert numbers["forced_checked"] >= 1, numbers
    assert numbers["snapshots_checked"] >= 2, numbers
    kinds = r["record"]["round_kinds"]
    assert len(kinds) == r["attempted"] and "forced" in kinds


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("fault", sorted(SPOT_FAULTS) + ["always_partial"])
def test_spot_fault_is_not_correct(monkeypatch, spot, fault, seed):
    {**FAULTS, **SPOT_FAULTS}[fault](monkeypatch.setattr)
    ok, numbers, _ = judged(seed)
    assert not ok, numbers


def test_reference_market_is_the_programs_price_path(config):
    """The reference's copy of the mean-reverting path prices every type
    as the program's ``Catalog.at`` does, on and between grid points and
    past the horizon."""
    from repro.core.catalog import PriceModel, aws_catalog
    market = dict(config["market"])
    theirs = aws_catalog(getattr(PriceModel, market.pop("price_model"))(
        **market))
    mine = ref.Catalog(config["catalog"], config["families"],
                       config["market"])
    for t in (0.0, 299.9, 300.0, 12345.6, 86400.0 * 13.9, 86400.0 * 20):
        snap = theirs.at(t)
        assert mine.at(t).costs.tolist() == snap.costs.tolist()
        assert mine.at(t).order.tolist() == snap.order_desc.tolist()
    assert not np.array_equal(mine.at(0.0).costs, mine.at(3600.0).costs)
    static = ref.Catalog(config["catalog"], config["families"])
    assert static.at(3600.0) is static


@pytest.mark.parametrize("layer, said", [
    ("NoSuchLayer", "no policy layer 'NoSuchLayer'"),
    ("CreditLayer", "no reference for the policy layers ['CreditLayer']"),
])
def test_a_layer_the_harness_cannot_judge_stops_the_run(spot, config, layer,
                                                         said):
    config["scheduler"]["policies"].append({"layer": layer, "args": {}})
    with pytest.raises(SystemExit, match=said.replace("[", r"\[")):
        run.simulate(NAME, SEEDS[0], 1.5, traced=False,
                     rehearse=run.REHEARSAL_JOBS)
