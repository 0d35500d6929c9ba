"""``correct`` on a run with the timed path sound, with the control in the
pack's place, and with the timed path broken underneath.  Each drives the
rest of a run at a tiny fleet on the CPU (the harness's look for a chip is
skipped)."""
import pytest

import run
from chipbench import check
from chipbench.faults import FAULTS

CELLS = ["fleet1k-steady", "paper-steady"]
SEED = 2**35 + 11


def correct_of(name, seconds=1.5, seed=SEED):
    r = run.run_cell(name, seed, seconds, traced=False,
                     rehearse=run.REHEARSAL_JOBS)
    return r["correct"], {k: v["value"] for k, v in r["checks"].items()}


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    ok, numbers = correct_of(name)
    assert ok, numbers


@pytest.mark.parametrize("name", CELLS)
def test_control_fails(name):
    # bfloat16 rounds alike to float64 on a fleet of 20 jobs: 200 differ
    r = run.simulate(name, SEED, 1.5, traced=False, rehearse=200)
    numbers = run.judge(r, packer=check.bf16_packer)
    ok, shown = check.verdicts(numbers, r["harness"]["limits"])
    assert not ok, shown


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_path_is_not_correct(monkeypatch, name, fault):
    FAULTS[fault](monkeypatch.setattr)
    ok, numbers = correct_of(name)
    assert not ok, numbers


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("seed", [2**35 + 31, 2**35 + 32, 2**35 + 33])
def test_always_partial_is_not_correct_on_any_seed(monkeypatch, name, seed):
    """The sample holds a round whose Full candidate the ensemble's own
    values rate higher, so an ensemble that never adopts Full is caught
    whichever rounds the seed draws."""
    FAULTS["always_partial"](monkeypatch.setattr)
    ok, numbers = correct_of(name, seed=seed)
    assert not ok, numbers
