"""The readers of the pack's greedy steps, on a span record made by hand:
``pack_picks`` sums the ``picks`` tags of the ``jax_pack`` spans per round,
``pack_pick_us`` divides the kernel's traced device time by them."""
import pytest

import run
from test_spans import record

MS = 1e-3


def picked():
    """``test_spans``'s two rounds, their three device calls tagged with
    the steps and fills the kernel counted."""
    rec = record()
    for s, (picks, fills) in zip(
            (s for s in rec["spans"] if s["name"] == "jax_pack"),
            ((9, 4), (1450, 370), (3, 2))):
        s["tags"].update(picks=picks, fills=fills)
    rec["trace"] = {"kernel_s": 29.4 * MS, "kernel_calls": 3}
    return rec


def test_pack_picks_sums_by_hand():
    assert run.reader("pack_picks")(picked()) == pytest.approx(
        (9 + 1450 + 3) / 2, rel=1e-12)


def test_pack_pick_us_divides_by_hand():
    assert run.reader("pack_pick_us")(picked()) == pytest.approx(
        29.4e3 / (9 + 1450 + 3), rel=1e-12)


def untagged():
    """A program whose pack spans carry no count, as before they did."""
    rec = record()
    rec["trace"] = {"kernel_s": 29.4 * MS, "kernel_calls": 3}
    return rec


def untraced():
    rec = picked()
    rec["trace"] = None
    return rec


def no_kernel():
    rec = picked()
    rec["trace"] = {"kernel_s": 0.0, "kernel_calls": 0}
    return rec


def no_spans():
    rec = picked()
    rec["spans"] = None
    return rec


@pytest.mark.parametrize("name", ["pack_picks", "pack_pick_us"])
@pytest.mark.parametrize("make", [untagged, no_spans, lambda: {"rounds": 2}],
                         ids=["untagged", "untraced spans", "no key"])
def test_pick_readers_without_counts_read_nothing(name, make):
    assert run.reader(name)(make()) is None


@pytest.mark.parametrize("make", [untraced, no_kernel],
                         ids=["no trace", "no kernel"])
def test_pick_time_without_a_trace_reads_nothing(make):
    assert run.reader("pack_pick_us")(make()) is None
