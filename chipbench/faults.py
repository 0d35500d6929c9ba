"""Faults planted in the timed path, to show that ``correct`` catches them.

Each entry of ``FAULTS`` takes a ``setattr``-like function (``setattr``
itself, or pytest's ``monkeypatch.setattr``) and breaks the program through
it: the device pack's answer altered where it is produced, half of the pack's
rows left out, a round that returns its state unchanged, or an ensemble
that adopts Partial whatever the two plans are worth.  ``SPOT_FAULTS``
break what only a deployment under ``SpotLayer`` runs: rounds planned at
the static on-demand prices, or an instance under a revocation notice kept.
"""
import numpy as np

from repro.core import engine_jax, scheduler
from repro.core.cluster_types import ClusterConfig
from repro.core.ensemble import EnsembleDecision
from repro.policies import SpotLayer


def _broken_pack(alter):
    orig = engine_jax.pack_jax

    def pack_jax(demand_by_family, workloads, rp, job_rp, catalog, pairwise,
                 type_mask=None, region_budget=None):
        out = orig(demand_by_family, workloads, rp, job_rp, catalog,
                   pairwise, type_mask, region_budget)
        return alter(out, catalog)
    return pack_jax


def _half_left_out(out, catalog):
    """Rows of odd index left out of the placements."""
    keep = []
    for k, rows in out:
        rows = [r for r in rows if r % 2 == 0]
        if rows:
            keep.append((k, rows))
    return keep


def _answer_altered(out, catalog):
    """The first instance moved to the dearest type of its family."""
    if not out:
        return out
    k, rows = out[0]
    fam = catalog.family_ids[k]
    same = np.nonzero(catalog.family_ids == fam)[0]
    dear = int(same[np.argmax(catalog.costs[same])])
    if dear == k:
        dear = int(same[np.argmin(catalog.costs[same])])
    return [(dear, rows)] + out[1:]


def _unchanged(self, view):
    self.rounds += 1
    return ClusterConfig([(i.type_index, i.task_ids) for i in view.live])


def _always_partial(s_full, m_full, s_partial, m_partial, d_hat_s):
    return EnsembleDecision(False, s_full, s_partial, m_full, m_partial,
                            d_hat_s)


FAULTS = {
    "half_left_out": lambda put: put(engine_jax, "pack_jax",
                                     _broken_pack(_half_left_out)),
    "answer_altered": lambda put: put(engine_jax, "pack_jax",
                                      _broken_pack(_answer_altered)),
    "state_unchanged": lambda put: put(scheduler.EvaScheduler, "schedule",
                                       _unchanged),
    "always_partial": lambda put: put(scheduler, "choose", _always_partial),
}


SPOT_FAULTS = {
    "static_prices": lambda put: put(
        SpotLayer, "plan_catalog", lambda self, catalog, view, d: catalog),
    "kept_revoked": lambda put: put(
        SpotLayer, "evacuate", lambda self, raw, view: set()),
}
