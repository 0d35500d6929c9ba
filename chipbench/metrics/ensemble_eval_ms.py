"""Wall time per round of the ensemble's evaluation of its two candidates:
S_F and S_P (``ensemble.saving``), M_F and M_P (``ensemble.migration``)
(ms/round)."""
from chipbench.spans import ms_per_round


def read(rec):
    return ms_per_round(rec, "ensemble.saving", "ensemble.migration")
