"""Wall time per round of Partial's best fit into the kept instances'
spare capacity (``partial.best_fit``): the capacity scan and the grown-set
evaluations (ms/round)."""
from chipbench.spans import ms_per_round


def read(rec):
    return ms_per_round(rec, "partial.best_fit")
