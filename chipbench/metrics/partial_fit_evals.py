"""Grown-set evaluations of Partial's best fit per round: the ``evals`` tag
of ``partial.best_fit``, summed (count/round)."""
from chipbench.spans import tag_per_round


def read(rec):
    return tag_per_round(rec, "partial.best_fit", "evals")
