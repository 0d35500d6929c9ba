"""Wall time per round of Partial's keep test (``partial.keep_test``): the
round's reservation prices and job RP sums, the live instances' evaluation
and the keep-or-evict verdicts (ms/round)."""
from chipbench.spans import ms_per_round


def read(rec):
    return ms_per_round(rec, "partial.keep_test")
