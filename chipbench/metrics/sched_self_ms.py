"""Wall time per round inside ``sched.round`` that none of its direct
children covers: ``schedule()``'s own glue between the phases (ms/round)."""
from chipbench.spans import self_ms_per_round


def read(rec):
    return self_ms_per_round(rec, "sched.round")
