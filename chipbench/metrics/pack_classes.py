"""Task classes the device packs were given per round: the ``classes`` tag
of ``pack_jax``'s ``pack.prepare``, summed over the round's packs
(count/round).  Below the task count where tasks share workload, demand and
job RP, as a multi-task job's do."""
from chipbench.spans import tag_per_round


def read(rec):
    return tag_per_round(rec, "pack.prepare", "classes")
