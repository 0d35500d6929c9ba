"""Host time per round around the device pack: Algorithm 1's inputs and
``pack_jax``'s class collapse and padding (both ``pack.prepare`` spans),
and the fill records read back and expanded to task rows
(``pack.readback``) (ms/round)."""
from chipbench.spans import ms_per_round


def read(rec):
    return ms_per_round(rec, "pack.prepare", "pack.readback")
