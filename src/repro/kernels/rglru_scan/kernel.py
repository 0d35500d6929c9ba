"""Pallas TPU kernel for the RG-LRU linear recurrence.

Grid: (batch, R/block_r, S/block_s) with the sequence dimension innermost
and sequential; the hidden state is carried across sequence blocks in VMEM
scratch.  Within a block, a fori_loop walks sublane-aligned tiles of rows
(8 for f32, 16 for bf16) and unrolls the steps inside a tile — each step
is a fused multiply-add over a (block_r,) vector lane, which is VPU-bound
by nature
(the recurrence has no matmul to feed the MXU; the surrounding projections
do that).  block_r = 512 lanes amortizes loop overhead.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, u_ref, h0_ref, o_ref, h_scr, *, block_s: int, rows: int):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        h_scr[...] = h0_ref[0].astype(jnp.float32)  # (1, block_r)

    row_id = jax.lax.broadcasted_iota(jnp.int32, (rows, h_scr.shape[1]), 0)

    def group(g, h):
        # one sublane-aligned tile of `rows` steps per iteration: Mosaic
        # loads and stores whole tiles, the steps inside are unrolled
        sl = pl.ds(pl.multiple_of(g * rows, rows), rows)
        a = a_ref[0, sl, :].astype(jnp.float32)  # (rows, block_r)
        u = u_ref[0, sl, :].astype(jnp.float32)
        out = jnp.zeros_like(a)
        for j in range(rows):
            h = a[j:j + 1] * h + u[j:j + 1]
            out = jnp.where(row_id == j, h, out)
        o_ref[0, sl, :] = out.astype(o_ref.dtype)
        return h

    h_scr[...] = jax.lax.fori_loop(0, block_s // rows, group, h_scr[...])


def rglru_scan_pallas(a, u, h0, *, block_r: int = 512, block_s: int = 256,
                      interpret: bool = False):
    """a, u: (B, S, R); h0: (B, R).  Returns h_seq (B, S, R)."""
    B, S, R = a.shape
    block_r = min(block_r, R)
    block_s = min(block_s, S)
    assert R % block_r == 0 and S % block_s == 0
    # sublane tile height of the narrower stream: 8 rows of f32, 16 of bf16
    rows = 32 // min(a.dtype.itemsize, u.dtype.itemsize)
    assert block_s % rows == 0, (block_s, rows)
    kernel = functools.partial(_kernel, block_s=block_s, rows=rows)
    out = pl.pallas_call(
        kernel,
        grid=(B, R // block_r, S // block_s),
        in_specs=[
            pl.BlockSpec((1, block_s, block_r), lambda b, ri, si: (b, si, ri)),
            pl.BlockSpec((1, block_s, block_r), lambda b, ri, si: (b, si, ri)),
            pl.BlockSpec((1, 1, block_r), lambda b, ri, si: (b, 0, ri)),
        ],
        out_specs=pl.BlockSpec((1, block_s, block_r),
                               lambda b, ri, si: (b, si, ri)),
        out_shape=jax.ShapeDtypeStruct((B, S, R), u.dtype),
        scratch_shapes=[pltpu.VMEM((1, block_r), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, u, h0[:, None, :])
    return out
