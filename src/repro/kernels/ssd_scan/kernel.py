"""Pallas TPU kernel for the Mamba2 SSD intra-chunk computation.

Grid: (batch·heads, n_chunks).  Each cell computes, for one (head, chunk):

    scores = (C · Bᵀ) ⊙ L ⊙ dtᵀ          (Q×Q masked decay "attention")
    y_intra = scores · x                  (Q×P)
    chunk_in = (x ⊙ dt·decay_to_end)ᵀ · B (P×N input->state contribution)

Cumulative log-decays are precomputed outside (cheap elementwise); the
inter-chunk state passing is a tiny scan over n_chunks in the ops wrapper.
Q (chunk) = 256 and N = 128 keep every matmul MXU-aligned; the working set
(~0.5 MB fp32) fits VMEM comfortably.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, row_ref, col_ref, b_ref, c_ref, y_ref, cin_ref, *,
            chunk: int):
    # dt and cum arrive twice, as rows (2, Q) and as columns (Q, 2), so the
    # outer difference and both broadcasts need no in-kernel transpose
    x = x_ref[0].astype(jnp.float32)        # (Q, P)
    dt_r = row_ref[0, 0:1, :]               # (1, Q)
    cum_r = row_ref[0, 1:2, :]              # (1, Q)
    dt_c = col_ref[0, :, 0:1]               # (Q, 1)
    cum_c = col_ref[0, :, 1:2]              # (Q, 1)
    Bm = b_ref[0].astype(jnp.float32)       # (Q, N)
    Cm = c_ref[0].astype(jnp.float32)       # (Q, N)

    diff = cum_c - cum_r
    qi = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    ki = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    L = jnp.exp(jnp.where(qi >= ki, diff, -jnp.inf))  # mask pre-exp
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ()))) * L
    scores = scores * dt_r
    y_ref[0] = jax.lax.dot(scores, x).astype(y_ref.dtype)

    decay_end = jnp.exp(cum_c[chunk - 1:, :] - cum_c)  # (Q, 1)
    xw = x * (dt_c * decay_end)  # (Q, P)
    cin_ref[0, 0] = jax.lax.dot_general(
        xw, Bm, (((0,), (0,)), ((), ()))).astype(cin_ref.dtype)  # (P, N)


def ssd_chunk_pallas(x, dt, cum, Bm, Cm, *, chunk: int,
                     interpret: bool = False):
    """x: (BH, S, P); dt/cum: (BH, S) float32; Bm/Cm: (BH, S, N) (already
    head-expanded).  Returns (y_intra (BH,S,P), chunk_in (BH,nc,P,N))."""
    BH, S, P = x.shape
    N = Bm.shape[-1]
    assert S % chunk == 0
    nc = S // chunk
    rows = jnp.stack([dt, cum], axis=1)  # (BH, 2, S)
    cols = jnp.stack([dt, cum], axis=2)  # (BH, S, 2)
    kernel = functools.partial(_kernel, chunk=chunk)
    y, cin = pl.pallas_call(
        kernel,
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, P), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, 2, chunk), lambda bh, ci: (bh, 0, ci)),
            pl.BlockSpec((1, chunk, 2), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, N), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, N), lambda bh, ci: (bh, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, P), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, 1, P, N), lambda bh, ci: (bh, ci, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, P), jnp.float32),
            jax.ShapeDtypeStruct((BH, nc, P, N), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(x, rows, cols, Bm, Cm)
    return y, cin
