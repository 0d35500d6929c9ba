"""Pallas TPU flash attention (causal / local-window, GQA).

Grid: (batch·q_heads, Sq/block_q, Sk/block_k) with the KV dimension
innermost and sequential; online-softmax statistics (m, l) and the output
accumulator live in VMEM scratch across KV iterations.  Blocks are
MXU-aligned (block_q = block_k = 128 by default).  Causal/local block
skipping prunes fully-masked KV blocks via pl.when.

The kernel is forward-only; ``flash_attention_pallas`` carries a custom VJP
whose backward recomputes through the memory-efficient jnp oracle
(``ref.attention_chunked``), so training steps differentiate through it.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import attention_chunked

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, window: Optional[int],
            block_q: int, block_k: int, num_k_blocks: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k
    # block-level skip: causal => kv block must start at/below the last query
    # row; local window => kv block must end within the window of the first
    # query row.
    run = jnp.asarray(True)
    if causal:
        run = jnp.logical_and(run, k_start <= q_start + block_q - 1)
    if window is not None:
        run = jnp.logical_and(run, k_start + block_k - 1 > q_start - window)

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32) * scale  # (bq, hd)
        k = k_ref[0].astype(jnp.float32)  # (bk, hd)
        v = v_ref[0].astype(jnp.float32)  # (bk, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones_like(s, dtype=bool)
        if causal:
            mask &= qpos >= kpos
        if window is not None:
            mask &= qpos - kpos < window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot(
            p.astype(v.dtype), v)
        m_scr[...] = m_new

    @pl.when(ki == num_k_blocks - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-30)[:, None]
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flash_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                           causal: bool = True, window: Optional[int] = None,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = False) -> jnp.ndarray:
    """q: (B, Sq, H, hd); k/v: (B, Sk, KH, hd).  Sq == Sk (self-attention
    train/prefill); decode-style single-token attention should use the
    reference matvec path instead."""
    return _flash(q, k, v, causal, window, block_q, block_k, interpret)


def _flash_forward(q, k, v, causal, window, block_q, block_k, interpret):
    B, Sq, H, hd = q.shape
    _, Sk, KH, _ = k.shape
    assert H % KH == 0
    group = H // KH
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, Sk, block_q, block_k)
    scale = 1.0 / (hd ** 0.5)

    qh = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, hd)
    kh = k.transpose(0, 2, 1, 3).reshape(B * KH, Sk, hd)
    vh = v.transpose(0, 2, 1, 3).reshape(B * KH, Sk, hd)
    nq, nk = Sq // block_q, Sk // block_k

    def kv_index(bh, qi, ki):
        b = bh // H
        h = bh % H
        return (b * KH + h // group, ki, 0)

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, num_k_blocks=nk)
    out = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, hd), kv_index),
            pl.BlockSpec((1, block_k, hd), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),      # m: running max
            pltpu.VMEM((block_q,), jnp.float32),      # l: running denom
            pltpu.VMEM((block_q, hd), jnp.float32),   # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qh, kh, vh)
    return out.reshape(B, H, Sq, hd).transpose(0, 2, 1, 3)


def _flash_fwd(q, k, v, *static):
    return _flash_forward(q, k, v, *static), (q, k, v)


def _flash_bwd(causal, window, block_q, block_k, interpret, res, g):
    _, vjp = jax.vjp(functools.partial(attention_chunked, causal=causal,
                                       window=window), *res)
    return vjp(g)


_flash = jax.custom_vjp(_flash_forward, nondiff_argnums=(3, 4, 5, 6, 7))
_flash.defvjp(_flash_fwd, _flash_bwd)
