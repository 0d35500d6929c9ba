"""Jit'd SSD entry point: Pallas intra-chunk kernel + jnp state passing on
TPU, chunked pure-jnp implementation elsewhere.  The Pallas path carries a
custom VJP whose backward recomputes through ``ssd_chunked_ref``."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .kernel import ssd_chunk_pallas
from .ref import ssd_chunked_ref, ssd_decode_step, ssd_ref  # noqa: F401


def _default_impl() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "reference"


def ssd(x, dt, A, B, C, D, *, chunk: int = 256, h0=None, impl: str = "auto",
        interpret: bool = False):
    """Mamba2 SSD forward. x: (Bt,S,H,P); dt: (Bt,S,H); A,D: (H,);
    B,C: (Bt,S,G,N).  Returns (y, h_final)."""
    if impl == "auto":
        impl = _default_impl()
    S = x.shape[1]
    pad = (-S) % chunk
    if pad and impl != "sequential":
        # dt = 0 padding: decay exp(A·0) = 1 and zero input leave the state
        # untouched, so trailing pad steps are inert.
        zp = lambda a: jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        y, h = ssd(zp(x), zp(dt), A, zp(B), zp(C), D, chunk=chunk, h0=h0,
                   impl=impl, interpret=interpret)
        return y[:, :S], h
    if impl == "reference":
        return ssd_chunked_ref(x, dt, A, B, C, D, chunk=chunk, h0=h0)
    if impl == "sequential":
        return ssd_ref(x, dt, A, B, C, D, h0=h0)
    return _ssd_pallas(x, dt, A, B, C, D, h0, chunk, interpret)


def _ssd_pallas_forward(x, dt, A, B, C, D, h0, chunk, interpret):
    Bt, S, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    rep = H // G
    nc = S // chunk
    dtf = dt.astype(jnp.float32)
    cum_full = jnp.cumsum((A[None, None, :] * dtf).reshape(Bt, nc, chunk, H),
                          axis=2).reshape(Bt, S, H)
    # head-major flattening for the kernel
    xh = x.transpose(0, 2, 1, 3).reshape(Bt * H, S, P)
    dth = dtf.transpose(0, 2, 1).reshape(Bt * H, S)
    cumh = cum_full.transpose(0, 2, 1).reshape(Bt * H, S)
    Bh = jnp.repeat(B, rep, axis=2).transpose(0, 2, 1, 3).reshape(Bt * H, S, N)
    Ch = jnp.repeat(C, rep, axis=2).transpose(0, 2, 1, 3).reshape(Bt * H, S, N)

    y_intra, chunk_in = ssd_chunk_pallas(xh, dth, cumh, Bh, Ch, chunk=chunk,
                                         interpret=interpret)

    chunk_decay = jnp.exp(cumh.reshape(Bt * H, nc, chunk)[:, :, -1])  # (BH,nc)
    if h0 is None:
        h0_f = jnp.zeros((Bt * H, P, N), jnp.float32)
    else:
        h0_f = h0.reshape(Bt * H, P, N).astype(jnp.float32)

    def pass_state(h, inp):
        dec, cin = inp
        return h * dec[:, None, None] + cin, h

    h_final, h_ins = jax.lax.scan(
        pass_state, h0_f,
        (chunk_decay.transpose(1, 0), chunk_in.transpose(1, 0, 2, 3)))
    h_ins = h_ins.transpose(1, 0, 2, 3)  # (BH, nc, P, N)

    # carry contribution: (C_q · h_in) * exp(cum_q)
    Chc = Ch.reshape(Bt * H, nc, chunk, N)
    y_carry = jnp.einsum("scqn,scpn->scqp", Chc, h_ins) \
        * jnp.exp(cumh).reshape(Bt * H, nc, chunk)[..., None]
    y = y_intra + y_carry.reshape(Bt * H, S, P)
    y = y.reshape(Bt, H, S, P).transpose(0, 2, 1, 3)
    y = y + x.astype(jnp.float32) * D[None, None, :, None]
    return y.astype(x.dtype), h_final.reshape(Bt, H, P, N)


def _ssd_fwd(x, dt, A, B, C, D, h0, *static):
    return _ssd_pallas_forward(x, dt, A, B, C, D, h0, *static), \
        (x, dt, A, B, C, D, h0)


def _ssd_bwd(chunk, interpret, res, g):
    def ref(x, dt, A, B, C, D, h0):
        return ssd_chunked_ref(x, dt, A, B, C, D, chunk=chunk, h0=h0)

    _, vjp = jax.vjp(ref, *res)
    return vjp(g)


_ssd_pallas = jax.custom_vjp(_ssd_pallas_forward, nondiff_argnums=(7, 8))
_ssd_pallas.defvjp(_ssd_fwd, _ssd_bwd)
