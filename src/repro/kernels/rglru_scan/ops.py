"""Jit'd RG-LRU scan entry point.  The Pallas path carries a custom VJP
whose backward recomputes through the associative-scan oracle."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .kernel import rglru_scan_pallas
from .ref import rglru_scan_assoc, rglru_scan_ref  # noqa: F401


def _default_impl() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "reference"


def rglru_scan(a, u, h0=None, *, impl: str = "auto", interpret: bool = False):
    """h_t = a_t h_{t-1} + u_t over axis 1.  Returns (h_seq, h_final)."""
    if impl == "auto":
        impl = _default_impl()
    if impl == "pallas":
        if h0 is None:
            h0 = jnp.zeros((a.shape[0], a.shape[2]), jnp.float32)
        return _rglru_pallas(a, u, h0, interpret)
    if impl == "sequential":
        return rglru_scan_ref(a, u, h0)
    return rglru_scan_assoc(a, u, h0)


def _rglru_pallas_forward(a, u, h0, interpret):
    hs = rglru_scan_pallas(a, u, h0, interpret=interpret)
    return hs, hs[:, -1].astype(jnp.float32)


def _rglru_fwd(a, u, h0, interpret):
    return _rglru_pallas_forward(a, u, h0, interpret), (a, u, h0)


def _rglru_bwd(interpret, res, g):
    _, vjp = jax.vjp(rglru_scan_assoc, *res)
    return vjp(g)


_rglru_pallas = jax.custom_vjp(_rglru_pallas_forward, nondiff_argnums=(3,))
_rglru_pallas.defvjp(_rglru_fwd, _rglru_bwd)
