"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracles,
sweeping shapes and dtypes."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import attention_chunked, attention_ref
from repro.kernels.rglru_scan.kernel import rglru_scan_pallas
from repro.kernels.rglru_scan.ops import rglru_scan
from repro.kernels.rglru_scan.ref import rglru_scan_assoc, rglru_scan_ref
from repro.kernels.ssd_scan.ops import ssd
from repro.kernels.ssd_scan.ref import ssd_chunked_ref, ssd_decode_step, ssd_ref

RNG = np.random.default_rng(0)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,KH,hd,window", [
    (1, 128, 2, 2, 64, None),
    (2, 256, 4, 2, 64, None),
    (1, 256, 4, 1, 128, None),     # MQA
    (2, 256, 4, 2, 64, 64),        # local window
    (1, 512, 2, 2, 64, 128),
])
def test_flash_attention_pallas_vs_ref(B, S, H, KH, hd, window, dtype):
    q = jnp.asarray(RNG.normal(size=(B, S, H, hd)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, S, KH, hd)), dtype)
    v = jnp.asarray(RNG.normal(size=(B, S, KH, hd)), dtype)
    ref = attention_ref(q, k, v, causal=True, window=window)
    pal = flash_attention_pallas(q, k, v, causal=True, window=window,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(pal, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("B,S,H,KH,hd,window", [
    (1, 128, 2, 2, 64, None),
    (2, 256, 6, 2, 64, None),      # GQA groups of 3, as smollm-135m
    (1, 256, 4, 1, 128, 64),       # MQA + local window
])
def test_flash_attention_pallas_grad_vs_ref(B, S, H, KH, hd, window):
    """The custom VJP (backward through the chunked oracle) matches the
    gradient of the naive oracle, and the forward still runs the kernel."""
    q = jnp.asarray(RNG.normal(size=(B, S, H, hd)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, S, KH, hd)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, S, KH, hd)), jnp.float32)
    w = jnp.asarray(RNG.normal(size=(B, S, H, hd)), jnp.float32)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v, causal=True, window=window)
                                * w).sum()

    pal = functools.partial(flash_attention_pallas, interpret=True)
    l_pal, g_pal = jax.value_and_grad(loss(pal), argnums=(0, 1, 2))(q, k, v)
    l_ref, g_ref = jax.value_and_grad(loss(attention_ref),
                                      argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(float(l_pal), float(l_ref), rtol=1e-4)
    for gp, gr in zip(g_pal, g_ref):
        np.testing.assert_allclose(np.asarray(gp), np.asarray(gr),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("B,S,H,KH,hd,window", [
    (2, 1024, 4, 2, 64, None),
    (1, 2048, 2, 1, 64, 256),
])
def test_attention_chunked_vs_ref(B, S, H, KH, hd, window):
    q = jnp.asarray(RNG.normal(size=(B, S, H, hd)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, S, KH, hd)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, S, KH, hd)), jnp.float32)
    ref = attention_ref(q, k, v, causal=True, window=window)
    chk = attention_chunked(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(chk), np.asarray(ref), rtol=2e-5,
                               atol=2e-5)


def test_attention_chunked_grads_finite():
    q = jnp.asarray(RNG.normal(size=(1, 1024, 2, 64)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(1, 1024, 2, 64)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(1, 1024, 2, 64)), jnp.float32)
    g = jax.grad(lambda q, k, v: attention_chunked(q, k, v).sum(),
                 argnums=(0, 1, 2))(q, k, v)
    for x in g:
        assert np.all(np.isfinite(np.asarray(x)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("Bt,S,H,P,G,N,chunk", [
    (1, 64, 2, 16, 1, 32, 16),
    (2, 128, 4, 16, 2, 32, 32),
    (1, 96, 2, 32, 1, 16, 32),     # padding path (96 % 32 == 0; also 80)
    (1, 80, 2, 16, 1, 16, 32),     # pad 80 -> 96
])
def test_ssd_pallas_vs_sequential(Bt, S, H, P, G, N, chunk, dtype):
    x = jnp.asarray(RNG.normal(size=(Bt, S, H, P)), dtype)
    dt = jnp.asarray(RNG.uniform(0.1, 0.9, size=(Bt, S, H)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.5, 2.0, size=(H,)), jnp.float32)
    B = jnp.asarray(RNG.normal(size=(Bt, S, G, N)), dtype)
    C = jnp.asarray(RNG.normal(size=(Bt, S, G, N)), dtype)
    D = jnp.asarray(RNG.normal(size=(H,)), jnp.float32)
    y_ref, h_ref = ssd_ref(x, dt, A, B, C, D)
    y_pal, h_pal = ssd(x, dt, A, B, C, D, chunk=chunk, impl="pallas",
                       interpret=True)
    tol = dict(rtol=5e-2, atol=5e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(y_pal, np.float32),
                               np.asarray(y_ref, np.float32), **tol)
    np.testing.assert_allclose(np.asarray(h_pal), np.asarray(h_ref),
                               rtol=1e-3, atol=1e-3)


def test_ssd_chunked_matches_sequential_and_decode():
    Bt, S, H, P, G, N = 2, 64, 4, 16, 2, 32
    x = jnp.asarray(RNG.normal(size=(Bt, S + 1, H, P)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.1, 0.9, size=(Bt, S + 1, H)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.5, 2.0, size=(H,)), jnp.float32)
    B = jnp.asarray(RNG.normal(size=(Bt, S + 1, G, N)), jnp.float32)
    C = jnp.asarray(RNG.normal(size=(Bt, S + 1, G, N)), jnp.float32)
    D = jnp.asarray(RNG.normal(size=(H,)), jnp.float32)
    y_all, _ = ssd_ref(x, dt, A, B, C, D)
    y_chk, h = ssd_chunked_ref(x[:, :S], dt[:, :S], A, B[:, :S], C[:, :S], D,
                               chunk=16)
    np.testing.assert_allclose(np.asarray(y_chk), np.asarray(y_all[:, :S]),
                               rtol=2e-4, atol=2e-4)
    y_dec, _ = ssd_decode_step(h, x[:, S], dt[:, S], A, B[:, S], C[:, S], D)
    np.testing.assert_allclose(np.asarray(y_dec), np.asarray(y_all[:, S]),
                               rtol=2e-4, atol=2e-4)


def test_ssd_grads_finite():
    Bt, S, H, P, G, N = 1, 32, 2, 8, 1, 16
    x = jnp.asarray(RNG.normal(size=(Bt, S, H, P)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.1, 0.9, size=(Bt, S, H)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.5, 2.0, size=(H,)), jnp.float32)
    B = jnp.asarray(RNG.normal(size=(Bt, S, G, N)), jnp.float32)
    C = jnp.asarray(RNG.normal(size=(Bt, S, G, N)), jnp.float32)
    D = jnp.asarray(RNG.normal(size=(H,)), jnp.float32)

    def loss(x, dt, B, C):
        y, _ = ssd_chunked_ref(x, dt, A, B, C, D, chunk=8)
        return (y ** 2).sum()

    g = jax.grad(loss, argnums=(0, 1, 2, 3))(x, dt, B, C)
    for t in g:
        assert np.all(np.isfinite(np.asarray(t)))


@pytest.mark.parametrize("with_h0", [False, True])
def test_ssd_pallas_grad_vs_ref(with_h0):
    """The Pallas path's custom VJP matches the chunked oracle's gradient
    in every differentiable input."""
    Bt, S, H, P, G, N, chunk = 1, 64, 2, 16, 1, 32, 16
    x = jnp.asarray(RNG.normal(size=(Bt, S, H, P)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.1, 0.9, size=(Bt, S, H)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.5, 2.0, size=(H,)), jnp.float32)
    B = jnp.asarray(RNG.normal(size=(Bt, S, G, N)), jnp.float32)
    C = jnp.asarray(RNG.normal(size=(Bt, S, G, N)), jnp.float32)
    D = jnp.asarray(RNG.normal(size=(H,)), jnp.float32)
    h0 = (jnp.asarray(RNG.normal(size=(Bt, H, P, N)), jnp.float32)
          if with_h0 else None)

    def loss(impl):
        def f(x, dt, A, B, C, D):
            y, h = ssd(x, dt, A, B, C, D, chunk=chunk, h0=h0, impl=impl,
                       interpret=True)
            return (y ** 2).sum() + h.sum()
        return f

    args = (x, dt, A, B, C, D)
    g_pal = jax.grad(loss("pallas"), argnums=range(6))(*args)
    g_ref = jax.grad(loss("reference"), argnums=range(6))(*args)
    for gp, gr in zip(g_pal, g_ref):
        np.testing.assert_allclose(np.asarray(gp), np.asarray(gr),
                                   rtol=1e-4, atol=1e-4)


def test_rglru_pallas_grad_vs_ref():
    a = jnp.asarray(RNG.uniform(0.5, 0.999, size=(2, 64, 128)), jnp.float32)
    u = jnp.asarray(RNG.normal(size=(2, 64, 128)), jnp.float32)
    h0 = jnp.asarray(RNG.normal(size=(2, 128)), jnp.float32)

    def loss(impl):
        def f(a, u, h0):
            hs, hf = rglru_scan(a, u, h0, impl=impl, interpret=True)
            return (hs ** 2).sum() + hf.sum()
        return f

    g_pal = jax.grad(loss("pallas"), argnums=(0, 1, 2))(a, u, h0)
    g_ref = jax.grad(loss("sequential"), argnums=(0, 1, 2))(a, u, h0)
    for gp, gr in zip(g_pal, g_ref):
        np.testing.assert_allclose(np.asarray(gp), np.asarray(gr),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,R,br,bs", [
    (1, 64, 64, 64, 16),
    (2, 128, 128, 64, 32),
    (2, 96, 192, 96, 32),
])
def test_rglru_pallas_vs_ref(B, S, R, br, bs, dtype):
    a = jnp.asarray(RNG.uniform(0.5, 0.999, size=(B, S, R)), dtype)
    u = jnp.asarray(RNG.normal(size=(B, S, R)), dtype)
    h0 = jnp.asarray(RNG.normal(size=(B, R)), jnp.float32)
    ref, _ = rglru_scan_ref(a, u, h0)
    pal = rglru_scan_pallas(a, u, h0, block_r=br, block_s=bs, interpret=True)
    tol = dict(rtol=5e-2, atol=5e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(pal, np.float32),
                               np.asarray(ref, np.float32), **tol)


def test_rglru_assoc_matches_ref():
    a = jnp.asarray(RNG.uniform(0.5, 0.999, size=(2, 200, 32)), jnp.float32)
    u = jnp.asarray(RNG.normal(size=(2, 200, 32)), jnp.float32)
    r1, f1 = rglru_scan_ref(a, u)
    r2, f2 = rglru_scan_assoc(a, u)
    np.testing.assert_allclose(np.asarray(r1), np.asarray(r2), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(f1), np.asarray(f2), rtol=1e-5,
                               atol=1e-5)
