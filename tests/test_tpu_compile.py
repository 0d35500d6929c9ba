"""Compile the device programs at real widths for a described TPU v5e chip.

No chip is attached: the TPU compiler is installed and compiles for a
topology it is given, so whatever it would refuse on the chip (block shapes
off the (8, 128) tiling, too much VMEM, an operation with no lowering)
fails here.  Covered: flash attention forward and gradient at smollm-135m
width, SSD at mamba2-780m width, the RG-LRU scan at recurrentgemma-2b
width, and the planner's fused packing pass.  Nothing runs, so these say
nothing about results or times.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.catalog import FAMILIES, NUM_RESOURCES
from repro.core.engine_jax import _pack_all_types
from repro.core.workloads import NUM_WORKLOADS
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.rglru_scan.ops import rglru_scan
from repro.kernels.ssd_scan.ops import ssd

SEQ = 2048


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text  # the Pallas kernel is in the program
    return text


def _spec(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _attn_inputs(sharding, batch=8):
    # smollm-135m: 9 query heads over 3 KV heads, head dim 64
    return (_spec(sharding, (batch, SEQ, 9, 64)),
            _spec(sharding, (batch, SEQ, 3, 64)),
            _spec(sharding, (batch, SEQ, 3, 64)))


def _with_grad(fn, grad):
    """``fn`` itself, or its value and gradient in every input (the value
    keeps the kernel's forward live: the backward recomputes in jnp)."""
    if not grad:
        return fn

    def loss(*args):
        return sum(o.astype(jnp.float32).sum() for o in fn(*args))

    return jax.value_and_grad(loss, argnums=(0, 1, 2))


@pytest.mark.parametrize("grad", [False, True])
def test_flash_attention_compiles_for_v5e(one_chip, grad):
    fn = _with_grad(lambda q, k, v: (flash_attention_pallas(q, k, v),), grad)
    _compile(fn, *_attn_inputs(one_chip))


@pytest.mark.parametrize("grad", [False, True])
def test_ssd_compiles_for_v5e(one_chip, grad):
    # mamba2-780m: d_inner 3072 = 48 heads x 64, one group, state 128
    Bt, H, P, N = 2, 48, 64, 128
    f32 = jnp.float32
    args = (_spec(one_chip, (Bt, SEQ, H, P)), _spec(one_chip, (Bt, SEQ, H), f32),
            _spec(one_chip, (H,), f32), _spec(one_chip, (Bt, SEQ, 1, N)),
            _spec(one_chip, (Bt, SEQ, 1, N)), _spec(one_chip, (H,), f32))
    _compile(_with_grad(lambda *a: ssd(*a, chunk=256, impl="pallas"), grad),
             *args)


@pytest.mark.parametrize("dtype,grad", [(jnp.float32, False),
                                        (jnp.bfloat16, False),
                                        (jnp.float32, True)])
def test_rglru_compiles_for_v5e(one_chip, dtype, grad):
    # recurrentgemma-2b: recurrence width 2560
    shape = (2, SEQ, 2560)
    fn = _with_grad(lambda a, u, h0: rglru_scan(a, u, h0, impl="pallas"),
                    grad)
    _compile(fn, _spec(one_chip, shape, dtype), _spec(one_chip, shape, dtype),
             _spec(one_chip, (2, 2560), jnp.float32))


def _compiled_pack(cache, sharding, C, M, K, max_fills):
    """``_pack_all_types`` compiled at a bucket of C task classes, M rows
    per class and K catalog types (once per module and bucket)."""
    key = (C, M, K, max_fills)
    if key not in cache:
        W, F, R = NUM_WORKLOADS, len(FAMILIES), NUM_RESOURCES
        f32, i32 = np.float32, np.int32
        args = [_spec(sharding, s, d) for s, d in (
            ((F, R, C), f32), ((C,), i32), ((C,), f32), ((C,), f32),
            ((C,), i32), ((C, M), i32), ((W, W), f32), ((W, W), f32),
            ((K,), f32), ((K, R), f32), ((K,), i32), ((K,), i32),
            ((4,), i32))]
        cache[key] = _pack_all_types.lower(
            *args, max_fills=max_fills).compile()
    return cache[key]


@pytest.fixture(scope="module")
def pack_programs():
    return {}


@pytest.mark.parametrize("C,M,max_fills", [(16, 8192, 4096),
                                           (64, 1 << 17, 1 << 16)])
def test_pack_all_types_compiles_for_v5e(one_chip, pack_programs, C, M,
                                         max_fills):
    """The planner's fused pass at a fleet-sized bucket: C task classes,
    the 64-type catalog bucket, M rows per class."""
    compiled = _compiled_pack(pack_programs, one_chip, C, M, 64, max_fills)
    assert compiled.memory_analysis() is not None


def _computations(text):
    """Name -> body of every computation in an HLO module's text."""
    comps, name, lines = {}, None, []
    for line in text.splitlines():
        m = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{$", line)
        if m:
            name, lines = m.group(1), []
        elif line == "}" and name is not None:
            comps[name] = "\n".join(lines)
            name = None
        elif name is not None:
            lines.append(line)
    return comps


def _innermost_loops(text):
    """For each ``while`` whose body runs no other ``while``: the bodies of
    its body computation and of every computation that one calls."""
    comps = _computations(text)

    def reach(name, seen):
        if name not in seen:
            seen.add(name)
            for ref in re.findall(r"%([\w.\-]+)", comps[name]):
                if ref in comps:
                    reach(ref, seen)
        return seen

    loops = []
    for body in re.findall(r"\bwhile\(.*?body=%([\w.\-]+)", text):
        called = [comps[c] for c in reach(body, set())]
        if not any(re.search(r"\bwhile\(", c) for c in called):
            loops.append(called)
    return loops


@pytest.mark.parametrize("C,M,K,max_fills", [(1024, 8, 21, 512),
                                             (16, 8192, 64, 4096),
                                             (64, 1 << 17, 64, 1 << 16)],
                         ids=["fleet1k-steady", "M8192", "M131072"])
def test_pack_pick_loop_has_no_gather(one_chip, pack_programs, C, M, K,
                                      max_fills):
    """The greedy pick loop, the innermost ``while`` of the fused pass,
    reads every per-class operand densely: no ``gather`` in its body nor
    in any fusion it calls, whatever the class or row bucket."""
    text = _compiled_pack(pack_programs, one_chip, C, M, K,
                          max_fills).as_text()
    loops = _innermost_loops(text)
    assert loops
    for called in loops:
        assert not any(re.search(r"\bgather\(", c) for c in called)
