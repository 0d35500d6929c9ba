"""Dry-run machinery on a small (2,2) debug mesh via a subprocess (the
512-device flag must be set before jax initializes, so in-process testing is
impossible).  Exercises lower+compile+analysis for representative reduced
cells, including the multi-pod (2,2,2) pod axis."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, dataclasses
import jax
from repro.configs import ARCHS
from repro.configs.base import ShapeSpec
from repro.models.sharding import mesh_context
from repro.launch.specs import input_specs
from repro.launch.hlo_analysis import analyze
from repro.models.steps import make_train_step, make_decode_step
from repro.launch.mesh import make_debug_mesh

out = {}
for name, multi_pod in (("smollm-135m", False), ("granite-moe-3b-a800m", False),
                        ("mamba2-780m", True)):
    cfg = ARCHS[name].reduced()
    mesh = make_debug_mesh(2, 2, multi_pod=multi_pod)
    shape = ShapeSpec("t", "train", 64, 8)
    with mesh_context(mesh):
        inputs = input_specs(cfg, shape, mesh)
        compiled = jax.jit(make_train_step(cfg), donate_argnums=0).lower(*inputs).compile()
    res = analyze(compiled.as_text())
    ma = compiled.memory_analysis()
    out[name] = {"flops": res["flops"], "coll": res["collective_bytes"],
                 "temp": int(ma.temp_size_in_bytes)}
    # decode path too
    shape_d = ShapeSpec("d", "decode", 64, 8)
    with mesh_context(mesh, profile="inference-tp"):
        inputs = input_specs(cfg, shape_d, mesh, profile="inference-tp")
        jax.jit(make_decode_step(cfg), donate_argnums=1).lower(*inputs).compile()
    out[name]["decode_ok"] = True
print(json.dumps(out))
"""


@pytest.mark.slow
def test_dryrun_small_mesh():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    for name, r in out.items():
        assert r["flops"] > 0, name
        assert r["coll"] > 0, name
        assert r["decode_ok"], name


ELASTIC_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, tempfile
import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import ARCHS
from repro.models import lm
from repro.models.steps import init_train_state
from repro.train.checkpoint import save_checkpoint, restore_checkpoint

cfg = ARCHS["smollm-135m"].reduced()
mesh_a = jax.make_mesh((2, 2), ("data", "model"))
mesh_b = jax.make_mesh((4, 2), ("data", "model"))  # elastic re-scale 4 -> 8

state = init_train_state(cfg, jax.random.PRNGKey(0))
specs_a = lm.param_pspecs(cfg, mesh_a)
params_a = jax.tree.map(
    lambda x, s: jax.device_put(x, NamedSharding(mesh_a, s)),
    state["params"], specs_a)

d = tempfile.mkdtemp()
save_checkpoint(d, {"params": params_a}, step=1)

specs_b = lm.param_pspecs(cfg, mesh_b)
shardings_b = {"params": jax.tree.map(
    lambda s: NamedSharding(mesh_b, s), specs_b)}
restored, step, _ = restore_checkpoint(d, shardings=shardings_b)

ok = True
for a, b in zip(jax.tree.leaves(params_a), jax.tree.leaves(restored["params"])):
    ok &= bool(np.array_equal(np.asarray(a), np.asarray(b)))
    ok &= len(b.sharding.device_set) >= 1
print(json.dumps({"ok": ok, "step": step}))
"""


@pytest.mark.slow
def test_elastic_restore_across_meshes():
    """Fault-tolerance / elasticity: a checkpoint written on a (2,2) mesh
    restores bit-exactly onto a (4,2) mesh with new shardings."""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    proc = subprocess.run([sys.executable, "-c", ELASTIC_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["ok"] and out["step"] == 1
