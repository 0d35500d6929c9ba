"""Training launcher: ``python -m repro.launch.train --arch smollm-135m
--reduced --steps 200``.

Supports every assigned architecture, reduced or full configs, optional
(data, model) meshes, periodic async checkpointing with restart-resume
(fault tolerance), and deterministic data so a restart reproduces the run.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import time
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import ARCHS
from ..data.pipeline import SyntheticTokens, shard_batch
from ..models import lm
from ..models.sharding import mesh_context
from ..models.steps import init_train_state, make_train_step
from ..train.checkpoint import AsyncCheckpointer, latest_step, restore_checkpoint
from ..train.optimizer import OptConfig
from .compile_cache import use_compile_cache
from .mesh import make_mesh

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


@dataclasses.dataclass
class TrainRun:
    state: dict
    losses: List[Tuple[int, float]]  # (step, loss) at every logged step
    compiled: jax.stages.Compiled  # the train step, as compiled
    compile_s: float


def shard_state(state: dict, cfg, mesh) -> dict:
    """Place a fresh train state on the mesh: parameters and both Adam
    moments by the parameter sharding rules, the step counter replicated."""
    ps = jax.tree.map(lambda _, sp: NamedSharding(mesh, sp),
                      state["params"], lm.param_pspecs(cfg, mesh))
    opt = {"m": ps, "v": ps, "step": NamedSharding(mesh, P())}
    return jax.device_put(state, {"params": ps, "opt": opt})


def main(argv=None) -> TrainRun:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m", choices=sorted(ARCHS))
    ap.add_argument("--reduced", action="store_true",
                    help="structure-preserving small config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", default=None,
                    help="e.g. 2x2 for a (data,model) mesh")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)
    use_compile_cache(REPO)

    cfg = ARCHS[args.arch]
    if args.reduced:
        cfg = cfg.reduced()
    mesh = None
    if args.mesh:
        d, m = (int(x) for x in args.mesh.split("x"))
        mesh = make_mesh((d, m), ("data", "model"))

    key = jax.random.PRNGKey(args.seed)
    start_step = 0
    state = init_train_state(cfg, key)
    if args.checkpoint_dir and latest_step(args.checkpoint_dir) is not None:
        state, start_step, _ = restore_checkpoint(args.checkpoint_dir)
        print(f"[train] resumed from step {start_step}")
    if mesh is not None:
        state = shard_state(state, cfg, mesh)

    oc = OptConfig(lr=args.lr, total_steps=max(args.steps, 1000))
    step_fn = jax.jit(make_train_step(cfg, oc), donate_argnums=0)
    src = SyntheticTokens(cfg.vocab, args.batch, args.seq, seed=args.seed,
                          start_step=start_step)
    ckpt = AsyncCheckpointer(args.checkpoint_dir) if args.checkpoint_dir else None

    def next_batch():
        batch = src.next_batch()
        if cfg.enc_dec:
            batch["enc_embeds"] = np.zeros(
                (args.batch, cfg.enc_seq, cfg.d_model), jnp.dtype(cfg.compute_dtype))
        return shard_batch(batch, mesh)

    n_params = lm.num_params(cfg)
    print(f"[train] arch={cfg.name} params={n_params/1e6:.1f}M "
          f"batch={args.batch} seq={args.seq} steps={args.steps}")
    tok_per_step = args.batch * args.seq
    losses: List[Tuple[int, float]] = []
    with mesh_context(mesh):
        batch = next_batch()
        # compile ahead of the loop so set-up is reported apart from steps;
        # the jitted call below reuses this executable
        t0 = time.time()
        compiled = step_fn.lower(state, batch).compile()
        compile_s = time.time() - t0
        print(f"[train] compiled train step in {compile_s:.1f}s")
        t0 = time.time()
        for step in range(start_step, args.steps):
            if step > start_step:
                batch = next_batch()
            state, metrics = step_fn(state, batch)
            if (step + 1) % args.log_every == 0 or step + 1 == args.steps:
                loss = float(metrics["loss"])
                losses.append((step + 1, loss))
                dt = time.time() - t0
                tps = tok_per_step * (step + 1 - start_step) / max(dt, 1e-9)
                print(f"[train] step={step + 1} loss={loss:.4f} "
                      f"tok/s={tps:,.0f}")
                assert np.isfinite(loss), "loss diverged"
            if ckpt and (step + 1) % args.checkpoint_every == 0:
                ckpt.save(state, step + 1)
    if ckpt:
        ckpt.save(state, args.steps)
        ckpt.wait()
        print(f"[train] checkpointed at {args.checkpoint_dir}")
    return TrainRun(state, losses, compiled, compile_s)


if __name__ == "__main__":
    main()
