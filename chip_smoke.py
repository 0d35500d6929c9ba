#!/usr/bin/env python3
"""Bring-up smoke on one TPU chip: the device planner and the physical-mode
training jobs, driven through their normal entry points and checked against
the repository's own references.

    python3 chip_smoke.py                 # one TPU chip, phases 1-5
    python3 chip_smoke.py --four-chips    # sharded training on a 2x2 mesh
                                          # against the same run on one chip
    python3 chip_smoke.py --cpu-rehearsal # every phase at tiny sizes on the
                                          # CPU; prints no result line (with
                                          # --four-chips: the sharded check
                                          # on four virtual CPU devices)

Phases, in order, each printing one ``[smoke]`` line with its wall time,
the XLA compiles it triggered and its sizes:

1. device   -- the first JAX device must be a TPU.
2. planner  -- Full Reconfiguration on an array-built fleet: 10^4 tasks on
   the jitted pass against the numpy engine (interference off and on; equal
   hourly cost, every task placed once), then 10^5 tasks on the device.
3. scheduler -- ``Simulator`` + ``EvaScheduler(engine="jax")`` on the
   Alibaba-like trace; every k-th round is re-packed with the numpy engine
   and held to the same checks.
4. kernels  -- flash attention (forward and gradient), SSD and RG-LRU at
   real model widths on the device, against their ``ref.py`` oracles.
5. physical -- ``launch/train.main`` on full-width smollm-135m (the
   compiled step must hold a Pallas kernel), then a ``LocalCloud`` round
   loop running a full-width smollm job in this process.

Everything runs in this one process: a chip belongs to one process, and a
child that touched JAX would fail or hang.  Any failed check raises, so
the exit code is non-zero and no result line is printed.  The last line of
a passing run is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import multiprocessing
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

#: jobs in the phase-3 trace, as ``benchmarks/bench_endtoend.py``'s
#: default (11,290 rounds).  The paper-scale 6,274-job trace (34,781
#: rounds) passes too but takes ~9 minutes on a v5e chip (CHANGES.md).
SIM_JOBS = 800
CHECK_EVERY = 250  # phase 3: cross-check every k-th round against numpy


class Compiles:
    """Counts XLA executables built (compiled or loaded from the
    persistent cache) through JAX's monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kwargs):
        if event == self.EVENT:
            self.n += 1


def phase(name, compiles, fn):
    c0, t0 = compiles.n, time.time()
    info = fn()
    items = " ".join(f"{k}={v}" for k, v in info.items())
    print(f"[smoke] {name}: wall_s={time.time() - t0:.2f} "
          f"compiles={compiles.n - c0} {items}", flush=True)
    return info


def check_same_plan(cfg_np, cfg_jx, tasks, catalog):
    """The phase-2 equality checks: equal hourly cost within 1e-6 relative,
    and every task placed exactly once by both engines."""
    c_np = cfg_np.total_hourly_cost(catalog)
    c_jx = cfg_jx.total_hourly_cost(catalog)
    assert abs(c_jx - c_np) <= 1e-6 * abs(c_np), (c_jx, c_np)
    for cfg in (cfg_np, cfg_jx):
        check_placed_once(cfg, tasks)
    return c_jx


def check_placed_once(cfg, tasks):
    placed = sorted(t for _, ts in cfg.assignments for t in ts)
    assert placed == sorted(tasks.ids.tolist()), "a task is lost or doubled"


def random_table(seed):
    import numpy as np
    from repro.core import NUM_WORKLOADS, ThroughputTable
    rng = np.random.default_rng(seed)
    table = ThroughputTable(NUM_WORKLOADS, default=0.97)
    for _ in range(25):
        w1, w2 = rng.integers(NUM_WORKLOADS, size=2)
        table.record(int(w1), (int(w2),), float(rng.uniform(0.7, 1.0)))
    return table


def planner(n_checked, n_device):
    import numpy as np
    from benchmarks.bench_micro import array_fleet
    from repro.core import aws_catalog, full_reconfiguration
    cat = aws_catalog()
    tasks = array_fleet(n_checked, np.random.default_rng(n_checked))
    out = {"tasks_checked": n_checked}
    for interference in (False, True):
        table = random_table(0) if interference else None
        kw = dict(interference_aware=interference, multi_task_aware=True)
        cfg_np = full_reconfiguration(tasks, cat, table, engine="numpy", **kw)
        cfg_jx = full_reconfiguration(tasks, cat, table, engine="jax", **kw)
        cost = check_same_plan(cfg_np, cfg_jx, tasks, cat)
        out[f"cost_interference_{'on' if interference else 'off'}"] = cost
    tasks = array_fleet(n_device, np.random.default_rng(n_device))
    t0 = time.time()
    cfg = full_reconfiguration(tasks, cat, random_table(1), engine="jax",
                               interference_aware=True, multi_task_aware=True)
    check_placed_once(cfg, tasks)
    out.update(tasks_device=n_device, instances_device=len(cfg.assignments),
               device_pack_s=round(time.time() - t0, 3))
    return out


def scheduler(n_jobs, every, compiles):
    from repro.cluster import SimConfig, Simulator, alibaba_like_trace
    from repro.core import EvaScheduler, aws_catalog, full_reconfiguration

    class CheckedEva(EvaScheduler):
        """Eva on the device planner; every ``every``-th round re-packs the
        round's task set with both engines and holds them to the phase-2
        checks.  The stack is empty, so the round plans on the catalog
        itself."""

        def __init__(self, catalog):
            super().__init__(catalog, engine="jax")
            assert not self.stack.layers
            self.checked = 0
            self.compiles_at = []

        def schedule(self, view):
            if self.rounds % every == 0 and len(view.tasks):
                table = self.table if self.interference_aware else None
                kw = dict(interference_aware=self.interference_aware,
                          multi_task_aware=self.multi_task_aware)
                check_same_plan(
                    full_reconfiguration(view.tasks, self.catalog, table,
                                         engine="numpy", **kw),
                    full_reconfiguration(view.tasks, self.catalog, table,
                                         engine="jax", **kw),
                    view.tasks, self.catalog)
                self.checked += 1
            cfg = super().schedule(view)
            self.compiles_at.append(compiles.n)
            return cfg

    cat = aws_catalog()
    sched = CheckedEva(cat)
    jobs = alibaba_like_trace(n_jobs=n_jobs, seed=7)
    t0 = time.time()
    m = Simulator(cat, jobs, sched, SimConfig(seed=1)).run()
    wall = time.time() - t0
    assert sched.checked >= 20, f"only {sched.checked} rounds cross-checked"
    half = sched.compiles_at[len(sched.compiles_at) // 2]
    return {"jobs": n_jobs, "rounds": sched.rounds,
            "rounds_checked": sched.checked, "total_cost": m.total_cost,
            "avg_jct_hours": m.avg_jct_hours,
            "compiles_after_warmup": sched.compiles_at[-1] - half,
            "ms_per_round": round(1e3 * wall / max(sched.rounds, 1), 3)}


def max_rel_err(got, want):
    """Largest absolute error, relative to the oracle's largest magnitude."""
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    assert got.shape == want.shape, (got.shape, want.shape)
    assert np.all(np.isfinite(got)), "non-finite kernel output"
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


def compiled_text(fn, *args):
    import jax
    return jax.jit(fn).lower(*args).compile().as_text()


def kernels(on_chip, seq):
    """Each kernel at real widths against its oracle; on the chip the
    compiled program must hold the Pallas kernel (no reference fallback)."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.flash_attention.kernel import flash_attention_pallas
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.kernels.rglru_scan.ops import rglru_scan
    from repro.kernels.rglru_scan.ref import rglru_scan_ref
    from repro.kernels.ssd_scan.ops import ssd
    from repro.kernels.ssd_scan.ref import ssd_ref

    rng = np.random.default_rng(0)
    bf16, f32 = jnp.bfloat16, jnp.float32
    interpret = not on_chip
    out = {}

    def arr(shape, dtype, lo=None, hi=None):
        x = (rng.normal(size=shape) if lo is None
             else rng.uniform(lo, hi, size=shape))
        return jnp.asarray(x, dtype)

    def uses_kernel(fn, *args):
        if on_chip:
            assert "tpu_custom_call" in compiled_text(fn, *args), \
                "the Pallas kernel is missing from the compiled program"

    def exact(fn, *args):  # oracles at full f32 matmul precision
        with jax.default_matmul_precision("highest"):
            return jax.jit(fn)(*args)

    # flash attention at smollm-135m width: 9 heads over 3 KV heads, hd 64
    q, k, v = (arr((2, seq, h, 64), bf16) for h in (9, 3, 3))
    w = arr((2, seq, 9, 64), f32)
    fa = functools.partial(flash_attention_pallas, interpret=interpret)

    def loss(attn):
        return lambda q, k, v: (attn(q, k, v).astype(f32) * w).sum()

    grad_fa = jax.value_and_grad(loss(fa), argnums=(0, 1, 2))
    uses_kernel(fa, q, k, v)
    uses_kernel(grad_fa, q, k, v)
    out["flash_fwd_err"] = max_rel_err(jax.jit(fa)(q, k, v),
                                       exact(attention_ref, q, k, v))
    _, g_got = jax.jit(grad_fa)(q, k, v)
    _, g_want = exact(jax.value_and_grad(loss(attention_ref),
                                         argnums=(0, 1, 2)), q, k, v)
    out["flash_grad_err"] = max(max_rel_err(a, b)
                                for a, b in zip(g_got, g_want))

    # SSD at mamba2-780m width: 48 heads x 64, one group, state 128
    H, P, N = 48, 64, 128
    x, Bm, Cm = arr((1, seq, H, P), bf16), arr((1, seq, 1, N), bf16), \
        arr((1, seq, 1, N), bf16)
    dt = arr((1, seq, H), f32, 0.001, 0.1)
    A = -arr((H,), f32, 0.5, 2.0)
    D = arr((H,), f32)
    chunk = min(256, seq)
    ssd_fn = functools.partial(ssd, chunk=chunk, impl="pallas",
                               interpret=interpret)
    uses_kernel(ssd_fn, x, dt, A, Bm, Cm, D)
    y_got, h_got = jax.jit(ssd_fn)(x, dt, A, Bm, Cm, D)
    y_want, h_want = exact(ssd_ref, x, dt, A, Bm, Cm, D)
    out["ssd_err"] = max(max_rel_err(y_got, y_want),
                         max_rel_err(h_got, h_want))

    # RG-LRU at recurrentgemma-2b width 2560 (the model feeds f32)
    a, u = arr((2, seq, 2560), f32, 0.5, 0.999), arr((2, seq, 2560), f32)
    h0 = arr((2, 2560), f32)
    lru = functools.partial(rglru_scan, impl="pallas", interpret=interpret)
    uses_kernel(lru, a, u, h0)
    hs_got, _ = jax.jit(lru)(a, u, h0)
    hs_want, _ = exact(rglru_scan_ref, a, u, h0)
    out["rglru_err"] = max_rel_err(hs_got, hs_want)

    limits = {"flash_fwd_err": 2e-2, "flash_grad_err": 2e-2,
              "ssd_err": 5e-2, "rglru_err": 1e-4}
    for key, lim in limits.items():
        assert out[key] <= lim, f"{key}={out[key]} exceeds {lim}"
    out["seq"] = seq
    return out


def physical(on_chip, arch_cfg, train_args, round_s, cloud_s):
    import tempfile

    import jax
    import numpy as np
    from repro.cluster.localcloud import LocalCloud, LocalJob
    from repro.core import Catalog, EvaScheduler
    from repro.core.catalog import InstanceType
    from repro.launch import train

    run = train.main(train_args)
    losses = [loss for _, loss in run.losses]
    assert losses and np.all(np.isfinite(losses)), losses
    if on_chip:
        assert "tpu_custom_call" in run.compiled.as_text(), \
            "the train step runs no Pallas kernel"
    del run.state

    catalog = Catalog.from_types([
        InstanceType("local.large", "c7i", (0, 4, 16), 1.0),
        InstanceType("local.small", "c7i", (0, 2, 8), 0.55),
    ])
    # a job that outlives the window: the loop runs its rounds for cloud_s
    # seconds, then stops the worker, which checkpoints the steps it took
    job = LocalJob(job_id=1, workload=7, arch_cfg=arch_cfg,
                   total_steps=10**9, demand=(0, 1, 4), standalone_sps=20.0)
    sched = EvaScheduler(catalog, engine="jax")
    with tempfile.TemporaryDirectory() as workdir:
        res = LocalCloud(catalog, sched, [job], round_s=round_s,
                         workdir=workdir).run(timeout_s=cloud_s)
    assert res["steps"][1] > 0, res
    assert sched.rounds >= 2, sched.rounds
    assert not multiprocessing.active_children(), "a child process started"
    assert jax.default_backend() == ("tpu" if on_chip else "cpu")
    return {"arch": arch_cfg.name, "train_compile_s": round(run.compile_s, 2),
            "train_losses": ",".join(f"{x:.4f}" for x in losses),
            "cloud_rounds": sched.rounds, "cloud_steps": res["steps"][1],
            "cloud_cost": res["cost"]}


#: sharded vs one-chip losses: bf16 activations reduced in another order
LOSS_RTOL = 1e-2


def four_chips(on_chip, size_args):
    """Sharded training (2x2 data x model mesh) against the same seed and
    batches on one chip; the per-step losses must agree.  smollm's 9 heads
    do not divide the model axis, so the sharded step attends through the
    model's context-parallel path; the one-chip step runs the Pallas
    kernel."""
    from repro.launch import train
    common = ["--arch", "smollm-135m", "--steps", "3", "--log-every", "1",
              "--seed", "0"] + size_args

    def run(extra):
        r = train.main(common + extra)
        return [loss for _, loss in r.losses], r.compiled.as_text()

    sharded, _ = run(["--mesh", "2x2"])
    single, text = run([])
    if on_chip:
        assert "tpu_custom_call" in text, \
            "the one-chip step runs no Pallas kernel"
    rel = max(abs(a - b) / abs(b) for a, b in zip(sharded, single))
    assert len(sharded) == len(single) == 3
    assert rel <= LOSS_RTOL, (sharded, single)
    return {"losses_2x2": ",".join(f"{x:.5f}" for x in sharded),
            "losses_one_chip": ",".join(f"{x:.5f}" for x in single),
            "max_rel_loss_diff": rel, "tolerance": LOSS_RTOL}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="only sharded training on a 2x2 mesh vs one chip")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny sizes on the CPU, Pallas in interpret mode; "
                         "prints no result line")
    args = ap.parse_args()

    import jax
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache(ROOT)
    compiles = Compiles()

    devs = jax.devices()
    dev = devs[0]
    on_chip = not args.cpu_rehearsal
    print(f"[smoke] device: platform={dev.platform} "
          f"kind={dev.device_kind} count={len(devs)}", flush=True)
    if on_chip and dev.platform != "tpu":
        sys.exit("chip_smoke: JAX found no TPU")
    if args.four_chips:
        assert len(devs) >= 4, "--four-chips needs four devices"
        size = (["--batch", "8", "--seq", "2048"] if on_chip else
                ["--reduced", "--batch", "8", "--seq", "256"])
        phase("four_chips", compiles, lambda: four_chips(on_chip, size))
    elif on_chip:
        from repro.configs import ARCHS
        smollm = ARCHS["smollm-135m"]
        phase("planner", compiles, lambda: planner(10_000, 100_000))
        phase("scheduler", compiles,
              lambda: scheduler(SIM_JOBS, CHECK_EVERY, compiles))
        phase("kernels", compiles, lambda: kernels(True, 2048))
        phase("physical", compiles, lambda: physical(
            True, smollm, ["--arch", "smollm-135m", "--batch", "8",
                           "--seq", "2048", "--steps", "5",
                           "--log-every", "1"], round_s=4.0, cloud_s=30.0))
    else:
        from repro.configs import ARCHS
        tiny = ARCHS["smollm-135m"].reduced()
        phase("planner", compiles, lambda: planner(500, 2_000))
        phase("scheduler", compiles, lambda: scheduler(60, 10, compiles))
        phase("kernels", compiles, lambda: kernels(False, 256))
        phase("physical", compiles, lambda: physical(
            False, tiny, ["--arch", "smollm-135m", "--reduced", "--batch",
                          "2", "--seq", "64", "--steps", "3",
                          "--log-every", "1"], round_s=1.0, cloud_s=10.0))
    if not on_chip:
        print("[smoke] CPU rehearsal passed; no device result", flush=True)
        return
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
