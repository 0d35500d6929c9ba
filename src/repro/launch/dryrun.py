import os
os.environ["XLA_FLAGS"] = os.environ.get("DRYRUN_XLA_FLAGS",
                                         "--xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: lower + compile every (architecture × input shape)
cell on the production meshes, and extract the roofline terms.

MUST be executed as a standalone process (``python -m repro.launch.dryrun``):
the XLA_FLAGS line above runs before any other import — including jax —
because jax locks the device count on first init.  Results are cached per
cell in a JSON file so interrupted sweeps resume for free.

Per cell we record:
  * per-device bytes from compiled.memory_analysis() (proves it fits HBM),
  * HLO FLOPs / bytes from compiled.cost_analysis(),
  * collective bytes parsed from the partitioned HLO text (all-gather /
    all-reduce / reduce-scatter / all-to-all / collective-permute),
  * the three roofline terms against TPU v5e constants.
"""
import argparse  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402

from ..configs import ARCHS, SHAPES, shape_applicable  # noqa: E402
from ..models import lm  # noqa: E402
from ..models.sharding import mesh_context  # noqa: E402
from ..models.steps import (make_decode_step, make_prefill_step,  # noqa: E402
                            make_train_step)
from .mesh import make_mesh, make_production_mesh  # noqa: E402
from .specs import input_specs  # noqa: E402

# TPU v5e hardware constants (per chip)
PEAK_FLOPS = 197e12  # bf16
HBM_BW = 819e9  # bytes/s
ICI_BW = 50e9  # bytes/s/link
HBM_BYTES = 16 * 2 ** 30

_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f64": 8,
                "s8": 1, "u8": 1, "pred": 1, "s64": 8, "u64": 8, "f8e4m3": 1,
                "f8e5m2": 1, "s16": 2, "u16": 2}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_bytes(shape_str: str) -> int:
    """bytes of an HLO shape string like 'bf16[256,4096]{1,0}' or a tuple."""
    total = 0
    for m in re.finditer(r"(\w+)\[([\d,]*)\]", shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _parse_computations(hlo_text: str):
    """Split HLO text into {computation_name: [lines]}."""
    comps = {}
    cur = None
    for line in hlo_text.splitlines():
        m = re.match(r"(?:ENTRY )?%?([\w\.\-]+) (?:\([^)]*\))? ?->", line)
        if m and not line.startswith(" "):
            cur = m.group(1)
            comps[cur] = []
        elif cur is not None:
            comps[cur].append(line.strip())
    return comps


def collective_bytes(hlo_text: str):
    """Sum output-shape bytes of every collective op in partitioned HLO,
    multiplying ops inside while-loop bodies (scan-over-layers, CE chunks)
    by their trip counts.  Trip counts are recovered from the largest
    integer constant in the loop's condition computation — exact for
    scan-lowered loops.  Returns (total_bytes, per_kind, op_count)."""
    comps = _parse_computations(hlo_text)

    # while ops: (parent_comp, body_name, cond_name)
    whiles = []
    for cname, lines in comps.items():
        for s in lines:
            m = re.search(r"\bwhile\(.*?\), condition=%?([\w\.\-]+), "
                          r"body=%?([\w\.\-]+)", s)
            if m:
                whiles.append((cname, m.group(2), m.group(1)))

    def trip_count(cond_name: str) -> int:
        best = 1
        for s in comps.get(cond_name, []):
            for m in re.finditer(r"constant\((\d+)\)", s):
                best = max(best, int(m.group(1)))
        return best

    # multiplier per computation (nested whiles compose)
    mult = {c: 1 for c in comps}
    changed = True
    iters = 0
    while changed and iters < 10:
        changed = False
        iters += 1
        for parent, body, cond in whiles:
            want = mult.get(parent, 1) * trip_count(cond)
            if mult.get(body, 1) != want:
                mult[body] = want
                changed = True

    per = {k: 0 for k in _COLLECTIVES}
    count = 0
    for cname, lines in comps.items():
        m_c = mult.get(cname, 1)
        for s in lines:
            m = re.match(r"(?:ROOT )?%?[\w\.\-]+ = (.+?) (\w[\w\-]*)\(", s)
            if not m:
                continue
            shape_str, opname = m.group(1), m.group(2)
            for kind in _COLLECTIVES:
                if opname == kind or opname.startswith(kind + "-start"):
                    per[kind] += _shape_bytes(shape_str) * m_c
                    count += m_c
                    break
    return sum(per.values()), per, count


def step_fn_and_inputs(arch: str, shape_name: str, mesh, profile: str = "2d"):
    cfg = ARCHS[arch]
    shape = SHAPES[shape_name]
    inputs = input_specs(cfg, shape, mesh, profile)
    if shape.kind == "train":
        fn = make_train_step(cfg)
        in_shardings = jax.tree.map(lambda s: s.sharding, inputs)
        donate = (0,)
    elif shape.kind == "prefill":
        fn = make_prefill_step(cfg)
        in_shardings = jax.tree.map(lambda s: s.sharding, inputs)
        donate = ()
    else:
        fn = make_decode_step(cfg)
        in_shardings = jax.tree.map(lambda s: s.sharding, inputs)
        donate = (1,)  # cache donated
    return fn, inputs, in_shardings, donate


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             extract_roofline: bool = True, profile: str = "2d",
             mesh_shape=None):
    if mesh_shape is not None:  # logical re-mesh of the same 256-chip pod
        mesh = make_mesh(mesh_shape, ("data", "model"))
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(list(mesh.shape.values())))
    cfg = ARCHS[arch]
    t0 = time.time()
    with mesh_context(mesh, profile=profile):
        fn, inputs, in_shardings, donate = step_fn_and_inputs(
            arch, shape_name, mesh, profile)
        jfn = jax.jit(fn, in_shardings=None, donate_argnums=donate)
        lowered = jfn.lower(*inputs)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    out = {"arch": arch, "shape": shape_name, "profile": profile,
           "mesh": "multi_pod" if multi_pod else "single_pod",
           "n_chips": n_chips, "ok": True,
           "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1)}

    try:
        ma = compiled.memory_analysis()
        out["bytes_per_device"] = int(
            getattr(ma, "argument_size_in_bytes", 0)
            + getattr(ma, "output_size_in_bytes", 0)
            + getattr(ma, "temp_size_in_bytes", 0))
        out["temp_bytes"] = int(getattr(ma, "temp_size_in_bytes", 0))
        out["arg_bytes"] = int(getattr(ma, "argument_size_in_bytes", 0))
    except Exception as e:  # CPU backend may not support it
        out["memory_analysis_error"] = str(e)

    try:
        ca = compiled.cost_analysis()
        out["hlo_flops"] = float(ca.get("flops", 0.0))
        out["hlo_bytes"] = float(ca.get("bytes accessed", 0.0))
    except Exception as e:
        out["cost_analysis_error"] = str(e)

    if extract_roofline:
        try:
            from .hlo_analysis import analyze
            text = compiled.as_text()
            res = analyze(text)
            out["hlo_flops"] = res["flops"]  # loop-aware (overrides XLA's
            out["hlo_bytes"] = res["traffic_bytes"]  # once-per-loop counts)
            out["collective_bytes"] = res["collective_bytes"]
            out["collective_ops"] = res["collective_ops"]
            out["collective_by_kind"] = res["collective_by_kind"]
        except Exception as e:
            out["collective_error"] = str(e)

    # roofline terms (per-device quantities / per-chip rates)
    if "hlo_flops" in out:
        out["t_compute_s"] = out["hlo_flops"] / PEAK_FLOPS
        out["t_memory_s"] = out.get("hlo_bytes", 0.0) / HBM_BW
        out["t_collective_s"] = out.get("collective_bytes", 0) / ICI_BW
        terms = {"compute": out["t_compute_s"], "memory": out["t_memory_s"],
                 "collective": out["t_collective_s"]}
        out["bottleneck"] = max(terms, key=terms.get)
    return out


def cells(archs=None, shapes=None):
    for a in sorted(archs or ARCHS):
        for s in (shapes or SHAPES):
            if shape_applicable(ARCHS[a], SHAPES[s]):
                yield a, s


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", action="append", default=None)
    ap.add_argument("--shape", action="append", default=None)
    ap.add_argument("--mesh", choices=["single_pod", "multi_pod", "both"],
                    default="both")
    ap.add_argument("--profile", default="2d",
                    choices=["2d", "fsdp", "inference-tp"])
    ap.add_argument("--mesh-shape", default=None,
                    help="logical DxM re-mesh of the 256-chip pod, e.g. 64x4")
    ap.add_argument("--out", default="results/dryrun.json")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    results = {}
    if os.path.exists(args.out):  # --force re-runs cells but never drops data
        with open(args.out) as f:
            results = json.load(f)

    meshes = (["single_pod", "multi_pod"] if args.mesh == "both"
              else [args.mesh])
    todo = [(a, s, m) for a, s in cells(args.arch, args.shape)
            for m in meshes]
    print(f"dry-run: {len(todo)} cells, devices={len(jax.devices())}")
    mesh_shape = None
    if args.mesh_shape:
        mesh_shape = tuple(int(x) for x in args.mesh_shape.split("x"))
    for a, s, m in todo:
        key = f"{a}|{s}|{m}" + ("" if args.profile == "2d"
                                else f"|{args.profile}")
        if mesh_shape:
            key += f"|mesh{args.mesh_shape}"
        if key in results and results[key].get("ok") and not args.force:
            print(f"[cached] {key}")
            continue
        print(f"[run]    {key} ...", flush=True)
        try:
            r = run_cell(a, s, multi_pod=(m == "multi_pod"),
                         profile=args.profile, mesh_shape=mesh_shape)
        except Exception as e:
            r = {"arch": a, "shape": s, "mesh": m, "ok": False,
                 "error": f"{type(e).__name__}: {e}",
                 "traceback": traceback.format_exc()[-2000:]}
            print(f"  FAILED: {r['error']}")
        results[key] = r
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
        if r.get("ok"):
            print(f"  ok: compile={r.get('compile_s')}s "
                  f"flops={r.get('hlo_flops', 0):.3g} "
                  f"coll={r.get('collective_bytes', 0):.3g}B "
                  f"bottleneck={r.get('bottleneck')}")
    bad = [k for k, v in results.items() if not v.get("ok")]
    print(f"done: {len(results) - len(bad)} ok, {len(bad)} failed")
    for k in bad:
        print(f"  FAIL {k}: {results[k].get('error')}")


if __name__ == "__main__":
    main()
