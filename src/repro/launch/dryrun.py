import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture × input shape) on
the production meshes and extract the roofline terms from the compiled
artifact. MUST be imported before any other jax user (the XLA_FLAGS above
lock in 512 placeholder host devices).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                      # all combos, single-pod
  PYTHONPATH=src python -m repro.launch.dryrun --multi-pod          # 2×16×16
  PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-135m --shape train_4k -v
  PYTHONPATH=src python -m repro.launch.dryrun --sync allreduce     # baseline collective
Outputs one JSON record per combo to benchmarks/artifacts/dryrun_<mesh>.json.
"""

import argparse
import json
import time
import traceback

import numpy as np

import jax

from repro.configs import ARCHS, INPUT_SHAPES, get_arch, shape_supported
from repro.launch.mesh import make_mesh, make_production_mesh
from repro.launch.steps import build_step
from repro.launch.sharding import params_bytes
from repro.models import transformer
from repro.roofline import (
    analytic_flops_bytes,
    collective_bytes_from_hlo,
    model_flops,
    roofline_report,
)
from repro.roofline.analysis import active_param_count

ARTIFACTS = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                         "benchmarks", "artifacts")


def _param_counts(cfg) -> dict:
    shapes = jax.eval_shape(
        lambda: transformer.init_params(jax.random.PRNGKey(0), cfg))
    total = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(shapes))
    moe = 0
    if cfg.num_experts:
        flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
        moe = sum(int(np.prod(l.shape)) for p, l in flat
                  if "moe" in jax.tree_util.keystr(p))
    return {"params": total,
            "active": active_param_count(cfg, total, moe),
            "param_bytes": params_bytes(cfg)}


def _cache_bytes(cfg, built) -> int:
    if built.mode != "decode":
        return 0
    caches = built.args[1].caches
    return sum(int(np.prod(l.shape)) * l.dtype.itemsize
               for l in jax.tree.leaves(caches))


def run_combo(arch: str, shape_name: str, mesh, mesh_name: str, *,
              sync: str = "gossip", topo_kind: str = "ba",
              topo_r: int | None = None, verbose: bool = False,
              keep_hlo: bool = False, accum_steps: int = 1,
              tp_only: bool | None = None, expert_parallel: bool = False) -> dict:
    cfg = get_arch(arch)
    shape = INPUT_SHAPES[shape_name]
    rec: dict = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                 "sync": sync, "topo": topo_kind}
    t0 = time.time()
    built = build_step(arch, shape_name, mesh, sync=sync, topo_kind=topo_kind,
                       topo_r=topo_r, accum_steps=accum_steps, tp_only=tp_only,
                       expert_parallel=expert_parallel)
    # donation mirrors production: train updates (params, opt) in place,
    # decode updates the KV/SSM caches in place — without it the dry-run
    # double-counts a full state copy in temp bytes
    donate = {"train": (0,), "decode": (1,), "prefill": ()}[built.mode]
    with jax.set_mesh(mesh):
        lowered = jax.jit(built.fn, donate_argnums=donate).lower(*built.args)
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    coll = collective_bytes_from_hlo(hlo)
    chips = int(np.prod(mesh.devices.shape))

    counts = _param_counts(cfg)
    counts["cache_bytes"] = _cache_bytes(cfg, built)
    analytic = analytic_flops_bytes(cfg, shape, built.mode, counts)
    mflops = model_flops(cfg, int(analytic["tokens"]), built.mode,
                         counts["params"], counts["active"])
    rep = roofline_report(
        arch=arch, shape=shape, mesh_name=mesh_name, mode=built.mode,
        chips=chips, analytic=analytic, mflops=mflops, collective=coll,
        hlo_flops_raw=float(cost.get("flops", 0.0)),
        cross_pod="pod" in mesh.axis_names and mesh.shape["pod"] > 1,
        extras={"collective_by_op": coll["by_op"], "n_collectives": coll["count"]})

    rec.update(rep.as_dict())
    rec.update(
        lower_s=round(t1 - t0, 2), compile_s=round(t2 - t1, 2),
        mem_per_device={
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
        },
        # args + scratch; aliased (donated) outputs live in their argument
        # buffers, and XLA CPU's accounting re-counts them inside temp
        hbm_per_device_gb=round((mem.argument_size_in_bytes +
                                 mem.temp_size_in_bytes -
                                 mem.alias_size_in_bytes) / 1e9, 3),
        plan={"gossip_axes": built.plan.gossip_axes,
              "tensor_axes": built.plan.tensor_axes,
              "n_workers": built.plan.n_workers},
        step_meta=built.meta,
    )
    if keep_hlo:
        os.makedirs(ARTIFACTS, exist_ok=True)
        with open(os.path.join(ARTIFACTS, f"hlo_{arch}_{shape_name}_{mesh_name}.txt"),
                  "w") as f:
            f.write(hlo)
    if verbose:
        print(json.dumps({k: v for k, v in rec.items()
                          if k not in ("collective_by_op",)}, indent=2,
                         default=str))
    return rec


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, help="one arch id (default: all)")
    ap.add_argument("--shape", default=None, help="one input shape (default: all)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--mesh-shape", default=None,
                    help="override single-pod mesh, e.g. 32x8 (beyond-paper "
                         "worker-geometry experiments; chip count must stay 256)")
    ap.add_argument("--sync", default="gossip",
                    choices=["gossip", "allreduce", "none"])
    ap.add_argument("--topo", default="ba",
                    choices=["ba", "ring", "exponential", "equistatic", "torus"])
    ap.add_argument("--topo-r", type=int, default=None)
    ap.add_argument("--tag", default="", help="suffix for the output json")
    ap.add_argument("--keep-hlo", action="store_true")
    ap.add_argument("--expert-parallel", action="store_true",
                    help="MoE expert dim owns the data axis (pod-worker train)")
    ap.add_argument("--accum", type=int, default=1,
                    help="gradient-accumulation microbatches (train shapes)")
    ap.add_argument("--tp-only", default=None, choices=[None, "on", "off"],
                    help="force TP-only (on) / 2-D FSDP (off) inference sharding")
    ap.add_argument("-v", "--verbose", action="store_true")
    args = ap.parse_args()

    if args.mesh_shape:
        d, m = (int(x) for x in args.mesh_shape.split("x"))
        mesh = make_mesh((d, m), ("data", "model"))
        mesh_name = f"{d}x{m}"
    else:
        mesh = make_production_mesh(multi_pod=args.multi_pod)
        mesh_name = "2x16x16" if args.multi_pod else "16x16"
    archs = [args.arch] if args.arch else list(ARCHS)
    shapes = [args.shape] if args.shape else list(INPUT_SHAPES)

    records, failures = [], []
    for arch in archs:
        for shape in shapes:
            if not shape_supported(arch, shape):
                records.append({"arch": arch, "shape": shape, "mesh": mesh_name,
                                "skipped": "long_500k needs sub-quadratic attention"})
                print(f"[skip] {arch} × {shape} (full attention)")
                continue
            label = f"{arch} × {shape} on {mesh_name}"
            try:
                t0 = time.time()
                rec = run_combo(arch, shape, mesh, mesh_name, sync=args.sync,
                                topo_kind=args.topo, topo_r=args.topo_r,
                                verbose=args.verbose, keep_hlo=args.keep_hlo,
                                accum_steps=args.accum,
                                tp_only={None: None, "on": True, "off": False}[args.tp_only],
                                expert_parallel=args.expert_parallel)
                records.append(rec)
                print(f"[ok]   {label}: dominant={rec['dominant']} "
                      f"compute={rec['compute_s']:.2e}s memory={rec['memory_s']:.2e}s "
                      f"collective={rec['collective_s']:.2e}s "
                      f"hbm/dev={rec['hbm_per_device_gb']}GB "
                      f"({time.time() - t0:.0f}s)")
            except Exception as e:  # a failure here is a sharding bug
                failures.append(label)
                records.append({"arch": arch, "shape": shape, "mesh": mesh_name,
                                "error": f"{type(e).__name__}: {e}"})
                print(f"[FAIL] {label}: {type(e).__name__}: {e}")
                if args.verbose:
                    traceback.print_exc()

    os.makedirs(ARTIFACTS, exist_ok=True)
    suffix = f"_{args.tag}" if args.tag else ""
    out = os.path.join(ARTIFACTS, f"dryrun_{mesh_name}{suffix}.json")
    with open(out, "w") as f:
        json.dump(records, f, indent=1, default=str)
    print(f"\nwrote {len(records)} records → {out}")
    if failures:
        print(f"{len(failures)} FAILURES: {failures}")
        raise SystemExit(1)
    print("all combinations lowered + compiled.")


if __name__ == "__main__":
    main()
