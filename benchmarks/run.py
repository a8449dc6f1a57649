"""Benchmark driver: one entry per paper table/figure + system benches.

  PYTHONPATH=src python -m benchmarks.run            # quick versions of all
  PYTHONPATH=src python -m benchmarks.run --full     # paper-scale settings

Individual benchmarks (full CLIs):
  benchmarks.bench_consensus      Figs 1 / 2 / 4 / 6
  benchmarks.bench_scalability    Table I
  benchmarks.bench_training_time  Table II, Figs 7–10
  benchmarks.bench_admm           §V-C solver scalability
  benchmarks.bench_pipeline       outer-pipeline phase breakdown (DESIGN §10)
  benchmarks.bench_kernels        Pallas kernels vs oracles
  benchmarks.bench_roofline       dry-run roofline table (deliverable g)
"""
from __future__ import annotations

import argparse
import os
import time

ART = os.path.join(os.path.dirname(__file__), "artifacts")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--full", action="store_true",
                    help="paper-scale settings (slow: ~1h)")
    ap.add_argument("--json", default=None, metavar="BENCH_admm.json",
                    help="run ONLY the tracked perf benchmarks (ADMM solver "
                         "grid + outer-pipeline phase breakdown + DSGD "
                         "training-engine compare) and write their "
                         "machine-readable rows (n, solver, psd_backend, "
                         "dtype, ms_per_iter, cg_per_step, r_asym, phase "
                         "timings, train_speedup, …) to this path — the perf "
                         "trajectory file committed across PRs")
    ap.add_argument("--sharded", action="store_true",
                    help="with --json: also run the multi-device sharded-ADMM "
                         "partition compare at n=256/512/1024 (spawns an "
                         "8-simulated-device subprocess; slow — used when "
                         "refreshing the committed baseline, while CI gates "
                         "a dedicated n=512 smoke subset)")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    os.makedirs(ART, exist_ok=True)
    quick = not args.full

    if args.json:
        import json as _json
        import tempfile

        from . import (bench_admm, bench_anytime, bench_chaos,
                       bench_compression, bench_dynamic, bench_elastic,
                       bench_pipeline, bench_service, bench_training_time)
        # Fixed, quick configuration so rows stay comparable across PRs:
        # backend×driver grid at n=16/32 + the fast-compare row at n=64,
        # the end-to-end outer-pipeline rows (device vs host phase
        # breakdown at the ISSUE-3 acceptance point: n=64, 4 restarts),
        # the DSGD training-engine compare at the ISSUE-4 acceptance
        # point (homo, n=16, default epochs; host oracle vs scan engine),
        # and the ISSUE-5 cross-product engines (dynamic round-robin and
        # CHOCO compression at the homo n=16 / 9-topology tracked point,
        # scan vs host-loop compare rows). Only engine-level summary and
        # compare rows are tracked; per-topology rows stay in artifacts.
        with tempfile.TemporaryDirectory() as td:
            bench_admm.main(["--nodes", "16,32", "--iters", "60",
                             "--fast-nodes", "64",
                             "--json-out", f"{td}/admm.json"])
            bench_pipeline.main(["--nodes", "64", "--restarts", "4",
                                 "--json-out", f"{td}/pipeline.json"])
            bench_anytime.main(["--nodes", "64", "--restarts", "4",
                                "--json-out", f"{td}/anytime.json"])
            bench_training_time.main(["--scenario", "homo", "--engine", "both",
                                      "--json-out", f"{td}/training.json"])
            bench_dynamic.main(["--engine", "both",
                                "--json-out", f"{td}/dynamic.json"])
            bench_compression.main(["--engine", "both",
                                    "--json-out", f"{td}/compression.json"])
            bench_chaos.main(["--engine", "both",
                              "--json-out", f"{td}/chaos.json"])
            bench_elastic.main(["--json-out", f"{td}/elastic.json"])
            bench_service.main(["--json-out", f"{td}/service.json"])
            rows = (_json.load(open(f"{td}/admm.json"))
                    + _json.load(open(f"{td}/pipeline.json"))
                    + _json.load(open(f"{td}/anytime.json"))
                    + [r for r in _json.load(open(f"{td}/training.json"))
                       if r.get("bench") == "training"]
                    + [r for r in _json.load(open(f"{td}/dynamic.json"))
                       if r.get("bench") == "dynamic"]
                    + [r for r in _json.load(open(f"{td}/compression.json"))
                       if r.get("bench") == "compression"]
                    + [r for r in _json.load(open(f"{td}/chaos.json"))
                       if r.get("bench") == "chaos"]
                    + [r for r in _json.load(open(f"{td}/elastic.json"))
                       if r.get("bench") == "elastic"]
                    + [r for r in _json.load(open(f"{td}/service.json"))
                       if r.get("bench") == "service"])
            if args.sharded:
                from . import bench_scalability
                bench_scalability.main(
                    ["--nodes", "", "--partition-nodes", "256,512,1024",
                     "--json-out", f"{td}/sharded.json"])
                rows += _json.load(open(f"{td}/sharded.json"))
        with open(args.json, "w") as f:
            _json.dump(rows, f, indent=1)
        print("tracked ADMM + pipeline + anytime + training + dynamic "
              "+ compression + chaos + elastic + service perf rows "
              f"written to {args.json}")
        return

    from . import (bench_admm, bench_compression, bench_consensus,
                   bench_dynamic, bench_kernels, bench_pipeline,
                   bench_roofline, bench_scalability, bench_training_time)

    t0 = time.time()
    sa = "300" if quick else "1500"

    for scenario in (["homo", "node"] if quick else ["homo", "node", "intra", "bcube"]):
        print(f"\n### bench_consensus --scenario {scenario}")
        bench_consensus.main(["--scenario", scenario, "--sa-iters", sa,
                              "--iters", "300" if quick else "600",
                              "--json-out", f"{ART}/consensus_{scenario}.json"])

    print("\n### bench_scalability (Table I)")
    bench_scalability.main(["--nodes", "4,8,16" if quick else "4,8,16,32,64,128",
                            "--sa-iters", sa,
                            "--json-out", f"{ART}/scalability.json"])

    print("\n### bench_training_time (Table II)")
    for scenario in (["homo"] if quick else ["homo", "node", "intra", "bcube"]):
        bench_training_time.main(["--scenario", scenario,
                                  "--epochs", "12" if quick else "40",
                                  "--sa-iters", sa,
                                  "--json-out", f"{ART}/training_{scenario}.json"])

    print("\n### bench_admm (§V-C)")
    bench_admm.main(["--nodes", "8,16" if quick else "8,16,32,64",
                     "--iters", "100" if quick else "400",
                     "--json-out", f"{ART}/admm.json"])

    print("\n### bench_pipeline (outer-pipeline phase breakdown, DESIGN §10)")
    if quick:
        bench_pipeline.main(["--nodes", "24", "--restarts", "2",
                             "--sa-iters", "300", "--polish-iters", "150",
                             "--admm-iters", "200",
                             "--json-out", f"{ART}/pipeline.json"])
    else:
        bench_pipeline.main(["--nodes", "64", "--restarts", "4",
                             "--json-out", f"{ART}/pipeline.json"])

    print("\n### bench_anytime (budgeted best-so-far pipeline, DESIGN §17)")
    from . import bench_anytime
    if quick:
        bench_anytime.main(["--nodes", "24", "--restarts", "2",
                            "--sa-iters", "300", "--polish-iters", "150",
                            "--json-out", f"{ART}/anytime.json"])
    else:
        bench_anytime.main(["--nodes", "64", "--restarts", "4",
                            "--json-out", f"{ART}/anytime.json"])

    print("\n### bench_dynamic (beyond-paper: time-varying gossip)")
    bench_dynamic.main(["--json-out", f"{ART}/dynamic.json"])

    print("\n### bench_compression (beyond-paper: CHOCO gossip)")
    bench_compression.main(["--iters", "800" if quick else "3000",
                            "--json-out", f"{ART}/compression.json"])

    print("\n### bench_chaos (beyond-paper: faults + online re-optimization)")
    from . import bench_chaos
    bench_chaos.main(["--json-out", f"{ART}/chaos.json"])

    print("\n### bench_elastic (elastic real-model training, DESIGN §16)")
    from . import bench_elastic
    bench_elastic.main(["--json-out", f"{ART}/elastic.json"])

    print("\n### bench_service (fault-tolerant topology service, DESIGN §15)")
    from . import bench_service
    bench_service.main((["--n", "16", "--r", "32"] if quick else []) +
                       ["--json-out", f"{ART}/service.json"])

    print("\n### bench_kernels")
    bench_kernels.main(["--json-out", f"{ART}/kernels.json"])

    print("\n### bench_roofline (from dry-run artifacts)")
    bench_roofline.main([])

    print(f"\nall benchmarks done in {time.time() - t0:.0f}s; artifacts in {ART}/")


if __name__ == "__main__":
    main()
