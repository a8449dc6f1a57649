"""Scalability across node counts — paper Table I, plus large-n constraint
scenarios on the fast solver stack and the multi-device partition compare.

Asymptotic convergence factor + convergence time (consensus error ≤ 1e-4)
for exponential vs U-EquiStatic vs BA-Topo, with BA-Topo's edge budget at
half the exponential graph's degree sum (the paper's sparsity protocol).

``--scenarios`` additionally runs the four heterogeneous constraint
scenarios (node-level, intra-server n=8, BCube, pod-boundary) at
``--scenario-nodes`` through the device-resident scan driver with the fast
solver stack (inexact CG + fp32, DESIGN.md §9) — no host-side
per-iteration syncs, which is what makes n = 256/512 tractable.

``--partition-nodes`` runs the tracked sharded-ADMM compare (DESIGN.md §13):
for each n it solves the same homogeneous instance on (a) the single-device
fast stack with eigh, (b) single-device with Newton–Schulz (the measured
eigh↔NS crossover data), and (c) the edge-partitioned ``core.shard`` path
across ``--partition-devices`` devices, then emits a compare row with the
sharded-vs-single speedup and the best-candidate ``r_asym`` parity drift.
On an accelerator it runs in this process over the attached devices (a chip
belongs to one process, so a child could not reach it). Only on the CPU
backend with too few devices does it re-exec itself in a CPU-only subprocess
with ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (the flag must
precede the first backend initialization).

  PYTHONPATH=src python -m benchmarks.bench_scalability --nodes 4,8,16,32,64
  PYTHONPATH=src python -m benchmarks.bench_scalability --nodes "" \
      --scenarios node,intra,bcube,pod --scenario-nodes 256
  PYTHONPATH=src python -m benchmarks.bench_scalability --nodes "" \
      --partition-nodes 256,512,1024
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

import numpy as np

from repro.core import make_baseline
from repro.core.admm import ADMMConfig, HeterogeneousADMM
from repro.core.consensus import simulate_consensus, time_to_error

from .common import ba_topo, edge_b_min

#: Newton–Schulz sign iterations for the tracked large-n rows: the parity
#: tests bound the projection error at 16 iterations well below the support
#: decision the pipeline consumes; 30 (the engine default) doubles the
#: matmul cost without moving the rounded support on these instances.
PARTITION_PSD_ITERS = 16


def run(nodes: list[int], iters: int, sa_iters: int, seed: int,
        restarts: int = 1) -> list[dict]:
    rows = []
    for n in nodes:
        expo = make_baseline("exponential", n)
        # paper: Σdeg(BA) = ½ Σdeg(exp); undirected edge count = Σdeg/2
        r_budget = max(len(expo.edges) // 2, n)
        try:
            equi = make_baseline("equistatic", n,
                                 M=max(1, int(np.ceil(np.log2(n)) // 2)))
        except Exception:
            equi = None
        t0 = time.time()
        # restarts > 1 run as ONE batched, vmapped ADMM device call
        ba = ba_topo(n, r_budget, "homo", seed=seed, sa_iters=sa_iters,
                     restarts=restarts)
        solve_s = time.time() - t0
        for topo, label in [(expo, "exponential"), (equi, "u-equistatic"),
                            (ba, "ba-topo")]:
            if topo is None:
                continue
            b_min = edge_b_min(topo, "homo")
            tr = simulate_consensus(topo, iters=iters, b_min=b_min, seed=seed)
            rows.append({
                "n": n, "topology": label, "edges": len(topo.edges),
                "r_asym": round(float(topo.r_asym()), 3),
                "t_converge_ms": round(time_to_error(tr, 1e-4), 1),
                "solve_s": round(solve_s, 1) if label == "ba-topo" else None,
            })
        print(f"  n={n} done ({solve_s:.1f}s ADMM)")
    return rows


def _scenario_instance(scenario: str, n: int):
    """(cs, n_eff, r) for one constraint scenario at target size n."""
    from repro.core.constraints import (bcube_constraints,
                                        intra_server_constraints,
                                        node_level_constraints,
                                        pod_boundary_constraints)

    if scenario == "node":
        cs = node_level_constraints(n, np.full(n, 4), np.full(n, 9.76))
        return cs, n, 2 * n
    if scenario == "intra":  # the paper's 8-GPU server — n fixed by Fig. 3
        return intra_server_constraints(), 8, 12
    if scenario == "bcube":
        # exact (p, k) factorization with p^k == n when one exists — the
        # paper's p=4 preferred (256 → BCube(4,4)), else the smallest
        # fitting p (512 → BCube(2,9)); otherwise the nearest power of 4,
        # loudly
        for p in (4, 2, 3, 5, 6, 7, 8):
            k = round(np.log(n) / np.log(p))
            if k >= 1 and p ** k == n:
                break
        else:
            p, k = 4, max(1, round(np.log(n) / np.log(4)))
            print(f"  [bcube] no p^k == {n} for p ≤ 8; "
                  f"running BCube({p},{k}) with n={p**k} instead")
        n_eff = p ** k
        # level-0 at the paper's PIX rate, switch levels at the SYS rate
        bw = tuple(4.88 if lay == 0 else 9.76 for lay in range(k))
        return bcube_constraints(p, k, layer_bw=bw), n_eff, 2 * n_eff
    if scenario == "pod":
        cs = pod_boundary_constraints(n, pods=max(2, n // 128),
                                      dci_cap_total=max(8, n // 16))
        return cs, n, 2 * n
    raise ValueError(f"unknown scenario {scenario!r}")


def run_scenarios(scenarios: list[str], n_target: int, admm_iters: int,
                  seed: int) -> list[dict]:
    """Large-n heterogeneous solves on the scan driver + fast solver stack.

    One warm start per scenario (greedy feasible graph — SA is host-side
    O(iters·n³) and not what this benchmark measures), one scan-compiled
    device call per solve; compile and steady-state times are reported
    separately."""
    from repro.core.api import _greedy_constraint_graph
    from repro.core.graph import all_edges, edge_index

    rows = []
    for scenario in scenarios:
        cs, n, r = _scenario_instance(scenario, n_target)
        rng = np.random.default_rng(seed)
        t0 = time.time()
        edges0 = _greedy_constraint_graph(n, r, cs, rng)
        t_warm = time.time() - t0
        eidx = edge_index(n)
        m = len(all_edges(n))
        g0 = np.zeros(m)
        for e in edges0:
            g0[eidx[e]] = 1.0 / max(len(edges0), 1)
        z0 = (g0 > 0).astype(np.float64)
        cfg = ADMMConfig(max_iters=admm_iters,
                         check_every=min(20, admm_iters),
                         precond="jacobi", cg_inexact=True, dtype="float32")
        solver = HeterogeneousADMM(
            n, r, np.asarray(cs.M, np.float64), np.asarray(cs.e_cap, np.float64),
            cfg, equality=cs.equality, edge_ok=np.asarray(cs.edge_ok))
        t0 = time.time()
        res = solver.solve(g0=g0, z0=z0, lam0=0.3)  # compile + run
        t_first = time.time() - t0
        t0 = time.time()
        res = solver.solve(g0=g0, z0=z0, lam0=0.3)
        t_solve = time.time() - t0
        rows.append({
            "scenario": cs.name, "n": n, "r": r, "q": int(cs.q),
            "warm_start_s": round(t_warm, 2),
            "compile_s": round(t_first - t_solve, 2),
            "solve_s": round(t_solve, 2),
            "ms_per_iter": round(t_solve / max(res.iters, 1) * 1e3, 1),
            "admm_iters": res.iters,
            "cg_per_step": round(res.cg_iters / max(res.iters, 1), 1),
            "residual": float(res.residual),
            "z_edges": int(res.z.sum()) if res.z is not None else None,
        })
        print("  " + json.dumps(rows[-1]))
    return rows


def _partition_warm_start(n: int, r: int, seed: int):
    """(g0, lam0) structured warm start — greedy balanced-degree graph with
    Metropolis weights (SA is host-side O(iters·n³), not measured here)."""
    from repro.core.api import _homo_degree_targets, _pack_warm
    from repro.core.anneal import greedy_degree_graph

    rng = np.random.default_rng(seed)
    edges0 = greedy_degree_graph(n, _homo_degree_targets(n, r), rng, None)
    g0, _, lam0 = _pack_warm(n, edges0)
    return g0, lam0


def _candidate_r_asym(n: int, res, r: int) -> float:
    """ρ_asym of the rounded candidate a solve produces: top-r support →
    Metropolis weights → Lanczos spectral gap (no polish — the drift metric
    compares SOLVER outputs, and polish would mask small support flips)."""
    from repro.core.api import extract_support
    from repro.core.graph import Topology, all_edges, is_connected
    from repro.core.weights import metropolis_weights

    sel = extract_support(n, np.asarray(res.g) + np.asarray(res.g_raw), r,
                          tol=1e-6)
    edges_full = all_edges(n)
    edges = [edges_full[l] for l in np.nonzero(sel)[0]]
    if not edges or not is_connected(n, edges):
        return 1.0
    return float(Topology(n, edges, metropolis_weights(n, edges)).r_asym())


def run_partition_compare(nodes: list[int], admm_iters: int, seed: int,
                          ndev: int) -> list[dict]:
    """Single-device vs edge-sharded solves of one homogeneous instance per n.

    Three solve rows per n — (partition, psd_backend) ∈ {(none, eigh),
    (none, newton_schulz), (edges, newton_schulz)} on the fp32 inexact-CG
    stack — plus a compare row carrying ``ns_vs_eigh`` (the measured eigh↔NS
    crossover backing ``engine.NS_MIN_N``), ``speedup_sharded`` (sharded vs
    the best single-device row; ≈ 1/ndev · ideal on a single physical core,
    see DESIGN.md §13), and the ``r_asym`` drift of the rounded candidates.
    ``eps=0`` pins the iteration count so ms_per_iter is load-comparable.
    """
    import jax
    import jax.numpy as jnp

    from repro.core.engine import (ADMMConfig, init_state, make_homo_spec,
                                   solve_spec)
    from repro.core.shard import solve_spec_sharded

    assert jax.device_count() >= ndev, (jax.device_count(), ndev)
    rows = []
    for n in nodes:
        r = 2 * n
        t0 = time.time()
        g0, lam0 = _partition_warm_start(n, r, seed)
        t_warm = time.time() - t0

        def solve_with(psd_backend: str, sharded: bool) -> dict:
            cfg = ADMMConfig(max_iters=admm_iters,
                             check_every=min(10, admm_iters), eps=0.0,
                             cg_inexact=True, dtype="float32",
                             psd_backend=psd_backend,
                             psd_iters=PARTITION_PSD_ITERS)
            spec = make_homo_spec(n, r, cfg)
            st = init_state(spec, jnp.asarray(g0), lam0)
            if sharded:
                def run():
                    return solve_spec_sharded(spec, st, cfg, ndev=ndev)
            else:
                def run():
                    return solve_spec(spec, st, cfg)
            t0 = time.time()
            res = run()  # compile + run
            t_first = time.time() - t0
            t0 = time.time()
            res = run()
            t_solve = time.time() - t0
            return {
                "bench": "scalability", "mode": "solve", "n": n, "r": r,
                "partition": "edges" if sharded else "none",
                "devices": ndev if sharded else 1,
                "psd_backend": psd_backend, "dtype": "float32",
                "cg_inexact": True, "psd_iters": PARTITION_PSD_ITERS,
                "warm_start_s": round(t_warm, 2),
                "compile_s": round(max(t_first - t_solve, 0.0), 2),
                "solve_s": round(t_solve, 2),
                "ms_per_iter": round(t_solve / max(res.iters, 1) * 1e3, 1),
                "admm_iters": res.iters,
                "cg_per_step": round(res.cg_iters / max(res.iters, 1), 1),
                "residual": float(res.residual),
                "r_asym": round(_candidate_r_asym(n, res, r), 6),
            }

        single_eigh = solve_with("eigh", sharded=False)
        single_ns = solve_with("newton_schulz", sharded=False)
        sharded_ns = solve_with("newton_schulz", sharded=True)
        best_single = min(single_eigh, single_ns, key=lambda d: d["solve_s"])
        compare = {
            "bench": "scalability", "mode": "compare", "n": n, "r": r,
            "devices": ndev, "dtype": "float32",
            "single_ms_per_iter": best_single["ms_per_iter"],
            "sharded_ms_per_iter": sharded_ns["ms_per_iter"],
            "speedup_sharded": round(
                best_single["solve_s"] / sharded_ns["solve_s"], 3),
            "ns_vs_eigh": round(
                single_eigh["solve_s"] / single_ns["solve_s"], 3),
            "r_asym_drift": round(
                abs(best_single["r_asym"] - sharded_ns["r_asym"]), 6),
        }
        rows += [single_eigh, single_ns, sharded_ns, compare]
        for row in rows[-4:]:
            print("  " + json.dumps(row))
    return rows


def _partition_compare_subprocess(nodes: list[int], admm_iters: int,
                                  seed: int, ndev: int) -> list[dict]:
    """Re-exec this benchmark with N simulated host devices.

    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` only takes effect
    before the first backend initialization, so the simulated-device run
    needs a fresh interpreter. Only called when this process runs on the
    CPU backend (see ``main``), so the child never competes for a chip.
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    with tempfile.TemporaryDirectory() as td:
        out = os.path.join(td, "partition.json")
        cmd = [sys.executable, "-m", "benchmarks.bench_scalability",
               "--nodes", "", "--partition-nodes",
               ",".join(str(n) for n in nodes),
               "--partition-iters", str(admm_iters),
               "--partition-devices", str(ndev),
               "--seed", str(seed), "--json-out", out]
        subprocess.run(cmd, check=True, env=env,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
        with open(out) as f:
            return json.load(f)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--nodes", default="4,8,16,32,64")
    ap.add_argument("--iters", type=int, default=600)
    ap.add_argument("--sa-iters", type=int, default=600)
    ap.add_argument("--restarts", type=int, default=1,
                    help="ADMM restarts, solved batched on device when > 1")
    ap.add_argument("--scenarios", default="",
                    help="comma-separated constraint scenarios "
                         "(node,intra,bcube,pod) to solve at --scenario-nodes")
    ap.add_argument("--scenario-nodes", type=int, default=256)
    ap.add_argument("--admm-iters", type=int, default=40,
                    help="ADMM iterations for the --scenarios solves")
    ap.add_argument("--partition-nodes", default="",
                    help="comma-separated node counts for the sharded-ADMM "
                         "compare (e.g. 256,512,1024); spawns an "
                         "8-simulated-device subprocess when needed")
    ap.add_argument("--partition-iters", type=int, default=20,
                    help="ADMM iterations for the --partition-nodes solves")
    ap.add_argument("--partition-devices", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json-out", default=None)
    args = ap.parse_args(argv)
    nodes = [int(x) for x in args.nodes.split(",") if x]

    rows = []
    if nodes:
        print("== scalability (paper Table I) ==")
        rows = run(nodes, args.iters, args.sa_iters, args.seed, args.restarts)
        print(f"{'n':>5} {'topology':>14} {'edges':>6} {'r_asym':>7} {'t_conv_ms':>10}")
        for r in rows:
            print(f"{r['n']:>5} {r['topology']:>14} {r['edges']:>6} "
                  f"{r['r_asym']:>7} {r['t_converge_ms']:>10}")

    if args.scenarios:
        print(f"== constraint scenarios at n={args.scenario_nodes} "
              "(scan driver, fast solver stack) ==")
        rows += run_scenarios([s for s in args.scenarios.split(",") if s],
                              args.scenario_nodes, args.admm_iters, args.seed)

    if args.partition_nodes:
        pnodes = [int(x) for x in args.partition_nodes.split(",") if x]
        ndev = args.partition_devices
        import jax

        # a chip belongs to one process, so on an accelerator the compare
        # runs here over the attached devices; the CPU backend is not
        # exclusive, so there a child with simulated devices is safe
        if jax.default_backend() == "cpu" and jax.device_count() < ndev:
            print(f"== sharded-ADMM partition compare "
                  f"(subprocess, {ndev} simulated devices) ==")
            rows += _partition_compare_subprocess(pnodes, args.partition_iters,
                                                  args.seed, ndev)
        else:
            if jax.device_count() < ndev:
                raise SystemExit(
                    f"--partition-devices {ndev} but {jax.device_count()} "
                    f"{jax.default_backend()} device(s) are attached")
            print(f"== sharded-ADMM partition compare ({ndev} devices) ==")
            rows += run_partition_compare(pnodes, args.partition_iters,
                                          args.seed, ndev)

    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
