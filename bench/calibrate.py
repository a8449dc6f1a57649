"""Readings that set a cell's check limits: the program's sound runs, the
lower-precision control, and the faults a cell can have. Not run by the
benchmark's own runs.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --what program
    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --what control
    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --what faults
    python3 bench/calibrate.py --workload <solve cell> --seeds 0 --what best_known

``program`` runs the cell as the benchmark does, with a short window, and
prints its compared numbers. ``control`` puts the reference, computed one
precision below what the configuration states, in the program's place.
``faults`` plants each fault: in a training cell in the reference put in
the program's place (half of the batch left out with the mean over the
rest, the exchange left out; a state left unchanged reads 1 by
construction); in a solve cell in the program's answer (one edge weight
altered where it is produced) and in its pipeline (``SOLVE_FAULTS``).
``best_known`` prints a solve cell's ``best_known_r_asym``, which its cell
file holds. A solve cell's readings cover every request of the cell, whatever
the seeds. Each reading is printed as one JSON line.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def say(**kv) -> None:
    print(json.dumps(kv, default=float), flush=True)


def train_readings(cell: dict, seed: int, what: str, root: str) -> list[dict]:
    """Numbers of a reference put in the program's place, against the
    float32 reference, for one seed."""
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.steps import topology_for

    from bench import loader, tokens
    from bench.drivers.train import EXCLUDE_GRAD_SHARE, norm_gap

    cfg, tr = cell["config_data"], cell["traffic_data"]
    ref = loader.reference(cfg["reference"], os.path.join(root, "bench"))
    n, b, S = tr["workers"], tr["batch_per_worker"], tr["seq_len"]
    rounds = tr["check_rounds"]
    topo = topology_for(n, kind=tr["topology"]["kind"], r=tr["topology"]["r"],
                        seed=0)
    W = ref.mixing_matrix(n, topo.edges, topo.g)
    toks, labels = tokens.token_pool(seed, cfg["vocab_size"],
                                     (rounds, n, b), S)
    batches = [(toks[t], labels[t]) for t in range(rounds)]
    p0 = ref.init_stacked(seed, cfg, n, dtype=jnp.dtype(cfg["dtype"]))
    base = ref.train_rounds(p0, batches, W, cfg, rounds)
    exclude = base["grad_norm"] < EXCLUDE_GRAD_SHARE * np.median(
        base["grad_norm"], axis=0, keepdims=True)
    variants = ({"control_fp8": {"quant": True}} if what == "control" else
                {"half_batch": {"half_batch": True},
                 "no_exchange": {"W": np.eye(n)}})
    out = []
    for name, kw in variants.items():
        Wv = kw.pop("W", W)
        v = ref.train_rounds(p0, batches, Wv, cfg, rounds, **kw)
        out.append({"variant": name, "seed": seed,
                    "loss_gap": max(abs(a - r) / abs(r)
                                    for a, r in zip(v["loss"], base["loss"])),
                    "grad_gap": norm_gap(v["grad_norm"], base["grad_norm"]),
                    "change_gap": norm_gap(v["change_norm"],
                                           base["change_norm"], exclude)})
    return out


#: the faults a solve can have, planted in the program's pipeline
SOLVE_FAULTS = ("restarts_1", "sa_only", "admm_half", "sa_half",
                "polish_half", "unpolished")


@contextlib.contextmanager
def solve_fault(name: str):
    """Plant fault ``name`` in the program's topology pipeline: every
    ``repro.core.solve_topology`` call inside the block has it.

    ``restarts_1``: one SA/ADMM restart instead of the configuration's;
    ``sa_only``: the ADMM candidate is never offered, so the incumbent comes
    from the SA warm starts and the classics; ``admm_half``, ``sa_half``,
    ``polish_half``: half of the pipeline's ADMM, SA or polish iterations;
    ``unpolished``: every candidate keeps its Metropolis weights."""
    from dataclasses import replace

    import numpy as np

    import repro.core
    from repro.core import anytime, api

    base = api.BATopoConfig()
    cfg = {"admm_half": replace(base, admm=replace(
               base.admm, max_iters=base.admm.max_iters // 2)),
           "sa_half": replace(base, sa_iters=base.sa_iters // 2),
           "polish_half": replace(base, polish_iters=base.polish_iters // 2),
           }.get(name)
    if name not in SOLVE_FAULTS:
        raise ValueError(f"unknown solve fault {name!r}")
    orig_solve = repro.core.solve_topology
    orig_offer = anytime.AnytimeSolver._polish_and_offer
    orig_polish = anytime.polish_weights_batched

    def solve(req, **kw):
        if name == "restarts_1":
            req = replace(req, restarts=1)
        return orig_solve(req, cfg=cfg, **kw)

    def offer(self, sel, label, meta, order, tier, source):
        if source == "admm":
            return None
        return orig_offer(self, sel, label, meta, order, tier, source)

    repro.core.solve_topology = solve
    if name == "sa_only":
        anytime.AnytimeSolver._polish_and_offer = offer
    if name == "unpolished":
        anytime.polish_weights_batched = (
            lambda n, edge_lists, g0s, **kw: [np.asarray(g, np.float64)
                                              for g in g0s])
    try:
        yield
    finally:
        repro.core.solve_topology = orig_solve
        anytime.AnytimeSolver._polish_and_offer = orig_offer
        anytime.polish_weights_batched = orig_polish


def cell_requests(cell: dict) -> list[int]:
    """Every request's solver seed in a solve cell: the warm-up's, then the
    pool's."""
    from bench.drivers.solve import request_order

    warmup, pool = request_order(cell["traffic_data"], 0)
    return warmup + sorted(pool)


def _solve(cfg: dict, seed: int):
    import repro.core

    return repro.core.solve_topology(repro.core.TopologyRequest(
        n=int(cfg["n"]), r=int(cfg["r"]), scenario=cfg["scenario"],
        restarts=int(cfg["restarts"]), seed=seed))


def best_known(cell: dict, root: str) -> dict[str, float]:
    """``best_known_r_asym`` of a solve cell: each request's r_asym as the
    sound program answers it, by the float64 reference."""
    import numpy as np

    from bench import loader

    cfg = cell["config_data"]
    ref = loader.reference(cfg["reference"], os.path.join(root, "bench"))
    return {str(s): ref.r_asym(np.asarray(_solve(cfg, s).topology.W))
            for s in cell_requests(cell)}


def solve_readings(cell: dict, what: str, root: str) -> list[dict]:
    """The cell's numbers over every request of the cell: for the control,
    for the sound program (``what="faults"`` reads it first: in a process of
    its own it shows that the program answers as ``best_known_r_asym``
    says), one altered weight in its answers, and each of ``SOLVE_FAULTS``.
    ``r_asym_excess`` is the worst request's; ``excess`` lists each one's."""
    import numpy as np

    from bench import loader

    cfg = cell["config_data"]
    ref = loader.reference(cfg["reference"], os.path.join(root, "bench"))
    n, r = cfg["n"], cfg["r"]
    best = cell.get("best_known_r_asym", {})
    _, _, _, classic = ref.best_classic(n, r)
    seeds = cell_requests(cell)

    def reading(name, answers):
        cs = [ref.check(n, r, edges, W, val) for edges, W, val in answers]
        excess = [c["r_asym"] - best.get(str(s), -np.inf)
                  for c, s in zip(cs, seeds)]
        return {"variant": name, "requests": len(cs),
                "r_asym_excess": max(excess),
                "w_dev": max(c["w_dev"] for c in cs),
                "r_asym_dev": max(c["r_asym_dev"] for c in cs),
                "vs_classic": max(c["r_asym"] for c in cs) - classic,
                "over_budget": max(c["over_budget"] for c in cs),
                "disconnected": max(c["disconnected"] for c in cs),
                "excess": excess}

    if what == "control":
        return [reading("control_fp32", [ref.control(n, r)] * len(seeds))]
    out, sound, altered = [], [], []
    for s in seeds:
        res = _solve(cfg, s)
        topo = res.topology
        sound.append((topo.edges, topo.W, res.r_asym))
        topo.g = np.array(topo.g, np.float64)
        topo.g[0] *= 0.5                      # one weight altered where produced
        altered.append((topo.edges, topo.W, res.r_asym))
    out += [reading("program", sound), reading("answer_altered", altered)]
    for name in SOLVE_FAULTS:
        answers = []
        with solve_fault(name):
            for s in seeds:
                res = _solve(cfg, s)
                answers.append((res.topology.edges, res.topology.W, res.r_asym))
        out.append(reading(name, answers))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--what", choices=("program", "control", "faults",
                                       "best_known"),
                    required=True)
    ap.add_argument("--seconds", type=float, default=2.0,
                    help="window of each program run")
    ap.add_argument("--root", default=ROOT)
    args = ap.parse_args(argv)
    sys.path[0] = args.root
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(args.root,
                                                           ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    sys.path.insert(1, os.path.join(args.root, "src"))
    from bench import harness, loader

    seeds = [int(s) for s in args.seeds.split(",")]
    cell = loader.cell(args.workload, os.path.join(args.root, "bench"))
    harness.check_devices(cell["chips"],
                          require_tpu=os.environ.get("JAX_PLATFORMS") != "cpu")
    if cell["config_data"]["driver"] == "solve" and args.what != "program":
        t0 = time.perf_counter()
        rows = ([{"variant": "best_known",
                  "best_known_r_asym": best_known(cell, args.root)}]
                if args.what == "best_known"
                else solve_readings(cell, args.what, args.root))
        for row in rows:
            say(**row, s=time.perf_counter() - t0)
        return 0
    for seed in seeds:
        t0 = time.perf_counter()
        if args.what == "program":
            res = harness.execute(
                args.workload, seed, args.seconds, False,
                require_tpu=os.environ.get("JAX_PLATFORMS") != "cpu",
                device_kind=None if os.environ.get("JAX_PLATFORMS") != "cpu"
                else "TPU v5 lite",
                base=os.path.join(args.root, "bench"), root=args.root)
            say(variant="program", seed=seed, correct=res["correct"],
                **{k: v["value"] for k, v in res["checks"].items()},
                metrics={k: v["value"] for k, v in res["metrics"].items()},
                memory_peak_bytes=res["device"]["memory_peak_bytes"],
                s=time.perf_counter() - t0)
            continue
        for row in train_readings(cell, seed, args.what, args.root):
            say(**row, s=time.perf_counter() - t0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
