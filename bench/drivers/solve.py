"""Solve driver: a closed loop of topology solves through the program's
``solve_topology``, one caller that waits for each topology, like a job that
re-plans.

Set-up solves ``warmup_solves`` requests (compiling every program a solve
uses), then the window solves back to back until ``--seconds`` have passed;
a solve started in the window runs to its end and counts. Every request has
the configuration's shape. The window cycles through a fixed pool of
``request_pool`` requests, each with a solver seed of its own drawn from
the mix's ``pool_seed``, in an order drawn from ``--seed``: how long a solve
takes depends on its solver seed (ADMM stops on convergence), so every run
gets the same set of requests and only their order changes.

End-to-end: ``solve_s``, the span from the first solve's start to the last
one's end over the number of solves. Every answer, set-up's included, is
held to the configuration's guarantees by the float64 reference
(``references/topology_f64.py``) once the window has closed, and its
quality to the cell's ``best_known_r_asym``: for each request of the cell,
the r_asym that the sound program reaches on the chip, by that reference
(``calibrate.py --what best_known`` reads it). ``r_asym_excess`` is the
worst answer's r_asym above its request's; a request with no reading fails.
"""
from __future__ import annotations

import itertools
import time

import numpy as np


def request_seeds(seed: int, count: int) -> list[int]:
    """``count`` solver seeds in [0, 2**31) drawn from ``seed``."""
    ss = np.random.SeedSequence([int(seed), 0x5017E])
    return [int(s) >> 1 for s in ss.generate_state(count)]


def request_order(traffic: dict, seed: int) -> tuple[list[int], list[int]]:
    """``(warm-up seeds, window seeds)``: the warm-up requests lie outside
    the pool; the window cycles through the pool in an order drawn from the
    run's ``seed``."""
    k, w = int(traffic["request_pool"]), int(traffic.get("warmup_solves", 1))
    seeds = request_seeds(int(traffic["pool_seed"]), k + w)
    order = np.random.default_rng(np.random.SeedSequence([int(seed), 0x0D3E])
                                  ).permutation(k)
    return seeds[k:], [seeds[i] for i in order]


def run(ctx) -> dict:
    from repro.core import TopologyRequest, solve_topology
    from bench.harness import memory_peak_bytes

    cfg, tr = ctx.config, ctx.traffic
    n, r = int(cfg["n"]), int(cfg["r"])
    warmup, pool = request_order(tr, ctx.seed)
    window_seeds = itertools.cycle(pool)

    def solve(seed):
        req = TopologyRequest(n=n, r=r, scenario=cfg["scenario"],
                              restarts=int(cfg["restarts"]), seed=seed)
        return solve_topology(req)

    answers = [(s, solve(s)) for s in warmup]

    units, phases = [], []
    t_window = time.perf_counter()
    setup_s = t_window - ctx.t_start

    def one():
        seed = next(window_seeds)
        t0 = time.perf_counter()
        with ctx.span("bench.solve"):
            res = solve(seed)
        units.append((t0, time.perf_counter()))
        phases.append(dict(res.profile.phases))
        answers.append((seed, res))

    if ctx.trace:
        with ctx.profile():
            for _ in range(int(tr["trace_solves"])):
                one()
    else:
        while True:
            one()
            if units[-1][1] - t_window >= ctx.seconds:
                break
    peak = memory_peak_bytes(ctx.devices)

    ref = ctx.reference
    limits = ctx.cell["limits"]
    best = ctx.cell.get("best_known_r_asym", {})
    _, _, _, classic = ref.best_classic(n, r)
    worst = {"not_full": 0.0, "over_budget": 0.0, "disconnected": 0.0,
             "w_dev": 0.0, "r_asym_dev": 0.0, "vs_classic": -np.inf,
             "r_asym_excess": -np.inf}
    failed = 0
    for k, (seed, res) in enumerate(answers):
        topo = res.topology
        c = ref.check(n, r, topo.edges, topo.W, res.r_asym)
        c["not_full"] = float(res.quality_tier != "full" or not res.complete)
        c["vs_classic"] = c["r_asym"] - classic
        c["r_asym_excess"] = c["r_asym"] - best.get(str(seed), -np.inf)
        for key in worst:
            worst[key] = (worst[key] + c[key] if key == "not_full"
                          else max(worst[key], c[key]))
        in_window = k >= len(answers) - len(units)
        failed += int(in_window and any(
            c[key] > limits.get(key, 0.0) for key in worst))

    e2e = {}
    if not ctx.trace:
        e2e["solve_s"] = (units[-1][1] - units[0][0]) / len(units)
    return {"setup_s": setup_s, "e2e": e2e, "unit": "bench.solve",
            "attempted": len(units), "failed": failed,
            "memory_peak_bytes": peak, "phases": phases,
            "checks": list(worst.items())}
