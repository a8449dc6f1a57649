"""Chip smoke test: the system's main path, once, on a TPU.

  python chip_smoke.py               # one chip: topology solves + gossip training
  python chip_smoke.py --four-chips  # the two paths that exist only across chips

One process, no fallback: it exits non-zero unless JAX's first device is a
TPU, and it must be run from a checkout (it imports ``src/repro``). Phases:

1. device check — platform, device kind, device count, JAX version.
2. topology solves through ``solve_topology`` (no budget): homo n=64 r=128
   with 4 restarts, and the paper's heterogeneous node scenario n=16 r=32
   (9.76 GB/s ×8, 3.25 GB/s ×8). Each must come back ``full`` and
   complete, pass ``guard.validate_topology``, match a host numpy
   ``eigvalsh`` recomputation of r_asym to 1e-6, and be no worse than the
   best classic baseline (ring / grid / torus / hypercube with Metropolis
   weights) that fits the same budget and constraints — none fits the node
   scenario's per-node degree allocation, which the output then says.
   Cold and warm wall times and the phase profile are printed.
3. gossip training of smollm-135m at its published widths (30 layers,
   d_model 576, vocab 49 152, bf16) through ``repro.launch.train.main``
   with ``--topo ba --elastic`` (fault-free), workers stacked on the chip,
   seq 512, 5 steps. Four workers, batch 2 per worker or else 1, whichever
   compiled step first fits 75 % of the chip's memory. The BA budget is
   one edge per worker, so W is sparse and its rows differ (a permuted or
   mis-weighted row shows). Every loss must be finite, and the first
   elastic step must match ``dsgd_train_step`` on the same state and batch
   (the plain reference; bit-exact on the CPU) within ``PARITY_REL``.

``--four-chips`` runs instead the edge-partitioned ADMM (homo n=512,
``partition="edges"`` against ``"none"``: r_asym drift ≤ ``DRIFT_MAX`` of the
rounded candidates, support flips reported) and the sharded elastic step
(one smollm-135m worker per chip, ppermute gossip) against the stacked step
on one chip, on the same topology as phase 3. The two steps are different
programs, so their bf16 gradients differ by rounding; the comparison keeps
that apart from the mixing. Each step runs twice on the same input: with
every worker out of the exchange (``mix_mask`` 0, so it returns the local
update) and with every worker in it. Each mixed result must equal W times
its own local result, computed on the host in f32, element by element
(``MIX_REL``); the workers start from different weights so that every row
of W shows. The local results of the two steps must agree in loss
(``LOSS_REL``) and in gradient, read from the f32 momentum (``GRAD_REL``).

The last line of standard output is the JSON result; nothing is printed
there when a phase fails.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))

#: fraction of the chip's memory one training step may plan to use
MEM_FRACTION = 0.75
#: (workers, per-worker batch) candidates, largest first; the BA budget is
#: one edge per worker
TRAIN_SHAPES = ((4, 2), (4, 1))
ARCH, SEQ, STEPS = "smollm-135m", 512, 5
PUBLISHED = {"num_layers": 30, "d_model": 576, "vocab_size": 49152,
             "dtype": "bfloat16"}
#: elastic vs dsgd_train_step: max |Δparam| over a leaf relative to that
#: leaf's largest magnitude — 2^-7, one bf16 ulp at the leaf's scale (the
#: two are bit-exact on the CPU) — and relative loss difference
PARITY_REL = 2.0 ** -7
LOSS_REL = 1e-3
#: mixed vs W·local on the host, per element relative to |W|·|local|. The
#: mix may read the local update before its rounding to bf16 (XLA keeps
#: excess precision): ≤ 2^-8 of that scale; the stacked mix rounds W to
#: bf16: ≤ 2^-9; both round the result to bf16: ≤ 2^-8 — 2.5·2^-8 in all,
#: so 2^-6. A wrong row of W is off by O(1)
MIX_REL = 2.0 ** -6
#: sharded vs stacked local update: relative L2 of the gradient (the f32
#: momentum after one step) per worker. bf16 backprop through 30 layers in
#: two programs that fuse differently; a worker fed another's batch or
#: weights differs by O(1)
GRAD_REL = 2.0 ** -3
#: r_asym drift bound of the edge-partitioned ADMM's rounded candidate
DRIFT_MAX = 1e-3
#: r_asym: solver value vs host recomputation; BA vs classic slack
R_ASYM_TOL = 1e-6


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


def say(tag: str, **kv) -> None:
    print(f"[{tag}] " + json.dumps(kv, default=float), flush=True)


# ---------------------------------------------------------------------------
# phase 1
# ---------------------------------------------------------------------------

def device_check(need: int) -> dict:
    import jax

    devs = jax.devices()
    d = devs[0]
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}
    say("device", **info, jax=jax.__version__)
    check(d.platform == "tpu",
          f"no TPU: JAX's devices are {d.platform!r}; there is no CPU path")
    check(len(devs) >= need, f"needs {need} chips, {len(devs)} attached")
    return info


def hbm_bytes(dev) -> int:
    stats = dev.memory_stats() or {}
    check("bytes_limit" in stats, f"{dev.device_kind} reports no bytes_limit")
    return int(stats["bytes_limit"])


# ---------------------------------------------------------------------------
# phase 2: topology solves
# ---------------------------------------------------------------------------

def host_r_asym(W: np.ndarray) -> float:
    n = W.shape[0]
    return float(np.max(np.abs(np.linalg.eigvalsh(W - 1.0 / n))))


def best_classic(n: int, r: int, cs) -> tuple[str | None, float]:
    """Best Metropolis-weighted classic topology within r edges (and the
    constraints), r_asym by host eigvalsh — the plain reference. ``(None,
    inf)`` when no classic is feasible."""
    from repro.core import make_baseline
    from repro.core.graph import all_edges, edge_index, weight_matrix_from_weights
    from repro.core.weights import metropolis_weights

    eidx = edge_index(n)
    best = (None, float("inf"))
    for kind in ("ring", "grid", "torus", "hypercube"):
        try:
            t = make_baseline(kind, n)
        except ValueError:
            continue
        if len(t.edges) > r:
            continue
        if cs is not None:
            sel = np.zeros(len(all_edges(n)), dtype=bool)
            sel[[eidx[tuple(sorted(e))] for e in t.edges]] = True
            if not cs.feasible(sel):
                continue
        W = weight_matrix_from_weights(n, t.edges,
                                       metropolis_weights(n, t.edges))
        val = host_r_asym(W)
        if val < best[1]:
            best = (t.name, val)
    return best


def solve_checked(label: str, req_kw: dict, cfg) -> None:
    from repro.core import TopologyRequest, solve_topology
    from repro.core.anytime import resolve_scenario
    from repro.core.guard import validate_topology

    times, res = [], None
    for _ in range(2):                      # cold, then warm
        req = TopologyRequest(**req_kw)
        t0 = time.perf_counter()
        res = solve_topology(req, cfg=cfg)
        times.append(time.perf_counter() - t0)
        if len(times) == 1:
            cold_profile = dict(res.profile.phases)
    check(res.quality_tier == "full" and res.complete,
          f"{label}: tier={res.quality_tier} complete={res.complete} "
          f"reason={res.reason}")
    topo = validate_topology(res.topology, context=label)
    host = host_r_asym(np.asarray(topo.W, np.float64))
    check(abs(host - res.r_asym) <= R_ASYM_TOL,
          f"{label}: solver r_asym {res.r_asym} vs host eigvalsh {host}")
    n, r = req_kw["n"], req_kw["r"]
    cs = None
    if req_kw.get("scenario", "homo") != "homo":
        cs, _, _ = resolve_scenario(n, r, req_kw["scenario"], None,
                                    req_kw.get("node_bandwidths"),
                                    context="chip_smoke")
    base_name, base_val = best_classic(n, r, cs)
    check(res.r_asym <= base_val + R_ASYM_TOL,
          f"{label}: BA r_asym {res.r_asym} worse than {base_name} "
          f"{base_val}")
    say("topology", case=label, n=n, r=r, edges=len(topo.edges),
        quality_tier=res.quality_tier, complete=res.complete,
        r_asym=res.r_asym, host_r_asym=host,
        r_asym_abs_diff=abs(host - res.r_asym),
        best_classic=base_name, best_classic_r_asym=base_val,
        cold_s=times[0], warm_s=times[1],
        cold_profile_s=cold_profile, warm_profile_s=res.profile.phases)


def topology_phase() -> None:
    from repro.core import BATopoConfig

    cfg = BATopoConfig(restarts=4)
    solve_checked("homo", {"n": 64, "r": 128, "scenario": "homo"}, cfg)
    bw = np.array([9.76] * 8 + [3.25] * 8)
    solve_checked("node", {"n": 16, "r": 32, "scenario": "node",
                           "node_bandwidths": bw}, cfg)


# ---------------------------------------------------------------------------
# phase 3: gossip training at published widths
# ---------------------------------------------------------------------------

def published_config():
    from repro.configs import get_arch

    cfg = get_arch(ARCH)
    for k, v in PUBLISHED.items():
        check(getattr(cfg, k) == v, f"{ARCH}.{k} = {getattr(cfg, k)}, "
              f"published {v}")
    return cfg


def optimizer():
    """The optimizer ``launch/train.py`` builds for ``--steps STEPS``."""
    from repro.optim import make_optimizer, warmup_cosine

    return make_optimizer("sgd", warmup_cosine(0.05, max(STEPS // 20, 1),
                                               STEPS))


def step_inputs(cfg, n: int, b: int, opt_init, abstract: bool):
    import jax
    import jax.numpy as jnp

    from repro.data import DataConfig, synthetic_lm_batch
    from repro.dsgd import init_dsgd_state

    if abstract:
        state = jax.eval_shape(
            lambda: init_dsgd_state(jax.random.PRNGKey(0), cfg, n, opt_init))
        i32 = jax.ShapeDtypeStruct((n, b, SEQ), jnp.int32)
        return state, {"tokens": i32, "labels": i32}
    state = init_dsgd_state(jax.random.PRNGKey(0), cfg, n, opt_init)
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=SEQ, batch_size=b,
                    seed=0)
    per = [synthetic_lm_batch(dc, 0, node=i) for i in range(n)]
    return state, {k: jnp.stack([p[k] for p in per]) for k in per[0]}


def mixing_matrix(topo) -> np.ndarray:
    from repro.core.graph import weight_matrix_from_weights

    return np.asarray(weight_matrix_from_weights(topo.n, topo.edges, topo.g),
                      np.float32)


def mixing_inputs(n: int, topo, mix: float = 1.0):
    """``(W, alive, link_up, mix_mask)`` of the stacked elastic step, fault
    free; ``mix=0`` takes every worker out of the exchange."""
    import jax.numpy as jnp

    ones = jnp.ones((n,), jnp.float32)
    return (jnp.asarray(mixing_matrix(topo)), ones,
            jnp.ones((n, n), jnp.float32), mix * ones)


def gossip_topology(n: int):
    """The BA topology phase 3 trains on (``topology_for`` memoizes it),
    checked to be sparse gossip rather than exact averaging."""
    from repro.launch.steps import topology_for

    topo = topology_for(n, kind="ba", r=n, seed=0)
    W = mixing_matrix(topo)
    check(topo.r_asym() > 0 and np.any(W == 0),
          f"{topo.name}: W is exact averaging (r_asym {topo.r_asym()})")
    return topo


def step_bytes(cfg, n: int, b: int) -> int:
    """Device bytes the compiled stacked elastic step plans to use."""
    import jax
    import jax.numpy as jnp

    from repro.dsgd.elastic import make_elastic_train_step

    opt_init, opt_update = optimizer()
    state, batch = step_inputs(cfg, n, b, opt_init, abstract=True)
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    compiled = make_elastic_train_step(cfg, opt_update).lower(
        state, batch, f32(n, n), f32(n), f32(n, n), f32(n)).compile()
    m = compiled.memory_analysis()
    return int(m.argument_size_in_bytes + m.output_size_in_bytes
               + m.temp_size_in_bytes - m.alias_size_in_bytes
               + m.generated_code_size_in_bytes)


def worst_leaf(a_tree, b_tree) -> dict:
    """The leaf with the largest max|a − b| / max|b|, with that ratio, the
    element where |a − b| peaks and both readings there. Works on host
    (numpy) and device trees alike."""
    import jax

    worst = {"rel": -1.0}
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(a_tree)[0],
                            jax.tree.leaves(b_tree)):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        d = np.abs(a - b)
        k = int(np.argmax(d))
        rel = float(d.flat[k] / max(float(np.max(np.abs(b))), 1e-30))
        if rel > worst["rel"]:
            worst = {"rel": rel, "leaf": jax.tree_util.keystr(path),
                     "index": [int(v) for v in np.unravel_index(k, a.shape)],
                     "a": float(a.flat[k]), "b": float(b.flat[k]),
                     "leaf_max_abs_b": float(np.max(np.abs(b)))}
    return worst


def elastic_vs_dsgd(cfg, n: int, b: int) -> None:
    """First fault-free elastic step against ``dsgd_train_step``."""
    from repro.dsgd import dsgd_train_step
    from repro.dsgd.elastic import make_elastic_train_step

    topo = gossip_topology(n)               # the topology main() solved
    opt_init, opt_update = optimizer()
    state, batch = step_inputs(cfg, n, b, opt_init, abstract=False)
    e_state, e_m = make_elastic_train_step(cfg, opt_update)(
        state, batch, *mixing_inputs(n, topo))
    e_params = e_state.params
    del e_state
    d_state, d_m = dsgd_train_step(cfg, topo, opt_update)(state, batch)
    worst = worst_leaf(e_params, d_state.params)
    dloss = abs(float(e_m["loss"]) - float(d_m["loss"]))
    say("reference", compare="elastic step vs dsgd_train_step",
        workers=n, topology=topo.name, r_asym=topo.r_asym(),
        max_rel_param_diff=worst["rel"], worst_leaf=worst,
        loss_abs_diff=dloss, bound_rel=PARITY_REL)
    check(worst["rel"] <= PARITY_REL
          and dloss <= LOSS_REL * abs(float(d_m["loss"])),
          f"elastic vs dsgd: {worst}, |Δloss| {dloss}")


def training_phase() -> None:
    import jax

    from repro.launch import train

    cfg = published_config()
    dev = jax.devices()[0]
    limit = MEM_FRACTION * hbm_bytes(dev)
    for n, b in TRAIN_SHAPES:
        need = step_bytes(cfg, n, b)
        say("train_plan", workers=n, batch=b, step_bytes=need,
            limit_bytes=limit)
        if need <= limit:
            break
    else:
        fail(f"no (workers, batch) in {TRAIN_SHAPES} fits {limit} bytes")
    out = train.main(["--arch", ARCH, "--workers", str(n), "--batch", str(b),
                      "--seq", str(SEQ), "--steps", str(STEPS), "--topo",
                      "ba", "--r", str(n), "--elastic", "--log-every", "1",
                      "--seed", "0"])
    hist = out["history"]
    check([h["step"] for h in hist] == list(range(STEPS)),
          f"logged steps {[h['step'] for h in hist]}")
    losses = [h["loss"] for h in hist]
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    step_s = [h["step_s"] for h in hist]
    stats = dev.memory_stats() or {}
    say("train", arch=ARCH, workers=n, batch=b, seq=SEQ, steps=STEPS,
        topology=out["topology"], r_asym=out["r_asym"], losses=losses,
        step_s=step_s, warm_step_s=statistics.median(step_s[1:]),
        peak_bytes_in_use=stats.get("peak_bytes_in_use"))
    elastic_vs_dsgd(cfg, n, b)


# ---------------------------------------------------------------------------
# --four-chips
# ---------------------------------------------------------------------------

def admm_partition_compare(ndev: int) -> None:
    from repro.core.admm import ADMMConfig, HomogeneousADMM
    from repro.core.anneal import greedy_degree_graph
    from repro.core.api import _homo_degree_targets, _pack_warm, extract_support
    from repro.core.graph import Topology, all_edges, is_connected
    from repro.core.shard import EDGE_PARTITION_MIN_N
    from repro.core.weights import metropolis_weights

    n = EDGE_PARTITION_MIN_N
    r = 2 * n
    edges0 = greedy_degree_graph(n, _homo_degree_targets(n, r),
                                 np.random.default_rng(0), None)
    g0, _, lam0 = _pack_warm(n, edges0)
    rows = {}
    for part in ("none", "edges"):
        cfg = ADMMConfig(max_iters=100, check_every=10, eps=0.0,
                         cg_inexact=True, dtype="float32",
                         psd_backend="newton_schulz", psd_iters=16,
                         partition=part)
        solver = HomogeneousADMM(n, r, cfg)
        t0 = time.perf_counter()
        res = solver.solve(g0=g0, lam0=lam0)
        cold = time.perf_counter() - t0
        t0 = time.perf_counter()
        res = solver.solve(g0=g0, lam0=lam0)
        warm = time.perf_counter() - t0
        sel = extract_support(n, np.asarray(res.g) + np.asarray(res.g_raw),
                              r, tol=1e-6)
        edges = [all_edges(n)[i] for i in np.nonzero(sel)[0]]
        check(edges and is_connected(n, edges),
              f"partition={part}: rounded candidate is disconnected")
        val = Topology(n, edges, metropolis_weights(n, edges)).r_asym()
        rows[part] = (sel, val)
        say("admm", n=n, r=r, partition=part,
            devices=ndev if part == "edges" else 1, iters=res.iters,
            residual=float(res.residual), r_asym=val, cold_s=cold,
            warm_s=warm)
    drift = abs(rows["edges"][1] - rows["none"][1])
    flips = int(np.sum(rows["edges"][0] != rows["none"][0]))
    say("admm_compare", n=n, r_asym_drift=drift, support_flips=flips,
        bound=DRIFT_MAX)
    check(drift <= DRIFT_MAX, f"edge-partitioned r_asym drift {drift}")


def distinct_workers(cfg, n: int, opt_init):
    """A stacked state whose worker i starts from weights drawn with seed i,
    so that every row of W shows in the mix."""
    import jax
    import jax.numpy as jnp

    from repro.dsgd import DSGDState
    from repro.models import transformer

    params = jax.tree.map(
        lambda *x: jnp.stack(x),
        *[transformer.init_params(jax.random.PRNGKey(i), cfg)
          for i in range(n)])
    return DSGDState(params, jax.vmap(opt_init)(params),
                     jnp.zeros((), jnp.int32))


def check_mix(label: str, W: np.ndarray, local, mixed) -> None:
    """``mixed`` (host tree) against W·``local`` in f32 on the host, element
    by element relative to |W|·|local|, the scale the rounding of W and of
    the products goes with."""
    import jax

    worst = {"rel": -1.0}
    for (path, x), mx in zip(jax.tree_util.tree_flatten_with_path(local)[0],
                             jax.tree.leaves(mixed)):
        x = np.asarray(x, np.float32)
        x2 = x.reshape(len(W), -1)
        ref = (W @ x2).reshape(x.shape)
        scale = (np.abs(W) @ np.abs(x2)).reshape(x.shape)
        rel = np.abs(np.asarray(mx, np.float32) - ref) / np.maximum(scale,
                                                                    1e-30)
        k = int(np.argmax(rel))
        if rel.flat[k] > worst["rel"]:
            worst = {"rel": float(rel.flat[k]),
                     "leaf": jax.tree_util.keystr(path),
                     "index": [int(v) for v in np.unravel_index(k, x.shape)],
                     "mixed": float(np.asarray(mx, np.float32).flat[k]),
                     "W_local": float(ref.flat[k]),
                     "abs_W_abs_local": float(scale.flat[k])}
    say("mix_check", path=label, max_rel_diff=worst["rel"], worst=worst,
        bound_rel=MIX_REL)
    check(worst["rel"] <= MIX_REL, f"{label} mix vs W·local: {worst}")


def grad_rel(a_mom, b_mom, n: int) -> list[float]:
    """Per worker ‖a − b‖ / ‖b‖ over all leaves of the f32 momentum."""
    import jax

    num, den = np.zeros(n), np.zeros(n)
    for a, b in zip(jax.tree.leaves(a_mom), jax.tree.leaves(b_mom)):
        a = np.asarray(a, np.float32).reshape(n, -1)
        b = np.asarray(b, np.float32).reshape(n, -1)
        num += np.sum(np.square(a - b, dtype=np.float64), axis=1)
        den += np.sum(np.square(b, dtype=np.float64), axis=1)
    return [float(v) for v in np.sqrt(num / den)]


def sharded_vs_stacked(ndev: int) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.dsgd import (make_elastic_sharded_train_step,
                            schedule_from_topology, schedule_weight_arrays)
    from repro.dsgd.elastic import make_elastic_train_step
    from repro.launch.mesh import make_mesh

    cfg = published_config()
    n, b = ndev, 2
    topo = gossip_topology(n)
    W = mixing_matrix(topo)
    opt_init, opt_update = optimizer()
    _, batch = step_inputs(cfg, n, b, opt_init, abstract=False)
    state = distinct_workers(cfg, n, opt_init)

    stacked, t0 = {}, time.perf_counter()
    step = make_elastic_train_step(cfg, opt_update)
    for mix in (0.0, 1.0):
        out, m = step(state, batch, *mixing_inputs(n, topo, mix))
        stacked[mix] = (jax.device_get(out.params),
                        jax.device_get(out.opt.momentum), float(m["loss"]))
        del out
    t_stacked = time.perf_counter() - t0

    mesh = make_mesh((n,), ("data",))
    sched = schedule_from_topology(topo)
    w_self, w_recv = (jnp.asarray(a) for a in schedule_weight_arrays(sched))
    shard = NamedSharding(mesh, P("data"))
    put = lambda t: jax.tree.map(lambda x: jax.device_put(x, shard), t)  # noqa: E731
    state = state._replace(params=put(state.params), opt=put(state.opt))
    batch = put(batch)
    ones = jnp.ones((n,), jnp.float32)
    step = jax.jit(make_elastic_sharded_train_step(cfg, sched, opt_update,
                                                   mesh))
    sharded, times = {}, []
    with jax.set_mesh(mesh):
        for mix in (0.0, 1.0):               # the first call compiles
            t0 = time.perf_counter()
            out, m = step(state, batch, ones, mix * ones, w_self, w_recv)
            loss = float(m["loss"])
            times.append(time.perf_counter() - t0)
            sharded[mix] = (jax.device_get(out.params),
                            jax.device_get(out.opt.momentum), loss)
            del out

    say("sharded_elastic", arch=ARCH, workers=n, batch=b, seq=SEQ,
        topology=topo.name, r_asym=topo.r_asym(), rounds=sched.rounds,
        loss=sharded[1.0][2], stacked_loss=stacked[1.0][2],
        cold_s=times[0], warm_s=times[1], stacked_two_steps_s=t_stacked)
    for label, res in (("stacked", stacked), ("sharded", sharded)):
        check(np.isfinite(res[0.0][2]) and res[0.0][2] == res[1.0][2],
              f"{label} loss {res[0.0][2]} / {res[1.0][2]}")
        check_mix(label, W, res[0.0][0], res[1.0][0])
    dloss = abs(sharded[0.0][2] - stacked[0.0][2])
    grads = grad_rel(sharded[0.0][1], stacked[0.0][1], n)
    worst = worst_leaf(sharded[0.0][0], stacked[0.0][0])
    say("local_update", compare="sharded vs stacked, no exchange",
        loss_abs_diff=dloss, grad_rel_l2_per_worker=grads,
        bound_grad_rel=GRAD_REL, max_rel_param_diff=worst["rel"],
        worst_leaf=worst)
    check(dloss <= LOSS_REL * abs(stacked[0.0][2]),
          f"sharded vs stacked loss: |Δ| {dloss}")
    check(max(grads) <= GRAD_REL,
          f"sharded vs stacked gradient: rel L2 {grads}")


# ---------------------------------------------------------------------------

def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the edge-partitioned ADMM and the sharded "
                         "elastic step across four chips")
    args = ap.parse_args()
    src = os.path.join(REPO, "src")
    check(os.path.isdir(os.path.join(src, "repro")),
          f"no src/repro next to {__file__}: run from a checkout")
    sys.path.insert(0, src)

    ndev = 4 if args.four_chips else 1
    info = device_check(ndev)
    from repro.launch.compile_cache import enable_compile_cache

    say("compile_cache", dir=enable_compile_cache())
    t0 = time.perf_counter()
    if args.four_chips:
        admm_partition_compare(ndev)
        sharded_vs_stacked(ndev)
    else:
        topology_phase()
        training_phase()
    say("done", total_s=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": info}), flush=True)


if __name__ == "__main__":
    main()
