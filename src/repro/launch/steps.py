"""Step builders: (arch × input-shape × mesh) → (jit-able fn, abstract args).

``input_specs()`` returns weak-type-correct ShapeDtypeStruct stand-ins with
NamedShardings attached — no device allocation — so ``jax.jit(fn).lower(*args)``
compiles the production program exactly as it would run on the target mesh.

Topology selection: the gossip topology for n workers is BA-Topo by default
(the paper's contribution, solved by the ADMM core in this process and
memoized for its lifetime — never read from disk), with
baseline topologies (ring / exponential / u_equistatic) and the centralized
all-reduce selectable for comparisons — the knobs the §Perf hillclimb turns.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import INPUT_SHAPES, ModelConfig, get_arch, shape_supported
from repro.core import BATopoConfig, TopologyRequest, make_baseline, solve_topology
from repro.core.graph import Topology
from repro.dsgd import (
    DSGDState,
    init_dsgd_state,
    make_sharded_train_step,
    make_tp_train_step,
    schedule_from_topology,
)
from repro.models import transformer
from repro.models.partitioning import rules_ctx
from repro.optim import sgd_momentum
from repro.serve import DecodeState, ServeConfig, make_functional_serve_step

from .sharding import (
    DistPlan,
    axis_sizes,
    batch_specs,
    cache_specs,
    plan_for,
    tree_param_specs,
    with_sharding,
)

__all__ = ["BuiltStep", "build_step", "input_specs", "topology_for"]


@dataclass
class BuiltStep:
    fn: Callable               # jit-able (args…) → outputs
    args: tuple                # abstract ShapeDtypeStructs with shardings
    plan: DistPlan
    mode: str                  # train | prefill | decode
    meta: dict


def _sharding_rules(plan: DistPlan, mesh, mode: str) -> dict:
    """Logical→mesh axis rules for in-model hints (models/partitioning.py).

    MoE dispatch groups follow the token sharding ("data" axis) so the
    scatter/gather stays shard-local (GShard local_groups). Inside the
    partial-manual gossip region "data" is a manual axis and may not be
    referenced → standard train keeps G = 1 (per-worker dispatch is already
    local to the worker's 16-chip slice)."""
    sizes = axis_sizes(mesh)
    if mode == "train" and plan.gossip_axes and plan.gossip_axes != ("pod",):
        return {"moe_ff": "model", "embed": None, "moe_groups": 1,
                "moe_group": None}
    if mode == "train":  # pod-sized worker: per-worker batch shards over data
        if plan.expert_axis:  # expert parallelism: E owns "data", G = 1
            return {"moe_ff": "model", "embed": None, "moe_groups": 1,
                    "moe_group": None, "moe_expert": plan.expert_axis}
        return {"moe_ff": "model", "embed": None,
                "moe_groups": sizes.get("data", 1), "moe_group": "data"}
    axes = plan.batch_axes or ("data",)
    groups = int(np.prod([sizes.get(a, 1) for a in axes]))
    rules = {"moe_ff": "model", "embed": None, "moe_groups": groups,
             "moe_group": axes if len(axes) > 1 else axes[0]}
    if plan.expert_axis:  # shard_map expert-parallel MoE (moe_ep.py)
        rules.update(moe_impl="expert_parallel",
                     moe_expert_axis=plan.expert_axis, moe_groups=1,
                     moe_group=None, moe_token_axes=axes)
    return rules


def _with_rules(fn: Callable, rules: dict) -> Callable:
    def wrapped(*args, **kw):
        with rules_ctx(rules):
            return fn(*args, **kw)
    return wrapped


# ---------------------------------------------------------------------------
# topology memo (one solve per (n, kind, r, seed, bandwidths) per process)
# ---------------------------------------------------------------------------

_MEM_CACHE: dict[tuple, Topology] = {}


def topology_for(n: int, kind: str = "ba", r: int | None = None,
                 seed: int = 0,
                 node_bw: "list[float] | None" = None) -> Topology:
    """Gossip topology over n workers. kind ∈ {"ba", "ring", "exponential",
    "u_equistatic", "torus2d", "grid2d"}; r defaults to 2n (the paper's best
    homogeneous budget at n=16). ``node_bw`` (BA only): per-node GB/s —
    the solve runs the §VI-A2 node scenario (Algorithm 1 allocates edge
    capacities to the heterogeneous NICs) instead of homogeneous."""
    r = r if r is not None else 2 * n
    bw_key = tuple(float(b) for b in node_bw) if node_bw is not None else None
    key = (n, kind, r, seed, bw_key)
    if key in _MEM_CACHE:
        return _MEM_CACHE[key]
    if node_bw is not None and kind != "ba":
        raise ValueError("node_bw is a BA-Topo (ADMM) knob — baseline "
                         f"topologies ignore bandwidth (got kind={kind!r})")
    if node_bw is not None and len(node_bw) != n:
        raise ValueError(f"node_bw has {len(node_bw)} entries for n={n}")
    if n == 1:
        topo = Topology(1, [], np.zeros(0), name="singleton")
    elif n == 2:
        topo = Topology(2, [(0, 1)], np.array([0.5]), name="pair")
    elif kind == "ba":
        topo = _solve_ba_topology(n, r, seed, node_bw)
    elif kind == "random":
        topo = make_baseline(kind, n, r=r, seed=seed)
    else:
        topo = make_baseline(kind, n)
    _MEM_CACHE[key] = topo
    return topo


def _solve_ba_topology(n: int, r: int, seed: int,
                       node_bw: "list[float] | None" = None) -> Topology:
    if node_bw is not None:
        req = TopologyRequest(n=n, r=r, scenario="node",
                              node_bandwidths=np.asarray(node_bw, float))
    else:
        req = TopologyRequest(n=n, r=r, scenario="homo")
    return solve_topology(req, cfg=BATopoConfig(seed=seed)).topology


# ---------------------------------------------------------------------------
# abstract inputs
# ---------------------------------------------------------------------------

def _abstract(tree):
    return jax.tree.map(lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), tree)


def _batch_shapes(cfg: ModelConfig, B: int, S: int) -> dict:
    shp = {"tokens": (B, S), "labels": (B, S)}
    if cfg.frontend_tokens:
        shp["embeds"] = (B, cfg.frontend_tokens, cfg.d_model)
    return shp


def _batch_structs(shapes: dict, lead: tuple = ()) -> dict:
    dt = {"tokens": jnp.int32, "labels": jnp.int32, "embeds": jnp.float32}
    return {k: jax.ShapeDtypeStruct(lead + v, dt[k]) for k, v in shapes.items()}


def input_specs(arch: str, shape_name: str, mesh, *, mode: str | None = None,
                **kw) -> tuple:
    """Public helper: the abstract (sharded) inputs ``build_step`` lowers."""
    return build_step(arch, shape_name, mesh, **kw).args


# ---------------------------------------------------------------------------
# builders
# ---------------------------------------------------------------------------

def build_step(arch: str, shape_name: str, mesh, *, sync: str = "gossip",
               topo_kind: str = "ba", topo_r: int | None = None,
               param_dtype: str | None = None, accum_steps: int = 1,
               tp_only: bool | None = None,
               expert_parallel: bool = False) -> BuiltStep:
    cfg = get_arch(arch)
    if param_dtype:
        from dataclasses import replace
        cfg = replace(cfg, dtype=param_dtype)
    shape = INPUT_SHAPES[shape_name]
    if not shape_supported(arch, shape_name):
        raise ValueError(f"{arch} × {shape_name} not in the supported matrix "
                         "(long_500k needs sub-quadratic attention)")
    if shape.kind == "train":
        return _build_train(cfg, shape, mesh, sync=sync, topo_kind=topo_kind,
                            topo_r=topo_r, accum_steps=accum_steps,
                            expert_parallel=expert_parallel)
    if shape.kind == "prefill":
        return _build_prefill(cfg, shape, mesh, tp_only=tp_only,
                              expert_parallel=expert_parallel)
    return _build_decode(cfg, shape, mesh, tp_only=tp_only,
                         expert_parallel=expert_parallel)


def _build_train(cfg, shape, mesh, *, sync: str, topo_kind: str,
                 topo_r: int | None, accum_steps: int = 1,
                 expert_parallel: bool = False) -> BuiltStep:
    plan = plan_for(cfg, mesh, mode="train", expert_parallel=expert_parallel)
    n = plan.n_workers
    per_b = max(shape.global_batch // max(n, 1), 1)
    if accum_steps == 1 and len(plan.tensor_axes) > 1:
        # pod-sized worker sees the whole (or half the) global batch — auto
        # microbatch to ≤128k tokens/microbatch (§Perf: 68 → 28 GB/dev)
        while per_b % (accum_steps * 2) == 0 and \
                per_b * shape.seq_len // accum_steps > 131072:
            accum_steps *= 2
    opt_init, opt_update = sgd_momentum(0.05)

    bshapes = _batch_shapes(cfg, per_b, shape.seq_len)
    meta: dict = {"n_workers": n, "per_worker_batch": per_b, "sync": sync,
                  "accum_steps": accum_steps}

    if plan.gossip_axes and sync != "none":
        topo = topology_for(n, kind=topo_kind, r=topo_r)
        if plan.gossip_axes == ("pod",):
            # pod-sized workers: gossip = dense W matmul (Eq. 1) under pure
            # pjit — the partial-manual partitioner chokes on 512-device MoE
            # gathers, and at n = #pods the matmul costs the same bytes
            from repro.dsgd import make_matmul_gossip_train_step
            step = make_matmul_gossip_train_step(cfg, topo, opt_update,
                                                 accum_steps=accum_steps)
            meta.update(topology=topo.name, gossip_impl="W-matmul")
        else:
            sched = schedule_from_topology(topo)
            step = make_sharded_train_step(cfg, sched, opt_update, mesh,
                                           gossip_axes=plan.gossip_axes, sync=sync)
            meta.update(topology=topo.name, rounds=sched.rounds,
                        degree_max=int(sched.degrees.max()) if len(topo.edges) else 0,
                        gossip_impl="ppermute-schedule")
        state_sh = jax.eval_shape(
            lambda: init_dsgd_state(jax.random.PRNGKey(0), cfg, n, opt_init))
        stacked = True
        batch = _batch_structs(bshapes, lead=(n,))
    else:
        step = make_tp_train_step(cfg, opt_update, accum_steps=accum_steps)
        params_sh = jax.eval_shape(
            lambda: transformer.init_params(jax.random.PRNGKey(0), cfg))
        opt_sh = jax.eval_shape(opt_init, params_sh)
        state_sh = DSGDState(params_sh, opt_sh,
                             jax.ShapeDtypeStruct((), jnp.int32))
        stacked = False
        # single worker sees the whole global batch
        bshapes = _batch_shapes(cfg, shape.global_batch // max(n, 1), shape.seq_len)
        batch = _batch_structs(bshapes, lead=(n,) if n > 1 else ())
        if n > 1:
            stacked = True

    pspecs = tree_param_specs(state_sh.params, plan, mesh, stacked=stacked)
    ospecs = tree_param_specs(state_sh.opt, plan, mesh, stacked=stacked)
    state_specs = DSGDState(pspecs, ospecs, P())
    state = with_sharding(mesh, state_sh, state_specs)

    bsp = batch_specs(cfg, plan, mesh,
                      {k: v.shape for k, v in batch.items()}, stacked=stacked)
    batch_abs = with_sharding(mesh, batch, bsp)

    rules = _sharding_rules(plan, mesh, "train")
    return BuiltStep(fn=_with_rules(step, rules), args=(state, batch_abs),
                     plan=plan, mode="train", meta={**meta, "rules": rules})


def _build_prefill(cfg, shape, mesh, *, tp_only: bool | None = None,
                   expert_parallel: bool = False) -> BuiltStep:
    plan = plan_for(cfg, mesh, mode="prefill", tp_only=tp_only,
                    expert_parallel=expert_parallel)
    B, S = shape.global_batch, shape.seq_len

    def fn(params, batch):
        return transformer.prefill(params, cfg, batch, cache_cap=S)

    params_sh = jax.eval_shape(
        lambda: transformer.init_params(jax.random.PRNGKey(0), cfg))
    pspecs = tree_param_specs(params_sh, plan, mesh)
    params = with_sharding(mesh, params_sh, pspecs)

    bshapes = _batch_shapes(cfg, B, S)
    bshapes.pop("labels")
    batch = _batch_structs(bshapes)
    bsp = batch_specs(cfg, plan, mesh, bshapes)
    batch_abs = with_sharding(mesh, batch, bsp)

    rules = _sharding_rules(plan, mesh, "prefill")
    return BuiltStep(fn=_with_rules(fn, rules), args=(params, batch_abs),
                     plan=plan, mode="prefill", meta={"batch": B, "seq": S,
                                                      "rules": rules})


def _build_decode(cfg, shape, mesh, *, tp_only: bool | None = None,
                  expert_parallel: bool = False) -> BuiltStep:
    plan = plan_for(cfg, mesh, mode="decode", tp_only=tp_only,
                    expert_parallel=expert_parallel)
    B, S = shape.global_batch, shape.seq_len
    long_ctx = shape.name == "long_500k"
    if long_ctx and cfg.sliding_window:
        cache_cap = cfg.sliding_window          # ring buffer = the window
    elif long_ctx and cfg.arch_type == "hybrid":
        cache_cap = 4096                        # zamba2 long-context SWA cache
    else:
        cache_cap = S
    scfg = ServeConfig(batch_size=B, cache_len=cache_cap, long_context=long_ctx)
    step = make_functional_serve_step(cfg, scfg, eos_id=-1)

    params_sh = jax.eval_shape(
        lambda: transformer.init_params(jax.random.PRNGKey(0), cfg))
    pspecs = tree_param_specs(params_sh, plan, mesh)
    params = with_sharding(mesh, params_sh, pspecs)

    caches_sh = jax.eval_shape(
        lambda: transformer.init_caches(cfg, B, cache_cap))
    cspecs = cache_specs(cfg, plan, mesh, caches_sh, B)
    caches = with_sharding(mesh, caches_sh, cspecs)

    sizes = axis_sizes(mesh)
    btotal = int(np.prod([sizes[a] for a in plan.batch_axes]))
    baxis = (plan.batch_axes if len(plan.batch_axes) > 1 else plan.batch_axes[0]) \
        if (plan.batch_axes and B % btotal == 0 and B >= btotal) else None
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32,
                               sharding=NamedSharding(mesh, P(baxis, None)))
    done = jax.ShapeDtypeStruct((B,), jnp.bool_,
                                sharding=NamedSharding(mesh, P(baxis)))
    rep = lambda shp, dt: jax.ShapeDtypeStruct(
        shp, dt, sharding=NamedSharding(mesh, P(*([None] * len(shp)))))
    state = DecodeState(tokens=tok, caches=caches,
                        pos=rep((), jnp.int32), rng=rep((2,), jnp.uint32),
                        done=done)
    rules = _sharding_rules(plan, mesh, "decode")
    return BuiltStep(fn=_with_rules(step, rules), args=(params, state),
                     plan=plan, mode="decode",
                     meta={"batch": B, "kv_len": S, "cache_cap": cache_cap,
                           "long_context": long_ctx, "rules": rules})
