"""Training driver: a closed loop of gossip training rounds through the
program, each round waiting for the one before.

Layouts (the traffic mix's ``layout``):

- ``stacked``: every worker on one chip, the window drives
  ``ElasticRuntime.round`` (fault-free ``ElasticSpec``, re-optimisation on,
  no drift), which reads the round's loss on the host;
- ``sharded``: one worker per chip, the window drives the jitted
  ``make_elastic_sharded_train_step`` (ppermute gossip over the same
  topology) and reads the round's loss on the host.

Both train on the BA topology the program solves for the worker count
(``launch.steps.topology_for``). Weights come from the seed, made in one
jitted call in the type they train in; batches come from a seeded pool made
in set-up (``bench/tokens.py``); the program receives only the tokens.

Set-up drives the one built step through its first ``check_rounds`` rounds,
on the pool's first batches, and keeps what the check needs: each round's
loss, the first gradient's norms per leaf and worker (read from AdamW's first
moment after one round), and the norms of each leaf's change after the last
check round. The window then continues from that same state. Once the window
has closed and the state is freed, the float32 reference
(``references/llama_f32.py``) follows the same rounds and the gaps are the
numbers compared.

End-to-end: ``tokens_per_s``, every token every worker trained on in the
window over the window; ``round_p90_ms``, the 90th percentile of the
window's round times (feed and host work included).
"""
from __future__ import annotations

import gc
import time

import numpy as np

#: leaves whose first gradient in the reference is under this share of the
#: median leaf's move by round-off alone and are left out of the change
EXCLUDE_GRAD_SHARE = 1e-3


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for a Llama-style configuration."""
    from repro.configs.base import ModelConfig

    if cfg.get("architectures") != ["LlamaForCausalLM"] \
            or cfg.get("hidden_act") != "silu":
        raise ValueError("the train driver runs Llama-style configurations")
    return ModelConfig(
        name="bench", arch_type="dense",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        head_dim=cfg.get("head_dim", 0),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        norm_eps=float(cfg["rms_norm_eps"]), rope_theta=float(cfg["rope_theta"]),
        dtype=cfg["dtype"])


def norm_gap(prog: np.ndarray, ref: np.ndarray,
             exclude: np.ndarray | None = None) -> float:
    """Worst leaf's gap between two norms, over the reference's norm of that
    leaf or of the median leaf (per worker), whichever is larger.
    ``prog``/``ref``: (leaves, workers)."""
    med = np.median(ref, axis=0, keepdims=True)
    gap = np.abs(prog - ref) / np.maximum(np.maximum(ref, med), 1e-30)
    if exclude is not None:
        gap = np.where(exclude, 0.0, gap)
    return float(np.max(gap))


class _Stacked:
    """Every worker on one chip, through ``ElasticRuntime``."""

    def __init__(self, mc, topo, opt_update, n):
        from repro.dsgd.chaos import no_chaos
        from repro.dsgd.elastic import ElasticRuntime, ElasticSpec

        spec = ElasticSpec(chaos=no_chaos(1, n), reopt=True)
        self.runtime = ElasticRuntime(mc, spec, topo, opt_update)
        self.es = self.runtime.make_state(topo, seed=0)
        self.sharding = self.replicated = None

    def round(self, state, batch):
        state, m, rep = self.runtime.round(state, self.es, batch)
        loss = float(m["loss"])
        bad = (not np.isfinite(loss) or rep.attempts > 1
               or any(r.rung == "freeze" for r in rep.rungs))
        return state, loss, bad


class _Sharded:
    """One worker per chip, through the jitted sharded elastic step."""

    def __init__(self, mc, topo, opt_update, n, devices):
        import jax
        import jax.numpy as jnp
        from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

        from repro.dsgd import (make_elastic_sharded_train_step,
                                schedule_from_topology, schedule_weight_arrays)

        self.mesh = Mesh(np.asarray(devices[:n]), ("data",),
                         axis_types=(AxisType.Auto,))
        self.sharding = NamedSharding(self.mesh, P("data"))
        # the masks and schedule weights live on every chip once, as a
        # runtime keeps them, so that no round copies them from chip 0
        self.replicated = NamedSharding(self.mesh, P())
        sched = schedule_from_topology(topo)
        self.w = tuple(jax.device_put(a, self.replicated)
                       for a in schedule_weight_arrays(sched))
        self.ones = jax.device_put(np.ones((n,), np.float32), self.replicated)
        self.step = jax.jit(make_elastic_sharded_train_step(
            mc, sched, opt_update, self.mesh))

    def round(self, state, batch):
        import jax

        with jax.set_mesh(self.mesh):
            state, m = self.step(state, batch, self.ones, self.ones, *self.w)
        loss = float(m["loss"])
        return state, loss, not np.isfinite(loss)


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.dsgd import DSGDState
    from repro.launch.steps import topology_for
    from repro.optim import make_optimizer

    from bench import tokens
    from bench.harness import memory_peak_bytes

    cfg, tr, ref = ctx.config, ctx.traffic, ctx.reference
    n, b, S = int(tr["workers"]), int(tr["batch_per_worker"]), int(tr["seq_len"])
    checks_n, pool_n = int(tr["check_rounds"]), int(tr["pool_batches"])
    if len(ctx.devices) < (n if tr["layout"] == "sharded" else 1):
        raise ValueError(f"{tr['layout']} layout of {n} workers needs "
                         f"{n} chips")
    mc = model_config(cfg)
    opt = cfg["optimizer"]
    opt_init, opt_update = make_optimizer(
        opt["name"], float(opt["lr"]), b1=float(opt["b1"]), b2=float(opt["b2"]),
        eps=float(opt["eps"]), weight_decay=float(opt["weight_decay"]))

    topo = topology_for(n, kind=tr["topology"]["kind"], r=tr["topology"]["r"],
                        seed=0)
    loop = (_Sharded(mc, topo, opt_update, n, ctx.devices)
            if tr["layout"] == "sharded" else _Stacked(mc, topo, opt_update, n))

    toks, labels = tokens.token_pool(ctx.seed, cfg["vocab_size"],
                                     (pool_n, n, b), S)
    put = ((lambda x: jax.device_put(x, loop.sharding)) if loop.sharding
           else jnp.asarray)
    pool = [{"tokens": put(toks[k]), "labels": put(labels[k])}
            for k in range(pool_n)]

    def weights():
        return ref.init_stacked(ctx.seed, cfg, n, dtype=jnp.dtype(cfg["dtype"]),
                                sharding=loop.sharding)

    params0 = weights()
    opt_state = jax.jit(jax.vmap(opt_init), out_shardings=loop.sharding)(params0)
    step0 = jnp.zeros((), jnp.int32)
    if loop.replicated is not None:
        step0 = jax.device_put(step0, loop.replicated)
    state = DSGDState(params0, opt_state, step0)
    del params0, opt_state
    norms = jax.jit(jax.vmap(ref.leaf_norms, out_axes=1))
    change_norms = jax.jit(jax.vmap(
        lambda a, c: ref.leaf_norms(jax.tree.map(
            lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, c)),
        out_axes=1))

    # the first rounds: the window's own call and feed, checked afterwards
    prog_loss = []
    for k in range(checks_n):
        state, loss, _ = loop.round(state, pool[k])
        prog_loss.append(loss)
        if k == 0:
            prog_grad = np.asarray(norms(state.opt.mu)) / (1.0 - float(opt["b1"]))
    prog_change = np.asarray(change_norms(state.params, weights()))

    rounds, failed = [], 0

    def one(state, k):
        t0 = time.perf_counter()
        with ctx.span("bench.round"):
            with ctx.span("bench.feed"):
                batch = pool[k % pool_n]
            state, _, bad = loop.round(state, batch)
        rounds.append((t0, time.perf_counter()))
        return state, int(bad)

    k = checks_n
    t_window = time.perf_counter()
    setup_s = t_window - ctx.t_start
    if ctx.trace:
        with ctx.profile():
            for _ in range(int(tr["trace_rounds"])):
                state, bad = one(state, k)
                failed += bad
                k += 1
    else:
        while True:
            state, bad = one(state, k)
            failed += bad
            k += 1
            if rounds[-1][1] - t_window >= ctx.seconds:
                break
    peak = memory_peak_bytes(ctx.devices)
    del state, loop, pool
    gc.collect()

    # the reference follows the check rounds, in float32
    W = ref.mixing_matrix(n, topo.edges, topo.g)
    p0 = ref.init_stacked(ctx.seed, cfg, n, dtype=jnp.dtype(cfg["dtype"]))
    batches = [(toks[t], labels[t]) for t in range(checks_n)]
    out = ref.train_rounds(p0, batches, W, cfg, checks_n)
    exclude = out["grad_norm"] < EXCLUDE_GRAD_SHARE * np.median(
        out["grad_norm"], axis=0, keepdims=True)
    checks = [
        ("loss_gap", max(abs(a - r) / abs(r)
                         for a, r in zip(prog_loss, out["loss"]))),
        ("grad_gap", norm_gap(prog_grad, out["grad_norm"])),
        ("change_gap", norm_gap(prog_change, out["change_norm"], exclude)),
    ]

    e2e = {}
    if not ctx.trace:
        span = rounds[-1][1] - rounds[0][0]
        dts = np.array([e - s for s, e in rounds])
        e2e["tokens_per_s"] = len(rounds) * n * b * S / span
        e2e["round_p90_ms"] = float(np.percentile(dts, 90) * 1e3)
    return {"setup_s": setup_s, "e2e": e2e, "unit": "bench.round",
            "attempted": len(rounds), "failed": failed,
            "memory_peak_bytes": peak, "checks": checks}
