"""DSGD training driver.

Runs the full stack on whatever devices exist: reduced configs on CPU for
smoke-scale runs, production configs on a real mesh. The gossip topology is
BA-Topo by default — the paper's technique as a first-class launcher flag.

``--elastic`` wraps the loop in the elastic runtime (DESIGN.md §16):
chaos-spec faults (churn / packet loss / stragglers / bandwidth drift) hit
the REAL model's gossip loop, a heartbeat watchdog drops modeled stragglers
from rounds, a DriftDetector re-optimizes the topology mid-training, and
checkpoints carry the full elastic state so ``--resume`` after a SIGKILL
reproduces the uninterrupted loss curve bit-exactly. With no fault flags the
elastic path is bit-exact versus the plain trainer (tested).

  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m --reduced \
      --workers 8 --steps 50 --topo ba --r 16
  PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b --reduced \
      --workers 16 --topo exponential --sync allreduce
  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m --reduced \
      --workers 8 --steps 40 --elastic --churn-events 1 --drift-step 20 \
      --slow-nodes 2 --slow-bw 1.0 --ckpt-dir /tmp/ck --resume
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.configs import get_arch, reduced_for_smoke
from repro.core.bandwidth import (
    PaperConstants,
    homo_edge_bandwidth,
    min_edge_bandwidth,
    t_iter,
)
from repro.data import DataConfig, synthetic_lm_batch
from repro.dsgd import (
    DSGDState,
    ElasticRuntime,
    ElasticSpec,
    allreduce_train_step,
    drift_profile,
    dsgd_train_step,
    gossip_sim_tree,
    init_dsgd_state,
    make_chaos,
    no_chaos,
    random_churn_windows,
)
from repro.dsgd.dynamic import cycle_weight_matrices, round_robin_schedules
from repro.dsgd.trainer import _consensus_error, _loss_fn
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.steps import topology_for
from repro.optim import apply_updates, make_optimizer, warmup_cosine


def _build_chaos(args, n: int):
    """The run's ChaosSpec from the fault flags (all-defaults → fault-free)."""
    faulty = (args.churn_events > 0 or args.p_drop > 0
              or args.straggler_prob > 0 or args.drift_step >= 0)
    if not faulty:
        return no_chaos(args.steps, n, bandwidth=args.bw0)
    bw = np.full((args.steps, n), args.bw0, np.float64)
    if args.drift_step >= 0:
        bw = drift_profile(args.steps, n, args.drift_step, args.bw0,
                           args.slow_nodes, args.slow_bw)
    churn = random_churn_windows(n, args.steps, args.churn_events,
                                 seed=args.seed) if args.churn_events else []
    return make_chaos(args.steps, n, seed=args.seed, churn=churn,
                      p_drop=args.p_drop, straggler_prob=args.straggler_prob,
                      straggler_mult=args.straggler_mult, bandwidth=bw)


def _dynamic_step(cfg, topo, opt_update):
    """Beyond-paper ``--sync dynamic``: one matching per step (dsgd/dynamic)."""
    Ws = [jnp.asarray(W, jnp.float32)
          for W in cycle_weight_matrices(round_robin_schedules(topo))]
    loss_fn = _loss_fn(cfg)

    @jax.jit
    def _dyn_step(state, batch):
        losses, grads = jax.vmap(jax.value_and_grad(loss_fn))(state.params, batch)
        updates, opt = jax.vmap(opt_update)(grads, state.opt, state.params)
        params = jax.vmap(apply_updates)(state.params, updates)
        Wt = jax.lax.switch(state.step % len(Ws), [lambda W=W: W for W in Ws])
        params = gossip_sim_tree(params, Wt)
        return DSGDState(params, opt, state.step + 1), {
            "loss": losses.mean(), "loss_max": losses.max(),
            "consensus_err": _consensus_error(params)}

    return _dyn_step, len(Ws)


def main(argv: list[str] | None = None) -> dict:
    """Run the training loop; returns the run record (also written to
    ``--json-out``): config, topology, per-logged-step metrics with the
    step's wall time ``step_s``, and the elastic trail."""
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config of the same family (CPU-sized)")
    ap.add_argument("--workers", type=int, default=8)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=4, help="per-worker batch")
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--topo", default="ba",
                    choices=["ba", "ring", "exponential", "equistatic", "torus"])
    ap.add_argument("--r", type=int, default=None, help="edge budget (default 2n)")
    ap.add_argument("--node-bw", default=None,
                    help="comma-separated per-node GB/s — optimizes the BA "
                         "topology under the §VI-A2 node scenario")
    ap.add_argument("--sync", default="gossip",
                    choices=["gossip", "allreduce", "dynamic"])
    ap.add_argument("--optimizer", default="sgd", choices=["sgd", "adamw"])
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--use-kernel", action="store_true",
                    help="fused Pallas gossip_mix (compiled on the TPU, "
                         "interpreted elsewhere)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json-out", default=None)
    # ---- elastic runtime (DESIGN.md §16) --------------------------------
    ap.add_argument("--elastic", action="store_true",
                    help="elastic runtime: fault tensors + watchdog + "
                         "mid-training re-optimization")
    ap.add_argument("--churn-events", type=int, default=0)
    ap.add_argument("--p-drop", type=float, default=0.0)
    ap.add_argument("--straggler-prob", type=float, default=0.0)
    ap.add_argument("--straggler-mult", type=float, default=3.0)
    ap.add_argument("--drift-step", type=int, default=-1,
                    help="step at which the slow nodes' NICs collapse (−1 off)")
    ap.add_argument("--slow-nodes", type=int, default=2)
    ap.add_argument("--slow-bw", type=float, default=1.0)
    ap.add_argument("--bw0", type=float, default=PaperConstants().b_avail)
    ap.add_argument("--deadline-factor", type=float, default=3.0)
    ap.add_argument("--activation-lag", type=int, default=1)
    ap.add_argument("--no-reopt", action="store_true",
                    help="elastic without the DriftDetector→re-solve loop")
    ap.add_argument("--resume", action="store_true",
                    help="continue from the newest restorable checkpoint in "
                         "--ckpt-dir (crash-safe: bit-exact vs uninterrupted)")
    ap.add_argument("--kill-at-step", type=int, default=-1,
                    help="(testing) SIGKILL this process before running the "
                         "given step — simulates a crash mid-run")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduced_for_smoke(cfg)
    n = args.workers
    if args.elastic and args.sync != "gossip":
        ap.error("--elastic requires --sync gossip (the elastic runtime IS "
                 "the gossip loop)")
    if args.resume and not args.ckpt_dir:
        ap.error("--resume needs --ckpt-dir")

    lr = warmup_cosine(args.lr, max(args.steps // 20, 1), args.steps)
    opt_init, opt_update = make_optimizer(args.optimizer, lr)

    node_bw = ([float(v) for v in args.node_bw.split(",")]
               if args.node_bw else None)
    topo = topology_for(n, kind=args.topo, r=args.r, seed=args.seed,
                        node_bw=node_bw)

    runtime = es = None
    if args.elastic:
        chaos = _build_chaos(args, n)
        spec = ElasticSpec(chaos=chaos, deadline_factor=args.deadline_factor,
                           reopt=not args.no_reopt,
                           activation_lag_steps=args.activation_lag)
        runtime = ElasticRuntime(cfg, spec, topo, opt_update,
                                 use_kernel=args.use_kernel)
        es = runtime.make_state(topo, seed=args.seed)
        faults = "faultless" if chaos.faultless else "chaotic"
        sync_desc = f"elastic[{topo.name}] {faults} r_asym={topo.r_asym():.3f}"
        step = None
    elif args.sync == "allreduce":
        step = allreduce_train_step(cfg, n, opt_update)
        sync_desc = "allreduce"
    elif args.sync == "dynamic":
        step, rounds = _dynamic_step(cfg, topo, opt_update)
        sync_desc = f"dynamic[{topo.name}] rounds={rounds}"
    else:
        step = dsgd_train_step(cfg, topo, opt_update, use_kernel=args.use_kernel)
        sync_desc = f"gossip[{topo.name}] r_asym={topo.r_asym():.3f}"

    # paper's wall-clock model for this topology (Eq. 34/35)
    pc = PaperConstants()
    b_min = (min_edge_bandwidth(homo_edge_bandwidth(topo))
             if len(topo.edges) else pc.b_avail)
    iter_time = t_iter(b_min, pc) / 1e3  # s

    state = init_dsgd_state(jax.random.PRNGKey(args.seed), cfg, n, opt_init)
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                    batch_size=args.batch, seed=args.seed,
                    frontend_tokens=cfg.frontend_tokens, d_model=cfg.d_model)
    mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None

    start = 0
    if args.resume:
        restored, rstep, extras = mgr.restore(state, with_extra=True)
        if restored is not None:
            state, start = restored, int(rstep)
            if args.elastic and extras:
                es = runtime.from_extras(extras, name=topo.name)
            print(f"resumed from step {start} "
                  f"({'elastic state restored' if extras else 'pytree only'})")
        else:
            print("no restorable checkpoint found — starting fresh")

    def save(step_label: int) -> None:
        if mgr:
            mgr.save(state, step_label,
                     extra=runtime.to_extras(es) if args.elastic else None)

    print(f"arch={cfg.name} workers={n} sync={sync_desc} "
          f"modelled t_iter={iter_time * 1e3:.2f}ms (paper Eq. 34)")
    history = []
    elastic_log = []
    t0 = time.time()
    modeled_ms = 0.0
    for s in range(start, args.steps):
        t_step = time.perf_counter()
        if s == args.kill_at_step:
            os.kill(os.getpid(), signal.SIGKILL)     # crash, not cleanup
        data_step = es.data_step if args.elastic else s
        per = [synthetic_lm_batch(dc, data_step, node=i) for i in range(n)]
        batch = {k: jnp.stack([b[k] for b in per]) for k in per[0]}
        if args.elastic:
            state, metrics, rep = runtime.round(state, es, batch)
            modeled_ms += rep.round_ms
            if rep.dropped.any() or rep.swapped or rep.reopt is not None:
                elastic_log.append(
                    {"step": s, "dropped": int(rep.dropped.sum()),
                     "swapped": rep.swapped, "reopt": rep.reopt_reason,
                     "attempts": rep.attempts})
        else:
            state, metrics = step(state, batch)
            modeled_ms += iter_time * 1e3
        if s % args.log_every == 0 or s == args.steps - 1:
            m = {k: float(v) for k, v in metrics.items()}
            m.update(step=s, step_s=time.perf_counter() - t_step,
                     wall_s=round(time.time() - t0, 1),
                     modelled_time_s=round(modeled_ms / 1e3, 4))
            history.append(m)
            print("  " + json.dumps(m))
        if s and s % args.ckpt_every == 0:
            save(int(state.step))
    save(int(state.step) if args.steps > start else args.steps)
    out = {"config": vars(args), "topology": topo.name,
           "r_asym": topo.r_asym() if len(topo.edges) else None,
           "history": history}
    if args.elastic:
        out["elastic"] = {"events": es.events, "log": elastic_log,
                          "reopts": es.reopts, "adopted": es.adopted,
                          "drops": es.drops,
                          "final_topology": es.topology.name}
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(out, f, indent=1)
        print(f"wrote {args.json_out}")
    return out


if __name__ == "__main__":
    main()
