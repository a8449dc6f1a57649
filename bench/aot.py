"""Ahead-of-time compile of a training cell's step for a described TPU v5e,
with no chip attached: the planned device bytes of each candidate shape, and
the first that fits the memory rule.

    JAX_PLATFORMS=cpu python3 bench/aot.py --config smollm-135m --layout stacked
    JAX_PLATFORMS=cpu python3 bench/aot.py --config smollm-135m --layout sharded

Candidates are (workers, batch per worker, sequence length), largest first;
a step may plan at most ``MEM_FRACTION`` of the chip's memory. A compile is
not a chip run: it gives no time and no result, only what the compiler
accepts and plans.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MEM_FRACTION = 0.75
CANDIDATES = ((4, 1, 2048), (4, 2, 512), (4, 1, 512))


def planned_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return int(m.argument_size_in_bytes + m.output_size_in_bytes
               + m.temp_size_in_bytes - m.alias_size_in_bytes
               + m.generated_code_size_in_bytes)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--config", required=True)
    ap.add_argument("--layout", choices=("stacked", "sharded", "reference"),
                    default="stacked")
    args = ap.parse_args(argv)
    sys.path[0] = ROOT
    sys.path.insert(1, os.path.join(ROOT, "src"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                              SingleDeviceSharding)

    from repro.core.graph import Topology
    from repro.dsgd import (DSGDState, make_elastic_sharded_train_step,
                            schedule_from_topology)
    from repro.dsgd.elastic import make_elastic_train_step
    from repro.optim import make_optimizer

    from bench import loader
    from bench.drivers.train import model_config

    jax.config.update("jax_enable_compilation_cache", False)
    cfg = loader.config(args.config)
    ref = loader.reference(cfg["reference"])
    mc = model_config(cfg)
    opt = cfg["optimizer"]
    opt_init, opt_update = make_optimizer(
        opt["name"], float(opt["lr"]), b1=opt["b1"], b2=opt["b2"],
        eps=opt["eps"], weight_decay=opt["weight_decay"])
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    hbm = float(json.load(open(os.path.join(ROOT, "bench", "peaks.json")))
                ["TPU v5 lite"]["hbm_bytes"])
    dt = jnp.dtype(cfg["dtype"])
    f32 = jnp.float32

    def sds(tree, sharding):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
            tree)

    chosen = None
    for n, b, S in CANDIDATES:
        params = jax.eval_shape(
            lambda: jax.vmap(lambda k: ref._init_one(k, cfg, dt))(
                jax.random.split(jax.random.PRNGKey(0), n)))
        state = jax.eval_shape(lambda p: DSGDState(
            p, jax.vmap(opt_init)(p), jnp.zeros((), jnp.int32)), params)
        i32 = jax.ShapeDtypeStruct((n, b, S), jnp.int32)
        if args.layout == "reference":
            one = SingleDeviceSharding(topo.devices[0])
            p1 = sds(jax.tree.map(lambda x: jax.ShapeDtypeStruct(
                x.shape[1:], f32), params), one)
            tok = jax.ShapeDtypeStruct((b, S), jnp.int32, sharding=one)
            fn = jax.jit(jax.value_and_grad(
                lambda p, t, lab: ref.loss_fn(p, t, lab, cfg)))
            compiled = fn.lower(p1, tok, tok).compile()
        elif args.layout == "stacked":
            one = SingleDeviceSharding(topo.devices[0])
            f = lambda *s: jax.ShapeDtypeStruct(s, f32, sharding=one)  # noqa: E731
            batch = sds({"tokens": i32, "labels": i32}, one)
            compiled = make_elastic_train_step(mc, opt_update).lower(
                sds(state, one), batch, f(n, n), f(n), f(n, n), f(n)).compile()
        else:
            mesh = Mesh(np.asarray(topo.devices[:n]), ("data",),
                        axis_types=(AxisType.Auto,))
            shard = NamedSharding(mesh, P("data"))
            rep = NamedSharding(mesh, P())
            ring = Topology(n, [(i, (i + 1) % n) for i in range(n)],
                            np.full(n, 1.0 / 3.0))
            sched = schedule_from_topology(ring)
            step = jax.jit(make_elastic_sharded_train_step(mc, sched,
                                                           opt_update, mesh))
            st = DSGDState(sds(state.params, shard), sds(state.opt, shard),
                           jax.ShapeDtypeStruct((), jnp.int32, sharding=rep))
            batch = sds({"tokens": i32, "labels": i32}, shard)
            v = lambda *s: jax.ShapeDtypeStruct(s, f32, sharding=rep)  # noqa: E731
            with jax.set_mesh(mesh):
                compiled = step.lower(st, batch, v(n), v(n), v(n),
                                      v(sched.rounds, n)).compile()
        need = planned_bytes(compiled)
        fits = need <= MEM_FRACTION * hbm
        print(json.dumps({"layout": args.layout, "workers": n, "batch": b,
                          "seq_len": S, "planned_bytes": need,
                          "limit_bytes": MEM_FRACTION * hbm, "fits": fits,
                          "memory_analysis": str(compiled.memory_analysis())}),
              flush=True)
        if fits:
            chosen = (n, b, S)
            break
    print(json.dumps({"chosen": chosen}))
    return 0 if chosen else 1


if __name__ == "__main__":
    sys.exit(main())
