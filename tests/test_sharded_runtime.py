"""Multi-device runtime invariants, exercised in a subprocess with 8 host
devices (the main pytest process must keep the default single device — the
brief forbids setting XLA_FLAGS globally)."""
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import make_baseline, optimize_topology, BATopoConfig
from repro.core.admm import ADMMConfig
from repro.core.graph import weight_matrix_from_weights
from repro.dsgd import schedule_from_topology
from repro.dsgd.gossip import gossip_shard, gossip_sim
from repro.roofline import collective_bytes_from_hlo
from repro.launch.mesh import make_mesh

mesh = make_mesh((4, 2), ("data", "model"))
n = 4

# --- 1. ppermute gossip == dense W matmul on a real multi-device mesh ------
topo = optimize_topology(n, 5, "homo",
                         cfg=BATopoConfig(sa_iters=100, admm=ADMMConfig(max_iters=30)))
sched = schedule_from_topology(topo)
W = weight_matrix_from_weights(n, topo.edges, topo.g)

x = jax.random.normal(jax.random.PRNGKey(0), (n, 6, 64))

def worker(xs):
    return gossip_shard(xs, sched, "data")

g = jax.shard_map(worker, mesh=mesh, in_specs=P("data"), out_specs=P("data"),
                  axis_names={"data"}, check_vma=False)
with jax.set_mesh(mesh):
    out = jax.jit(g)(x)
expect = gossip_sim(x, jnp.asarray(W, jnp.float32))
np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=1e-5)
print("GOSSIP_EQUIV_OK")

# --- 2. HLO parser trip-count correction vs unrolled ground truth ----------
def make(fn_len, unroll):
    def f(xs):
        def body(c, _):
            s = jax.lax.psum(c, "data")
            return jnp.tanh(s @ w0), None
        c, _ = jax.lax.scan(body, xs[0], None, length=fn_len, unroll=unroll)
        return c
    return f

w0 = jnp.ones((64, 64))
xs = jax.device_put(jnp.ones((4, 64, 64)),
                    NamedSharding(mesh, P("data", None, None)))
L = 6
with jax.set_mesh(mesh):
    txts = {}
    for tag, unroll in [("scan", 1), ("unrolled", L)]:
        g2 = jax.shard_map(make(L, unroll), mesh=mesh, in_specs=P("data"),
                           out_specs=P(None), axis_names={"data"},
                           check_vma=False)
        txts[tag] = jax.jit(g2).lower(xs).compile().as_text()
scan_bytes = collective_bytes_from_hlo(txts["scan"])["total"]
unrolled_bytes = collective_bytes_from_hlo(txts["unrolled"])["total"]
assert unrolled_bytes > 0
ratio = scan_bytes / unrolled_bytes
assert 0.8 < ratio < 1.25, (scan_bytes, unrolled_bytes)
print("PARSER_TRIPCOUNT_OK", scan_bytes, unrolled_bytes)

# --- 3. sharded DSGD train step lowers + matches the sim oracle ------------
from repro.configs import get_arch, reduced_for_smoke
from repro.dsgd import (init_dsgd_state, dsgd_train_step, make_sharded_train_step)
from repro.optim import sgd_momentum
from repro.data import DataConfig, synthetic_lm_batch

cfg = reduced_for_smoke(get_arch("qwen1.5-0.5b"))
opt_init, opt_update = sgd_momentum(0.05)
state = init_dsgd_state(jax.random.PRNGKey(0), cfg, n, opt_init)
dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=16, batch_size=2)
per = [synthetic_lm_batch(dc, 0, node=i) for i in range(n)]
batch = {k: jnp.stack([b[k] for b in per]) for k in per[0]}

sim_step = dsgd_train_step(cfg, topo, opt_update)
sharded_step = make_sharded_train_step(cfg, sched, opt_update, mesh,
                                       gossip_axes=("data",))
with jax.set_mesh(mesh):
    s_sharded, m_sharded = jax.jit(sharded_step)(state, batch)
s_sim, m_sim = sim_step(state, batch)
np.testing.assert_allclose(float(m_sharded["loss"]), float(m_sim["loss"]),
                           rtol=1e-4)
for a, b in zip(jax.tree.leaves(s_sharded.params), jax.tree.leaves(s_sim.params)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5)
print("SHARDED_STEP_OK")
"""


@pytest.mark.slow
def test_multi_device_runtime():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert res.returncode == 0, res.stdout + "\n" + res.stderr
    for marker in ("GOSSIP_EQUIV_OK", "PARSER_TRIPCOUNT_OK", "SHARDED_STEP_OK"):
        assert marker in res.stdout, res.stdout + "\n" + res.stderr
