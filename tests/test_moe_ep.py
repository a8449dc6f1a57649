"""Expert-parallel MoE (shard_map + all_to_all) vs the pjit oracle.
Runs in a multi-device subprocess (main pytest keeps 1 device)."""
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.models.moe import init_moe, moe_forward
from repro.models.moe_ep import moe_forward_expert_parallel
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 4), ("data", "model"))

for E, k, cf in [(8, 2, 8.0), (4, 1, 8.0), (16, 4, 8.0)]:
    d, F = 32, 64
    p = init_moe(jax.random.PRNGKey(E), d, F, E, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(E + 1), (4, 16, d))
    ref, aux_ref = moe_forward(p, x, top_k=k, capacity_factor=cf)
    with jax.set_mesh(mesh):
        xs = jax.device_put(x, NamedSharding(mesh, P("data")))
        out, aux = jax.jit(lambda p, x: moe_forward_expert_parallel(
            p, x, top_k=k, capacity_factor=cf))(p, xs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(float(aux), float(aux_ref), rtol=1e-4)
    print(f"EP_OK E={E} k={k}")

# gradients flow through the shard_map dispatch
p = init_moe(jax.random.PRNGKey(0), 32, 64, 8, jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 32))
def loss_ep(p, x):
    out, aux = moe_forward_expert_parallel(p, x, top_k=2, capacity_factor=8.0)
    return jnp.sum(out ** 2) + 0.01 * aux
def loss_ref(p, x):
    out, aux = moe_forward(p, x, top_k=2, capacity_factor=8.0)
    return jnp.sum(out ** 2) + 0.01 * aux
with jax.set_mesh(mesh):
    g_ep = jax.jit(jax.grad(loss_ep))(p, x)
g_ref = jax.grad(loss_ref)(p, x)
for a, b in zip(jax.tree.leaves(g_ep), jax.tree.leaves(g_ref)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-3, rtol=2e-3)
print("EP_GRAD_OK")
"""


@pytest.mark.slow
def test_expert_parallel_moe_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert res.returncode == 0, res.stdout + "\n" + res.stderr
    assert res.stdout.count("EP_OK") == 3 and "EP_GRAD_OK" in res.stdout


def test_supports_expert_parallel():
    from repro.models.moe_ep import supports_expert_parallel
    assert supports_expert_parallel(32, 16)      # granite
    assert not supports_expert_parallel(8, 16)   # mixtral: needs virtual experts
