"""Sharded ADMM execution layer (core.shard, DESIGN.md §13).

Two tiers:
  - partition-resolution and config-validation tests run in-process on the
    default single device (``resolve_partition`` takes an explicit device
    count, so the dispatch policy is testable without a mesh), plus a
    1-device ``shard_map`` parity check — the sharded math itself does not
    need more than one device to be exercised.
  - the multi-device parity suite runs in a subprocess with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (same pattern as
    test_sharded_runtime.py — the main pytest process must keep the default
    single device).
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.engine import ADMMConfig, init_state, make_homo_spec, solve_spec
from repro.core.shard import (
    EDGE_PARTITION_MIN_N, resolve_partition, solve_spec_sharded)


# ---------------------------------------------------------------------------
# partition="auto" dispatch policy (pure host logic, explicit ndev)
# ---------------------------------------------------------------------------

def test_resolve_partition_auto_policy():
    big = EDGE_PARTITION_MIN_N
    # single device: always the engine path
    assert resolve_partition("auto", big, None, ndev=1) == "none"
    assert resolve_partition("auto", big, 16, ndev=1) == "none"
    # batch fills the devices → instance parallelism wins (no collectives)
    assert resolve_partition("auto", big, 8, ndev=8) == "instances"
    assert resolve_partition("auto", 64, 8, ndev=8) == "instances"
    # large single instance → edge partitioning
    assert resolve_partition("auto", big, None, ndev=8) == "edges"
    assert resolve_partition("auto", big, 4, ndev=8) == "edges"
    # small single instance: collectives would dominate
    assert resolve_partition("auto", big - 1, None, ndev=8) == "none"


def test_resolve_partition_explicit_passthrough():
    # explicit modes pass through un-second-guessed
    assert resolve_partition("edges", 8, None, ndev=1) == "edges"
    assert resolve_partition("instances", 8, 2, ndev=1) == "instances"
    assert resolve_partition("none", 10_000, 64, ndev=8) == "none"
    with pytest.raises(ValueError, match="unknown partition"):
        resolve_partition("Edges", 64, None, ndev=8)


def test_admm_config_validates_partition():
    with pytest.raises(ValueError, match="unknown partition"):
        make_homo_spec(8, 10, ADMMConfig(partition="shard"))


def test_sharded_rejects_unsupported_solver():
    cfg = ADMMConfig(max_iters=10, solver="kkt_bicgstab")
    spec = make_homo_spec(8, 10, cfg)
    st = init_state(spec, jnp.zeros(spec.m), 0.5)
    with pytest.raises(ValueError, match="schur_cg"):
        solve_spec_sharded(spec, st, cfg, ndev=1)


# ---------------------------------------------------------------------------
# 1-device parity: the shard_map path must reproduce the engine exactly
# (no cross-device reassociation on a singleton mesh)
# ---------------------------------------------------------------------------

def test_sharded_solve_single_device_parity():
    cfg = ADMMConfig(max_iters=60, check_every=10)
    spec = make_homo_spec(12, 20, cfg)
    rng = np.random.default_rng(0)
    g0 = np.abs(rng.normal(size=spec.m)) * 0.1
    st = init_state(spec, jnp.asarray(g0), 0.5)
    ref = solve_spec(spec, st, cfg)
    sh = solve_spec_sharded(spec, st, cfg, ndev=1)
    np.testing.assert_allclose(sh.g, ref.g, atol=1e-12)
    assert abs(sh.lam_tilde - ref.lam_tilde) < 1e-10
    assert sh.iters == ref.iters
    np.testing.assert_allclose(sh.residual, ref.residual, rtol=1e-9)


# ---------------------------------------------------------------------------
# 8-device parity suite (subprocess; XLA_FLAGS must precede jax init)
# ---------------------------------------------------------------------------

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax
import jax.numpy as jnp

from repro.core.engine import (ADMMConfig, make_homo_spec, make_hetero_spec,
                               init_state, solve_spec, solve_batched_spec)
from repro.core.shard import (resolve_partition, solve_spec_sharded,
                              solve_batched_spec_sharded)

assert jax.device_count() == 8, jax.device_count()
assert resolve_partition("auto", 1024) == "edges"
rng = np.random.default_rng(0)

# --- homo, fp64 exact stack: drift is pure psum reassociation ------------
cfg = ADMMConfig(max_iters=60, check_every=10)
spec = make_homo_spec(24, 60, cfg)
g0 = np.abs(rng.normal(size=spec.m)) * 0.1
st = init_state(spec, jnp.asarray(g0), 0.5)
ref = solve_spec(spec, st, cfg)
sh = solve_spec_sharded(spec, st, cfg)
np.testing.assert_allclose(sh.g, ref.g, atol=1e-9)
assert abs(sh.lam_tilde - ref.lam_tilde) < 1e-9
print("HOMO_PARITY_OK", np.abs(sh.g - ref.g).max())

# --- homo, large-n stack: fp32 + inexact CG + jacobi + NS ----------------
# 60 iterates, piece by piece in fp32 and all together in fp64
fast = dict(max_iters=60, check_every=10, psd_backend="newton_schulz",
            psd_iters=16)
drift = []
for kw, atol in ((dict(dtype="float32", cg_inexact=True), 5e-4),
                 (dict(dtype="float32", precond="jacobi"), 5e-4),
                 (dict(cg_inexact=True, precond="jacobi"), 1e-9)):
    cfg2 = ADMMConfig(**fast, **kw)
    spec2 = make_homo_spec(24, 60, cfg2)
    st2 = init_state(spec2, jnp.asarray(g0), 0.5)
    ref2 = solve_spec(spec2, st2, cfg2)
    sh2 = solve_spec_sharded(spec2, st2, cfg2)
    np.testing.assert_allclose(sh2.g, ref2.g, atol=atol)
    drift.append(np.abs(sh2.g - ref2.g).max())
# All together in fp32, compared converged. This stack is multi-stable at
# rounding level: the engine alone, started 1e-7 (relative) away from g0,
# converges to one of several fixed points (λ̃ 0.4940 or 0.5048 here) or
# not at all within 3000 iterates. So the sharded solve must converge to a
# point the engine itself converges to from such a start: same λ̃, same
# support.
cfg5 = ADMMConfig(max_iters=3000, check_every=10, dtype="float32",
                  cg_inexact=True, precond="jacobi",
                  psd_backend="newton_schulz", psd_iters=16)
spec5 = make_homo_spec(24, 60, cfg5)
sh5 = solve_spec_sharded(spec5, init_state(spec5, jnp.asarray(g0), 0.5), cfg5)
assert sh5.residual < cfg5.eps, sh5.residual
support = lambda g: np.flatnonzero(np.asarray(g) > 1e-3)
engine = []
for k in range(8):
    gk = g0 * (1 + 1e-7 * np.random.default_rng(k).normal(size=g0.shape)) \
        if k else g0
    rk = solve_spec(spec5, init_state(spec5, jnp.asarray(gk), 0.5), cfg5)
    engine.append((float(rk.lam_tilde), float(rk.residual)))
    if (rk.residual < cfg5.eps and abs(rk.lam_tilde - sh5.lam_tilde) < 1e-5
            and np.array_equal(support(rk.g), support(sh5.g))):
        np.testing.assert_allclose(sh5.g, rk.g, atol=1e-4)
        break
else:
    raise AssertionError(f"sharded fp32 λ̃ {sh5.lam_tilde} is no converged "
                         f"engine point: {engine}")
print("FAST_STACK_PARITY_OK", drift, sh5.lam_tilde, engine)

# --- hetero, inequality capacities + jacobi ------------------------------
n = 16
m = n * (n - 1) // 2
M = rng.integers(0, 2, size=(5, m)).astype(np.float64)
e_cap = M.sum(axis=1) * 0.4
cfg3 = ADMMConfig(max_iters=60, check_every=10, precond="jacobi")
spec3 = make_hetero_spec(n, 30, M, e_cap, cfg3, equality=False)
g0h = np.abs(rng.normal(size=m)) * 0.1
st3 = init_state(spec3, jnp.asarray(g0h), 0.5)
ref3 = solve_spec(spec3, st3, cfg3)
sh3 = solve_spec_sharded(spec3, st3, cfg3)
np.testing.assert_allclose(sh3.g, ref3.g, atol=1e-8)
np.testing.assert_array_equal(sh3.z, ref3.z)  # binary top-r rank-exact
print("HETERO_PARITY_OK", np.abs(sh3.g - ref3.g).max())

# --- hetero, equality capacities (pinned s-block) ------------------------
cfg4 = ADMMConfig(max_iters=40, check_every=10)
spec4 = make_hetero_spec(n, 30, M, M @ (g0h > 0.05), cfg4, equality=True)
st4 = init_state(spec4, jnp.asarray(g0h), 0.5)
ref4 = solve_spec(spec4, st4, cfg4)
sh4 = solve_spec_sharded(spec4, st4, cfg4)
np.testing.assert_allclose(sh4.g, ref4.g, atol=1e-8)
print("HETERO_EQ_PARITY_OK", np.abs(sh4.g - ref4.g).max())

# --- instance partitioning: bit-exact (same compiled math, moved data) ---
B = 8
g0s = np.abs(rng.normal(size=(B, spec.m))) * 0.1
states = jax.vmap(lambda g, l: init_state(spec, g, l))(
    jnp.asarray(g0s), jnp.full(B, 0.5))
ref_b = solve_batched_spec(spec, states, cfg)
sh_b = solve_batched_spec_sharded(spec, states, cfg)
for a, b in zip(ref_b, sh_b):
    np.testing.assert_array_equal(a.g, b.g)
    assert a.iters == b.iters
print("INSTANCES_PARITY_OK")

# --- non-divisible batch: padding is added and dropped -------------------
B2 = 5
g0s2 = np.abs(rng.normal(size=(B2, spec.m))) * 0.1
states2 = jax.vmap(lambda g, l: init_state(spec, g, l))(
    jnp.asarray(g0s2), jnp.full(B2, 0.5))
ref_b2 = solve_batched_spec(spec, states2, cfg)
sh_b2 = solve_batched_spec_sharded(spec, states2, cfg)
assert len(sh_b2) == B2
for a, b in zip(ref_b2, sh_b2):
    np.testing.assert_array_equal(a.g, b.g)
print("INSTANCES_PAD_OK")
"""

MARKERS = ("HOMO_PARITY_OK", "FAST_STACK_PARITY_OK", "HETERO_PARITY_OK",
           "HETERO_EQ_PARITY_OK", "INSTANCES_PARITY_OK", "INSTANCES_PAD_OK")


@pytest.mark.slow
def test_sharded_admm_multi_device_parity():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert res.returncode == 0, res.stdout + "\n" + res.stderr
    for marker in MARKERS:
        assert marker in res.stdout, res.stdout + "\n" + res.stderr
