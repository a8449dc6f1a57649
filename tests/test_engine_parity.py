"""Solver-engine parity: X-step backends, scan vs seed driver, batching,
and the dynamic-cardinality projections (DESIGN.md §2–§4)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import engine as E
from repro.core.admm import ADMMConfig, HeterogeneousADMM, HomogeneousADMM
from repro.core.anneal import greedy_degree_graph
from repro.core.constraints import node_level_constraints
from repro.core.graph import all_edges, edge_index
from repro.core.weights import metropolis_weights


def _warm(n, deg, seed=0):
    rng = np.random.default_rng(seed)
    edges = greedy_degree_graph(n, np.full(n, deg), rng)
    eidx = edge_index(n)
    m = len(all_edges(n))
    g0 = np.zeros(m)
    gm = metropolis_weights(n, edges)
    for k, e in enumerate(edges):
        g0[eidx[e]] = gm[k]
    return g0


def test_xstep_backend_parity():
    """schur_cg, kkt_bicgstab and kkt_bicgstab_ilu produce the same X-step
    solution (warm start; tol 1e-6 — measured agreement is ~1e-12)."""
    n, r = 6, 8
    g0 = _warm(n, 2)
    solver = HomogeneousADMM(n, r, ADMMConfig())
    st = solver.init_state(g0, 0.4)
    out_cg, _ = E.step(solver.spec, st, "schur_cg")
    out_kkt, _ = E.step(solver.spec, st, "kkt_bicgstab")
    out_ilu, _ = E.make_ilu_step(solver.spec)(st)
    for blk in range(4):  # x, S, y, T
        a = np.asarray(out_cg.X[blk])
        np.testing.assert_allclose(a, np.asarray(out_kkt.X[blk]), atol=1e-6)
        np.testing.assert_allclose(a, np.asarray(out_ilu.X[blk]), atol=1e-6)


def test_scan_driver_reproduces_seed_result():
    """The scan-compiled driver reproduces the seed per-iteration driver's
    ADMMResult (g, λ̃, support) on n=8, r=12. The python driver + unified
    step IS the seed solver (the step is bit-identical to the seed step
    bodies), so this pins the refactor against seed behaviour."""
    n, r = 8, 12
    g0 = _warm(n, 3)
    scan = HomogeneousADMM(n, r, ADMMConfig(max_iters=600)).solve(g0=g0, lam0=0.4)
    seed = HomogeneousADMM(n, r, ADMMConfig(max_iters=600, driver="python")).solve(
        g0=g0, lam0=0.4)
    assert scan.lam_tilde == pytest.approx(seed.lam_tilde, abs=1e-3)
    np.testing.assert_allclose(scan.g, seed.g, atol=1e-4)
    sup_scan = set(np.nonzero(scan.g > 1e-6)[0].tolist())
    sup_seed = set(np.nonzero(seed.g > 1e-6)[0].tolist())
    assert sup_scan == sup_seed
    # chunk-granular history: same logging cadence as the seed driver
    assert all(it % 10 == 0 for it, _, _ in scan.history)


def test_batched_solve_matches_single():
    """vmapped restarts return what per-restart solves return.

    Warm starts are tie-free (distinct random weights): with tied weights
    the nonconvex top-k projection makes trajectories sensitive to the
    last-bit float differences between the vmapped and single compilations
    (DESIGN.md §4), which is not what this test pins down.
    """
    n, r = 8, 12
    cfg = ADMMConfig(max_iters=100)
    solver = HomogeneousADMM(n, r, cfg)
    m = len(all_edges(n))
    rng = np.random.default_rng(1)
    g0s = 0.3 * rng.random((3, m))
    lam0s = np.array([0.3, 0.4, 0.5])
    batched = solver.solve_batched(g0s, lam0s)
    for b in range(3):
        single = solver.solve(g0=g0s[b], lam0=lam0s[b])
        np.testing.assert_allclose(batched[b].g, single.g, atol=1e-9)
        assert batched[b].lam_tilde == pytest.approx(single.lam_tilde, abs=1e-9)
        assert batched[b].iters == single.iters
        # history belongs to THIS restart (chunk axis, not batch axis)
        assert len(batched[b].history) == len(single.history)
        for (it_b, res_b, lam_b), (it_s, res_s, lam_s) in zip(
                batched[b].history, single.history):
            assert it_b == it_s
            assert res_b == pytest.approx(res_s, abs=1e-9)
            assert lam_b == pytest.approx(lam_s, abs=1e-9)


def test_batched_solve_hetero():
    n, r = 8, 12
    cs = node_level_constraints(n, np.full(n, 3), np.full(n, 9.76))
    solver = HeterogeneousADMM(n, r, np.asarray(cs.M, float),
                               np.asarray(cs.e_cap, float),
                               ADMMConfig(max_iters=80), equality=True)
    m = len(all_edges(n))
    rng = np.random.default_rng(3)
    base = np.stack([_warm(n, 3, seed=s) for s in range(2)])
    g0s = base + 1e-4 * rng.random((2, m)) * (base > 0)  # break weight ties
    z0s = (g0s > 0).astype(np.float64)
    lam0s = np.array([0.4, 0.4])
    batched = solver.solve_batched(g0s, z0s, lam0s)
    single = solver.solve(g0=g0s[1], z0=z0s[1], lam0=lam0s[1])
    np.testing.assert_allclose(batched[1].g, single.g, atol=1e-9)
    np.testing.assert_allclose(batched[1].z, single.z, atol=1e-12)
    assert all(int(res.z.sum()) == r for res in batched)


def test_sweep_over_budgets():
    """One vmapped call solves instances with different cardinality budgets
    (r is a data leaf, not a static top-k arg). The iteration is nonconvex,
    so the sweep and the single solve are compared once both trajectories
    have settled: λ̃ and the support's weights, not a mid-trajectory
    iterate (at 60 iterations the two still differ by ~4e-3 in λ̃; at 1500
    they agree to ~1e-11). At r=10 the two pick different edges of one
    tied pair (equal weight, equal λ̃), so the weights are compared as a
    sorted multiset rather than edge by edge."""
    n = 8
    cfg = ADMMConfig(max_iters=1500)
    g0 = _warm(n, 3)
    spec = E.make_homo_spec(n, 14, cfg)
    states = [E.init_state(spec, jnp.asarray(g0), 0.4) for _ in range(2)]
    batched = jax.tree.map(lambda *xs: jnp.stack(xs), *states)
    rs = [10, 14]
    outs = E.solve_sweep_spec(spec, np.asarray(rs), batched, cfg)
    for r, out in zip(rs, outs):
        assert int((out.g > 1e-8).sum()) <= r
        single = HomogeneousADMM(n, r, cfg).solve(g0=g0, lam0=0.4)
        assert out.lam_tilde == pytest.approx(single.lam_tilde, abs=1e-9)
        np.testing.assert_allclose(np.sort(out.g[out.g > 1e-8]),
                                   np.sort(single.g[single.g > 1e-8]),
                                   atol=1e-9)


def test_dynamic_r_projections_match_static():
    """The sort-based projections equal the seed's static top-k semantics,
    with r either a Python int or a traced scalar."""
    rng = np.random.default_rng(2)
    v = jnp.asarray(rng.normal(size=40))
    ok = jnp.asarray(rng.random(40) > 0.2)
    for r in (1, 5, 39, 40, 60):
        ref = np.asarray(E.proj_card_nonneg(v, r, ok))
        traced = np.asarray(jax.jit(E.proj_card_nonneg)(v, jnp.asarray(r), ok))
        np.testing.assert_allclose(ref, traced)
        # top-k semantics: kept entries are the largest admissible positives
        kept = np.nonzero(ref > 0)[0]
        assert len(kept) <= r
        vv = np.where(np.asarray(ok), np.maximum(np.asarray(v), 0.0), 0.0)
        top = set(np.argsort(-vv)[:min(r, 40)].tolist())
        assert set(kept.tolist()) <= top
    r_sel = 6
    z = np.asarray(jax.jit(E.proj_binary_topr)(v, jnp.asarray(r_sel), ok))
    assert int(z.sum()) == r_sel
    assert set(np.unique(z)) <= {0.0, 1.0}
