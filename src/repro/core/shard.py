"""Multi-device sharded execution layer for the ADMM engine (DESIGN.md §13).

``core.engine`` solves one topology MI-SDP instance per device; this module
scales the same pure ``step(spec, state)`` math out over devices along two
orthogonal axes, selected by ``ADMMConfig.partition``:

  - ``"edges"``     — ONE instance, its edge-space leaves block-partitioned
    over a 1-D mesh axis. Each device owns a contiguous window of the packed
    edge vector (g, μ_g, and heterogeneous z/ν blocks plus the coupling
    multiplier v); the node-space (n, n) blocks (S, T, Laplacian, PSD
    projections) stay replicated. Per CG matvec the only cross-device
    collectives are one ``psum`` of the per-window additive Laplacian
    contribution (``kernels.edge_laplacian.edge_laplacian_window``), a
    ``psum`` of the capacity-row partials M z, and — heterogeneous only — a
    ``psum`` of the fp64 partial dot over the partitioned v-leaf. The
    quadform/degree pullbacks in Aᵀ are purely local gathers. Cardinality /
    binary projections run a distributed top-k (local ``top_k`` +
    ``all_gather`` of candidates); the Newton–Schulz PSD projection is
    row-partitioned, ``all_gather``-ing the iterate once per sign iteration.
  - ``"instances"`` — a batch of restarts / sweep elements laid out over the
    mesh (data parallelism): the engine's vmapped drivers are reused
    unchanged, with the state leaves ``device_put`` under a
    ``NamedSharding`` so the computation follows the data.
  - ``"auto"``      — resolved by :func:`resolve_partition` from
    (n, batch, device count); single-device environments resolve to
    ``"none"``, so the default pipeline is unchanged on one device.

Padding invariant (edge partitioning): the packed edge dimension m is padded
to a multiple of the device count. Padded slots carry ``edge_ok=False`` and
endpoint (0, 0); every projection zeroes them, Aᵀ masks its edge-space
output there (the degree pullback w_i + w_j is nonzero even at the (0, 0)
sentinel endpoints), and all other padded-slot values are zero-preserved by
induction — so padded slots contribute exactly 0 to every psum and the
sharded iterates match the single-device ones up to float reassociation of
the cross-device reductions (the parity tests bound the drift).
"""
from __future__ import annotations

from functools import lru_cache
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..kernels.edge_laplacian import ops as _el_ops
from ..launch.mesh import make_mesh
from . import engine
from .engine import (
    FP32_TOL_FLOOR, INEXACT_CAP, INEXACT_ETA,
    ADMMConfig, ADMMResult, ADMMState, ProblemSpec, proj_psd,
)

__all__ = [
    "EDGE_PARTITION_MIN_N", "resolve_partition",
    "solve_spec_sharded", "solve_batched_spec_sharded",
    "solve_sweep_spec_sharded",
]

_AXIS = "edges"
_INST_AXIS = "inst"

# ---------------------------------------------------------------------------
# Partition resolution ("auto" policy) — thresholds measured in
# benchmarks/bench_scalability.py, tables in DESIGN.md §13.
# ---------------------------------------------------------------------------

# Below this node count the per-matvec psum of the (n, n) Laplacian costs
# more than the O(m) edge work it parallelizes; instance parallelism (when a
# batch exists) or the single-device path wins.
EDGE_PARTITION_MIN_N = 512

_PARTITIONS = ("none", "edges", "instances", "auto")


def resolve_partition(partition: str, n: int, batch: int | None = None,
                      ndev: int | None = None) -> str:
    """Resolve ``ADMMConfig.partition`` to a concrete layout.

    ``auto`` prefers instance parallelism whenever the batch can fill the
    devices (restarts/sweep elements are embarrassingly parallel — no
    per-iteration collectives), falls back to edge partitioning for single
    large instances, and degenerates to the single-device path otherwise.
    """
    if partition not in _PARTITIONS:
        raise ValueError(f"unknown partition {partition!r}; expected one of "
                         f"{_PARTITIONS}")
    if partition != "auto":
        return partition
    ndev = jax.device_count() if ndev is None else ndev
    if ndev <= 1:
        return "none"
    if batch is not None and batch >= ndev:
        return "instances"
    if n >= EDGE_PARTITION_MIN_N:
        return "edges"
    return "none"


# ---------------------------------------------------------------------------
# Edge-partitioned solver
# ---------------------------------------------------------------------------

class SState(NamedTuple):
    """Sharded ADMM iterate. Same blocks as ``engine.ADMMState`` but with the
    x-vector split into its partitioned g-part and replicated λ̃ scalar:
    ``X = (g, λ̃, S, y, T[, z, ν, s])``; constraint multipliers
    ``lam = (P, Q, w[, u, v])`` with only the v-leaf partitioned."""

    X: tuple
    Y: tuple
    D: tuple
    lam: tuple
    res: jnp.ndarray
    cg: jnp.ndarray


def _pad1(a, size, fill=0):
    """Pad axis 0 of ``a`` to ``size`` with a constant."""
    pad = size - a.shape[0]
    if pad == 0:
        return a
    return jnp.concatenate(
        [a, jnp.full((pad,) + a.shape[1:], fill, dtype=a.dtype)])


def _state_specs(hetero: bool) -> SState:
    Pp, Pr = P(_AXIS), P()
    X = (Pp, Pr, Pr, Pr, Pr) + ((Pp, Pp, Pr) if hetero else ())
    lam = (Pr, Pr, Pr) + ((Pr, Pp) if hetero else ())
    return SState(X=X, Y=X, D=X, lam=lam, res=Pr, cg=Pr)


def _data_keys(hetero: bool, precond: str):
    ed = ["ei", "ej", "ok", "pmask"]
    rd = ["lidx", "B0", "I", "r", "rho"]
    if hetero:
        ed.append("mt")
        rd.append("e_cap")
    if precond == "jacobi":
        rd += ["jP", "jw"]
        if hetero:
            rd.append("ju")
            ed.append("dv")
    return tuple(ed), tuple(rd)


@lru_cache(maxsize=None)
def _edge_mesh(ndev: int):
    return make_mesh((ndev,), (_AXIS,))


@lru_cache(maxsize=None)
def _instance_mesh(ndev: int):
    return make_mesh((ndev,), (_INST_AXIS,))


@lru_cache(maxsize=None)
def _get_runner(meta: tuple):
    """Build (and cache) the jitted ``shard_map`` driver for one static
    problem shape. Every function below mirrors its ``engine`` counterpart;
    the parity tests in tests/test_admm_sharding.py hold the pair together."""
    (n, m, m_loc, q, hetero, equality, dtype, psd_backend, psd_iters,
     precond, cg_inexact, cg_tol, cg_maxiter, r_cap, max_iters, check_every,
     eps, abort_nonfinite, ndev) = meta
    dt = jnp.dtype(dtype)
    m_pad = ndev * m_loc
    rows_loc = -(-n // ndev)
    n_pad = ndev * rows_loc
    k_cap = max(1, min(m_loc, r_cap + 1))

    def run(ed, rd, st0):
        ei, ej, ok, pmask = ed["ei"], ed["ej"], ed["ok"], ed["pmask"]
        lidx, B0, I = rd["lidx"], rd["B0"], rd["I"]
        r, rho = rd["r"], rd["rho"]
        offset = lax.axis_index(_AXIS).astype(jnp.int32) * m_loc

        # ---- constraint operator (engine A_op/AT_op, window form) ---------
        def A_sh(X):
            g, lamt, S, y, T = X[:5]
            L = lax.psum(_el_ops.edge_laplacian_window(g, lidx, offset), _AXIS)
            base = (L - lamt * I + S, L + lamt * I + T, jnp.diag(L) + y)
            if not hetero:
                return base
            z, nu, s = X[5], X[6], X[7]
            r4 = lax.psum(z @ ed["mt"], _AXIS) + (0.0 if equality else s)
            r5 = g - z + nu
            return base + (r4, r5)

        def AT_sh(lamv):
            Pm, Q, w = lamv[:3]
            PQ = Pm + Q
            xg = (PQ[ei, ei] + PQ[ej, ej] - PQ[ei, ej] - PQ[ej, ei]
                  + w[ei] + w[ej])
            xl = -jnp.trace(Pm) + jnp.trace(Q)
            if not hetero:
                return (jnp.where(pmask, xg, 0.0), xl, Pm, w, Q)
            u, v = lamv[3], lamv[4]
            xg = jnp.where(pmask, xg + v, 0.0)
            z_adj = ed["mt"] @ u - v
            s_adj = u if not equality else jnp.zeros_like(u)
            return (xg, xl, Pm, w, Q, z_adj, v, s_adj)

        def b_sh():
            base = (-B0, 2.0 * I, jnp.ones(n, dtype=dt))
            if not hetero:
                return base
            return base + (rd["e_cap"], jnp.zeros(m_loc, dtype=dt))

        # ---- fp64 constraint-space dot: only the v-leaf is partitioned ----
        def cdot(a, b):
            parts = [jnp.sum(x.astype(jnp.float64) * y.astype(jnp.float64))
                     for x, y in zip(a, b)]
            tot = parts[0]
            for p_ in parts[1 : (4 if hetero else 3)]:
                tot = tot + p_
            if hetero:
                tot = tot + lax.psum(parts[4], _AXIS)
            return tot

        if precond == "jacobi":
            jd = (rd["jP"], rd["jP"], rd["jw"])
            if hetero:
                jd = jd + (rd["ju"], ed["dv"])
            Minv = lambda rr: jax.tree.map(lambda rl, dl: rl / dl, rr, jd)  # noqa: E731
        else:
            Minv = lambda rr: rr  # noqa: E731

        def axpy(alpha, x, y):
            return jax.tree.map(
                lambda xl_, yl: xl_ + alpha.astype(xl_.dtype) * yl, x, y)

        def pcg_sh(V, lam0, tol):
            """linalg.pcg_solve with sharded operator and psum'd dots."""
            def matvec(lamv):
                return A_sh(AT_sh(lamv))

            b = b_sh()
            rhs = jax.tree.map(lambda av, bb_: av - bb_, A_sh(V), b)
            bb = cdot(rhs, rhs)
            r0 = jax.tree.map(lambda rh, ax: rh - ax, rhs, matvec(lam0))
            z0 = Minv(r0)
            rz0 = cdot(r0, z0)
            rr0 = cdot(r0, r0)
            tol2bb = jnp.asarray(tol, jnp.float64) ** 2 * bb

            def cond(carry):
                _, _, _, _, rr, _, k = carry
                return (rr > tol2bb) & (k < cg_maxiter)

            def body(carry):
                x, rr_, z, p, _, rz, k = carry
                Ap = matvec(p)
                alpha = rz / cdot(p, Ap)
                x = axpy(alpha, x, p)
                rr_ = axpy(-alpha, rr_, Ap)
                z = Minv(rr_)
                rz_new = cdot(rr_, z)
                beta = rz_new / rz
                p = axpy(beta, z, p)
                return (x, rr_, z, p, cdot(rr_, rr_), rz_new, k + 1)

            init = (lam0, r0, z0, z0, rr0, rz0, jnp.asarray(0, jnp.int32))
            lamv, _, _, _, _, _, iters = lax.while_loop(cond, body, init)
            AtL = AT_sh(lamv)
            X = jax.tree.map(lambda v_, a_: v_ - a_, V, AtL)
            return tuple(X), tuple(lamv), iters

        # ---- projections (engine Eq. 24/25/30, distributed) ---------------
        def proj_card_sh(v_loc):
            v_loc = jnp.where(ok, jnp.maximum(v_loc, 0.0), 0.0)
            top = lax.top_k(v_loc, k_cap)[0]
            desc = -jnp.sort(-lax.all_gather(top, _AXIS).reshape(-1))
            idx = jnp.clip(jnp.minimum(r, m - 1), 0, desc.shape[0] - 1)
            thresh = jnp.where(r >= m, -1.0, desc[idx])
            keep = v_loc > jnp.maximum(thresh, 0.0)
            return jnp.where(keep, v_loc, 0.0)

        def proj_binary_sh(v_loc):
            vm = jnp.where(ok, v_loc + 0.0, -jnp.inf)
            allv = lax.all_gather(vm, _AXIS).reshape(-1)
            order = jnp.argsort(-allv)  # stable: global packed order is
            rank = (jnp.zeros(m_pad, dtype=jnp.int64)  # device-major
                    .at[order].set(jnp.arange(m_pad)))
            rank_loc = lax.dynamic_slice(rank, (offset,), (m_loc,))
            return (rank_loc < jnp.asarray(r)).astype(dt)

        def proj_psd_ns_sh(Mx, sign):
            """Row-partitioned Newton–Schulz sign iteration: device d owns
            rows [d·rows_loc, (d+1)·rows_loc) of the iterate; one
            all_gather per iteration rebuilds the full matrix the local
            row-block multiplies against. Same left-association
            (X_loc @ X) @ X as the engine's X @ X @ X."""
            Msym = (Mx + Mx.T) / 2.0
            nrm = jnp.sqrt(jnp.sum(Msym * Msym)) + jnp.asarray(1e-30, dt)
            Y0 = Msym / nrm
            Yp = jnp.pad(Y0, ((0, n_pad - n), (0, 0)))
            roff = lax.axis_index(_AXIS).astype(jnp.int32) * rows_loc
            Xl = lax.dynamic_slice(Yp, (roff, jnp.asarray(0, jnp.int32)),
                                   (rows_loc, n))

            def body(_, Xl_):
                Xf = lax.all_gather(Xl_, _AXIS).reshape(n_pad, n)[:n]
                return 1.5 * Xl_ - 0.5 * ((Xl_ @ Xf) @ Xf)

            Xl = lax.fori_loop(0, psd_iters, body, Xl)
            Xf = lax.all_gather(Xl, _AXIS).reshape(n_pad, n)[:n]
            absM = nrm * (Xf @ Y0)
            absM = (absM + absM.T) / 2.0
            return (Msym + absM) / 2.0 if sign > 0 else (Msym - absM) / 2.0

        psd = (proj_psd_ns_sh if psd_backend == "newton_schulz"
               else proj_psd)

        def project(U):
            g1 = proj_card_sh(U[0])
            lam1 = jnp.maximum(U[1], 0.0)
            S1 = psd(U[2], -1.0)
            y1 = jnp.maximum(U[3], 0.0)
            T1 = psd(U[4], +1.0)
            if not hetero:
                return (g1, lam1, S1, y1, T1)
            z1 = proj_binary_sh(U[5])
            nu1 = jnp.maximum(U[6], 0.0)
            s1 = (jnp.zeros_like(U[7]) if equality
                  else jnp.maximum(U[7], 0.0))
            return (g1, lam1, S1, y1, T1, z1, nu1, s1)

        def xstep_target(Y, D):
            V = tuple(jax.tree.map(lambda y1, d_: y1 - d_ / rho, Y, D))
            # c has a single −1 at the λ̃ slot (minimize −λ̃)
            V = (V[0], V[1] + 1.0 / rho) + V[2:]
            if hetero and equality:
                V = V[:7] + (jnp.zeros_like(V[7]),)
            return V

        def cg_tolerance(prev_res):
            floor = FP32_TOL_FLOOR if dt == jnp.float32 else 0.0
            tol0 = max(cg_tol, floor)
            if not cg_inexact:
                return tol0
            cap = max(INEXACT_CAP, tol0)
            return jnp.clip(INEXACT_ETA * jnp.sqrt(prev_res), tol0, cap)

        part_idx = {0, 5, 6} if hetero else {0}

        def step_sh(st: SState):
            U = tuple(jax.tree.map(lambda x, d_: x + d_ / rho, st.X, st.D))
            Y = project(U)
            V = xstep_target(Y, st.D)
            tol = cg_tolerance(st.res)
            Xn, lamc, cg_it = pcg_sh(V, st.lam, tol)
            if hetero and equality:
                Xn = Xn[:7] + (jnp.zeros_like(Xn[7]),)
            D = tuple(jax.tree.map(
                lambda d_, xn, y1: d_ + rho * (xn - y1), st.D, Xn, Y))
            res = jnp.asarray(0.0, jnp.float64)
            for i, (xn, y1) in enumerate(zip(Xn, Y)):
                ssq = jnp.sum((xn - y1).astype(jnp.float64) ** 2)
                if i in part_idx:
                    ssq = lax.psum(ssq, _AXIS)
                res = res + ssq
            return SState(X=Xn, Y=Y, D=D, lam=lamc, res=res,
                          cg=st.cg + cg_it), res

        # ---- chunked scan driver (engine._run_chunks) ----------------------
        n_chunks = -(-max_iters // check_every)
        last = max_iters - check_every * (n_chunks - 1)
        lengths = jnp.full(n_chunks, check_every, dtype=jnp.int64).at[-1].set(last)

        def chunk_fn(carry, clen):
            st, it, res, done = carry

            def one_chunk(operand):
                st_, _ = operand

                def body(_, val):
                    st2, _ = val
                    return step_sh(st2)

                return lax.fori_loop(0, clen, body, (st_, jnp.asarray(jnp.inf)))

            st2, res2 = lax.cond(done, lambda op: op, one_chunk, (st, res))
            it2 = jnp.where(done, it, it + clen)
            done2 = done | (res2 < eps)
            if abort_nonfinite:  # solver guard (engine._run_chunks parity)
                done2 = done2 | ~jnp.isfinite(res2)
            return (st2, it2, res2, done2), (it2, res2, st2.X[1])

        init = (st0, jnp.asarray(0, dtype=jnp.int64), jnp.asarray(jnp.inf),
                jnp.asarray(False))
        (st, it, res, _), hist = lax.scan(chunk_fn, init, lengths)
        return st, it, res, hist

    ed_keys, rd_keys = _data_keys(hetero, precond)
    sspec = _state_specs(hetero)
    mesh = _edge_mesh(ndev)
    f = jax.shard_map(
        run, mesh=mesh,
        in_specs=({k: P(_AXIS) for k in ed_keys}, {k: P() for k in rd_keys},
                  sspec),
        out_specs=(sspec, P(), P(), (P(), P(), P())),
        check_vma=False)
    return jax.jit(f)


def _split_state(spec: ProblemSpec, st: ADMMState, m_pad: int) -> SState:
    """engine.ADMMState → SState: split x into (g, λ̃), pad edge leaves."""
    m = spec.m

    def xsplit(t):
        x = t[0]
        base = (_pad1(x[:m], m_pad), x[m], t[1], t[2], t[3])
        if spec.hetero:
            base += (_pad1(t[4], m_pad), _pad1(t[5], m_pad), t[6])
        return base

    lam = tuple(st.lam[:3])
    if spec.hetero:
        lam += (st.lam[3], _pad1(st.lam[4], m_pad))
    return SState(X=xsplit(st.X), Y=xsplit(st.Y), D=xsplit(st.D),
                  lam=lam, res=st.res, cg=st.cg)


def _merge_state(spec: ProblemSpec, sst: SState) -> ADMMState:
    """SState → engine.ADMMState: rejoin x = [g; λ̃], drop padding."""
    m = spec.m

    def xjoin(t):
        x = jnp.concatenate([t[0][:m], jnp.reshape(t[1], (1,))])
        base = (x, t[2], t[3], t[4])
        if spec.hetero:
            base += (t[5][:m], t[6][:m], t[7])
        return base

    lam = tuple(sst.lam[:3])
    if spec.hetero:
        lam += (sst.lam[3], sst.lam[4][:m])
    return ADMMState(X=xjoin(sst.X), Y=xjoin(sst.Y), D=xjoin(sst.D),
                     lam=lam, res=sst.res, cg=sst.cg)


def _edge_repl_data(spec: ProblemSpec, m_pad: int):
    lidx = (spec.lidx if spec.lidx is not None
            else engine._packed_edge_index(spec.n))
    ed = {
        "ei": _pad1(spec.ei.astype(jnp.int32), m_pad),
        "ej": _pad1(spec.ej.astype(jnp.int32), m_pad),
        "ok": _pad1(spec.edge_ok, m_pad, False),
        "pmask": jnp.arange(m_pad) < spec.m,
    }
    rd = {"lidx": lidx, "B0": spec.B0, "I": spec.I,
          "r": spec.r, "rho": spec.rho}
    if spec.hetero:
        ed["mt"] = jnp.pad(spec.M.T, ((0, m_pad - spec.m), (0, 0)))
        rd["e_cap"] = spec.e_cap
    if spec.jd is not None:
        rd["jP"], rd["jw"] = spec.jd[0], spec.jd[2]
        if spec.hetero:
            rd["ju"] = spec.jd[3]
            # padded slots divide a zero residual — any nonzero diag works
            ed["dv"] = _pad1(spec.jd[4], m_pad, 1.0)
    return ed, rd


def solve_spec_sharded(spec: ProblemSpec, state0: ADMMState, cfg: ADMMConfig,
                       ndev: int | None = None,
                       r_cap: int | None = None) -> ADMMResult:
    """Edge-partitioned scan-compiled solve of ONE instance across devices.

    Drop-in for ``engine.solve_spec``; ``r_cap`` bounds the traced budget
    ``spec.r`` for the distributed top-k (defaults to the spec's own r —
    pass the sweep maximum when reusing the runner across budgets).
    """
    if cfg.solver != "schur_cg":
        raise ValueError("partition='edges' supports solver='schur_cg' only "
                         f"(got {cfg.solver!r})")
    if spec.edge_kernel:
        raise ValueError(
            "partition='edges' is incompatible with edge_kernel=True: the "
            "Pallas pair needs the complete edge list; the sharded path uses "
            "the windowed-gather form instead")
    ndev = jax.device_count() if ndev is None else ndev
    m = spec.m
    m_loc = -(-m // ndev)
    m_pad = ndev * m_loc
    r_cap = int(np.asarray(spec.r)) if r_cap is None else int(r_cap)
    max_iters, chunk = engine._chunk_plan(cfg)
    meta = (spec.n, m, m_loc, spec.q, spec.hetero, spec.equality, spec.dtype,
            spec.psd_backend, spec.psd_iters,
            "jacobi" if spec.jd is not None else "none",
            spec.cg_inexact, spec.cg_tol, spec.cg_maxiter, r_cap,
            max_iters, chunk, cfg.eps, cfg.abort_nonfinite, ndev)
    runner = _get_runner(meta)
    ed, rd = _edge_repl_data(spec, m_pad)
    sst, it, res, hist = runner(ed, rd, _split_state(spec, state0, m_pad))
    history = engine._history_list(*hist)
    if cfg.verbose:
        tag = "admm-het-sh" if spec.hetero else "admm-homo-sh"
        for it_, res_, lam_ in history:
            print(f"[{tag}] it={it_} res={res_:.3e} lam~={lam_:.4f}")
    return engine._result_from(spec, _merge_state(spec, sst), it, res, history)


# ---------------------------------------------------------------------------
# Instance-partitioned drivers (restarts / sweeps as data parallelism)
# ---------------------------------------------------------------------------

def _pad_batch(tree, B_pad: int):
    """Pad the leading batch axis by repeating element 0 (dropped on the way
    out) so the batch divides the device count."""

    def pad(leaf):
        reps = B_pad - leaf.shape[0]
        if reps == 0:
            return leaf
        fill = jnp.broadcast_to(leaf[:1], (reps,) + leaf.shape[1:])
        return jnp.concatenate([leaf, fill], axis=0)

    return jax.tree.map(pad, tree)


def _place_instances(tree, mesh):
    def put(leaf):
        spec = P(_INST_AXIS, *([None] * (leaf.ndim - 1)))
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    return jax.tree.map(put, tree)


def solve_batched_spec_sharded(spec: ProblemSpec, states: ADMMState,
                               cfg: ADMMConfig,
                               ndev: int | None = None) -> list[ADMMResult]:
    """``engine.solve_batched_spec`` with the restart batch laid out over the
    devices: leaves are placed under NamedSharding(P("inst", ...)) and the
    engine's vmapped driver follows the data — no per-iteration collectives,
    each device advances its slice of restarts independently."""
    ndev = jax.device_count() if ndev is None else ndev
    B = int(jax.tree.leaves(states)[0].shape[0])
    B_pad = -(-B // ndev) * ndev
    mesh = _instance_mesh(ndev)
    states_p = _place_instances(_pad_batch(states, B_pad), mesh)
    return engine.solve_batched_spec(spec, states_p, cfg)[:B]


def solve_sweep_spec_sharded(spec: ProblemSpec, rs, states: ADMMState,
                             cfg: ADMMConfig, rhos=None,
                             ndev: int | None = None) -> list[ADMMResult]:
    """``engine.solve_sweep_spec`` with sweep elements laid out over the
    devices (r and ρ are data leaves, so the padded elements re-solve
    element 0 and are dropped from the result list)."""
    ndev = jax.device_count() if ndev is None else ndev
    rs = jnp.asarray(rs, dtype=jnp.int64)
    B = int(rs.shape[0])
    B_pad = -(-B // ndev) * ndev
    mesh = _instance_mesh(ndev)
    rhos = (jnp.broadcast_to(spec.rho, rs.shape) if rhos is None
            else jnp.asarray(rhos, dtype=jnp.dtype(spec.dtype)))
    rs_p = _place_instances(_pad_batch(rs, B_pad), mesh)
    rhos_p = _place_instances(_pad_batch(rhos, B_pad), mesh)
    states_p = _place_instances(_pad_batch(states, B_pad), mesh)
    return engine.solve_sweep_spec(spec, rs_p, states_p, cfg, rhos=rhos_p)[:B]
