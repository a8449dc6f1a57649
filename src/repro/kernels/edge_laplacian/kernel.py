"""Pallas TPU kernels for the ADMM constraint-operator hot pair (§V-C).

Every ``A_op``/``AT_op`` matvec inside the X-step CG spends its time in two
index-shuffling primitives:

  - ``L(g)``: m = n(n−1)/2 edge weights scattered into an n×n Laplacian —
    the naive lowering is 4 scatter-adds (two off-diagonal, two diagonal),
    each a serialized HBM read-modify-write pass over the matrix.
  - ``⟨∂L/∂g_l, P⟩``: 4 gathers of m elements each from an n×n dual block.

The kernels fuse each group into ONE pass over the output:

  - ``edge_laplacian_2d`` exploits that the engine's candidate-edge list is
    the *complete* lexicographic list (all pairs i < j), so the packed edge
    index of entry (a, b) is analytic: l = lo·n − lo(lo+1)/2 + (hi−lo−1)
    with lo = min(a,b), hi = max(a,b). Each grid step materializes one
    (SUBLANE, n_pad) row-band of L directly from g — off-diagonals are a
    gather, the diagonal is the row-sum reduction of the same tile — so the
    Laplacian is written exactly once, with no read-modify-write.
  - ``edge_quadform_2d`` streams (SUBLANE, LANE) tiles of the packed edge
    index arrays (ei, ej) and gathers the 4 matrix entries per edge from a
    VMEM-resident P, writing the packed result once.

TPU adaptation notes (mirroring ``gossip_mix``):
  - tiles are VPU-aligned (last dim multiple of 128, sublane multiple of 8);
    wrappers in ``ops.py`` pad n and m up and slice the result back.
  - P / the L row-band stay whole in VMEM: n ≤ ~1500 keeps n² f32 within
    the ~16 MB budget, far above the paper's regime.
  - both kernels gather with a 2-D index tile from a 1-D vector (L) or
    from a VMEM-resident matrix (quadform). Mosaic refuses both ("Only 2D
    gather is supported"; the quadform gather fails its lowering asserts),
    so neither compiles for the TPU: with ``interpret`` resolved to False
    they raise ``kernels.mode.TPUUnsupportedError`` instead of running the
    interpreter or the pure-JAX path without a word. They run in interpret
    mode off the TPU; ``ADMMConfig.edge_kernel=False`` (the default) keeps
    the engine on the XLA gather/scatter path everywhere.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..mode import interpret_only, pallas_call

LANE = 128     # last-dim tile (multiple of 128)
SUBLANE = 8    # second-to-last dim tile

_NO_TPU = ("Mosaic refuses its in-kernel dynamic gather; run the ADMM with "
           "ADMMConfig(edge_kernel=False) on the TPU")


def _edge_laplacian_kernel(n, g_ref, out_ref):
    """g: (m_pad,); out: one (SUBLANE, n_pad) row-band of L."""
    band = pl.program_id(0)
    cols = out_ref.shape[1]
    a = band * SUBLANE + jax.lax.broadcasted_iota(jnp.int32, (SUBLANE, cols), 0)
    b = jax.lax.broadcasted_iota(jnp.int32, (SUBLANE, cols), 1)
    lo = jnp.minimum(a, b)
    hi = jnp.maximum(a, b)
    l = lo * n - (lo * (lo + 1)) // 2 + (hi - lo - 1)
    valid = (a < n) & (b < n) & (a != b)
    g = g_ref[...]
    G = jnp.where(valid, g[jnp.where(valid, l, 0)], jnp.zeros((), g.dtype))
    deg = jnp.sum(G, axis=1, keepdims=True)  # row degree: Σ_b g_{ab}
    out_ref[...] = jnp.where(a == b, deg, jnp.zeros((), g.dtype)) - G


@functools.partial(jax.jit, static_argnames=("n", "interpret"))
def edge_laplacian_2d(g, n: int, *, interpret: bool | None = None):
    """g: (m_pad,) packed complete-graph edge weights; returns L (r_pad, c_pad)
    with r_pad = ceil(n/SUBLANE)·SUBLANE, c_pad = ceil(n/LANE)·LANE."""
    r_pad = -(-n // SUBLANE) * SUBLANE
    c_pad = -(-n // LANE) * LANE
    m_pad = g.shape[0]
    interpret = interpret_only("edge_laplacian", interpret, _NO_TPU)
    return pallas_call(
        functools.partial(_edge_laplacian_kernel, n),
        grid=(r_pad // SUBLANE,),
        in_specs=[pl.BlockSpec((m_pad,), lambda i: (0,))],
        out_specs=pl.BlockSpec((SUBLANE, c_pad), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r_pad, c_pad), g.dtype),
        interpret=interpret,
    )(g)


def _edge_quadform_kernel(P_ref, ei_ref, ej_ref, out_ref):
    """P: (n_pad, n_pad); ei/ej/out: (SUBLANE, LANE) packed edge tiles."""
    P = P_ref[...]
    ii = ei_ref[...]
    jj = ej_ref[...]
    out_ref[...] = P[ii, ii] + P[jj, jj] - P[ii, jj] - P[jj, ii]


@functools.partial(jax.jit, static_argnames=("interpret",))
def edge_quadform_2d(P, ei, ej, *, interpret: bool | None = None):
    """P: (n_pad, n_pad); ei/ej: (R, LANE) int32 edge endpoints (R % SUBLANE
    == 0, padding entries 0 — they read P[0,0] terms that cancel to 0)."""
    R, L = ei.shape
    assert L == LANE and R % SUBLANE == 0, (R, L)
    nr, nc = P.shape
    interpret = interpret_only("edge_quadform", interpret, _NO_TPU)
    return pallas_call(
        _edge_quadform_kernel,
        grid=(R // SUBLANE,),
        in_specs=[
            pl.BlockSpec((nr, nc), lambda i: (0, 0)),
            pl.BlockSpec((SUBLANE, LANE), lambda i: (i, 0)),
            pl.BlockSpec((SUBLANE, LANE), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((SUBLANE, LANE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, LANE), P.dtype),
        interpret=interpret,
    )(P, ei, ej)
