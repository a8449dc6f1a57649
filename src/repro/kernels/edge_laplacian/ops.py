"""jit'd public wrappers: pad the packed edge vector / the n×n block to the
kernel tiling, dispatch, slice the result back to logical shape."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref
from .kernel import LANE, SUBLANE, edge_laplacian_2d, edge_quadform_2d

_TILE = LANE * SUBLANE


def _pad_to(x, size):
    return jnp.pad(x, (0, size - x.shape[0]))


@functools.partial(jax.jit, static_argnames=("n", "use_kernel", "interpret"))
def edge_laplacian(g, ei, ej, n: int, *, use_kernel: bool = True,
                   interpret: bool | None = None):
    """Laplacian L(g) of the complete candidate-edge list.

    g: (m,) edge weights in ``all_edges(n)`` (lexicographic) order; ei/ej:
    (m,) edge endpoints — used by the oracle path (the kernel derives the
    packed index analytically, which *requires* the complete lexicographic
    edge list; the wrapper asserts m = n(n−1)/2).
    """
    m = g.shape[0]
    assert m == n * (n - 1) // 2, (
        f"edge_laplacian kernel needs the complete edge list: m={m}, n={n}")
    if not use_kernel or n < 2:
        return ref.edge_laplacian(g, ei, ej, n)
    m_pad = max(-(-m // LANE) * LANE, LANE)
    L = edge_laplacian_2d(_pad_to(g, m_pad), n, interpret=interpret)
    return L[:n, :n]


def edge_laplacian_window(g_loc, lidx, offset):
    """Per-device additive Laplacian contribution of one packed-edge window
    (see ``ref.edge_laplacian_window``). Pure gather — no Pallas variant:
    the 2-D kernel derives the packed index analytically, which requires
    the complete lexicographic edge list, while the window form is what the
    edge-partitioned ADMM (``core.shard``) runs per device before the
    cross-device ``psum``. Not jit-wrapped: it is always called inside an
    already-traced ``shard_map``/``jit`` region."""
    return ref.edge_laplacian_window(g_loc, lidx, offset)


@functools.partial(jax.jit, static_argnames=("use_kernel", "interpret"))
def edge_quadform(P, ei, ej, *, use_kernel: bool = True,
                  interpret: bool | None = None):
    """Per-edge quadratic forms ⟨∂L/∂g_l, P⟩ = P_ii + P_jj − P_ij − P_ji.

    P: (n, n); ei/ej: (m,) edge endpoints (any edge list — the gather is
    index-driven). Returns (m,) in edge order.
    """
    m = ei.shape[0]
    if not use_kernel or m == 0:
        return ref.edge_quadform(P, ei, ej)
    n = P.shape[0]
    r_pad = -(-n // SUBLANE) * SUBLANE
    c_pad = -(-n // LANE) * LANE
    Pp = jnp.pad(P, ((0, r_pad - n), (0, c_pad - n)))
    m_pad = max(-(-m // _TILE) * _TILE, _TILE)
    R = m_pad // LANE
    ei2 = _pad_to(ei.astype(jnp.int32), m_pad).reshape(R, LANE)
    ej2 = _pad_to(ej.astype(jnp.int32), m_pad).reshape(R, LANE)
    q = edge_quadform_2d(Pp, ei2, ej2, interpret=interpret)
    return q.reshape(-1)[:m]
