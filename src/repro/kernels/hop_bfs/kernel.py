"""Pallas TPU kernel for the matmul-BFS hop of the SA warm start (§VI).

The device simulated-annealing loop evaluates every candidate 2-swap by
re-running an all-sources BFS on the proposed adjacency matrix: hop k
expands the boolean reach matrix by one step, and the ASPL accumulator
needs only *how many* (src, dst) pairs became reachable (`hop counts
summed on the fly`). The naive lowering is three passes over n²: the
matmul, the OR-combine, and the count reduction.

``hop_step_2d`` fuses them into ONE pass per (SUBLANE, n_pad) row band:

  - the band of ``reach @ Adj`` is one MXU matmul (f32 0/1 operands —
    exact, since row counts are ≤ n ≪ 2²⁴),
  - the OR with the incoming band and the threshold happen in-register,
  - the band's per-row reach counts are the row-sum reduction of the same
    tile, written to a (SUBLANE, LANE) count block (column 0 carries the
    value; the broadcast keeps the store lane-aligned).

TPU adaptation notes (mirroring ``edge_laplacian``/``gossip_mix``):
  - tiles are VPU/MXU-aligned (last dim multiple of 128, sublane multiple
    of 8); wrappers in ``ops.py`` pad n up and slice the result back.
    Padded rows/columns are all-zero, so they contribute nothing to the
    matmul, the OR, or the counts.
  - Adj stays whole in VMEM (n_pad² f32, double-buffered); the v5e
    compiler accepts it through n = 2048 (ahead-of-time compile tests),
    far above the paper's regime.
  - ``interpret=None`` compiles on the TPU and interprets elsewhere.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..mode import pallas_call

LANE = 128     # last-dim tile (multiple of 128)
SUBLANE = 8    # second-to-last dim tile


def _hop_step_kernel(reach_ref, adj_ref, out_ref, cnt_ref):
    """reach band: (SUBLANE, n_pad) f32 0/1; adj: (n_pad, n_pad) f32 0/1;
    out: (SUBLANE, n_pad) f32 0/1; cnt: (SUBLANE, LANE) f32 row counts."""
    R = reach_ref[...]
    A = adj_ref[...]
    prod = jnp.dot(R, A, preferred_element_type=jnp.float32)
    new = jnp.where(prod + R > 0, 1.0, 0.0).astype(R.dtype)
    out_ref[...] = new
    rows = jnp.sum(new, axis=1, keepdims=True)  # per-source reach count
    cnt_ref[...] = jnp.broadcast_to(rows, cnt_ref.shape)


@functools.partial(jax.jit, static_argnames=("interpret",))
def hop_step_2d(reach, adj, *, interpret: bool | None = None):
    """reach: (r_pad, c_pad) f32 0/1 with r_pad % SUBLANE == 0 and
    c_pad % LANE == 0; adj: (c_pad, c_pad) f32 0/1 (symmetric, zero
    padding). Returns ``(new_reach (r_pad, c_pad), counts (r_pad, LANE))``
    where ``counts[:, 0]`` is the per-source reach count."""
    r_pad, c_pad = reach.shape
    assert r_pad % SUBLANE == 0 and c_pad % LANE == 0, (r_pad, c_pad)
    assert adj.shape == (c_pad, c_pad), (adj.shape, c_pad)
    return pallas_call(
        _hop_step_kernel,
        grid=(r_pad // SUBLANE,),
        in_specs=[
            pl.BlockSpec((SUBLANE, c_pad), lambda i: (i, 0)),
            pl.BlockSpec((c_pad, c_pad), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((SUBLANE, c_pad), lambda i: (i, 0)),
            pl.BlockSpec((SUBLANE, LANE), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r_pad, c_pad), reach.dtype),
            jax.ShapeDtypeStruct((r_pad, LANE), reach.dtype),
        ],
        interpret=interpret,
    )(reach, adj)
