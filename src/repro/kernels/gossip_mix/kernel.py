"""Pallas TPU kernel for the gossip mixing hot spot.

The paper's parameter synchronization (Eq. 1) on each worker is
``x_i ← W_ii·x_i + Σ_{j∈N_i} W_ij·x_j``. After the ppermute schedule lands
the ``deg`` neighbor copies in HBM, the naive lowering is ``deg`` separate
HBM-round-trip axpys over the flattened parameter vector (~(deg+1)·2·|params|
bytes of HBM traffic). This kernel fuses the weighted accumulation into ONE
pass: each grid step streams a VMEM tile of the self vector plus the matching
tile of every neighbor buffer and writes the mixed tile once —
(deg+2)·|params| bytes total, the streaming minimum.

TPU adaptation notes (vs a GPU axpy chain):
  - the flattened parameter vector is reshaped to (R, 1024) by the ops
    wrapper; a grid step moves a (rows, 1024) tile. ``rows`` is the largest
    multiple of 16 (so bf16 tiles fill whole packed sublanes), at most 256,
    whose deg+2 double-buffered tiles fit a fixed VMEM budget — it shrinks
    as the degree and the element width grow (``block_rows``).
  - neighbors arrive stacked as (deg, R, 1024) so a single BlockSpec covers
    all neighbor tiles; ``deg`` is a compile-time constant of the topology,
    so the accumulation unrolls into VPU fmas.
  - the per-edge weights (rows of the BA-Topo W matrix) are scalars: the
    whole weight array sits in SMEM and each step reads its own row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..mode import pallas_call

LANE = 1024        # last-dim tile (multiple of 128)
SUBLANE = 16       # row granule: one packed bf16 sublane group
BLOCK_ROWS = 256   # rows per grid step for large leaves, at most
#: VMEM for one grid step's tiles — under the 16 MiB a TPU kernel may use
#: by default (v5e's scoped VMEM), leaving room for the compiler's own
VMEM_BUDGET = 12 * 2**20


def _step_bytes(rows: int, deg: int, itemsize: int) -> int:
    """VMEM one grid step holds: the self, out and deg neighbor tiles, each
    double-buffered, plus the f32 accumulator."""
    return rows * LANE * (2 * (deg + 2) * itemsize + 4)


def block_rows(deg: int, itemsize: int) -> int:
    """Rows per grid step for ``deg`` neighbors of ``itemsize``-byte
    elements: the largest multiple of SUBLANE, at most BLOCK_ROWS, whose
    tiles fit VMEM_BUDGET (SUBLANE when even that does not fit)."""
    rows = VMEM_BUDGET // _step_bytes(1, deg, itemsize)
    return max(SUBLANE, min(BLOCK_ROWS, rows // SUBLANE * SUBLANE))


def padded_rows(m: int, rows: int) -> int:
    """Row count R the ops wrappers pad a length-m vector to: a multiple of
    SUBLANE, and of ``rows`` (from :func:`block_rows`) once it exceeds one
    block."""
    R = -(-max(m, 1) // (LANE * SUBLANE)) * SUBLANE
    return R if R <= rows else -(-R // rows) * rows


def _tiling(R: int, deg: int, dtype) -> tuple[int, dict]:
    """Rows per grid step for an (…, R, LANE) operand, and the compiler
    parameters: the VMEM limit is raised only where the smallest tile of a
    very high degree still exceeds the budget."""
    itemsize = jnp.dtype(dtype).itemsize
    rows = min(R, block_rows(deg, itemsize))
    need = _step_bytes(rows, deg, itemsize)
    if need <= VMEM_BUDGET:
        return rows, {}
    return rows, {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=need + 4 * 2**20)}


_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def _gossip_mix_batched_kernel(w_ref, self_ref, nbrs_ref, out_ref):
    """w: (n, deg+1) in SMEM; self/out: (1, rows, LANE);
    nbrs: (1, deg, rows, LANE).

    One grid step = one worker's tile. The worker axis is a grid dimension,
    so the WHOLE stacked (n, ...) parameter tensor is mixed by a single
    ``pallas_call`` — n× fewer dispatches than the per-row path, and ``deg``
    is the topology's max degree (padded rows carry weight 0).
    """
    i = pl.program_id(0)
    deg = nbrs_ref.shape[1]
    acc = self_ref[0].astype(jnp.float32) * w_ref[i, 0]
    for d in range(deg):  # static max degree — unrolls to VPU fmas
        acc = acc + nbrs_ref[0, d].astype(jnp.float32) * w_ref[i, d + 1]
    out_ref[0] = acc.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gossip_mix_batched_2d(x, nbrs, weights, *, interpret: bool | None = None):
    """All-workers mix: x (n, R, LANE); nbrs (n, deg, R, LANE) — neighbor
    copies pre-gathered per worker; weights (n, deg+1), w[:, 0] = self.

    Grid is (n, R // rows): one dispatch covers every worker row."""
    n, R, L = x.shape
    deg = nbrs.shape[1]
    rows, params = _tiling(R, deg, x.dtype)
    assert L == LANE and R % SUBLANE == 0 and R % rows == 0, (n, R, L)
    return pallas_call(
        _gossip_mix_batched_kernel,
        grid=(n, R // rows),
        in_specs=[
            _SMEM,
            pl.BlockSpec((1, rows, LANE), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, deg, rows, LANE), lambda i, j: (i, 0, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, rows, LANE), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((n, R, L), x.dtype),
        interpret=interpret, **params,
    )(weights.astype(jnp.float32), x, nbrs)


def _gossip_mix_kernel(w_ref, self_ref, nbrs_ref, out_ref):
    """w: (deg+1,) in SMEM; self/out: (rows, LANE); nbrs: (deg, rows, LANE)."""
    deg = nbrs_ref.shape[0]
    acc = self_ref[...].astype(jnp.float32) * w_ref[0]
    for d in range(deg):  # static deg — unrolls to VPU fmas on the tile
        acc = acc + nbrs_ref[d].astype(jnp.float32) * w_ref[d + 1]
    out_ref[...] = acc.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gossip_mix_2d(x, nbrs, weights, *, interpret: bool | None = None):
    """x: (R, LANE); nbrs: (deg, R, LANE); weights: (deg+1,), w[0] = self."""
    R, L = x.shape
    deg = nbrs.shape[0]
    rows, params = _tiling(R, deg, x.dtype)
    assert L == LANE and R % SUBLANE == 0 and R % rows == 0, (R, L)
    return pallas_call(
        _gossip_mix_kernel,
        grid=(R // rows,),
        in_specs=[
            _SMEM,
            pl.BlockSpec((rows, LANE), lambda i: (i, 0)),
            pl.BlockSpec((deg, rows, LANE), lambda i: (0, i, 0)),
        ],
        out_specs=pl.BlockSpec((rows, LANE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, L), x.dtype),
        interpret=interpret, **params,
    )(weights.astype(jnp.float32), x, nbrs)
