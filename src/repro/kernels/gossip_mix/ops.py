"""jit'd public wrapper: flatten arbitrary parameter shapes to the kernel's
(R, 1024) tiling, pad the tail, dispatch, restore shape."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref
from .kernel import (LANE, block_rows, gossip_mix_2d, gossip_mix_batched_2d,
                     padded_rows)


@functools.partial(jax.jit, static_argnames=("use_kernel", "interpret"))
def gossip_mix(x, nbrs, weights, *, use_kernel: bool = True,
               interpret: bool | None = None):
    """Mix one worker's parameter tensor with its neighbors' copies.

    x: (...,) any shape; nbrs: (deg, ...) stacked neighbor copies;
    weights: (deg+1,) with w[0] the self weight (a BA-Topo W row).
    """
    if not use_kernel:
        return ref.gossip_mix(x, nbrs, weights)
    shape = x.shape
    deg = nbrs.shape[0]
    flat = x.reshape(-1)
    n = flat.shape[0]
    R = padded_rows(n, block_rows(deg, x.dtype.itemsize))
    pad = R * LANE - n
    flat = jnp.pad(flat, (0, pad))
    nflat = jnp.pad(nbrs.reshape(deg, -1), ((0, 0), (0, pad)))
    out = gossip_mix_2d(flat.reshape(R, LANE), nflat.reshape(deg, R, LANE),
                        weights, interpret=interpret)
    return out.reshape(-1)[:n].reshape(shape)


@functools.partial(jax.jit, static_argnames=("use_kernel", "interpret"))
def gossip_mix_batched(x, nbr_idx, weights, *, use_kernel: bool = True,
                       interpret: bool | None = None):
    """Mix ALL workers' copies of one parameter tensor in a single dispatch.

    x: (n, ...) stacked worker copies; nbr_idx: (n, deg) int32 padded
    neighbor row indices (pad = own row); weights: (n, deg+1) with
    weights[:, 0] the self weight and 0.0 in padded slots (see
    ``repro.dsgd.gossip.padded_neighbors``).

    Neighbor tiles are pre-gathered by one XLA gather (x[nbr_idx]); the
    weighted accumulation then runs as ONE ``pallas_call`` whose grid spans
    (workers × row tiles) — versus n dispatches (one per worker row, each
    recompiled per neighbor count) for the per-row path. Trace-safe: no
    host reads of the weight matrix.
    """
    if not use_kernel:
        return ref.gossip_mix_batched(x, nbr_idx, weights)
    n = x.shape[0]
    shape = x.shape
    flat = x.reshape(n, -1)
    m = flat.shape[1]
    R = padded_rows(m, block_rows(nbr_idx.shape[1], x.dtype.itemsize))
    flat = jnp.pad(flat, ((0, 0), (0, R * LANE - m)))
    xr = flat.reshape(n, R, LANE)
    nbrs = xr[nbr_idx]                       # (n, deg, R, LANE), one gather
    out = gossip_mix_batched_2d(xr, nbrs, weights, interpret=interpret)
    return out.reshape(n, -1)[:, :m].reshape(shape)


def gossip_mix_tree(params, nbr_params, weights, *, use_kernel: bool = True,
                    interpret: bool | None = None):
    """Apply gossip_mix leaf-wise over a parameter pytree.

    params: pytree of arrays; nbr_params: same pytree with a leading (deg,)
    axis on every leaf; weights: (deg+1,).
    """
    return jax.tree.map(
        lambda x, nb: gossip_mix(x, nb, weights, use_kernel=use_kernel,
                                 interpret=interpret),
        params, nbr_params)
