"""jit'd public wrapper: pad the boolean reach/adjacency matrices to the
kernel tiling, dispatch, slice the result back to logical shape."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref
from .kernel import LANE, SUBLANE, hop_step_2d


@functools.partial(jax.jit, static_argnames=("use_kernel", "interpret"))
def hop_step(reach, adj, *, use_kernel: bool = True,
             interpret: bool | None = None):
    """One matmul-BFS hop: ``new = reach ∨ (reach @ Adj)`` plus the total
    reached-pair count of ``new``.

    reach/adj: (n, n) bool. Returns ``(new_reach bool (n, n), count int32)``.
    The kernel path fuses the boolean matmul, the OR, and the count
    reduction into one pass per row band; zero padding is inert in all
    three (see kernel.py).
    """
    n = reach.shape[0]
    if not use_kernel or n < 2:
        return ref.hop_step(reach, adj)
    r_pad = -(-n // SUBLANE) * SUBLANE
    c_pad = -(-n // LANE) * LANE
    Rp = jnp.pad(reach.astype(jnp.float32), ((0, r_pad - n), (0, c_pad - n)))
    Ap = jnp.pad(adj.astype(jnp.float32), ((0, c_pad - n), (0, c_pad - n)))
    new, cnt = hop_step_2d(Rp, Ap, interpret=interpret)
    return new[:n, :n] > 0, jnp.sum(cnt[:n, 0]).astype(jnp.int32)
