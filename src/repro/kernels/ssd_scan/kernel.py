"""Pallas TPU kernel for the Mamba-2 SSD intra-chunk dual form.

The SSD chunked scan (arXiv:2405.21060 §6) splits the linear recurrence into
an intra-chunk *quadratic dual form* (this kernel — all MXU matmuls) and a
cheap inter-chunk state scan (left in jax.lax.scan). Per (batch, chunk, head)
grid cell, with chunk length Q, state N, head dim P:

  G    = C · Bᵀ                        (Q×N)·(N×Q)  MXU
  M    = G ⊙ exp(la_t − la_s) ⊙ dt_s   causal-masked decay
  y    = M · x                          (Q×Q)·(Q×P)  MXU
  st   = (B ⊙ exp(la_Q − la) ⊙ dt)ᵀ·x  (N×Q)·(Q×P)  MXU → outgoing state

TPU adaptation notes (vs the paper's Triton kernel):
  - one grid cell = one head's whole chunk; Q=256, N=128, P=64 keeps every
    operand MXU-shaped (≥128 on contracting dims where possible) and the
    VMEM working set at ~Q² + 2·Q·N + 2·Q·P floats ≈ 0.5 MiB.
  - the decay matrix is built in-VMEM from the la cumsum (computed once
    outside) — exp(la_t − la_s) ≤ 1 under causal masking since la is
    non-increasing, so no extra max-subtraction is needed.
  - B/C blocks are shared across heads (G=1 groups): the (b, c, :) BlockSpec
    re-streams them per head, trading a little DMA for zero layout shuffles.
  - not yet compiled for the TPU: the Pallas TPU lowering refuses the
    (1, 1, Q, 1, P) x block (second-minor block dim 1 against H heads), so
    off the interpreter the call raises ``TPUUnsupportedError``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..mode import interpret_only, pallas_call


def _ssd_intra_kernel(x_ref, dt_ref, la_ref, b_ref, c_ref, y_ref, st_ref):
    """x: (1,1,Q,1,P); dt/la: (1,1,Q,1); b/c: (1,1,Q,N);
    y: (1,1,Q,1,P); st: (1,1,1,P,N)."""
    x = x_ref[0, 0, :, 0, :].astype(jnp.float32)        # (Q, P)
    dt = dt_ref[0, 0, :, 0].astype(jnp.float32)         # (Q,)
    la = la_ref[0, 0, :, 0].astype(jnp.float32)         # (Q,)
    B = b_ref[0, 0].astype(jnp.float32)                 # (Q, N)
    C = c_ref[0, 0].astype(jnp.float32)                 # (Q, N)
    Q = x.shape[0]

    G = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)      # (Q, Q)
    decay = jnp.exp(la[:, None] - la[None, :])                       # (Q_t, Q_s)
    causal = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    M = jnp.where(causal, G * decay * dt[None, :], 0.0)
    y = jax.lax.dot_general(M, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)      # (Q, P)
    y_ref[0, 0, :, 0, :] = y.astype(y_ref.dtype)

    decay_out = jnp.exp(la[-1] - la) * dt                            # (Q,)
    Bw = B * decay_out[:, None]                                      # (Q, N)
    st = jax.lax.dot_general(x, Bw, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)     # (P, N)
    st_ref[0, 0, 0] = st.astype(st_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_intra_chunk_kernel(xc, dtc, la, Bc, Cc, *, interpret: bool | None = None):
    """xc: (B,nc,Q,H,P); dtc/la: (B,nc,Q,H); Bc/Cc: (B,nc,Q,N).

    Returns (y_intra (B,nc,Q,H,P) f32, chunk_states (B,nc,H,P,N) f32)."""
    Bsz, nc, Q, H, P = xc.shape
    N = Bc.shape[-1]
    grid = (Bsz * nc, H)
    xg = xc.reshape(Bsz * nc, Q, H, P)[:, None]          # (BC,1,Q,H,P)
    dtg = dtc.reshape(Bsz * nc, Q, H)[:, None]
    lag = la.reshape(Bsz * nc, Q, H)[:, None]
    Bg = Bc.reshape(Bsz * nc, Q, N)[:, None]
    Cg = Cc.reshape(Bsz * nc, Q, N)[:, None]

    y, st = pallas_call(
        _ssd_intra_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, Q, 1, P), lambda bc, h: (bc, 0, 0, h, 0)),
            pl.BlockSpec((1, 1, Q, 1), lambda bc, h: (bc, 0, 0, h)),
            pl.BlockSpec((1, 1, Q, 1), lambda bc, h: (bc, 0, 0, h)),
            pl.BlockSpec((1, 1, Q, N), lambda bc, h: (bc, 0, 0, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda bc, h: (bc, 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Q, 1, P), lambda bc, h: (bc, 0, 0, h, 0)),
            pl.BlockSpec((1, 1, 1, P, N), lambda bc, h: (bc, 0, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bsz * nc, 1, Q, H, P), jnp.float32),
            jax.ShapeDtypeStruct((Bsz * nc, 1, H, P, N), jnp.float32),
        ],
        interpret=interpret_only(
            "ssd_intra_chunk", interpret,
            "its (1, 1, Q, 1, P) blocks put a size-1 block on the head axis, "
            "which the TPU lowering refuses; use_kernel=False runs the "
            "pure-JAX dual form"),
    )(xg, dtg, lag, Bg, Cg)
    return (y.reshape(Bsz, nc, Q, H, P), st.reshape(Bsz, nc, H, P, N))
