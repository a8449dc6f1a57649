"""Pallas TPU flash-decode kernel: single-token GQA attention over a KV cache.

serve_step's hot spot is one query token attending to a C-position cache
(decode_32k: C = 32768). The HBM-bound term is streaming K and V once; the
kernel tiles the cache into (BLOCK_K, hd) VMEM blocks and keeps the online-
softmax running (m, l, acc) state in VMEM scratch across the KV grid axis.

TPU adaptation notes (vs a CUDA flash-decode):
  - grid = (B, Hkv, C/BLOCK_K); the GQA query group (group = Hq/Hkv rows)
    rides along the sublane dim so the q·kᵀ product is an MXU
    (group × hd) · (hd × BLOCK_K) matmul per step — the systolic array
    replaces CUDA's per-warp reduction tree; no warp-shuffle analogue needed.
  - BLOCK_K = 512 keys per step (512·hd·2 tensors ≈ 0.5 MiB VMEM at
    hd=128/f32 — far inside the ~16 MiB budget, deep enough to amortize the
    HBM→VMEM DMA).
  - the validity mask (ring-buffer occupancy) streams as an int32 block;
    attention-score softcap (gemma2) is applied in-kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..mode import pallas_call

BLOCK_K = 512


def _decode_attn_kernel(q_ref, k_ref, v_ref, valid_ref, out_ref, m_ref, l_ref, acc_ref,
                        *, softcap: float, scale: float):
    """One (batch, kv-head, kv-block) grid step.

    q/out: (1, 1, group, hd); k/v: (1, 1, BLOCK_K, hd); valid: (1, BLOCK_K);
    scratch m/l: (group, 1), acc: (group, hd) — carried across grid axis 2.
    """
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                     # (g, hd)
    k = k_ref[0, 0].astype(jnp.float32)                     # (bk, hd)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale  # (g, bk)
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    s = jnp.where(valid_ref[0, :][None, :] > 0, s, -1e30)

    m_prev, l_prev, acc_prev = m_ref[...], l_ref[...], acc_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)                                  # (g, bk)
    corr = jnp.exp(m_prev - m_new)                          # (g, 1)
    l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    v = v_ref[0, 0].astype(jnp.float32)                     # (bk, hd)
    pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_new = acc_prev * corr + pv
    m_ref[...], l_ref[...], acc_ref[...] = m_new, l_new, acc_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        out_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("softcap", "interpret"))
def decode_attention_kernel(q, k, v, valid, *, softcap: float = 0.0,
                            interpret: bool | None = None):
    """q: (B, Hq, hd); k/v: (B, C, Hkv, hd); valid: (C,) bool/int32.

    Returns (B, Hq, hd). C must be a multiple of BLOCK_K (ops.py pads)."""
    B, Hq, hd = q.shape
    C, Hkv = k.shape[1], k.shape[2]
    group = Hq // Hkv
    assert C % BLOCK_K == 0, C
    qg = q.reshape(B, Hkv, group, hd)
    kt = k.transpose(0, 2, 1, 3)    # (B, Hkv, C, hd)
    vt = v.transpose(0, 2, 1, 3)
    valid2 = valid.astype(jnp.int32).reshape(1, C)
    scale = 1.0 / float(hd) ** 0.5

    out = pallas_call(
        functools.partial(_decode_attn_kernel, softcap=softcap, scale=scale),
        grid=(B, Hkv, C // BLOCK_K),
        in_specs=[
            pl.BlockSpec((1, 1, group, hd), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, BLOCK_K, hd), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, BLOCK_K, hd), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, BLOCK_K), lambda b, h, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, group, hd), lambda b, h, j: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, group, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qg, kt, vt, valid2)
    return out.reshape(B, Hq, hd)
