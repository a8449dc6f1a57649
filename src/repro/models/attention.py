"""Grouped-query attention with full / sliding-window masks, optional score
soft-capping (Gemma-2) and QKV bias (Qwen1.5); prefill + single-token decode
paths with an explicit KV cache.

Shapes:
  x              (B, S, D)
  q              (B, S, Hq, hd)
  k, v           (B, S, Hkv, hd)
  cache k/v      (B, C, Hkv, hd)   C = cache capacity (full seq or window)
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .. import obs
from .common import apply_rope, dense_init, softcap

__all__ = ["AttnParams", "init_attn", "attend_full", "attend_chunked", "attn_forward",
           "attn_decode", "KVCache", "init_kv_cache"]


class KVCache(NamedTuple):
    k: jnp.ndarray  # (B, C, Hkv, hd)
    v: jnp.ndarray
    # ring-buffer write index is derived from absolute position for SWA caches


def init_kv_cache(batch: int, capacity: int, kv_heads: int, head_dim: int, dtype) -> KVCache:
    shape = (batch, capacity, kv_heads, head_dim)
    return KVCache(jnp.zeros(shape, dtype=dtype), jnp.zeros(shape, dtype=dtype))


def init_attn(key, cfg, dtype) -> dict:
    hd = cfg.resolved_head_dim
    kq, kk, kv, ko = jax.random.split(key, 4)
    p = {
        "wq": dense_init(kq, cfg.d_model, cfg.num_heads * hd, dtype),
        "wk": dense_init(kk, cfg.d_model, cfg.num_kv_heads * hd, dtype),
        "wv": dense_init(kv, cfg.d_model, cfg.num_kv_heads * hd, dtype),
        "wo": dense_init(ko, cfg.num_heads * hd, cfg.d_model, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.num_heads * hd,), dtype=dtype)
        p["bk"] = jnp.zeros((cfg.num_kv_heads * hd,), dtype=dtype)
        p["bv"] = jnp.zeros((cfg.num_kv_heads * hd,), dtype=dtype)
    return p


def _qkv(params, x, cfg):
    hd = cfg.resolved_head_dim
    B, S, _ = x.shape
    q = x @ params["wq"]
    k = x @ params["wk"]
    v = x @ params["wv"]
    if "bq" in params:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    q = q.reshape(B, S, cfg.num_heads, hd)
    k = k.reshape(B, S, cfg.num_kv_heads, hd)
    v = v.reshape(B, S, cfg.num_kv_heads, hd)
    return q, k, v


def attend_full(q, k, v, mask, attn_softcap: float = 0.0):
    """q: (B,Sq,Hq,hd); k,v: (B,Sk,Hkv,hd); mask: (B,1,Sq,Sk) or broadcastable.
    GQA: query heads grouped onto kv heads."""
    B, Sq, Hq, hd = q.shape
    Hkv = k.shape[2]
    group = Hq // Hkv
    q = q.reshape(B, Sq, Hkv, group, hd)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", q, k).astype(jnp.float32) / jnp.sqrt(hd).astype(jnp.float32)
    if attn_softcap:
        scores = softcap(scores, attn_softcap)
    scores = jnp.where(mask[:, :, None] if mask.ndim == 4 else mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
    return out.reshape(B, Sq, Hq, hd)


def _causal_mask(S: int, window, dtype=jnp.bool_):
    """Tracer-safe causal(+sliding-window) mask. ``window`` may be a traced
    int32 scalar (0 → full causal) so it can be a per-layer scan input."""
    i = jnp.arange(S)[:, None]
    j = jnp.arange(S)[None, :]
    m = j <= i
    w = jnp.asarray(window, jnp.int32)
    m = m & jnp.where(w > 0, j > i - w, True)
    return m[None, None]  # (1,1,S,S)


def attend_chunked(q, k, v, window, attn_softcap: float = 0.0, *, chunk: int = 1024,
                   causal: bool = True):
    """Flash-style online-softmax attention, lax.scan over KV chunks.

    Memory O(S·chunk) instead of O(S²) — the pure-JAX analogue of the Pallas
    flash kernel's tiling, and the oracle the kernel validates against.
    q: (B,S,Hq,hd); k,v: (B,S,Hkv,hd); window traced int32 (0 = full causal).
    """
    B, S, Hq, hd = q.shape
    Hkv = k.shape[2]
    group = Hq // Hkv
    C = min(chunk, S)
    while S % C:  # largest divisor of S ≤ chunk (VLM/audio odd lengths)
        C -= 1
    nc = S // C
    qf = q.reshape(B, S, Hkv, group, hd).astype(jnp.float32)
    kc = k.reshape(B, nc, C, Hkv, hd).astype(jnp.float32)
    vc = v.reshape(B, nc, C, Hkv, hd).astype(jnp.float32)
    w = jnp.asarray(window, jnp.int32)
    qpos = jnp.arange(S)
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)

    def body(carry, inp):
        m, l, acc = carry                      # (B,S,Hkv,g), (B,S,Hkv,g), (B,S,Hkv,g,hd)
        kb, vb, c_idx = inp                    # (B,C,Hkv,hd), (B,C,Hkv,hd), scalar
        kpos = c_idx * C + jnp.arange(C)
        s = jnp.einsum("bqhgd,bkhd->bqhgk", qf, kb) * scale
        if attn_softcap:
            s = attn_softcap * jnp.tanh(s / attn_softcap)
        msk = kpos[None, :] <= qpos[:, None] if causal else jnp.ones((S, C), bool)
        msk = msk & jnp.where(w > 0, kpos[None, :] > qpos[:, None] - w, True)
        s = jnp.where(msk[None, :, None, None, :], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum("bqhgk,bkhd->bqhgd", p, vb)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, S, Hkv, group), -1e30, jnp.float32)
    l0 = jnp.zeros((B, S, Hkv, group), jnp.float32)
    acc0 = jnp.zeros((B, S, Hkv, group, hd), jnp.float32)
    # checkpoint: recompute the (B,S,Hkv,g,C) score block in bwd instead of
    # saving one per chunk — otherwise bwd memory is O(S²) again
    (m, l, acc), _ = jax.lax.scan(
        jax.checkpoint(body, prevent_cse=False), (m0, l0, acc0),
        (kc.transpose(1, 0, 2, 3, 4), vc.transpose(1, 0, 2, 3, 4), jnp.arange(nc)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, S, Hq, hd).astype(q.dtype)


def _attend_causal(q, k, v, window, attn_softcap: float):
    """Causal (sliding-window where ``window`` > 0) attention of a training or
    prefill sequence. Lowered for a TPU, where the shape fits its tiles, it
    is the Pallas flash kernel; everywhere else ``attend_chunked``."""
    from repro.kernels.flash_attention import ops as flash

    chunked = partial(attend_chunked, attn_softcap=attn_softcap)
    S, Hq, hd = q.shape[1:]
    if flash.tile_size(S, hd, Hq // k.shape[2]) is None:
        return chunked(q, k, v, window)
    return jax.lax.platform_dependent(
        q, k, v, window, default=chunked,
        tpu=partial(flash.flash_attention, attn_softcap=attn_softcap,
                    interpret=False))


def attn_forward(params, x, cfg, *, window=0, positions=None, cache: KVCache | None = None,
                 chunked: bool = True):
    """Full-sequence forward (train / prefill). Returns (out, new_cache)."""
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S)[None, :]
    q, k, v = _qkv(params, x, cfg)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    with obs.scope("attention"):
        if chunked and S > 128:
            out = _attend_causal(q, k, v, window, cfg.attn_logit_softcap)
        else:
            mask = _causal_mask(S, window)
            out = attend_full(q, k, v, mask, cfg.attn_logit_softcap)
    new_cache = None
    if cache is not None:
        C = cache.k.shape[1]
        if C >= S:
            newk = jax.lax.dynamic_update_slice(cache.k, k.astype(cache.k.dtype), (0, 0, 0, 0))
            newv = jax.lax.dynamic_update_slice(cache.v, v.astype(cache.v.dtype), (0, 0, 0, 0))
        else:  # ring cache keeps the last C positions at slot = pos % C
            newk = jnp.roll(k[:, S - C:], S % C, axis=1).astype(cache.k.dtype)
            newv = jnp.roll(v[:, S - C:], S % C, axis=1).astype(cache.v.dtype)
        new_cache = KVCache(newk, newv)
    hd = cfg.resolved_head_dim
    return out.reshape(B, S, cfg.num_heads * hd) @ params["wo"], new_cache


def attn_decode(params, x, cfg, cache: KVCache, pos: jnp.ndarray, *, window=0,
                ring: bool = False, use_kernel: bool = False):
    """Single-token decode: x (B, 1, D); pos scalar absolute position.

    Two static cache regimes (chosen by the serving layer):
      linear (C ≥ max position): slot = pos, window enforced by explicit mask
        — ``window`` may be a traced per-layer scan input (gemma2 local/global);
      ring  (C == window): slot = pos % C, the buffer itself IS the window.
    Returns (out (B,1,D), updated cache)."""
    B = x.shape[0]
    hd = cfg.resolved_head_dim
    w = jnp.asarray(window, jnp.int32)
    q, k, v = _qkv(params, x, cfg)
    q = apply_rope(q, pos[None, None].astype(jnp.int32) * jnp.ones((B, 1), jnp.int32), cfg.rope_theta)
    k = apply_rope(k, pos[None, None].astype(jnp.int32) * jnp.ones((B, 1), jnp.int32), cfg.rope_theta)
    C = cache.k.shape[1]
    slot = ((pos % C) if ring else jnp.minimum(pos, C - 1)).astype(jnp.int32)
    zero = jnp.zeros((), jnp.int32)
    newk = jax.lax.dynamic_update_slice(cache.k, k.astype(cache.k.dtype), (zero, slot, zero, zero))
    newv = jax.lax.dynamic_update_slice(cache.v, v.astype(cache.v.dtype), (zero, slot, zero, zero))
    idx = jnp.arange(C)
    if ring:
        valid = (idx <= slot) | (pos >= C)   # fully valid once wrapped
    else:
        valid = (idx <= slot) & jnp.where(w > 0, idx > pos - w, True)
    if use_kernel:
        from repro.kernels.decode_attention import ops as dec_ops

        out = dec_ops.decode_attention(q[:, 0], newk, newv, valid,
                                       attn_softcap=cfg.attn_logit_softcap)
        out = out[:, None]
    else:
        mask = valid[None, None, None, :]  # (1,1,1,C)
        out = attend_full(q, newk, newv, mask, cfg.attn_logit_softcap)
    return out.reshape(B, 1, cfg.num_heads * hd) @ params["wo"], KVCache(newk, newv)
