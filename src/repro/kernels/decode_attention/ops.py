"""jit'd public wrapper: pads the cache to BLOCK_K, handles softcap plumbing,
and exposes the same signature the model decode path uses."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref
from .kernel import BLOCK_K, decode_attention_kernel


@functools.partial(jax.jit, static_argnames=("attn_softcap", "use_kernel", "interpret"))
def decode_attention(q, k, v, valid, *, attn_softcap: float = 0.0,
                     use_kernel: bool = True, interpret: bool | None = None):
    """q: (B, Hq, hd); k/v: (B, C, Hkv, hd); valid: (C,) — see ref.py."""
    if not use_kernel:
        return ref.decode_attention(q, k, v, valid, attn_softcap=attn_softcap)
    C = k.shape[1]
    pad = (-C) % BLOCK_K
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        valid = jnp.pad(valid.astype(jnp.int32), (0, pad))
    return decode_attention_kernel(q, k, v, valid, softcap=attn_softcap,
                                   interpret=interpret)
