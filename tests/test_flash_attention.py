"""The Pallas flash attention kernel (``kernels/flash_attention``) against its
oracle, ``attend_chunked``, in interpret mode on the CPU at small shapes,
and the tile plan it skips by.

Tolerance: both paths see the same bf16 q, k, v. The kernel then rounds the
probabilities P and the score gradient dS to bf16 before their matmuls,
where the oracle keeps float32 on the CPU; each rounding is within 2^-9
relative, so outputs and gradients may differ by a few of them:
relative L2 error ≤ 2^-7.
"""
from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import kernel, ops, ref
from repro.models import attention

REL_L2 = 2.0 ** -7


def _inputs(B, S, Hkv, g, hd, q_scale, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = (q_scale * jax.random.normal(ks[0], (B, S, Hkv * g, hd))).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, Hkv, hd)).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, Hkv, hd)).astype(jnp.bfloat16)
    w = jax.random.normal(ks[3], (B, S, Hkv * g, hd), jnp.float32)
    return q, k, v, w


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _out_and_grads(f, q, k, v, w):
    def loss(q, k, v):
        return jnp.sum(f(q, k, v).astype(jnp.float32) * w)
    return jax.jit(f)(q, k, v), jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)


def _lowers_kernel_for_tpu(S, Hkv, g, hd) -> bool:
    """Whether ``attn_forward``'s attention, lowered for a TPU, is the
    kernel (cross-platform lowering: no TPU and no TPU compiler needed)."""
    q = jax.ShapeDtypeStruct((1, S, Hkv * g, hd), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, S, Hkv, hd), jnp.bfloat16)
    f = jax.jit(lambda q, k, v: attention._attend_causal(q, k, v, jnp.int32(0), 0.0))
    return "tpu_custom_call" in f.trace(q, k, k).lower(
        lowering_platforms=("tpu",)).as_text()


@pytest.mark.parametrize("S", [512, 200], ids=["4x4-tiles", "refused-S"])
@pytest.mark.parametrize("softcap", [0.0, 50.0])
@pytest.mark.parametrize("window", [0, 200])
@pytest.mark.parametrize("g", [1, 3, 4])
def test_flash_attention_matches_attend_chunked(monkeypatch, g, window, softcap, S):
    """Forward output and the gradients of a scalar loss with respect to q, k
    and v, with 128-token tiles (4 per side at S = 512: diagonal, interior,
    skipped and window-edge tiles). At an S the tiling refuses the model
    keeps ``attend_chunked`` on every platform."""
    monkeypatch.setattr(ops, "TILES", (128,))
    B, Hkv, hd = 2, 2, 64
    # soft-capped scores reach the cap's non-linear range
    q, k, v, w = _inputs(B, S, Hkv, g, hd, q_scale=20.0 if softcap else 1.0)
    win = jnp.int32(window)
    expect = _out_and_grads(lambda q, k, v: ref.attend_chunked(q, k, v, win, softcap),
                            q, k, v, w)
    if ops.tile_size(S, hd, g) is None:
        assert S % 128 and not _lowers_kernel_for_tpu(S, Hkv, g, hd)
        with pytest.raises(ValueError, match="does not take"):
            ops.flash_attention(q, k, v, win, softcap, interpret=True)
        got = _out_and_grads(
            lambda q, k, v: attention._attend_causal(q, k, v, win, softcap), q, k, v, w)
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(expect[0]))
        return
    assert ops.tile_size(S, hd, g) == 128 and _lowers_kernel_for_tpu(S, Hkv, g, hd)
    got = _out_and_grads(
        lambda q, k, v: ops.flash_attention(q, k, v, win, softcap, interpret=True),
        q, k, v, w)
    assert got[0].dtype == q.dtype and got[0].shape == q.shape
    assert _rel(got[0], expect[0]) <= REL_L2
    for name, a, b in zip("qkv", got[1], expect[1]):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert _rel(a, b) <= REL_L2, (name, _rel(a, b))


def test_flash_attention_under_vmap_over_workers(monkeypatch):
    """The stacked training step vmaps the model over workers; the kernel's
    batching rule must give each worker its own attention."""
    monkeypatch.setattr(ops, "TILES", (128,))
    q, k, v, _ = _inputs(3, 256, 1, 3, 64, q_scale=1.0)
    q, k, v = (x[:, None] for x in (q, k, v))           # (workers, 1, S, H, hd)
    win = jnp.int32(0)
    got = jax.vmap(lambda q, k, v: ops.flash_attention(q, k, v, win, interpret=True))(q, k, v)
    expect = jax.vmap(lambda q, k, v: ref.attend_chunked(q, k, v, win))(q, k, v)
    assert _rel(got, expect) <= REL_L2


@pytest.mark.parametrize("S,window,group,expect", [
    (2048, 0, 3, (10, 6)),        # smollm-135m's training shape: 512-token tiles
    (2048, 512, 3, (7, 9)),       # sliding window: the edge tile is kept
    (4096, 1024, 1, (21, 43)),    # gemma2-style local layer
    (1024, 0, 8, (10, 6)),        # g = 8 halves the tile: 256 tokens
    (200, 0, 1, (0, 0)),          # refused: attend_chunked
])
def test_tile_counts(S, window, group, expect):
    assert ops.tile_counts(S, window, group) == expect


@pytest.mark.parametrize("S,T", [(2048, 512), (1024, 128), (512, 128)])
@pytest.mark.parametrize("window", [0, 1, 129, 300, 700])
def test_tile_ranges_cover_exactly_the_unmasked_pairs(S, T, window):
    """The forward's k range and the backward's q range of each tile hold
    exactly the tile pairs with an unmasked (key ≤ query, key > query −
    window) element, so a skipped tile never held a score that counts."""
    n = S // T
    pos = np.arange(S)
    keep = pos[None, :] <= pos[:, None]
    if window:
        keep &= pos[None, :] > pos[:, None] - window
    needed = keep.reshape(n, T, n, T).any(axis=(1, 3))        # [q tile, k tile]
    w = jnp.int32(window)
    for i in range(n):
        lo, hi = (int(x) for x in kernel.k_range(jnp.int32(i), w, T))
        assert [j for j in range(n) if lo <= j <= hi] == list(np.flatnonzero(needed[i]))
    for j in range(n):
        lo, hi = (int(x) for x in kernel.q_range(jnp.int32(j), w, T, n))
        assert [i for i in range(n) if lo <= i <= hi] == list(np.flatnonzero(needed[:, j]))
    if T == ops.tile_size(S, 64, 1):
        assert ops.tile_counts(S, window) == (int(needed.sum()), int((~needed).sum()))


def test_describe_names_the_path():
    assert ops.describe(2048, 64, 3, platform="tpu") == "flash, 10/16 tiles"
    assert ops.describe(2048, 64, 3, platform="cpu") == "chunked"
    assert ops.describe(2048, 64, 3, windows=(0, 512), platform="tpu") == \
        "flash, 10/16 tiles, 7/16 tiles (window 512)"
    assert ops.describe(1500, 64, 1, platform="tpu") == "chunked"
