"""Public flash attention for the training step: layouts, tile choice and the
custom VJP around the Pallas kernels (``kernel.py``), and the tile counts
that the launchers log."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import flash_bwd, flash_fwd

#: tile sizes tried, largest first; each a power of two and a multiple of the
#: 128 lanes a score block's query rows need
TILES = (512, 256, 128)
#: most query rows (g heads × T positions) one tile may hold: the (T, g·T)
#: float32 score block and its temporaries live in VMEM
MAX_ROWS = 2048
#: bytes of the float32 dQᵀ (hd × g·S) kept in VMEM for one (batch, kv head)
#: through the backward pass
MAX_DQ_BYTES = 8 * 1024 * 1024


def _tile(S: int, group: int) -> int | None:
    """The largest tile that divides S and keeps g·T query rows in bounds."""
    for T in TILES:
        if S % T == 0 and (group * T <= MAX_ROWS or T == TILES[-1]):
            return T
    return None


def tile_size(S: int, head_dim: int, group: int) -> int | None:
    """Square tile T for sequence length ``S``, or ``None`` where the kernel
    does not take the shape (the caller keeps ``attend_chunked``): S must be
    a multiple of T, longer than 128, hd a multiple of 8, and dQᵀ must fit
    VMEM."""
    if S <= 128 or head_dim % 8 or head_dim * group * S * 4 > MAX_DQ_BYTES:
        return None
    return _tile(S, group)


def tile_counts(S: int, window: int = 0, group: int = 1) -> tuple[int, int]:
    """(computed, skipped) (q tile, k tile) pairs at the tile the kernel
    picks for S and g, under the causal mask and a sliding window of
    ``window`` keys (0: none). ``(0, 0)`` where no tile divides S."""
    T = _tile(S, group) if S > 128 else None
    if T is None:
        return 0, 0
    n = S // T
    computed = 0
    for i in range(n):
        lo = max(i * T - window + 1, 0) // T if window > 0 else 0
        computed += i - lo + 1
    return computed, n * n - computed


def describe(S: int, head_dim: int, group: int, windows=(0,),
             platform: str | None = None) -> str:
    """The attention path ``attn_forward`` takes on ``platform`` (default:
    JAX's default backend) at this shape: ``"flash, 10/16 tiles"`` or
    ``"chunked"``; one tile count per distinct window."""
    platform = platform or jax.default_backend()
    if platform != "tpu" or tile_size(S, head_dim, group) is None:
        return "chunked"
    counts = []
    for w in sorted(set(int(w) for w in windows)):
        c, s = tile_counts(S, w, group)
        counts.append(f"{c}/{c + s} tiles" + (f" (window {w})" if w else ""))
    return "flash, " + ", ".join(counts)


def _rows(x, Hkv, T):
    """(B, S, Hkv·g, hd) → (B, Hkv, g·S, hd), each q tile's g heads adjacent."""
    B, S, Hq, hd = x.shape
    g = Hq // Hkv
    x = x.reshape(B, S // T, T, Hkv, g, hd).transpose(0, 3, 1, 4, 2, 5)
    return x.reshape(B, Hkv, g * S, hd)


def _stat_rows(x, Hkv, T):
    """(B, S, Hq) → (B, Hkv, 1, g·S) in the order of :func:`_rows`."""
    B, S, Hq = x.shape
    g = Hq // Hkv
    x = x.reshape(B, S // T, T, Hkv, g).transpose(0, 3, 1, 4, 2)
    return x.reshape(B, Hkv, 1, g * S)


def _from_rows_t(xt, S, T):
    """outᵀ (B, Hkv, hd, g·S) → (B, S, Hkv·g, hd)."""
    B, Hkv, hd, R = xt.shape
    g = R // S
    x = xt.reshape(B, Hkv, hd, S // T, g, T).transpose(0, 3, 5, 1, 4, 2)
    return x.reshape(B, S, Hkv * g, hd)


def _heads_major(x):
    return x.transpose(0, 2, 1, 3)          # (B, S, H, hd) → (B, H, S, hd)


def _forward(q, k, v, window, attn_softcap, interpret):
    B, S, Hq, hd = q.shape
    Hkv = k.shape[2]
    T = tile_size(S, hd, Hq // Hkv)
    win = jnp.asarray(window, jnp.int32).reshape(1)
    out_t, lse = flash_fwd(win, _rows(q, Hkv, T), _heads_major(k),
                           v.transpose(0, 2, 3, 1), T=T, softcap=attn_softcap,
                           interpret=interpret)
    return _from_rows_t(out_t, S, T), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _flash(q, k, v, window, attn_softcap, interpret):
    return _forward(q, k, v, window, attn_softcap, interpret)[0]


def _flash_fwd(q, k, v, window, attn_softcap, interpret):
    out, lse = _forward(q, k, v, window, attn_softcap, interpret)
    return out, (q, k, v, window, out, lse)


def _flash_bwd(attn_softcap, interpret, res, dout):
    q, k, v, window, out, lse = res
    B, S, Hq, hd = q.shape
    Hkv = k.shape[2]
    T = tile_size(S, hd, Hq // Hkv)
    win = jnp.asarray(window, jnp.int32).reshape(1)
    d = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    dk, dv, dqt = flash_bwd(
        win, _rows(q, Hkv, T), _heads_major(k), _heads_major(v),
        k.transpose(0, 2, 3, 1), _rows(dout.astype(q.dtype), Hkv, T), lse,
        _stat_rows(d, Hkv, T), T=T, softcap=attn_softcap, interpret=interpret)
    dq = _from_rows_t(dqt.transpose(0, 1, 3, 2, 4).reshape(B, Hkv, hd, -1), S, T)
    return dq, _heads_major(dk), _heads_major(dv), None


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, window, attn_softcap: float = 0.0, *,
                    interpret: bool | None = None):
    """Causal GQA attention, sliding-window where ``window`` > 0 (a traced
    int32 scalar; 0 is full causal), score soft-cap ``attn_softcap``
    (static; 0 is none). q: (B, S, Hq, hd); k, v: (B, S, Hkv, hd); returns
    (B, S, Hq, hd) in q's dtype. The shape must pass :func:`tile_size`."""
    B, S, Hq, hd = q.shape
    if tile_size(S, hd, Hq // k.shape[2]) is None:
        raise ValueError(f"flash_attention does not take S={S}, hd={hd}, "
                         f"group={Hq // k.shape[2]}: see tile_size")
    return _flash(q, k, v, jnp.asarray(window, jnp.int32), float(attn_softcap),
                  interpret)
