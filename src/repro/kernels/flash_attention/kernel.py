"""Pallas TPU flash attention for training: causal (optionally sliding-window)
GQA, forward and backward, with tiles above the diagonal or outside the
window neither loaded nor computed.

Layouts (``ops.py`` builds them from the model's (B, S, H, hd) arrays):

  q rows   (B, Hkv, g·S, hd)  q tile i holds rows [i·g·T, (i+1)·g·T): the g
                              query heads of one kv head, each T positions
                              long, so row r of a tile is position
                              i·T + (r mod T). GQA without repeating k, v.
  k, v     (B, Hkv, S, hd)
  kᵀ, vᵀ   (B, Hkv, hd, S)
  outᵀ     (B, Hkv, hd, g·S)  lse, D: (B, Hkv, 1, g·S)

Scores are formed transposed, keys on sublanes and query rows on lanes:
sᵀ = k·qᵀ is a (T, g·T) block, so the softmax statistics (running max and
sum, logsumexp, D = rowsum(dO∘O)) are lane vectors (1, g·T) that broadcast
down the block, and every matmul is an NN or NT MXU product. bf16 operands,
float32 accumulation, float32 softmax statistics and ``exp``.

Masks match ``models.attention.attend_chunked``: key ≤ query, and
key > query − window where window > 0; masked scores are −1e30.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..mode import pallas_call

NT = (((1,), (1,)), ((), ()))  # contract the last dims: a·bᵀ
NN = (((1,), (0,)), ((), ()))
MASKED = -1e30
#: scoped VMEM the kernels may use: the (T, g·T) float32 score blocks and
#: their temporaries at T = 512, g ≤ 4 need more than the default
VMEM_LIMIT = 64 * 1024 * 1024


def k_range(i, window, T):
    """First and last k tile that q tile ``i`` attends to (T×T tiles)."""
    lo = jnp.where(window > 0, jnp.maximum(i * T - window + 1, 0) // T, 0)
    return lo, i


def q_range(j, window, T, nq):
    """First and last q tile that attends to k tile ``j``."""
    hi = jnp.where(window > 0,
                   jnp.minimum((j * T + T - 1 + window - 1) // T, nq - 1),
                   nq - 1)
    return j, hi


def _inside(i, j, window, T):
    """Whether every (query, key) pair of tile (i, j) is unmasked: the tile
    lies below the diagonal and, with a window, inside it."""
    return (j < i) & ((window <= 0) | (j * T > i * T + T - 1 - window))


def _each_tile(run, inside, step):
    """``step(masked)`` on the tiles to compute: unmasked inside the mask,
    masked per element on the diagonal and window-edge tiles."""
    pl.when(run & inside)(lambda: step(False))
    pl.when(run & jnp.logical_not(inside))(lambda: step(True))


def _scores(q, k, i, j, window, *, T, scale, softcap, masked):
    """sᵀ block (T keys, g·T query rows), float32, masked where ``masked``;
    and tanh(s/c) where soft-capped (its derivative is needed backward)."""
    s = jax.lax.dot_general(k, q, NT, preferred_element_type=jnp.float32)
    s = s * scale
    t = None
    if softcap:
        t = jnp.tanh(s / softcap)
        s = softcap * t
    if masked:
        kpos = j * T + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        qpos = i * T + (jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) & (T - 1))
        keep = (kpos <= qpos) & ((window <= 0) | (kpos > qpos - window))
        s = jnp.where(keep, s, MASKED)
    return s, t


def _fwd_kernel(win_ref, q_ref, k_ref, vt_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc,
                *, T, scale, softcap):
    """Grid (b, h, q tile i, k tile j); online softmax over j."""
    i, j = pl.program_id(2), pl.program_id(3)
    window = win_ref[0]

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, MASKED)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    lo, hi = k_range(i, window, T)

    def step(masked):
        s, _ = _scores(q_ref[0, 0], k_ref[0, 0], i, j, window,
                       T=T, scale=scale, softcap=softcap, masked=masked)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_sc[...] = l_sc[...] * corr + jnp.sum(p, axis=0, keepdims=True)
        vt = vt_ref[0, 0]
        acc_sc[...] = acc_sc[...] * corr + jax.lax.dot_general(
            vt, p.astype(vt.dtype), NN, preferred_element_type=jnp.float32)
        m_sc[...] = m_new

    _each_tile((j >= lo) & (j <= hi), _inside(i, j, window, T), step)

    @pl.when(j == pl.num_programs(3) - 1)
    def _finish():
        l = jnp.maximum(l_sc[...], 1e-30)
        o_ref[0, 0] = (acc_sc[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_sc[...] + jnp.log(l)


def _bwd_kernel(win_ref, q_ref, k_ref, v_ref, kt_ref, do_ref, lse_ref, d_ref,
                dk_ref, dv_ref, dqt_ref, dk_sc, dv_sc, dqt_sc, *, T, scale, softcap):
    """Grid (b, h, k tile j, q tile i). dK and dV of tile j accumulate over i
    (and over the g query heads folded into each q tile's rows); dQᵀ of the
    whole (b, h) stays in VMEM across the grid steps of that (b, h)."""
    j, i = pl.program_id(2), pl.program_id(3)
    nk, nq = pl.num_programs(2), pl.num_programs(3)
    window = win_ref[0]

    @pl.when((j == 0) & (i == 0))
    def _init_dq():
        dqt_sc[...] = jnp.zeros_like(dqt_sc)

    @pl.when(i == 0)
    def _init_dkv():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    lo, hi = q_range(j, window, T, nq)

    def step(masked):
        q, do = q_ref[0, 0], do_ref[0, 0]
        s, t = _scores(q, k_ref[0, 0], i, j, window,
                       T=T, scale=scale, softcap=softcap, masked=masked)
        p = jnp.exp(s - lse_ref[0, 0])                          # (T, g·T)
        dv_sc[...] += jax.lax.dot_general(p.astype(do.dtype), do, NN,
                                          preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v_ref[0, 0], do, NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - d_ref[0, 0])
        if softcap:
            ds = ds * (1.0 - t * t)
        ds = (ds * scale).astype(q.dtype)
        dk_sc[...] += jax.lax.dot_general(ds, q, NN,
                                          preferred_element_type=jnp.float32)
        dqt_sc[i] += jax.lax.dot_general(kt_ref[0, 0], ds, NN,
                                         preferred_element_type=jnp.float32)

    _each_tile((i >= lo) & (i <= hi), _inside(i, j, window, T), step)

    @pl.when(i == nq - 1)
    def _write_dkv():
        dk_ref[0, 0] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_sc[...].astype(dv_ref.dtype)

    @pl.when((j == nk - 1) & (i == nq - 1))
    def _write_dq():
        dqt_ref[0, 0] = dqt_sc[...].astype(dqt_ref.dtype)


def _params(n_grid: int):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * (n_grid - 2) + ("arbitrary",) * 2,
        vmem_limit_bytes=VMEM_LIMIT)


@functools.partial(jax.jit, static_argnames=("T", "softcap", "interpret"))
def flash_fwd(window, q_rows, k, vt, *, T: int, softcap: float = 0.0,
              interpret: bool | None = None):
    """window (1,) int32; q_rows (B, Hkv, g·S, hd); k (B, Hkv, S, hd);
    vt (B, Hkv, hd, S). Returns outᵀ (B, Hkv, hd, g·S) in q's dtype and the
    logsumexp (B, Hkv, 1, g·S) float32."""
    B, H, R, hd = q_rows.shape
    S = k.shape[2]
    nq = nk = S // T
    Rt = R // nq

    def kv_tile(i, j, win):   # a skipped tile repeats a neighbour's index: no copy
        return jnp.clip(j, *k_range(i, win[0], T))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, Rt, hd), lambda b, h, i, j, win: (b, h, i, 0)),
            pl.BlockSpec((1, 1, T, hd),
                         lambda b, h, i, j, win: (b, h, kv_tile(i, j, win), 0)),
            pl.BlockSpec((1, 1, hd, T),
                         lambda b, h, i, j, win: (b, h, 0, kv_tile(i, j, win))),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, hd, Rt), lambda b, h, i, j, win: (b, h, 0, i)),
            pl.BlockSpec((1, 1, 1, Rt), lambda b, h, i, j, win: (b, h, 0, i)),
        ],
        scratch_shapes=[pltpu.VMEM((1, Rt), jnp.float32),
                        pltpu.VMEM((1, Rt), jnp.float32),
                        pltpu.VMEM((hd, Rt), jnp.float32)],
    )
    return pallas_call(
        functools.partial(_fwd_kernel, T=T, scale=hd ** -0.5, softcap=softcap),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, H, hd, R), q_rows.dtype),
                   jax.ShapeDtypeStruct((B, H, 1, R), jnp.float32)],
        compiler_params=_params(4),
        name="flash_attention_fwd",
        interpret=interpret,
    )(window, q_rows, k, vt)


@functools.partial(jax.jit, static_argnames=("T", "softcap", "interpret"))
def flash_bwd(window, q_rows, k, v, kt, do_rows, lse, d, *, T: int,
              softcap: float = 0.0, interpret: bool | None = None):
    """Layouts as :func:`flash_fwd`; do_rows like q_rows, d like lse.
    Returns dQᵀ (B, Hkv, nq, hd, g·T), dK and dV (B, Hkv, S, hd)."""
    B, H, R, hd = q_rows.shape
    S = k.shape[2]
    nq = nk = S // T
    Rt = R // nq

    def q_tile(j, i, win):    # a skipped tile repeats a neighbour's index: no copy
        return jnp.clip(i, *q_range(j, win[0], T, nq))

    rows = pl.BlockSpec((1, 1, Rt, hd),
                        lambda b, h, j, i, win: (b, h, q_tile(j, i, win), 0))
    stat = pl.BlockSpec((1, 1, 1, Rt),
                        lambda b, h, j, i, win: (b, h, 0, q_tile(j, i, win)))
    key = pl.BlockSpec((1, 1, T, hd), lambda b, h, j, i, win: (b, h, j, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, H, nk, nq),
        in_specs=[rows, key, key,
                  pl.BlockSpec((1, 1, hd, T), lambda b, h, j, i, win: (b, h, 0, j)),
                  rows, stat, stat],
        out_specs=[key, key,
                   pl.BlockSpec((1, 1, nq, hd, Rt),
                                lambda b, h, j, i, win: (b, h, 0, 0, 0))],
        scratch_shapes=[pltpu.VMEM((T, hd), jnp.float32),
                        pltpu.VMEM((T, hd), jnp.float32),
                        pltpu.VMEM((nq, hd, Rt), jnp.float32)],
    )
    return pallas_call(
        functools.partial(_bwd_kernel, T=T, scale=hd ** -0.5, softcap=softcap),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((B, H, nq, hd, Rt), q_rows.dtype)],
        compiler_params=_params(4),
        name="flash_attention_bwd",
        interpret=interpret,
    )(window, q_rows, k, v, kt, do_rows, lse, d)
