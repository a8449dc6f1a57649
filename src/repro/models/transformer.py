"""Model assembly for all assigned architecture families.

One functional model per family, layers stacked with ``jax.lax.scan`` over
vmapped-init parameter stacks (small HLO, fast multi-arch dry-run compiles):

  dense   — GQA attention + SwiGLU (smollm, minitron, qwen1.5, gemma2 with
            local/global alternating windows + logit softcaps)
  moe     — GQA attention + top-k MoE FFN (mixtral 8e/top2 SWA,
            granite 32e/top8)
  ssm     — Mamba-2 / SSD blocks (mamba2-780m)
  hybrid  — Mamba-2 blocks with one SHARED attention block every
            ``shared_attn_every`` layers (zamba2)
  vlm     — dense decoder consuming [patch-embeds ; text-embeds]
            (internvl2 backbone; ViT frontend is a stub per the brief)
  audio   — encoder-decoder with cross attention (whisper backbone;
            mel+conv frontend is a stub per the brief)

Public entry points (all pure functions of (params, cfg, ...)):
  init_params, train_loss, prefill, decode_step, init_caches
"""
from __future__ import annotations

from typing import Any, NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from .. import obs
from .attention import attn_decode, attn_forward, init_attn, init_kv_cache
from .common import dense_init, embed_init, rms_norm, softcap
from .mlp import gelu_mlp, init_gelu_mlp, init_swiglu, swiglu
from .moe import init_moe, moe_forward
from .partitioning import get_rules
from .ssm import init_mamba2, init_ssm_cache, mamba2_decode, mamba2_forward

__all__ = [
    "init_params", "train_loss", "prefill", "decode_step", "init_caches",
    "layer_windows", "param_count", "Caches",
]


def _moe(mp, h2, cfg, *, min_capacity: int = 1):
    """Route to the pjit dispatch (default) or the shard_map expert-parallel
    block when the launch layer installed ``moe_impl: expert_parallel``."""
    if get_rules().get("moe_impl") == "expert_parallel":
        from .moe_ep import moe_forward_expert_parallel
        return moe_forward_expert_parallel(
            mp, h2, top_k=cfg.experts_per_token,
            axis=get_rules().get("moe_expert_axis", "model"),
            token_axes=get_rules().get("moe_token_axes", ("data",)),
            capacity_factor=cfg.moe_capacity_factor, min_capacity=min_capacity)
    return moe_forward(mp, h2, top_k=cfg.experts_per_token,
                       capacity_factor=cfg.moe_capacity_factor,
                       min_capacity=min_capacity)


class Caches(NamedTuple):
    """Stacked per-layer decode state. Unused fields are () placeholders."""
    kv: Any = ()         # (L, B, C, Hkv, hd) ×2 — self-attention KV
    ssm: Any = ()        # SSMCache with (L, B, ...) leaves
    shared_kv: Any = ()  # hybrid: (G, B, C, Hkv, hd) ×2 for the shared block
    cross_kv: Any = ()   # audio: precomputed (L, B, Tenc, Hkv, hd) ×2


# ---------------------------------------------------------------------------
# per-layer heterogeneity
# ---------------------------------------------------------------------------

def layer_windows(cfg, *, long_context: bool = False) -> jnp.ndarray:
    """Per-layer sliding windows (int32, 0 = full attention).

    gemma2 ``local_global``: even layers SWA, odd layers global — in the
    documented long-context serving variant every layer is SWA.
    mixtral ``swa``: every layer windowed.
    """
    L = cfg.num_layers
    if cfg.attn_pattern == "local_global" and cfg.sliding_window:
        w = [cfg.sliding_window if (i % 2 == 0 or long_context) else 0 for i in range(L)]
    elif cfg.sliding_window:
        w = [cfg.sliding_window] * L
    elif long_context and cfg.arch_type == "hybrid":
        # zamba2 long-context serving: shared attention gets a sliding-window
        # ring cache (documented liberty — the Mamba2 state is the long path)
        w = [4096] * L
    else:
        w = [0] * L
    return jnp.asarray(w, jnp.int32)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _dtype(cfg):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def _init_attn_layer(key, cfg, dtype):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {"ln1": jnp.zeros((cfg.d_model,), dtype), "attn": init_attn(k1, cfg, dtype),
         "ln2": jnp.zeros((cfg.d_model,), dtype)}
    if cfg.num_experts:
        p["moe"] = init_moe(k2, cfg.d_model, cfg.d_ff, cfg.num_experts, dtype)
    elif cfg.arch_type == "audio":
        p["mlp"] = init_gelu_mlp(k2, cfg.d_model, cfg.d_ff, dtype)
    else:
        p["mlp"] = init_swiglu(k2, cfg.d_model, cfg.d_ff, dtype)
    if cfg.cross_attention and cfg.arch_type == "audio":
        p["ln_x"] = jnp.zeros((cfg.d_model,), dtype)
        p["xattn"] = init_attn(k3, cfg, dtype)
    return p


def _init_ssm_layer(key, cfg, dtype):
    return {"ln": jnp.zeros((cfg.d_model,), dtype), "mamba": init_mamba2(key, cfg, dtype)}


def init_params(key, cfg) -> dict:
    dtype = _dtype(cfg)
    keys = jax.random.split(key, 8)
    p: dict = {"embed": embed_init(keys[0], cfg.vocab_size, cfg.d_model, dtype),
               "final_norm": jnp.zeros((cfg.d_model,), dtype)}
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(keys[1], cfg.d_model, cfg.vocab_size, dtype)

    L = cfg.num_layers
    if cfg.arch_type in ("dense", "moe", "vlm"):
        lkeys = jax.random.split(keys[2], L)
        p["layers"] = jax.vmap(lambda k: _init_attn_layer(k, cfg, dtype))(lkeys)
    elif cfg.arch_type == "ssm":
        lkeys = jax.random.split(keys[2], L)
        p["layers"] = jax.vmap(lambda k: _init_ssm_layer(k, cfg, dtype))(lkeys)
    elif cfg.arch_type == "hybrid":
        lkeys = jax.random.split(keys[2], L)
        p["layers"] = jax.vmap(lambda k: _init_ssm_layer(k, cfg, dtype))(lkeys)
        p["shared_attn"] = _init_attn_layer(keys[3], cfg, dtype)  # ONE block, reused
    elif cfg.arch_type == "audio":
        ekeys = jax.random.split(keys[2], cfg.encoder_layers)
        enc_cfg = cfg  # same dims for whisper-tiny enc/dec
        p["enc_layers"] = jax.vmap(lambda k: _init_attn_layer(k, _no_cross(enc_cfg), dtype))(ekeys)
        p["enc_norm"] = jnp.zeros((cfg.d_model,), dtype)
        dkeys = jax.random.split(keys[3], L)
        p["layers"] = jax.vmap(lambda k: _init_attn_layer(k, cfg, dtype))(dkeys)
    else:
        raise ValueError(cfg.arch_type)
    if cfg.frontend:
        # projector from frontend embedding space to d_model (stubbed frontend
        # provides d_model-sized embeddings already; keep a learned projector
        # so the parameter inventory matches a real VLM/audio deployment)
        p["frontend_proj"] = dense_init(keys[4], cfg.d_model, cfg.d_model, dtype)
    return p


def _no_cross(cfg):
    from dataclasses import replace
    return replace(cfg, cross_attention=False)


def param_count(params) -> int:
    return int(sum(x.size for x in jax.tree.leaves(params)))


# ---------------------------------------------------------------------------
# block bodies (full-sequence)
# ---------------------------------------------------------------------------

def _attn_block(lp, x, cfg, window, positions, *, causal=True, cache=None):
    h, new_cache = attn_forward(lp["attn"], rms_norm(x, lp["ln1"], cfg.norm_eps), cfg,
                                window=window, positions=positions, cache=cache)
    x = x + h
    h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
    with obs.scope("mlp"):
        if "moe" in lp:
            out, aux = _moe(lp["moe"], h2, cfg)
        elif cfg.arch_type == "audio":
            out, aux = gelu_mlp(lp["mlp"], h2), 0.0
        else:
            out, aux = swiglu(lp["mlp"], h2), 0.0
    return x + out, aux, new_cache


def _ssm_block(lp, x, cfg, cache=None, use_kernel=False):
    h, new_cache = mamba2_forward(lp["mamba"], rms_norm(x, lp["ln"], cfg.norm_eps), cfg,
                                  cache=cache, use_kernel=use_kernel)
    return x + h, new_cache


# ---------------------------------------------------------------------------
# full-sequence stacks (train / prefill) — lax.scan over stacked layer params
# ---------------------------------------------------------------------------

def _stack_dense(params, x, cfg, windows, positions, *, with_cache: bool, cache_cap: int = 0):
    dtype = x.dtype
    B, S, _ = x.shape

    def body(carry, inp):
        h, aux = carry
        lp, w = inp
        cache = (init_kv_cache(B, cache_cap, cfg.num_kv_heads, cfg.resolved_head_dim, dtype)
                 if with_cache else None)
        h, a, new_cache = _attn_block(lp, h, cfg, w, positions, cache=cache)
        ys = new_cache if with_cache else 0
        return (h, aux + a), ys

    (x, aux), caches = jax.lax.scan(_maybe_remat(body, cfg), (x, 0.0), (params["layers"], windows))
    return x, aux, caches if with_cache else ()


def _stack_ssm(params, x, cfg, *, with_cache: bool, use_kernel: bool = False):
    B = x.shape[0]

    def body(h, lp):
        cache = init_ssm_cache(B, cfg, h.dtype) if with_cache else None
        h, new_cache = _ssm_block(lp, h, cfg, cache=cache, use_kernel=use_kernel)
        return h, (new_cache if with_cache else 0)

    x, caches = jax.lax.scan(_maybe_remat(body, cfg), x, params["layers"])
    return x, caches if with_cache else ()


def _stack_hybrid(params, x, cfg, windows, positions, *, with_cache: bool, cache_cap: int = 0):
    """zamba2: groups of ``shared_attn_every`` mamba layers, each followed by
    the single shared attention block. Scan over groups; inner scan over the
    group's mamba layers (params reshaped to (G, k, ...))."""
    k = cfg.shared_attn_every
    G = cfg.num_layers // k
    B = x.shape[0]
    grouped = jax.tree.map(lambda a: a.reshape((G, k) + a.shape[1:]), params["layers"])
    shared = params["shared_attn"]
    w = windows[0] if windows.shape[0] else jnp.int32(0)

    def group_body(carry, inp):
        h, _ = carry
        glp = inp

        def inner(hh, lp):
            cache = init_ssm_cache(B, cfg, hh.dtype) if with_cache else None
            hh, c = _ssm_block(lp, hh, cfg, cache=cache)
            return hh, (c if with_cache else 0)

        h, ssm_caches = jax.lax.scan(inner, h, glp)
        cache = (init_kv_cache(B, cache_cap, cfg.num_kv_heads, cfg.resolved_head_dim, h.dtype)
                 if with_cache else None)
        h, _, akv = _attn_block(shared, h, cfg, w, positions, cache=cache)
        return (h, 0.0), (ssm_caches if with_cache else 0, akv if with_cache else 0)

    (x, _), (ssm_caches, attn_caches) = jax.lax.scan(
        _maybe_remat(group_body, cfg), (x, 0.0), grouped)
    if with_cache:
        # ssm_caches leaves: (G, k, B, ...) → (L, B, ...)
        ssm_caches = jax.tree.map(lambda a: a.reshape((G * k,) + a.shape[2:]), ssm_caches)
        return x, ssm_caches, attn_caches
    return x, (), ()


def _encode_audio(params, frames, cfg):
    """Whisper encoder over (projected) stub frame embeddings: non-causal."""
    x = frames @ params["frontend_proj"]
    positions = jnp.arange(x.shape[1])[None, :]

    def body(h, lp):
        a, _ = attn_forward(lp["attn"], rms_norm(h, lp["ln1"], cfg.norm_eps),
                            _no_cross(cfg), window=0, positions=positions)
        h = h + a
        h = h + gelu_mlp(lp["mlp"], rms_norm(h, lp["ln2"], cfg.norm_eps))
        return h, 0

    x, _ = jax.lax.scan(body, x, params["enc_layers"])
    return rms_norm(x, params["enc_norm"], cfg.norm_eps)


def _stack_audio_decoder(params, x, enc_out, cfg, positions, *, with_cache: bool,
                         cache_cap: int = 0):
    """Whisper decoder: causal self-attn + cross-attn to enc_out + GELU MLP."""
    B, S, _ = x.shape
    from .attention import _qkv, attend_full  # cross-attn building blocks

    def body(carry, lp):
        h, _ = carry
        cache = (init_kv_cache(B, cache_cap, cfg.num_kv_heads, cfg.resolved_head_dim, h.dtype)
                 if with_cache else None)
        a, kv = attn_forward(lp["attn"], rms_norm(h, lp["ln1"], cfg.norm_eps), cfg,
                             window=0, positions=positions, cache=cache)
        h = h + a
        # cross attention (non-causal over encoder tokens)
        hq = rms_norm(h, lp["ln_x"], cfg.norm_eps)
        q, _, _ = _qkv(lp["xattn"], hq, cfg)
        _, ck, cv = _qkv(lp["xattn"], enc_out, cfg)
        mask = jnp.ones((1, 1, S, enc_out.shape[1]), bool)
        xa = attend_full(q, ck, cv, mask)
        hd = cfg.resolved_head_dim
        h = h + xa.reshape(B, S, cfg.num_heads * hd) @ lp["xattn"]["wo"]
        h = h + gelu_mlp(lp["mlp"], rms_norm(h, lp["ln2"], cfg.norm_eps))
        return (h, 0.0), ((kv, (ck, cv)) if with_cache else 0)

    (x, _), caches = jax.lax.scan(body, (x, 0.0), params["layers"])
    if with_cache:
        return x, caches[0], caches[1]
    return x, (), ()


def _maybe_remat(body, cfg):
    """Per-layer activation checkpointing for big configs (train memory)."""
    if getattr(cfg, "_remat", True):
        return jax.checkpoint(body, prevent_cse=False)
    return body


# ---------------------------------------------------------------------------
# embedding / head
# ---------------------------------------------------------------------------

def _embed(params, tokens, cfg):
    x = params["embed"][tokens]
    if cfg.arch_type in ("dense", "vlm") or cfg.arch_type == "moe":
        x = x * jnp.sqrt(cfg.d_model).astype(x.dtype) if cfg.logit_softcap else x
    return x


def _logits(params, x, cfg):
    head = params.get("lm_head", None)
    logits = x @ head if head is not None else x @ params["embed"].T
    logits = logits.astype(jnp.float32)
    if cfg.logit_softcap:
        logits = softcap(logits, cfg.logit_softcap)
    return logits


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def _forward_seq(params, cfg, batch, *, with_cache: bool = False, cache_cap: int = 0,
                 long_context: bool = False):
    """Shared full-sequence path. batch: {"tokens", optional "embeds"}.
    Returns (hidden (B,S_total,D), aux, caches, n_prefix)."""
    tokens = batch["tokens"]
    x = _embed(params, tokens, cfg)
    n_prefix = 0
    windows = layer_windows(cfg, long_context=long_context)
    positions = None
    if cfg.arch_type == "vlm":
        patches = batch["embeds"] @ params["frontend_proj"]
        x = jnp.concatenate([patches.astype(x.dtype), x], axis=1)
        n_prefix = patches.shape[1]
    if cfg.arch_type == "audio":
        enc_out = _encode_audio(params, batch["embeds"], cfg)
        positions = jnp.arange(x.shape[1])[None, :]
        x, kv, cross = _stack_audio_decoder(params, x, enc_out, cfg, positions,
                                            with_cache=with_cache, cache_cap=cache_cap)
        return rms_norm(x, params["final_norm"], cfg.norm_eps), 0.0, Caches(kv=kv, cross_kv=cross), 0
    positions = jnp.arange(x.shape[1])[None, :]
    if cfg.arch_type == "ssm":
        x, caches = _stack_ssm(params, x, cfg, with_cache=with_cache)
        caches = Caches(ssm=caches)
        aux = 0.0
    elif cfg.arch_type == "hybrid":
        x, ssm_c, attn_c = _stack_hybrid(params, x, cfg, windows, positions,
                                         with_cache=with_cache, cache_cap=cache_cap)
        caches = Caches(ssm=ssm_c, shared_kv=attn_c)
        aux = 0.0
    else:
        x, aux, kv = _stack_dense(params, x, cfg, windows, positions,
                                  with_cache=with_cache, cache_cap=cache_cap)
        caches = Caches(kv=kv)
    return rms_norm(x, params["final_norm"], cfg.norm_eps), aux, caches, n_prefix


def _nll_sum(params, x, labels, cfg):
    """Σ nll over valid positions + valid count, for one (B, c, D) chunk.

    nll = logsumexp(logits) − logits[label], written entirely as REDUCTIONS
    over the vocab axis (max / sum / masked-sum) — a ``take_along_axis``
    gather on a vocab-sharded logits tensor forces GSPMD to all-gather the
    full (B, c, V) block per chunk (≈8 GB f32 at V=256k), whereas reductions
    stay sharded and only their scalar partials cross chips. Runs under the
    ``repro.logits`` scope.
    """
    with obs.scope("logits"):
        logits = _logits(params, x, cfg)          # (B,c,V) f32
        valid = labels >= 0
        safe = jnp.where(valid, labels, 0)
        m = jnp.max(logits, axis=-1)
        lse = m + jnp.log(jnp.sum(jnp.exp(logits - m[..., None]), axis=-1))
        onehot = (jnp.arange(logits.shape[-1])[None, None, :]
                  == safe[..., None])
        target = jnp.sum(jnp.where(onehot, logits, 0.0), axis=-1)
        nll = lse - target
        return (jnp.sum(nll * valid).astype(jnp.float32),
                jnp.sum(valid).astype(jnp.int32))


def loss_chunk_for(cfg, batch_size: int, budget_bytes: float = 2e9) -> int:
    """Sequence-chunk length keeping the (B, c, V) f32 logits under budget —
    big-vocab models (gemma2: 256k) cannot materialize (B, S, V) at once."""
    c = budget_bytes / (4.0 * batch_size * cfg.vocab_size)
    return max(64, int(2 ** np.floor(np.log2(max(c, 64)))))


def train_loss(params, cfg, batch, *, aux_weight: float = 0.01,
               loss_chunk: int | None = None):
    """Causal-LM next-token loss. batch: tokens (B,S), labels (B,S) with
    -100 = ignore; vlm/audio additionally embeds (B,T,D).

    The unembedding + cross-entropy is scanned over sequence chunks so the
    f32 logits never materialize at (B, S, V) — with 256k vocabs that single
    tensor would dwarf the model. ``loss_chunk=None`` picks a chunk from a
    2 GB logits budget; pass 0 to disable chunking.
    """
    x, aux, _, n_prefix = _forward_seq(params, cfg, batch, with_cache=False)
    if n_prefix:
        x = x[:, n_prefix:]
    labels = batch["labels"]
    B, S, _ = x.shape
    if loss_chunk is None:
        loss_chunk = loss_chunk_for(cfg, B)
    if loss_chunk and S % loss_chunk == 0 and S > loss_chunk:
        nc = S // loss_chunk
        xc = x.reshape(B, nc, loss_chunk, -1).transpose(1, 0, 2, 3)
        lc = labels.reshape(B, nc, loss_chunk).transpose(1, 0, 2)

        def body(carry, inp):
            s, n = carry
            xi, li = inp
            si, ni = jax.checkpoint(
                lambda a, b: _nll_sum(params, a, b, cfg))(xi, li)
            return (s + si, n + ni), None

        (tot, cnt), _ = jax.lax.scan(body, (jnp.float32(0), jnp.int32(0)), (xc, lc))
    else:
        tot, cnt = _nll_sum(params, x, labels, cfg)
    loss = tot / jnp.maximum(cnt, 1)
    return loss + aux_weight * aux


def prefill(params, cfg, batch, *, cache_cap: int | None = None, long_context: bool = False):
    """Prefill: full forward writing KV/SSM caches. Returns (last_logits, caches)."""
    S = batch["tokens"].shape[1]
    if cfg.arch_type == "vlm":
        S = S + cfg.frontend_tokens  # patch prefix occupies cache slots too
    if cache_cap is None:
        w = int(cfg.sliding_window) if cfg.sliding_window else 0
        cache_cap = min(S, w) if (w and long_context) else S
    x, _, caches, _ = _forward_seq(params, cfg, batch, with_cache=True,
                                   cache_cap=cache_cap, long_context=long_context)
    return _logits(params, x[:, -1:], cfg), caches


def init_caches(cfg, batch_size: int, cache_cap: int, dtype=None) -> Caches:
    """Empty decode caches sized for ``cache_cap`` past positions."""
    dtype = dtype or _dtype(cfg)
    L, B = cfg.num_layers, batch_size
    if cfg.arch_type == "ssm":
        c = init_ssm_cache(B, cfg, dtype)
        return Caches(ssm=jax.tree.map(lambda a: jnp.broadcast_to(a, (L,) + a.shape), c))
    if cfg.arch_type == "hybrid":
        c = init_ssm_cache(B, cfg, dtype)
        ssm = jax.tree.map(lambda a: jnp.broadcast_to(a, (L,) + a.shape), c)
        G = cfg.num_layers // cfg.shared_attn_every
        kv = init_kv_cache(B, cache_cap, cfg.num_kv_heads, cfg.resolved_head_dim, dtype)
        shared = jax.tree.map(lambda a: jnp.broadcast_to(a, (G,) + a.shape), kv)
        return Caches(ssm=ssm, shared_kv=shared)
    kv = init_kv_cache(B, cache_cap, cfg.num_kv_heads, cfg.resolved_head_dim, dtype)
    kv = jax.tree.map(lambda a: jnp.broadcast_to(a, (L,) + a.shape), kv)
    if cfg.arch_type == "audio":
        xkv = init_kv_cache(B, max(cfg.frontend_tokens, 1), cfg.num_kv_heads,
                            cfg.resolved_head_dim, dtype)
        cross = jax.tree.map(lambda a: jnp.broadcast_to(a, (L,) + a.shape), xkv)
        return Caches(kv=kv, cross_kv=cross)
    return Caches(kv=kv)


def decode_step(params, cfg, token, caches: Caches, pos, *, long_context: bool = False,
                use_kernel: bool = False):
    """One-token decode. token: (B,1) int32; pos: scalar int32 absolute
    position. Returns (logits (B,1,V), new caches)."""
    x = _embed(params, token, cfg)
    windows = layer_windows(cfg, long_context=long_context)

    if cfg.arch_type == "ssm":
        def body(h, inp):
            lp, c = inp
            h2, nc = mamba2_decode(lp["mamba"], rms_norm(h, lp["ln"], cfg.norm_eps), cfg, c)
            return h + h2, nc
        x, ssm = jax.lax.scan(body, x, (params["layers"], caches.ssm))
        new = Caches(ssm=ssm)
    elif cfg.arch_type == "hybrid":
        k = cfg.shared_attn_every
        G = cfg.num_layers // k
        grouped = jax.tree.map(lambda a: a.reshape((G, k) + a.shape[1:]), params["layers"])
        gcaches = jax.tree.map(lambda a: a.reshape((G, k) + a.shape[1:]), caches.ssm)
        shared = params["shared_attn"]
        w = windows[0]

        def gbody(h, inp):
            glp, gc, akv = inp

            def inner(hh, i2):
                lp, c = i2
                h2, nc = mamba2_decode(lp["mamba"], rms_norm(hh, lp["ln"], cfg.norm_eps), cfg, c)
                return hh + h2, nc
            h, ssm_new = jax.lax.scan(inner, h, (glp, gc))
            a, nkv = attn_decode(shared["attn"], rms_norm(h, shared["ln1"], cfg.norm_eps),
                                 cfg, akv, pos, window=w, ring=long_context,
                                 use_kernel=use_kernel)
            h = h + a
            h = h + swiglu(shared["mlp"], rms_norm(h, shared["ln2"], cfg.norm_eps))
            return h, (ssm_new, nkv)
        x, (ssm_new, akv_new) = jax.lax.scan(gbody, x, (grouped, gcaches, caches.shared_kv))
        ssm_new = jax.tree.map(lambda a: a.reshape((G * k,) + a.shape[2:]), ssm_new)
        new = Caches(ssm=ssm_new, shared_kv=akv_new)
    elif cfg.arch_type == "audio":
        B = x.shape[0]
        hd = cfg.resolved_head_dim
        from .attention import _qkv, attend_full

        def body(h, inp):
            lp, kv, (ck, cv) = inp
            a, nkv = attn_decode(lp["attn"], rms_norm(h, lp["ln1"], cfg.norm_eps), cfg,
                                 kv, pos, window=jnp.int32(0), use_kernel=use_kernel)
            h = h + a
            hq = rms_norm(h, lp["ln_x"], cfg.norm_eps)
            q, _, _ = _qkv(lp["xattn"], hq, cfg)
            mask = jnp.ones((1, 1, 1, ck.shape[1]), bool)
            xa = attend_full(q, ck, cv, mask)
            h = h + xa.reshape(B, 1, cfg.num_heads * hd) @ lp["xattn"]["wo"]
            h = h + gelu_mlp(lp["mlp"], rms_norm(h, lp["ln2"], cfg.norm_eps))
            return h, nkv
        x, kv_new = jax.lax.scan(body, x, (params["layers"], caches.kv, caches.cross_kv))
        new = Caches(kv=kv_new, cross_kv=caches.cross_kv)
    else:
        def body(h, inp):
            lp, kv, w = inp
            a, nkv = attn_decode(lp["attn"], rms_norm(h, lp["ln1"], cfg.norm_eps), cfg,
                                 kv, pos, window=w, ring=long_context,
                                 use_kernel=use_kernel)
            h = h + a
            h2 = rms_norm(h, lp["ln2"], cfg.norm_eps)
            if "moe" in lp:
                out, _ = _moe(lp["moe"], h2, cfg,
                              min_capacity=h2.shape[0] * cfg.experts_per_token)
            else:
                out = swiglu(lp["mlp"], h2)
            return h + out, nkv
        x, kv_new = jax.lax.scan(body, x, (params["layers"], caches.kv, windows))
        new = Caches(kv=kv_new)

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return _logits(params, x, cfg), new
