"""Deterministic synthetic data substrate.

Two pipelines:

1. ``token_pipeline`` — language-model batches {tokens, labels} with a
   *learnable* structure (a hidden sparse bigram Markov chain: every token
   has ``SUCCESSORS`` likely successors) so training loss
   demonstrably decreases; used by the end-to-end DSGD example and the
   per-arch smoke tests. VLM/audio archs additionally get stub ``embeds``
   (the brief's frontend carve-out).

2. ``make_classification_data`` + ``class_balanced_partition`` — mirrors the
   paper's §VI-B protocol: "each node randomly samples the same number of
   samples from each class" (IID class-balanced CIFAR-like partition), on a
   synthetic Gaussian-mixture task so the decentralized-vs-topology
   comparisons of Table II can run offline.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache

import numpy as np

import jax.numpy as jnp

__all__ = ["DataConfig", "token_pipeline", "synthetic_lm_batch", "synthetic_batches",
           "make_classification_data", "class_balanced_partition",
           "epoch_permutations"]


@dataclass(frozen=True)
class DataConfig:
    vocab_size: int
    seq_len: int
    batch_size: int           # per-node batch
    frontend_tokens: int = 0  # > 0 → provide stub embeds (vlm/audio)
    d_model: int = 0          # embed dim for stub embeds
    seed: int = 0


#: Successors per token in the hidden bigram chain.
SUCCESSORS = 4


@lru_cache(maxsize=8)
def _successor_table(vocab: int, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """The hidden chain as ``(succ (vocab, k) int32, cdf (vocab, k))``:
    token v is followed by ``succ[v, j]`` with probability
    ``cdf[v, j] − cdf[v, j−1]`` (softmax of N(0, 2²) logits, low entropy so
    the chain is learnable). O(vocab·k) to build and hold, so it exists at
    any published vocabulary size; cached per ``(vocab, seed)``."""
    rng = np.random.default_rng(seed)
    succ = rng.integers(0, vocab, size=(vocab, SUCCESSORS)).astype(np.int32)
    p = np.exp(rng.normal(size=(vocab, SUCCESSORS)) * 2.0)
    cdf = np.cumsum(p / p.sum(axis=1, keepdims=True), axis=1)
    cdf[:, -1] = 1.0
    succ.setflags(write=False)
    cdf.setflags(write=False)
    return succ, cdf


def synthetic_lm_batch(cfg: DataConfig, step: int, node: int = 0) -> dict:
    """One {tokens, labels(, embeds)} batch. Pure function of (cfg, step, node)
    so every DSGD worker regenerates its own shard without host state."""
    rng = np.random.default_rng(
        np.random.SeedSequence([cfg.seed, node, step]))
    succ, cdf = _successor_table(cfg.vocab_size, cfg.seed)
    B, S = cfg.batch_size, cfg.seq_len
    toks = np.empty((B, S), dtype=np.int32)
    toks[:, 0] = rng.integers(0, cfg.vocab_size, size=B)
    u = rng.random((B, S))
    for t in range(1, S):
        prev = toks[:, t - 1]
        j = np.minimum((cdf[prev] <= u[:, t, None]).sum(axis=1),
                       SUCCESSORS - 1)
        toks[:, t] = succ[prev, j]
    batch = {
        "tokens": jnp.asarray(toks),
        "labels": jnp.asarray(np.concatenate(
            [toks[:, 1:], np.full((B, 1), -100, np.int32)], axis=1)),
    }
    if cfg.frontend_tokens:
        emb = rng.normal(size=(B, cfg.frontend_tokens, cfg.d_model)).astype(np.float32)
        batch["embeds"] = jnp.asarray(emb)
    return batch


def token_pipeline(cfg: DataConfig, node: int = 0):
    """Infinite iterator of LM batches for one worker."""
    step = 0
    while True:
        yield synthetic_lm_batch(cfg, step, node)
        step += 1


def synthetic_batches(cfg: DataConfig, steps: int, node: int = 0) -> list[dict]:
    return [synthetic_lm_batch(cfg, s, node) for s in range(steps)]


# ---------------------------------------------------------------------------
# classification substrate for the DSGD topology experiments (paper §VI-B)
# ---------------------------------------------------------------------------

def make_classification_data(num_classes: int = 10, dim: int = 64,
                             samples_per_class: int = 512, seed: int = 0,
                             class_sep: float = 3.0, noise_seed: int | None = None):
    """Gaussian-mixture classification set (CIFAR-10 stand-in, offline).

    ``seed`` fixes the class means (the task); ``noise_seed`` draws the
    samples — pass a different noise_seed for a held-out test split of the
    SAME task. Returns (X (N, dim) f32, y (N,) i32)."""
    rng = np.random.default_rng(seed)
    means = rng.normal(size=(num_classes, dim)) * class_sep / np.sqrt(dim)
    rng = np.random.default_rng(seed if noise_seed is None else noise_seed)
    # one (C, S, D) draw consumes the PCG64 stream exactly like C sequential
    # (S, D) draws, so this stays bit-identical to the seed per-class loop
    noise = rng.normal(size=(num_classes, samples_per_class, dim))
    X = (means[:, None, :] + noise).reshape(-1, dim).astype(np.float32)
    y = np.repeat(np.arange(num_classes, dtype=np.int32), samples_per_class)
    perm = rng.permutation(len(y))
    return X[perm], y[perm]


def epoch_permutations(parts: list[np.ndarray], epochs: int, batch: int,
                       seed: int = 0) -> np.ndarray:
    """Per-worker minibatch gather indices for a whole training run, as ONE
    int tensor of shape ``(epochs, iters, n, batch)`` (``iters`` = shared
    iterations per epoch = min partition length // batch).

    ``out[e, it, w]`` indexes the global X/y arrays for worker ``w``'s
    ``it``-th minibatch of epoch ``e`` — the device-resident engine gathers
    batches inside its scan (``X[idx]``) instead of host-assembling a
    ``jnp.stack`` per step. Index generation itself stays on the host
    numpy Generator, consuming the SAME stream as the per-epoch loop
    (``rng.permutation(part)`` per worker per epoch), so batch order is
    bit-identical to the host oracle given a seed. int32: device gather
    indices, and every consumer traces one dtype.
    """
    n = len(parts)
    per = min(len(p) for p in parts)
    iters = per // batch
    rng = np.random.default_rng(seed)
    out = np.empty((epochs, iters, n, batch), np.int32)
    for e in range(epochs):
        for w, p in enumerate(parts):
            order = rng.permutation(p)[: iters * batch]
            out[e, :, w, :] = order.reshape(iters, batch)
    return out


def class_balanced_partition(y: np.ndarray, n_nodes: int, seed: int = 0) -> list[np.ndarray]:
    """Paper §VI-B: each node samples the same number of samples per class."""
    rng = np.random.default_rng(seed)
    parts: list[list[int]] = [[] for _ in range(n_nodes)]
    for c in np.unique(y):
        idx = np.nonzero(y == c)[0]
        rng.shuffle(idx)
        take = (len(idx) // n_nodes) * n_nodes
        for k, chunk in enumerate(np.split(idx[:take], n_nodes)):
            parts[k].extend(chunk.tolist())
    return [np.asarray(sorted(p), dtype=np.int64) for p in parts]
