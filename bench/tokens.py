"""Seeded token pool: a copy of the program's hidden-bigram Markov generator
(``repro.data.pipeline``), vectorised over every row of the pool at once.

Every token has ``SUCCESSORS`` likely successors drawn from a table that the
seed fixes, so the stream is learnable and the loss can fall. The program
receives only the generated tokens and labels; nothing here imports it.
"""
from __future__ import annotations

import numpy as np

SUCCESSORS = 4
IGNORE = -100


def successor_table(vocab: int, rng: np.random.Generator):
    """``(succ (vocab, k) int32, cdf (vocab, k))``: token v is followed by
    ``succ[v, j]`` with probability ``cdf[v, j] - cdf[v, j - 1]``."""
    succ = rng.integers(0, vocab, size=(vocab, SUCCESSORS)).astype(np.int32)
    p = np.exp(rng.normal(size=(vocab, SUCCESSORS)) * 2.0)
    cdf = np.cumsum(p / p.sum(axis=1, keepdims=True), axis=1)
    cdf[:, -1] = 1.0
    return succ, cdf


def token_pool(seed: int, vocab: int, shape: tuple[int, ...], seq_len: int
               ) -> tuple[np.ndarray, np.ndarray]:
    """``(tokens, labels)`` of shape ``shape + (seq_len,)``, int32. Labels are
    the next token, and the last position of each row is ignored
    (``IGNORE``). The same seed gives the same pool; every seed gives the
    same sizes."""
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0x70C5]))
    succ, cdf = successor_table(vocab, rng)
    rows = int(np.prod(shape))
    toks = np.empty((rows, seq_len), np.int32)
    toks[:, 0] = rng.integers(0, vocab, size=rows)
    u = rng.random((rows, seq_len))
    for t in range(1, seq_len):
        prev = toks[:, t - 1]
        j = np.minimum((cdf[prev] <= u[:, t, None]).sum(axis=1), SUCCESSORS - 1)
        toks[:, t] = succ[prev, j]
    labels = np.concatenate([toks[:, 1:], np.full((rows, 1), IGNORE, np.int32)],
                            axis=1)
    return (toks.reshape(shape + (seq_len,)),
            labels.reshape(shape + (seq_len,)))
