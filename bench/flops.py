"""Model FLOPs of a training round, from the configuration's sizes alone.

The numerator of ``train.step_mfu``: the operations the forward and backward
passes require, 6 per matmul parameter per token (2 forward, 4 backward), plus
causal attention's score and value products, 3 x (2 x 2 x S x S x H x hd) per
layer and sequence, counted over the full S x S square as the standard
accounting does. Recomputed (rematerialised) operations do not count. The
embedding lookup is a gather, not a matmul; with tied embeddings the same
matrix is the output head and counts once, as the head (an untied input
table is a gather too, so the count is the same). Norm scales are
elementwise and do not count. Copied in spirit from the program's
``roofline.analysis.model_flops`` and ``_attn_flops``.
"""
from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    """Parameters that enter a matrix multiplication per token."""
    d = cfg["hidden_size"]
    hd = cfg.get("head_dim") or d // cfg["num_attention_heads"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    attn = d * q + 2 * d * kv + q * d
    mlp = 3 * d * cfg["intermediate_size"]
    head = cfg["vocab_size"] * d
    return cfg["num_hidden_layers"] * (attn + mlp) + head


def attention_flops(cfg: dict, sequences: int, seq_len: int) -> float:
    """Forward and backward score and value products."""
    hd = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    fwd = 2.0 * 2.0 * sequences * seq_len * seq_len \
        * cfg["num_attention_heads"] * hd * cfg["num_hidden_layers"]
    return 3.0 * fwd


def train_round_flops(cfg: dict, workers: int, batch: int, seq_len: int) -> float:
    """Model FLOPs of one round: every worker's forward and backward on its
    ``batch x seq_len`` tokens. The gossip mix and the optimizer are not
    model FLOPs."""
    sequences = workers * batch
    tokens = sequences * seq_len
    return 6.0 * matmul_params(cfg) * tokens + attention_flops(cfg, sequences,
                                                              seq_len)
