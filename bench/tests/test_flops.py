"""The FLOP function against a hand count for SmolLM-135M."""
from bench import flops, loader


def test_smollm_135m_round_at_4x1x2048():
    cfg = loader.config("smollm-135m")
    d, f, L, V, hd = 576, 1536, 30, 49152, 64
    per_layer = (d * 9 * hd + 2 * d * 3 * hd + 9 * hd * d) + 3 * d * f
    n_mm = L * per_layer + V * d                   # tied head counts once
    assert n_mm == 134_479_872
    assert flops.matmul_params(cfg) == n_mm
    tokens = 4 * 1 * 2048
    attn = 3 * (2 * 2 * 4 * 2048 * 2048 * 9 * hd * L)
    want = 6 * n_mm * tokens + attn
    assert want == 10_088_878_178_304
    assert flops.train_round_flops(cfg, 4, 1, 2048) == want
