"""Whole-step model FLOP utilisation of the traced training rounds: model
FLOPs of a round (``bench/flops.py``) over the round's wall time, over chips
times the chip's bf16 peak, in %. The round's wall time is its
``bench.round`` span on the host clock, feed included; the rounds are those
of the traced window."""
from bench import flops


def read(ctx):
    rounds = ctx.trace["unit_s"] if ctx.trace else []
    if not rounds:
        return None
    t = ctx.traffic
    f = flops.train_round_flops(ctx.config, t["workers"], t["batch_per_worker"],
                                t["seq_len"])
    return 100.0 * f * len(rounds) / (sum(rounds) * ctx.chips
                                      * ctx.peaks["bf16_flops_per_s"])
