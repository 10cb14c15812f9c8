"""Whole-step share of the chip's bf16 peak: the training FLOPs the
rounds of the traced window require (flops.py) over its seconds."""


def read(ctx):
    t, rounds = ctx.trace, ctx.counters.get("rounds")
    per_round = ctx.counters.get("flops_per_round")
    if t is None or not rounds or not per_round or t.window_s <= 0:
        return None
    return 100.0 * per_round * rounds / t.window_s / ctx.peaks["bf16_flops"]
