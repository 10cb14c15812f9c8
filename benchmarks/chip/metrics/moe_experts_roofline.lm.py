"""The grouped expert product's share of its roofline: the FLOPs it runs
a round (``flops_lm.expert_flops``, from the routed pairs) over its
device time under ``moe/experts``, against the lesser of the chip's
bf16 peak and its FLOP/byte times the HBM bandwidth (``peaks.json``).
The time holds every op of the scope, fused with another scope's or not,
so the share is never read above what the product achieved."""


def read(ctx):
    ms = ctx.counters.get("scope_ms", {}).get("moe/experts")
    flops = ctx.counters.get("expert_flops_per_round")
    nbytes = ctx.counters.get("expert_bytes_per_round")
    if not ms or not flops or not nbytes:
        return None
    bound = min(ctx.peaks["bf16_flops"],
                flops / nbytes * ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * flops / (ms / 1e3) / bound
