"""Model FLOPs of the credited tokens in the traced window over the
window, the chips and the bf16 peak (%): the whole step's share of peak.
Masked sequences are computed but not credited, so they do not count."""
from bench import counts


def read(ctx):
    if ctx.credited_tokens <= 0:
        return None
    flops = ctx.credited_tokens * counts.train_flops_per_token(
        ctx.config, ctx.traffic["seq_len"])
    return 100.0 * flops / (ctx.window_s * ctx.chips
                            * ctx.peak["bf16_flops_per_s"])
