"""round_mfu: model FLOP utilisation of the whole round, traced window.

Forward and backward model FLOPs of every client step the window's rounds
ran (``chipbench.flops.round_model_flops``; no optimizer, codec or
recomputed FLOPs), over the window's length times the chips' bf16 peak.
"""


def read(ctx):
    r = ctx.reduced
    if not r.n_devices or r.window_s <= 0:
        return None
    return 100.0 * ctx.round_flops * ctx.rounds / (
        r.window_s * r.n_devices * ctx.peaks.bf16_flops)
