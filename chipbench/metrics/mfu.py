"""Model FLOPs of the traced iterations over window seconds x chips x the
chip's peak bf16 FLOP/s: forward FLOPs of generation and the three
inference calls, forward and backward of the two train calls."""

LAYER = "iteration"
UNIT = "%"
MOVES = "tokens_per_s"


def read(ctx):
    if not ctx.iterations or ctx.trace.window_s <= 0:
        return None
    flops = ctx.iterations * sum(c["flops"] for c in ctx.costs.values())
    return 100.0 * flops / (ctx.trace.window_s * ctx.chips
                            * ctx.peak["flops_per_s"])
