"""Least time of the two train calls on the chip (each the larger of its
FLOPs over peak FLOP/s and its bytes over peak HBM bytes/s) over the device
busy time inside their spans."""

from chipbench.flops import least_seconds

LAYER = "models"
UNIT = "%"
MOVES = "tokens_per_s"
CALLS = ("actor_train", "critic_train")


def read(ctx):
    busy = sum(ctx.trace.call_device_s.get(c, 0.0) for c in CALLS)
    if not ctx.iterations or busy <= 0:
        return None
    least = sum(least_seconds(ctx.costs[c], ctx.peak)[0] for c in CALLS)
    return 100.0 * ctx.iterations * least / busy
