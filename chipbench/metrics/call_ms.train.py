"""Host milliseconds of the actor_train and critic_train calls per
iteration, synced on their outputs."""

LAYER = "runtime calls"
UNIT = "ms/iter"
MOVES = "tokens_per_s"
CALLS = ("actor_train", "critic_train")


def read(ctx):
    spans = ctx.trace.call_span_s
    if not ctx.iterations or not all(c in spans for c in CALLS):
        return None
    return 1e3 * sum(spans[c] for c in CALLS) / ctx.iterations
