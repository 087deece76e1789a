"""Host milliseconds of the actor_gen call per iteration, synced on its
outputs."""

LAYER = "runtime calls"
UNIT = "ms/iter"
MOVES = "tokens_per_s"
CALLS = ("actor_gen",)


def read(ctx):
    spans = ctx.trace.call_span_s
    if not ctx.iterations or not all(c in spans for c in CALLS):
        return None
    return 1e3 * sum(spans[c] for c in CALLS) / ctx.iterations
