"""CRMS refinement iterations per request (``Diagnostics.refine_iters``),
a request the threshold skips counting zero."""


def read(ctx):
    n = ctx.counters["requests"]
    return sum(ctx.counters["refine_iters"]) / n if n else None
