"""Milliseconds per request in ``crms()`` itself: the refinement loop's host
Python and its eager float64 ``evaluate`` calls, less Algorithm 1 and the
batched P1 solves (phase-1, grid seeding and the interior point)."""


def read(ctx):
    n = ctx.counters["requests"]
    if not n or not ctx.trace.spans(ctx.events, "crms"):
        return None
    return ctx.trace.self_ns(ctx.events, "crms", ["algorithm1", "p1_solve_batch"]) / n / 1e6
