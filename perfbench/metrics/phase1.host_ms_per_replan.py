"""Milliseconds per request in phase-1 (``engine.find_feasible_start_batch``,
host NumPy), from the spans around its calls."""


def read(ctx):
    n = ctx.counters["requests"]
    if not n or not ctx.trace.spans(ctx.events, "phase1"):
        return None
    return ctx.trace.span_ns(ctx.events, "phase1") / n / 1e6
