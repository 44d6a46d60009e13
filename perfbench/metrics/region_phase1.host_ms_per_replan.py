"""Milliseconds per request in the region planner's phase-1
(``engine.find_feasible_start_batch`` as ``core.placement`` calls it, host
NumPy: the hinted call and the waterfill fallback), from the spans around
its calls."""


def read(ctx):
    n = ctx.counters["requests"]
    if not n or not ctx.trace.spans(ctx.events, "region_phase1"):
        return None
    return ctx.trace.span_ns(ctx.events, "region_phase1") / n / 1e6
