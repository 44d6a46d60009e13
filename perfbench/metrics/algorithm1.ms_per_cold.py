"""Milliseconds per cold re-plan in Algorithm 1 (``crms.algorithm1``: the SP1
bisection and the SP2 argmin), from the spans around its calls."""


def read(ctx):
    cold = ctx.counters["cold"]
    if not cold or not ctx.trace.spans(ctx.events, "algorithm1"):
        return None
    return ctx.trace.span_ns(ctx.events, "algorithm1") / cold / 1e6
