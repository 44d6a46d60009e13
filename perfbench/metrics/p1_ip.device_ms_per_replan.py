"""Device milliseconds per request in the P1 interior point: the executions
of the jitted ``engine._ip_solve_batched`` program in the trace."""

PROGRAM = r"_ip_solve_batched"


def read(ctx):
    n = ctx.counters["requests"]
    t = ctx.trace.device_ns(ctx.events, PROGRAM, ctx.lo, ctx.hi, line=ctx.trace.MODULES_LINE)
    return t / n / 1e6 if n and t > 0 else None
