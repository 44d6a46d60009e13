"""Device milliseconds per request in the region's row solve: the executions
of the jitted ``engine._rows_core`` program (``engine.ip_solve_rows`` on one
device), which the trace names ``jit__rows_core``."""

PROGRAM = r"^jit__rows_core\("


def read(ctx):
    n = ctx.counters["requests"]
    t = ctx.trace.device_ns(ctx.events, PROGRAM, ctx.lo, ctx.hi, line=ctx.trace.MODULES_LINE)
    return t / n / 1e6 if n and t > 0 else None
