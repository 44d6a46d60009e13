"""Device nanoseconds per simulated customer in the vector DES scan: the
executions of the jitted ``des_vector._segment_scan_jax`` program."""

PROGRAM = r"_segment_scan_jax"


def read(ctx):
    n = ctx.counters["customers"]
    t = ctx.trace.device_ns(ctx.events, PROGRAM, ctx.lo, ctx.hi, line=ctx.trace.MODULES_LINE)
    return t / n if n and t > 0 else None
