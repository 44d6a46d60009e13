"""Share of the region window in which no operation ran on the device."""


def read(ctx):
    if not ctx.trace.device_events(ctx.events):
        return None
    return 100.0 * (1.0 - ctx.trace.busy_ns(ctx.events, ctx.lo, ctx.hi) / (ctx.hi - ctx.lo))
