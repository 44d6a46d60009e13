"""Device milliseconds per request in the grid seeding kernel
(``kernels/crms_grid``, Pallas). The kernel is dispatched eagerly, as its
own program, which the trace names only ``jit_wrapped``; it is told apart
as the ``jit_wrapped`` program that runs inside ``grid_seed_chints``."""

PROGRAM = r"^jit_wrapped\("
SPAN = "grid_seed"


def read(ctx):
    n = ctx.counters["requests"]
    t = ctx.trace.device_ns_within(ctx.events, PROGRAM, SPAN, ctx.lo, ctx.hi)
    return t / n / 1e6 if n and t > 0 else None
