"""Host milliseconds per request in calls of the grid seeding kernel
(``kernels/crms_grid.crms_grid_eval``, called eagerly): building its padded
inputs, tracing the ``pallas_call``, getting its program (compiled, or read
from the persistent cache), and dispatching it."""


def read(ctx):
    n = ctx.counters["requests"]
    if not n or not ctx.trace.spans(ctx.events, "crms_grid"):
        return None
    return ctx.trace.span_ns(ctx.events, "crms_grid") / n / 1e6
