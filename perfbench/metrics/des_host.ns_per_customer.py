"""Host nanoseconds per simulated customer in a validation job outside the
segment scan: building the simulator, ``configure()`` and the hand-off
between segments, the common-random-number draws, and reading the responses
back (the job span's self time, less the ``segment_scan`` calls)."""


def read(ctx):
    n = ctx.counters["customers"]
    if not n or not ctx.trace.spans(ctx.events, "job"):
        return None
    return ctx.trace.self_ns(ctx.events, "job", ["segment_scan"]) / n
