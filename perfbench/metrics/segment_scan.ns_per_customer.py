"""Host nanoseconds per simulated customer inside ``des_vector.segment_scan``:
moving the segment's arrays to the device, the scan itself, and bringing
the waits back (``kw_scan.device_ns_per_customer`` is the scan's device
share of it)."""


def read(ctx):
    n = ctx.counters["customers"]
    if not n or not ctx.trace.spans(ctx.events, "segment_scan"):
        return None
    return ctx.trace.span_ns(ctx.events, "segment_scan") / n
