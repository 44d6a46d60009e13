"""Host milliseconds per request in the region planner itself: each plan's
time (span ``decision``) outside phase-1 (``region_phase1``), the row
solve's dispatch (``region_rows``) and the row solve's device execution
(``jit__rows_core``), which the host waits out when it fetches the results.
What is left is the planner's own Python and NumPy: the policy's keys, the
per-app rate update, building the rows, scattering the results, the
emergency offload and the plan's copies."""
import re

PROGRAM = r"^jit__rows_core\("


def read(ctx):
    n = ctx.counters["requests"]
    tr = ctx.trace
    plans = tr.spans(ctx.events, "decision")
    if not n or not plans:
        return None
    inner = [(e["start_ns"], e["start_ns"] + e["dur_ns"])
             for name in ("region_phase1", "region_rows") for e in tr.spans(ctx.events, name)]
    inner += [(e["start_ns"], e["start_ns"] + e["dur_ns"])
              for e in tr.device_events(ctx.events) if re.search(PROGRAM, e["name"])]
    inner.sort()
    total = 0.0
    for p in plans:
        lo, hi = p["start_ns"], p["start_ns"] + p["dur_ns"]
        total += p["dur_ns"] - tr.union_ns([iv for iv in inner if iv[0] < hi and iv[1] > lo],
                                           lo, hi)
    return total / n / 1e6
