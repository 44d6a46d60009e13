#!/usr/bin/env python3
"""The program's own spans and counters, read from a traced window.

The program under test records host spans named ``repro.<name>``, with
integer stats, through the same ``jax.profiler`` session as the benchmark's
``bench.<name>`` spans (``src/repro/obs.py``), so they share one clock with
the device's programs. ``trace.load`` keeps only the benchmark's own spans,
and the readers under ``metrics/`` get what it loads, so no cell reports
these numbers yet (PERF.md, Open questions). This file holds what such a
reader needs: loading the program's spans, choosing them by name and stat,
putting device programs and the device's idle stretches to them, and the six
numbers of ``METRICS``, each a ``read(ctx)`` like a reader's.

    python3 perfbench/program_spans.py --workload <cell> --seed <n> --seconds <s>

runs one cell's traced window as ``run.py --trace 1`` does and prints, as one
JSON line, the six numbers beside the cell's per-layer metrics, each program
span's count and time, and both breakdowns of the device's idle time.
"""
from __future__ import annotations

import glob
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from perfbench import trace as tr  # noqa: E402

PREFIX = "repro."
P1_PROGRAM = r"_ip_solve_batched"


def load(trace_dir: str) -> list[dict]:
    """The program's spans of the newest ``.xplane.pb`` under ``trace_dir``,
    as ``trace.load`` gives events, each with its stats under ``"stats"``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    events = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if plane.name != tr.HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    events.append({"plane": plane.name, "line": line.name, "name": ev.name,
                                   "start_ns": int(ev.start_ns), "dur_ns": int(ev.duration_ns),
                                   "stats": dict(ev.stats)})
    return events


def spans(events, name: str | None = None, **where) -> list[dict]:
    """Program spans, all of them or those called ``repro.<name>``, whose
    stats hold every ``key=value`` of ``where``."""
    want = None if name is None else PREFIX + name
    return [e for e in events if e["plane"] == tr.HOST_PLANE and e["name"].startswith(PREFIX)
            and (want is None or e["name"] == want)
            and all(e.get("stats", {}).get(k) == v for k, v in where.items())]


def stat_sum(found, key: str) -> int:
    return sum(int(e.get("stats", {}).get(key, 0)) for e in found)


def span_ns(found) -> float:
    return float(sum(e["dur_ns"] for e in found))


def _as_bench(found, name: str | None = None) -> list[dict]:
    """Program spans under a benchmark span's name, for ``trace``'s
    functions: ``bench.<name>``, or without ``name`` ``bench.repro.<x>``,
    which ``trace`` reports as ``repro.<x>``."""
    return [dict(e, name=tr.SPAN_PREFIX + (name or e["name"])) for e in found]


def device_ns_within(events, pattern: str, inside, lo: int, hi: int) -> float:
    """``trace.device_ns_within`` for the program spans ``inside``: the
    device time of the programs matching ``pattern`` whose midpoint lies in
    one of them, the calls those spans issued and waited for."""
    marked = tr.device_events(events) + _as_bench(inside, "inside")
    return tr.device_ns_within(marked, pattern, "inside", lo, hi)


def idle_gaps(events, lo: int, hi: int, k: int = 10) -> list:
    """``trace.idle_gaps`` with the program's spans beside the benchmark's:
    each idle stretch goes to the innermost span of either kind. Benchmark
    spans keep their short name (``segment_scan``), program spans their
    whole one (``repro.des.fetch``)."""
    return tr.idle_gaps(events + _as_bench(spans(events)), lo, hi, k)


# --- the six numbers: read(ctx) each, None where the program records no span

def p1_single_device_ms_per_replan(ctx):
    """Device milliseconds per request of the P1 programs issued by
    single-row solves (``repro.p1.solve`` with ``rows == 1``): the solve
    that opens a warm decision and closes a cold one."""
    n = ctx.counters.get("requests")
    single = spans(ctx.events, "p1.solve", rows=1)
    if not n or not single:
        return None
    return device_ns_within(ctx.events, P1_PROGRAM, single, ctx.lo, ctx.hi) / n / 1e6


def crms_grid_retraces_per_replan(ctx):
    """Times per request that the grid kernel's body was traced
    (``repro.retrace.crms_grid`` markers)."""
    n = ctx.counters.get("requests")
    if not n or not spans(ctx.events, "decision"):
        return None
    return len(spans(ctx.events, "retrace.crms_grid")) / n


def refine_score_ms_per_replan(ctx):
    """Host milliseconds per request ranking refinement moves
    (``repro.crms.score``: ``evaluate_candidates`` and the eager
    ``evaluate`` of the picked move)."""
    n = ctx.counters.get("requests")
    if not n or not spans(ctx.events, "decision"):
        return None
    return span_ns(spans(ctx.events, "crms.score")) / n / 1e6


def kw_scan_pad_share(ctx):
    """Share of the scan's steps that are padding:
    100 (1 - Σ steps_used / Σ steps) over ``repro.des.segment``."""
    seg = [e for e in spans(ctx.events, "des.segment") if e["stats"].get("steps")]
    if not seg:
        return None
    return 100.0 * (1.0 - stat_sum(seg, "steps_used") / stat_sum(seg, "steps"))


def des_dispatch_ns_per_customer(ctx):
    """Host nanoseconds per customer in ``repro.des.dispatch``: the inputs'
    copy to the device and the scan's launch."""
    n = ctx.counters.get("customers")
    found = spans(ctx.events, "des.dispatch")
    return span_ns(found) / n if n and found else None


def des_fetch_ns_per_customer(ctx):
    """Host nanoseconds per customer in ``repro.des.fetch``: the wait for
    the scan and the copy of its outputs to the host."""
    n = ctx.counters.get("customers")
    found = spans(ctx.events, "des.fetch")
    return span_ns(found) / n if n and found else None


METRICS = {
    "p1_single.device_ms_per_replan": p1_single_device_ms_per_replan,
    "crms_grid.retraces_per_replan": crms_grid_retraces_per_replan,
    "refine.score_ms_per_replan": refine_score_ms_per_replan,
    "kw_scan.pad_share": kw_scan_pad_share,
    "des.dispatch_ns_per_customer": des_dispatch_ns_per_customer,
    "des.fetch_ns_per_customer": des_fetch_ns_per_customer,
}


def summary(events, lo: int, hi: int) -> dict:
    """Each program span's count and seconds; the P1 programs' device
    seconds by the rows of the solve that issued them; the share of the
    device's idle time that falls in a program span."""
    out: dict = {"spans": {}}
    for e in spans(events):
        c = out["spans"].setdefault(e["name"], [0, 0.0])
        c[0] += 1
        c[1] += e["dur_ns"] / 1e9
    solves = spans(events, "p1.solve")
    single = [e for e in solves if e["stats"].get("rows") == 1]
    batch = [e for e in solves if e["stats"].get("rows", 0) > 1]
    out["p1_device_s"] = {"all": tr.device_ns(events, P1_PROGRAM, lo, hi) / 1e9,
                          "single_row": device_ns_within(events, P1_PROGRAM, single, lo, hi) / 1e9,
                          "batch": device_ns_within(events, P1_PROGRAM, batch, lo, hi) / 1e9}
    gaps = idle_gaps(events, lo, hi, k=1000)
    idle = sum(s for _, s in gaps)
    out["idle_share_in_program_spans"] = (
        sum(s for name, s in gaps if name.startswith(PREFIX)) / idle if idle else None)
    return out


def main(argv=None) -> int:
    import argparse
    import json
    import tempfile

    from perfbench import core
    from perfbench import run

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    cell = core.resolve(core.load_spec(ROOT), args.workload, ROOT)
    os.environ["LIBTPU_INIT_ARGS"] = " ".join(
        filter(None, [os.environ.get("LIBTPU_INIT_ARGS"), tr.LIBTPU_TRACE_FLAGS]))
    import jax

    devices = core.check_devices(int(cell.entry["chips"]))
    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    compiles = core.CompileCounter()
    loop = cell.loop
    state = loop.setup(cell.config, cell.traffic, args.seed)
    bench_spans = core.Spans()
    bench_spans.install(loop.SPANS, record=loop.RECORD)
    with tempfile.TemporaryDirectory(prefix="perfbench_trace_") as trace_dir:
        jax.profiler.start_trace(trace_dir, profiler_options=run._profiler_options())
        try:
            with core.span("window"):
                loop.window(state, args.seconds, compiles)
        finally:
            jax.profiler.stop_trace()
            bench_spans.remove()
        events = tr.load(trace_dir) + load(trace_dir)
    lo, hi = tr.window_of(events)
    ctx = run.LayerContext(events, lo, hi, devices[0].device_kind, loop.counters(state),
                           bench_spans.calls)
    out = {
        "workload": args.workload, "seed": args.seed, "device": devices[0].device_kind,
        "report": loop.report_lines(state),
        "program": {name: fn(ctx) for name, fn in METRICS.items()},
        "metrics": {m["name"]: cell.readers[m["name"]].read(ctx) for m in cell.per_layer},
        "summary": summary(events, lo, hi),
        "idle_gaps": tr.idle_gaps(events, lo, hi),
        "idle_gaps_program": idle_gaps(events, lo, hi),
    }
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
