#!/usr/bin/env python3
"""Run one cell of the benchmark and print its result line.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's entry in BENCHMARK.json names its configuration and traffic mix;
their files, the traffic's load loop and the per-layer metrics' readers are
found by those names (``core.resolve``). A run builds the deployment from
the seed and warms up every shape its traffic uses (set-up), measures for
``--seconds`` with the profiler off (``--trace 0``, the end-to-end metrics)
or on (``--trace 1``, the per-layer metrics), reads the device's peak
memory, and then checks what the window produced against the plain
reference. The numbers compared and their limits end standard error and the
result line. The run exits non-zero, printing no result, where JAX finds no
TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from perfbench import core  # noqa: E402
from perfbench import trace as tr  # noqa: E402
from perfbench import roofline  # noqa: E402


@dataclasses.dataclass
class LayerContext:
    """What a per-layer metric's reader is given."""

    events: list  # the traced window's events (trace.load)
    lo: int  # window start, ns on the trace's clock
    hi: int  # window end
    device_kind: str
    counters: dict  # the load loop's counts over the window
    calls: dict  # span name -> recorded (args, kwargs) of each call

    trace = tr  # the reduction functions, for the readers
    roofline = roofline  # peaks and work counts


def _profiler_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # Python tracing slows the host many times over
    opts.host_tracer_level = 2
    return opts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        sys.exit(f"perfbench: no program under test at {ROOT / 'src'}")
    spec = core.load_spec(ROOT)
    cell = core.resolve(spec, args.workload, ROOT)
    if args.trace:
        # programs without per-op trace marks: the ops inside the solvers' and
        # the simulator's loops would overflow the profiler's buffer in seconds
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
    setup_s = time.perf_counter() - T_START

    spans = core.Spans()
    trace_dir = None
    if args.trace:
        spans.install(loop.SPANS, record=loop.RECORD)
        trace_dir = tempfile.TemporaryDirectory(prefix="perfbench_trace_")
        jax.profiler.start_trace(trace_dir.name, profiler_options=_profiler_options())
    try:
        with core.span("window"):
            loop.window(state, args.seconds, compiles)
    finally:
        if args.trace:
            jax.profiler.stop_trace()
            spans.remove()

    used = devices[: int(cell.entry["chips"])]
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in used)
    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}

    metrics, breakdown = {}, None
    if args.trace:
        events = tr.load(trace_dir.name)
        trace_dir.cleanup()
        lo, hi = tr.window_of(events)
        device["busy_s"] = tr.busy_ns(events, lo, hi) / 1e9
        device["window_s"] = (hi - lo) / 1e9
        ctx = LayerContext(events, lo, hi, used[0].device_kind, loop.counters(state),
                           spans.calls)
        for m in cell.per_layer:
            value = cell.readers[m["name"]].read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        breakdown = {"device_ops": tr.top_device_ops(events, lo, hi),
                     "idle_gaps": tr.idle_gaps(events, lo, hi)}
    else:
        e2e = loop.end_to_end(state)
        e2e["setup_s"] = setup_s
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    for line in loop.report_lines(state):
        print(line, flush=True)
    checks, counts = loop.check(state)
    correct = all(c.ok for c in checks)
    for c in checks:
        print(f"check {c.name}: {c.value!r} (limit {c.limit!r}) {'ok' if c.ok else 'FAILED'}",
              file=sys.stderr)
    result = {"correct": correct, "attempted": counts["attempted"], "failed": counts["failed"],
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit} for c in checks}
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
