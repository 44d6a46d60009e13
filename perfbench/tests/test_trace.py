"""The trace reduction, on a small recorded trace and on hand-made events."""
import json
from pathlib import Path

import pytest

from perfbench import trace as tr

DEV, HOST, MOD = "/device:TPU:0", "/host:CPU", tr.MODULES_LINE


def _ev(plane, line, name, start, dur):
    return {"plane": plane, "line": line, "name": name, "start_ns": start, "dur_ns": dur}


# A window of 100 ns: the host decides (a parent span with two children),
# the device runs three programs, two of them overlapping.
EVENTS = [
    _ev(HOST, "main", "bench.window", 0, 100),
    _ev(HOST, "main", "bench.crms", 10, 60),
    _ev(HOST, "main", "bench.p1_solve_batch", 20, 20),
    _ev(HOST, "main", "bench.algorithm1", 50, 10),
    _ev(HOST, "main", "bench.p1_ip", 25, 5),  # inside p1_solve_batch
    _ev(DEV, MOD, "jit__ip_solve_batched(123)", 22, 15),
    _ev(DEV, MOD, "jit__ip_solve_batched(456)", 30, 10),
    _ev(DEV, MOD, "jit_pad(9)", 90, 20),  # runs past the window's end
    _ev("/host:metadata", "x", "bench.ignored", 0, 5),
]


def test_union_and_busy_clip_to_the_window():
    assert tr.union_ns([(0, 10), (5, 20), (30, 40)]) == 30
    assert tr.union_ns([(0, 10), (5, 20)], lo=8, hi=15) == 7
    lo, hi = tr.window_of(EVENTS)
    assert (lo, hi) == (0, 100)
    # [22, 40) from the two overlapping programs, [90, 100) of the third
    assert tr.busy_ns(EVENTS, lo, hi) == 18 + 10


def test_program_time_by_name():
    assert tr.device_ns(EVENTS, r"_ip_solve_batched", 0, 100) == 25
    assert tr.device_ns(EVENTS, r"jit_pad", 0, 100) == 10
    assert tr.device_ns(EVENTS, r"nothing", 0, 100) == 0


def test_span_self_time():
    # crms lasts 60, of which its children p1_solve_batch (20) and algorithm1 (10)
    assert tr.self_ns(EVENTS, "crms", ["p1_solve_batch", "algorithm1"]) == 30
    assert tr.self_ns(EVENTS, "p1_solve_batch", ["p1_ip"]) == 15
    assert tr.span_ns(EVENTS, "algorithm1") == 10
    assert [e["name"] for e in tr.spans(EVENTS, "crms")] == ["bench.crms"]


def test_breakdown():
    top = tr.top_device_ops(EVENTS, 0, 100)
    assert top[0] == ["jit__ip_solve_batched", 25e-9] and top[1] == ["jit_pad", 10e-9]
    gaps = dict(tr.idle_gaps(EVENTS, 0, 100))
    # idle: [0, 22) -> midpoint 11 in crms; [40, 90) -> midpoint 65 in crms
    # (algorithm1 ends at 60); total 72 ns
    assert gaps == {"crms": pytest.approx(72e-9)}


RECORDED = Path(__file__).resolve().parent / "data" / "recorded_trace.json"


def test_recorded_trace():
    """A slice of a real v5e trace of a validation window: the numbers below
    were worked out by hand from the file's events."""
    rec = json.loads(RECORDED.read_text())
    events, want = rec["events"], rec["expected"]
    lo, hi = tr.window_of(events)
    assert hi - lo == want["window_ns"]
    assert tr.busy_ns(events, lo, hi) == want["busy_ns"]
    assert tr.device_ns(events, r"_segment_scan_jax", lo, hi) == want["scan_ns"]
    assert tr.self_ns(events, "job", ["segment_scan"]) == want["job_self_ns"]


def test_load_reads_spans_of_a_live_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) * 2.0)
    f(jnp.ones(8)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.job"):
            f(jnp.ones(8)).block_until_ready()
    jax.profiler.stop_trace()
    events = tr.load(str(tmp_path))
    lo, hi = tr.window_of(events)
    assert hi > lo
    job = tr.spans(events, "job")
    assert len(job) == 1 and lo <= job[0]["start_ns"] <= hi


def test_program_time_inside_a_span():
    # jit_pad runs inside no p1_solve_batch span; the two solves do (midpoints 29.5, 35)
    assert tr.device_ns_within(EVENTS, r"_ip_solve_batched", "p1_solve_batch", 0, 100) == 25
    assert tr.device_ns_within(EVENTS, r"jit_pad", "p1_solve_batch", 0, 100) == 0
    # the second solve's midpoint (35) lies outside p1_ip [25, 30]
    assert tr.device_ns_within(EVENTS, r"_ip_solve_batched", "p1_ip", 0, 100) == 15
