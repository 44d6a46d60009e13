"""The program's spans beside the benchmark's: hand-made events give each
number of ``program_spans.METRICS``, and adding program spans to the recorded
trace changes no existing reader and no breakdown."""
import json
from pathlib import Path

import pytest

from perfbench import core
from perfbench import program_spans as ps
from perfbench import trace as tr
from perfbench.run import LayerContext

DEV, HOST, MOD = "/device:TPU:0", "/host:CPU", tr.MODULES_LINE


def _ev(plane, line, name, start, dur, **stats):
    e = {"plane": plane, "line": line, "name": name, "start_ns": start, "dur_ns": dur}
    if name.startswith(ps.PREFIX):
        e["stats"] = stats
    return e


def _host(name, start, dur, **stats):
    return _ev(HOST, "python", name, start, dur, **stats)


def _ctx(events, **counters):
    lo, hi = tr.window_of(events)
    return LayerContext(events, lo, hi, "TPU v5 lite", counters, {})


# Two decisions in a window of 1000 ns: a warm one opening with a single-row
# solve (its program runs 50-180) and one refinement batch (program 300-480),
# the kernel traced twice; the device is idle in between.
REPLAN = [
    _host("bench.window", 0, 1000),
    _host("repro.decision", 5, 700, kind="warm"),
    _host("repro.p1.solve", 20, 180, rows=1, profile="reference", padded=1),
    _host("repro.p1.dispatch", 30, 10),
    _host("repro.p1.fetch", 40, 150),
    _ev(DEV, MOD, "jit__ip_solve_batched(1)", 50, 130),
    _host("repro.crms.refine", 210, 490, moves=8, accepted=1),
    _host("repro.p1.solve", 220, 280, rows=8, profile="refine", padded=8),
    _host("repro.retrace.crms_grid", 230, 0, shape="256x8"),
    _host("bench.crms_grid", 225, 20),
    _host("repro.p1.fetch", 260, 235),
    _ev(DEV, MOD, "jit__ip_solve_batched(2)", 300, 180),
    _host("repro.crms.score", 510, 90),
    _host("repro.decision", 800, 10, kind="skip"),
]

# Two segments of the scan, 4 lanes each; the device runs both scans.
VALIDATE = [
    _host("bench.window", 0, 10_000),
    _host("bench.job", 100, 9000),
    _host("repro.des.segment", 200, 4000, customers=6000, steps_used=6000, steps=8192),
    _host("bench.segment_scan", 1000, 3000),
    _host("repro.des.dispatch", 1000, 300),
    _host("repro.des.fetch", 1300, 2700),
    _ev(DEV, MOD, "jit_copy(3)", 1000, 350),
    _ev(DEV, MOD, "jit__segment_scan_jax(7)", 1500, 2000),
    _host("repro.des.segment", 4500, 4500, customers=9000, steps_used=9000, steps=16384),
    _host("bench.segment_scan", 5000, 3500),
    _host("repro.des.dispatch", 5000, 500),
    _host("repro.des.fetch", 5500, 3000),
    _ev(DEV, MOD, "jit__segment_scan_jax(7)", 5600, 2800),
]


def test_program_spans_by_name_and_stat():
    assert len(ps.spans(REPLAN)) == 10
    assert [e["start_ns"] for e in ps.spans(REPLAN, "p1.solve", rows=1)] == [20]
    assert ps.spans(REPLAN, "p1.solve", rows=2) == []
    assert ps.stat_sum(ps.spans(REPLAN, "crms.refine"), "moves") == 8
    assert ps.span_ns(ps.spans(REPLAN, "p1.fetch")) == 385
    # the single-row solve [20, 200] holds the first program's midpoint (115)
    single = ps.spans(REPLAN, "p1.solve", rows=1)
    assert ps.device_ns_within(REPLAN, ps.P1_PROGRAM, single, 0, 1000) == 130
    assert ps.device_ns_within(REPLAN, ps.P1_PROGRAM, ps.spans(REPLAN, "p1.solve"), 0, 1000) == 310


def test_replan_numbers():
    ctx = _ctx(REPLAN, requests=2)
    assert ps.p1_single_device_ms_per_replan(ctx) == pytest.approx(130 / 2 / 1e6)
    assert ps.crms_grid_retraces_per_replan(ctx) == 0.5
    assert ps.refine_score_ms_per_replan(ctx) == pytest.approx(90 / 2 / 1e6)
    summary = ps.summary(REPLAN, 0, 1000)
    assert summary["p1_device_s"] == {"all": 310e-9, "single_row": 130e-9, "batch": 180e-9}
    assert summary["spans"]["repro.p1.fetch"] == [2, pytest.approx(385e-9)]


def test_validate_numbers():
    ctx = _ctx(VALIDATE, customers=15_000)
    assert ps.kw_scan_pad_share(ctx) == pytest.approx(100 * (1 - 15_000 / 24_576))
    assert ps.des_dispatch_ns_per_customer(ctx) == pytest.approx(800 / 15_000)
    assert ps.des_fetch_ns_per_customer(ctx) == pytest.approx(5700 / 15_000)
    # the two spans split the benchmark's segment_scan span
    seg = tr.span_ns(VALIDATE, "segment_scan")
    assert seg == ps.span_ns(ps.spans(VALIDATE, "des.dispatch") + ps.spans(VALIDATE, "des.fetch"))


def test_idle_gaps_name_the_innermost_span_of_either_kind():
    gaps = dict(ps.idle_gaps(REPLAN, 0, 1000))
    # idle [0, 50): midpoint 25 in the single-row solve; [180, 300): midpoint
    # 240 in the benchmark's kernel span inside the refinement's solve;
    # [480, 1000): midpoint 740, in no span
    assert gaps == {"repro.p1.solve": pytest.approx(50e-9), "crms_grid": pytest.approx(120e-9),
                    "outside spans": pytest.approx(520e-9)}
    gaps = dict(ps.idle_gaps(VALIDATE, 0, 10_000))
    # [0, 1000) and [3500, 5600) in the segments, [1350, 1500) in the first
    # fetch, [8400, 10000) after the job
    assert gaps == {"repro.des.segment": pytest.approx(3100e-9),
                    "outside spans": pytest.approx(1600e-9),
                    "repro.des.fetch": pytest.approx(150e-9)}


def test_nothing_to_read_without_program_spans():
    bench_only = [e for e in REPLAN + VALIDATE[1:] if not e["name"].startswith(ps.PREFIX)]
    ctx = _ctx(bench_only, requests=2, customers=15_000)
    assert all(fn(ctx) is None for fn in ps.METRICS.values())


RECORDED = Path(__file__).resolve().parent / "data" / "recorded_trace.json"


def test_program_spans_change_no_existing_reading_of_the_recorded_trace():
    events = json.loads(RECORDED.read_text())["events"]
    lo, hi = tr.window_of(events)
    # a program span inside every segment_scan span, as the program records them
    extra = [_host("repro.des.fetch", e["start_ns"] + 1, e["dur_ns"] - 2)
             for e in tr.spans(events, "segment_scan")]
    assert ps.idle_gaps(events, lo, hi) == tr.idle_gaps(events, lo, hi)
    cell = core.resolve(core.load_spec(), "paper_node.validate")
    counters = {"customers": 3_000_000, "segments": 62}
    for name, reader in cell.readers.items():
        before = reader.read(_ctx(events, **counters))
        assert before is not None, name
        assert reader.read(_ctx(events + extra, **counters)) == before, name
    both = events + extra
    assert tr.top_device_ops(both, lo, hi) == tr.top_device_ops(events, lo, hi)
    assert tr.idle_gaps(both, lo, hi) == tr.idle_gaps(events, lo, hi)
    assert tr.busy_ns(both, lo, hi) == tr.busy_ns(events, lo, hi)
    moved = dict(ps.idle_gaps(both, lo, hi))
    assert sum(moved.values()) == pytest.approx(sum(s for _, s in tr.idle_gaps(events, lo, hi, 100)))


def test_load_reads_program_spans_and_stats_of_a_live_trace(tmp_path):
    import jax

    from repro import obs

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with obs.span("des.segment") as span:
            obs.retraced("segment_scan", shape=obs.shape(8, 4, 16))
            span.set_metadata(customers=5, steps_used=6, steps=8)
    jax.profiler.stop_trace()
    events = tr.load(str(tmp_path)) + ps.load(str(tmp_path))
    assert [e["name"] for e in tr.spans(events)] == ["bench.window"]
    seg = ps.spans(events, "des.segment", steps=8)
    assert len(seg) == 1 and seg[0]["stats"] == {"customers": 5, "steps_used": 6, "steps": 8}
    assert ps.spans(events, "retrace.segment_scan")[0]["stats"] == {"shape": "8x4x16"}
