"""Program spans under ``jax.profiler``: which ``repro.`` spans a CRMS decision
and a vector DES job record, how they nest, that their counters are exact,
when the retrace markers fire, and that tracing changes no result."""
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro import obs
from repro.api import AllocRequest, QuasiDynamicPolicy
from repro.core import des_vector, engine
from repro.core.des import FleetSimulator
from repro.core.problem import ServerCaps
from repro.core.profiler import make_paper_apps
from repro.kernels.crms_grid import crms_grid_eval

CAPS = ServerCaps(r_cpu=30.0, r_mem=10.0)
LAM = (8.0, 7.0, 10.0, 15.0)
# cold (first call), warm (one rate drifts past the 0.15 threshold), skip
RATES = [LAM, (8.0, 7.0, 10.0, 18.0), (8.0, 7.0, 10.0, 18.0)]


def _read(trace_dir) -> list[dict]:
    """The ``repro.`` spans of the newest trace under ``trace_dir``."""
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
                  key=os.path.getmtime)[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(obs.PREFIX):
                    out.append({"name": ev.name[len(obs.PREFIX):], "start": ev.start_ns,
                                "end": ev.start_ns + ev.duration_ns, "stats": dict(ev.stats)})
    return sorted(out, key=lambda e: e["start"])


def _named(spans, name):
    return [s for s in spans if s["name"] == name]


def _parent(child, spans, name):
    """The one span called ``name`` that holds ``child``."""
    outer = [s for s in _named(spans, name)
             if s["start"] <= child["start"] and child["end"] <= s["end"]]
    assert len(outer) == 1, (child, name)
    return outer[0]


def _decisions():
    policy = QuasiDynamicPolicy("crms", threshold=0.15)
    return [policy.allocate(AllocRequest(apps=make_paper_apps(lam=lam, fitted=False),
                                         caps=CAPS, alpha=1.4, beta=0.2))
            for lam in RATES]


def _job():
    sim = FleetSimulator(seed=11, engine="vector")
    sim.add_app("a", 8.0, 1.0, 10)
    sim.add_app("b", 15.0, 2.0, 9)
    sim.add_app("c", 5.0, 1.5, 4)
    sim.run_until(40.0)
    sim.configure("b", lam=17.0, n_servers=10)
    sim.configure("c", n_servers=3)
    sim.run_until(90.0)
    sim.drain()
    return sim, [sim.responses(name, 0.0, np.inf) for name in "abc"]


def test_crms_decisions_record_nested_spans_with_exact_counters(tmp_path):
    _decisions()  # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        traced = _decisions()
    spans = _read(tmp_path)

    assert [d["stats"]["kind"] for d in _named(spans, "decision")] == ["cold", "warm", "skip"]
    assert len(_named(spans, "crms.algorithm1")) == 1  # the cold decision's
    refines = _named(spans, "crms.refine")
    solved = [r.diagnostics for r in traced if not r.diagnostics.cache_hit]
    assert len(refines) == sum(d.refine_iters for d in solved) > 0
    assert sum(r["stats"]["accepted"] for r in refines) == sum(d.accepted_moves for d in solved)
    for fetch in _named(spans, "p1.fetch"):
        solve = _parent(fetch, spans, "p1.solve")
        _parent(solve, spans, "decision")
    solves_in_refine = 0
    for solve in _named(spans, "p1.solve"):
        assert solve["stats"]["padded"] == 1 << (solve["stats"]["rows"] - 1).bit_length()
        assert solve["stats"]["width"] == engine.P1_MIN_WIDTH  # every count here is <= 16
        refine = [r for r in refines if r["start"] <= solve["start"] and solve["end"] <= r["end"]]
        if refine:  # a refinement batch holds every move of its iteration
            assert solve["stats"]["rows"] == refine[0]["stats"]["moves"]
            assert solve["stats"]["profile"] == "refine"
            _parent(refine[0], spans, "decision")
            solves_in_refine += 1
        else:  # the warm decision's opening solve at the cached counts
            assert solve["stats"]["rows"] == 1
    assert solves_in_refine == len(refines)
    for name in ("p1.grid_seed", "p1.phase1", "p1.dispatch", "p1.fetch"):
        assert len(_named(spans, name)) >= solves_in_refine
    for score in _named(spans, "crms.score"):
        _parent(score, spans, "crms.refine")

    plain = _decisions()
    for a, b in zip(traced, plain):
        for key in ("n", "r_cpu", "r_mem"):
            np.testing.assert_array_equal(getattr(a.allocation, key), getattr(b.allocation, key))
        assert a.utility == b.utility


def test_vector_des_segments_count_every_recorded_customer(tmp_path):
    _job()
    with jax.profiler.trace(str(tmp_path)):
        _, traced = _job()
    spans = _read(tmp_path)

    segments = _named(spans, "des.segment")
    assert len(segments) in (2, 3)  # two epochs, and the drain where a queue is left
    assert sum(s["stats"]["customers"] for s in segments) == sum(r.shape[0] for r in traced)
    scanned = [s for s in segments if s["stats"]["steps"]]
    assert len(scanned) >= 2
    for s in scanned:
        st = s["stats"]
        assert st["steps"] == 1 << (st["steps_used"] - 1).bit_length()
        assert (st["lanes"], st["lanes_padded"], st["servers_padded"]) == (3, 4, 16)
    assert len(_named(spans, "des.draw")) == len(segments)
    for name in ("des.draw", "des.pack", "des.dispatch", "des.fetch", "des.record"):
        inner = _named(spans, name)
        assert len(inner) >= len(scanned)
        for e in inner:
            _parent(e, spans, "des.segment")

    _, plain = _job()
    for a, b in zip(traced, plain):
        np.testing.assert_array_equal(a, b)


def test_retrace_markers_fire_on_a_new_shape_only(tmp_path):
    apps = make_paper_apps(lam=LAM, fitted=False)
    n = np.array([[10, 9, 5, 8], [9, 9, 5, 8], [10, 8, 5, 8]], dtype=float)
    # a static width no other caller uses, so the first call here traces anew
    solve = lambda: engine.p1_solve_batch(apps, CAPS, n, 1.4, 0.2, pad=False, max_servers=37)
    k, m, srv = 24, 3, 5  # a scan shape no simulator pads to
    scan = lambda: des_vector.segment_scan(
        np.zeros((m, srv)), np.ones((m, srv), dtype=bool), np.ones((k, m)),
        np.full((k, m), 0.5), np.ones((k, m), dtype=bool))
    b, mm = 16, 4
    grid = lambda: crms_grid_eval(
        np.ones((mm, 3), np.float32), np.full(mm, 6.0), np.ones(mm), np.full((b, mm), 3.0),
        np.ones((b, mm)), np.ones((b, mm)), caps_cpu=30.0, power_span=10.0, alpha=1.4,
        beta=0.2, interpret=True, reduce="per_app")
    with jax.profiler.trace(str(tmp_path)):
        first = solve(), scan()
        second = solve(), scan()
        grid(), grid()
    spans = _read(tmp_path)

    assert [s["stats"]["shape"] for s in _named(spans, "retrace.ip_solve")] == ["3x8"]
    assert [s["stats"]["shape"] for s in _named(spans, "retrace.segment_scan")] == ["24x3x5"]
    # the eager Pallas call traces its kernel anew on every call
    assert [s["stats"]["shape"] for s in _named(spans, "retrace.crms_grid")] == ["16x8"] * 2
    np.testing.assert_array_equal(first[0].r_cpu, second[0].r_cpu)
    np.testing.assert_array_equal(first[1][1], second[1][1])


@pytest.mark.parametrize("top,max_servers,width", [
    (10, None, 16),  # below the floor: the floor
    (17, None, 32),  # the pow2 ceiling of the largest count
    (10, 37, 37),  # a caller's width as it is given
])
def test_p1_solve_records_its_erlang_width(tmp_path, top, max_servers, width):
    apps = make_paper_apps(lam=LAM, fitted=False)
    # five rows, unpadded: a shape no other solve uses, so the call traces anew
    n = np.array([[6, 7, 3, 7], [6, top, 3, 7], [6, 8, 3, 7], [5, 7, 3, 7], [6, 7, 4, 7]],
                 dtype=float)
    with jax.profiler.trace(str(tmp_path)):
        res = engine.p1_solve_batch(apps, CAPS, n, 1.4, 0.2, pad=False, profile="refine",
                                    max_servers=max_servers)
    spans = _read(tmp_path)

    assert res.converged[1]  # the row holding the largest count is solved
    assert [s["stats"]["width"] for s in _named(spans, "p1.solve")] == [width]
    assert [s["stats"]["width"] for s in _named(spans, "retrace.ip_solve")] == [width]


def test_spans_are_inert_without_a_profiler(tmp_path):
    assert not TraceAnnotation.is_enabled()
    with obs.span("p1.solve", rows=1) as span:
        span.set_metadata(padded=1)
    obs.retraced("ip_solve", shape=obs.shape(1, 8))
    assert obs.shape(16, 4) == "16x4"
    with jax.profiler.trace(str(tmp_path)):
        assert TraceAnnotation.is_enabled()
    assert not TraceAnnotation.is_enabled()


def test_region_plans_record_spans_with_exact_counters(tmp_path):
    from repro.core.placement import FleetPlanner, make_fleet

    apps, node_caps = make_fleet(5, 3, seed=21)
    # the "refine" schedule: a row-solve program no other planner compiles,
    # so the first solve of each batch size here traces anew
    planner = FleetPlanner(apps, node_caps, alpha=1.4, beta=0.2, profile="refine")
    with jax.profiler.trace(str(tmp_path)):
        plans = [planner.plan(),
                 planner.replan(lam={apps[0].name: apps[0].lam * 1.2}),
                 planner.replan(lam={apps[0].name: apps[0].lam * 1.3})]
        src = int(planner.assignment[1])
        plans.append(planner.replan(migrations=[(apps[1].name, (src + 1) % 5)]))
    spans = _read(tmp_path)

    outer = _named(spans, "region.plan") + _named(spans, "region.replan")
    assert [s["name"] for s in outer] == ["region.plan"] + ["region.replan"] * 3
    for span, plan in zip(outer, plans):
        diag = plan.diagnostics
        for key in ("nodes_solved", "migrations", "nodes_failed"):
            assert span["stats"][key] == diag[key]
        assert span["stats"]["emergency"] == 0
        solves = [s for s in _named(spans, "region.solve")
                  if span["start"] <= s["start"] and s["end"] <= span["end"]]
        assert sum(s["stats"]["rows"] for s in solves) == diag["rows_solved"]
        assert sum(s["stats"]["padded"] for s in solves) == diag["rows_dispatched"]
    assert [p.diagnostics["nodes_solved"] for p in plans] == [5, 1, 1, 2]
    (exchange,) = _named(spans, "region.exchange")
    _parent(exchange, spans, "region.plan")
    assert exchange["stats"]["accepted"] == plans[0].diagnostics["exchange_accepted"]

    solves = _named(spans, "region.solve")
    for s in solves:
        st = s["stats"]
        assert st["padded"] == 1 << (st["rows"] - 1).bit_length()
        assert (st["width"], st["M_pad"]) == (planner._width, planner.M_pad)
        assert st["masked"] == st["rescued"] == st["repaired"] == 0
    for name in ("region.phase1", "region.dispatch", "region.fetch"):
        inner = _named(spans, name)
        assert len(inner) == len(solves)
        for e in inner:
            _parent(e, spans, "region.solve")
    retraced = [(s["stats"]["shape"], s["stats"]["width"])
                for s in _named(spans, "retrace.ip_solve_rows")]
    assert sorted(retraced) == sorted(
        {(f"{s['stats']['padded']}x{2 * planner.M_pad}", planner._width) for s in solves})
