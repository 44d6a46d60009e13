"""The region cell's load loop and reference on the CPU, at 8 stations.

A region of 8 stations (32 apps, seeded load factors) is planned cold, then
re-planned on 3 drifted epochs, crowded by migrations until a rate surge
forces an emergency offload, and re-planned with one app's rate moved. The
planner's plans hold against ``reference/region.py`` on every node, nodes a
re-plan did not touch keep their allocations bit for bit, and a planted
fault makes ``correct`` false."""
import copy
import dataclasses
import json

import numpy as np
import pytest

from perfbench import core
from perfbench.reference import region as ref

SEED = 2**31 + 977
STATIONS = 8


def _loop():
    return core.load_module(core.BENCH_DIR / "loops" / "region.py")


def _cfg():
    return json.loads((core.BENCH_DIR / "configs" / "region_shanghai.json").read_text())


def _traffic():
    traffic = json.loads((core.BENCH_DIR / "traffic" / "region_drift.json").read_text())
    # a 3-epoch cycle keeps set-up short; every node's quotas are checked
    return dict(traffic, cycle_epochs=3, quota_sample_nodes=STATIONS)


def _correct(state) -> dict:
    checks, counts = _loop().check(state)
    return {"ok": all(c.ok for c in checks), "failing": [c.name for c in checks if not c.ok],
            "failed": counts["failed"]}


@pytest.fixture(scope="module")
def region():
    loop = _loop()
    state = loop.setup(_cfg(), _traffic(), SEED, stations=STATIONS)
    for _ in range(3):  # drifted re-plans
        state.decisions.append(loop.decide(state, state.stream[state.j].lam))
        state.j += 1
    last = state.decisions[-1]
    hot = int(last.assignment[0])
    crowd = [int(i) for i in np.argsort(-last.lam) if last.assignment[i] != hot][:4]
    state.decisions.append(loop.decide(
        state, last.lam, migrations=[(state.apps[i].name, hot) for i in crowd]))
    for factor in (1.5, 2.0, 3.0, 4.0, 6.0):  # a surge on the crowded node
        lam = state.decisions[-1].lam.copy()
        lam[state.decisions[-1].assignment == hot] *= factor
        state.decisions.append(loop.decide(state, lam))
        if state.decisions[-1].diagnostics["migrations"]:
            break
    lam = state.decisions[-1].lam.copy()
    lam[1] *= 1.1  # one app's rate moves
    state.decisions.append(loop.decide(state, lam))
    return state


def test_region_is_the_configured_stations():
    cfg = _cfg()
    reg = ref.region(cfg, STATIONS)
    factor = ref.station_factors(cfg, STATIONS)
    assert len(reg["apps"]) == STATIONS * len(cfg["apps"])
    assert reg["apps"][5]["name"] == "s0001." + cfg["apps"][1]["name"]
    assert reg["apps"][5]["lam"] == cfg["apps"][1]["lam"] * factor[1]
    # the full region's law: mean 1 over its 3,233 stations, within sampling error
    assert abs(ref.station_factors(cfg).mean() - 1.0) < 0.03


def test_region_plans_match_the_reference_on_every_node(region):
    assert all(p.error is None for p in region.decisions)
    assert all(p.diagnostics["nodes_failed"] == 0 for p in region.decisions)
    assert sum(p.diagnostics["migrations"] for p in region.decisions) > 4  # emergency moved
    got = _correct(region)
    assert got == {"ok": True, "failing": [], "failed": 0}


def test_untouched_nodes_stay_bit_for_bit(region):
    prev, cur = region.decisions[-2], region.decisions[-1]
    touched = ref.touched_nodes(prev.lam, cur.lam, prev.assignment, cur.assignment)
    assert touched.tolist() == [int(cur.assignment[1])]
    still = ~np.isin(cur.assignment, touched)
    for key in ("n", "c", "m", "ws"):
        np.testing.assert_array_equal(getattr(prev, key)[still], getattr(cur, key)[still])
    untouched = np.setdiff1d(np.arange(STATIONS), touched)
    np.testing.assert_array_equal(prev.node_utility[untouched], cur.node_utility[untouched])
    assert cur.diagnostics["nodes_solved"] == 1


def _quota_off_its_optimum(state):
    """The re-solved node's quotas a tenth off, its Ws and objective evaluated
    anew so that they agree with them: only the quota check can see it."""
    plan = state.decisions[-1]
    node = int(plan.assignment[1])
    on = plan.assignment == node
    c = plan.c.copy()
    c[on] *= 0.9
    apps = ref.Apps(state.cfg)
    caps = np.full(STATIONS, float(state.caps.r_cpu)), np.full(STATIONS, float(state.caps.r_mem))
    ev = ref.evaluate(apps, plan.lam, plan.n, c, plan.m, plan.assignment, *caps)
    ws = np.where(on, ev["ws"], plan.ws)
    utility = plan.node_utility.copy()
    utility[node] = ev["utility"][node]
    return dataclasses.replace(plan, c=c, ws=ws, node_utility=utility), "quota_gap"


def _untouched_node_changed(state):
    """A node the last re-plan did not touch reports an objective one unit in
    the last place off its previous one."""
    plan = state.decisions[-1]
    node = next(j for j in range(STATIONS) if j != int(plan.assignment[1]))
    utility = plan.node_utility.copy()
    utility[node] = np.nextafter(utility[node], np.inf)
    return dataclasses.replace(plan, node_utility=utility), "incremental_mismatch"


def _over_budget(state):
    """One node reported ok with one more container than its CPU budget holds."""
    plan = state.decisions[-1]
    node = int(plan.assignment[0])
    on = np.where(plan.assignment == node)[0]
    n = plan.n.copy()
    slack = state.caps.r_cpu - float(np.sum(plan.n[on] * plan.c[on]))
    n[on[0]] += int(np.ceil((slack + 1e-6) / plan.c[on[0]]))
    return dataclasses.replace(plan, n=n), "infeasible_nodes"


@pytest.mark.parametrize("plant", [_quota_off_its_optimum, _untouched_node_changed, _over_budget])
def test_a_planted_fault_makes_correct_false(region, plant):
    state = copy.copy(region)
    faulty, check = plant(region)
    state.decisions = region.decisions[:-1] + [faulty]
    got = _correct(state)
    assert not got["ok"] and check in got["failing"]


def test_a_quota_altered_in_the_planner_makes_correct_false(region, monkeypatch):
    """The planner hands back one app's quota a millionth off what it solved."""
    from repro.core.placement import FleetPlanner

    real = FleetPlanner._finish

    def altered(self, t0, **extra):
        plan = real(self, t0, **extra)
        plan.r_cpu = plan.r_cpu.copy()
        plan.r_cpu[0] *= 1.0 + 1e-6
        return plan

    loop = _loop()
    state = copy.copy(region)
    lam = region.decisions[-1].lam.copy()
    lam[0] *= 1.05
    monkeypatch.setattr(FleetPlanner, "_finish", altered)
    state.decisions = [loop.decide(state, lam)]
    monkeypatch.undo()
    state.before = region.decisions[-1]
    got = _correct(state)
    assert not got["ok"] and {"ws_gap", "utility_gap"} & set(got["failing"])
