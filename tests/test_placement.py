"""Fleet-of-fleets placement layer (core.placement + crms_fleet policy).

The load-bearing invariants:

  * node-axis padding parity — a ragged fleet (nodes with 3, 8, 16 apps)
    pushed through the padded/masked/width-narrowed batched row solve matches
    each node's standalone ``p1_solve_batch`` exactly, masking counters
    included;
  * Erlang width narrowing is EXACT, not approximate;
  * incremental re-plans re-solve only touched nodes and leave every other
    node's allocation byte-identical;
  * same-epoch scenario events apply in one pinned order regardless of their
    construction order (the timeline tie-break).
"""
import dataclasses

import numpy as np
import pytest

from repro.api import (
    AllocRequest,
    AppMigrate,
    CapResize,
    FleetScenario,
    FleetScenarioRunner,
    LambdaScale,
    Scenario,
    allocate,
    get_policy,
)
from repro.api.scenario import AppJoin, AppLeave, LambdaSet
from repro.core import queueing
from repro.core.engine import PackedApps, erlang_width, p1_solve_batch
from repro.core.placement import FleetPlanner, make_fleet
from repro.core.problem import App, ServerCaps
from repro.core.profiler import make_paper_apps

ALPHA, BETA = 1.4, 0.2


@pytest.fixture(scope="module")
def small_fleet():
    apps, node_caps = make_fleet(8, 6, seed=11)
    planner = FleetPlanner(apps, node_caps, alpha=ALPHA, beta=BETA)
    plan = planner.plan()
    return planner, plan


# ----------------------------------------------------------------------------
# Erlang width narrowing: exact, not approximate
# ----------------------------------------------------------------------------
def test_erlang_width_narrowing_is_exact():
    cases = [(1.0, 4.0, 6.0), (3.0, 9.0, 4.0), (7.0, 20.0, 3.5), (15.0, 31.0, 2.5)]
    for n, lam, mu in cases:  # scalar per lane, as the vmapped solver calls it
        full = float(queueing.erlang_ws(n, lam, mu))
        narrow = float(queueing.erlang_ws(n, lam, mu, width=16))
        # masked recurrence steps carry B through unchanged: bit-exact
        assert full == narrow


@pytest.mark.parametrize("n_max,width", [(1, 16), (16, 16), (17, 32), (24.0, 32), (511, 512)])
def test_erlang_width_rule(n_max, width):
    assert erlang_width(n_max) == width


def test_planner_width_is_the_engine_rule_and_only_grows():
    apps, node_caps = make_fleet(4, 3, seed=2)
    planner = FleetPlanner(apps, node_caps, alpha=ALPHA, beta=BETA, exchange_rounds=0)
    planner.plan()
    assert planner._width == erlang_width(planner.n.max()) == 16
    planner.replan(lam={apps[0].name: apps[0].lam * 12.0})
    grown = planner._width
    assert grown == erlang_width(planner.n.max()) > 16
    planner.replan(lam={apps[0].name: apps[0].lam})
    assert planner._width == grown > erlang_width(planner.n.max())


def test_count_repair_starts_a_node_held_by_one_hot_app():
    """An app whose load needs most of a node's CPU gets its counts from an
    ideal configuration sized for a node of its own: too few containers for
    phase-1 to start. The planner grows them until it does, and the row then
    matches the node's standalone solve at the grown counts."""
    hot, cool = make_paper_apps(lam=(7.0, 33.5, 6.0, 6.0), fitted=False)[1:3]
    hot = dataclasses.replace(hot, name="hot")
    planner = FleetPlanner([hot, cool], [ServerCaps(120.0, 40.0)] * 2, alpha=ALPHA,
                           beta=BETA, exchange_rounds=0, initial_assignment=[0, 1])
    n_before = planner.n.copy()
    plan = planner.plan()
    assert plan.diagnostics["rows_repaired"] == 1
    assert plan.diagnostics["nodes_failed"] == 0
    assert planner.n[0] > n_before[0] and planner.n[1] == n_before[1]
    on_j, n_apps, caps, n_row, c_hint = planner.node_problem(0)
    assert c_hint is None  # the repaired row started from the plain waterfill
    ref = p1_solve_batch(PackedApps.from_apps(n_apps), caps, n_row, ALPHA, BETA,
                         profile=planner.profile, max_servers=planner._width)
    assert bool(ref.converged[0])
    np.testing.assert_allclose(ref.r_cpu[0], planner.sol_c[on_j], rtol=1e-6)
    np.testing.assert_allclose(ref.r_mem[0], planner.sol_m[on_j], rtol=1e-6)


def test_width_below_counts_rejected():
    apps, node_caps = make_fleet(2, 4, seed=0)
    packed = PackedApps.from_apps(apps)
    caps = ServerCaps(*node_caps[0])
    n = np.full((1, len(apps)), 9.0)
    with pytest.raises(ValueError):
        p1_solve_batch(packed, caps, n, ALPHA, BETA, max_servers=8)


# ----------------------------------------------------------------------------
# node-axis padding parity (satellite 3)
# ----------------------------------------------------------------------------
def test_ragged_fleet_padding_parity():
    """Nodes with 3, 8 and 16 apps through ONE padded batch must match each
    node's standalone p1_solve_batch row exactly."""
    sizes = (3, 8, 16)
    apps, _ = make_fleet(3, 16, seed=5)
    apps = list(apps)[: sum(sizes)]
    assignment = np.repeat(np.arange(3), sizes)
    node_caps = [(10.0 * s, 13.0 * s) for s in sizes]
    planner = FleetPlanner(
        apps, node_caps, alpha=ALPHA, beta=BETA,
        exchange_rounds=0, initial_assignment=assignment,
    )
    plan = planner.plan()
    # pow2 of max_load+1: the fullest node (16) keeps one migration slot
    assert plan.diagnostics["M_pad"] == 32
    assert plan.diagnostics["nodes_failed"] == 0
    assert np.array_equal(planner.assignment, assignment)  # no exchange moves

    for j, size in enumerate(sizes):
        on_j, n_apps, caps, n_row, c_hint = planner.node_problem(j)
        assert len(on_j) == size
        ref = p1_solve_batch(
            PackedApps.from_apps(n_apps), caps, n_row, ALPHA, BETA,
            c_hint=c_hint, profile=planner.profile, max_servers=planner._width,
        )
        assert bool(ref.converged[0])
        np.testing.assert_allclose(ref.r_cpu[0], planner.sol_c[on_j], rtol=1e-6)
        np.testing.assert_allclose(ref.r_mem[0], planner.sol_m[on_j], rtol=1e-6)
        assert abs(ref.utility[0] - planner.node_utility[j]) <= 1e-6 * abs(
            planner.node_utility[j]
        )
        # the standalone solve must not have needed rescue/masking either:
        # identical phase-1 starts mean identical infeasible-row accounting
        assert ref.info["n_masked"] == 0
        assert ref.info.get("n_rescued", 0) == 0
    # ... and the fleet-side counters agree: no row was rescued or lost
    assert plan.diagnostics["p1_rescued_rows"] == 0
    assert plan.diagnostics["p1_masked_rows"] == 0


def test_fleet_parity_on_uniform_fleet(small_fleet):
    planner, plan = small_fleet
    assert plan.diagnostics["nodes_failed"] == 0
    for j in range(planner.N):
        on_j, n_apps, caps, n_row, c_hint = planner.node_problem(j)
        ref = p1_solve_batch(
            PackedApps.from_apps(n_apps), caps, n_row, ALPHA, BETA,
            c_hint=c_hint, profile=planner.profile, max_servers=planner._width,
        )
        assert bool(ref.converged[0])
        np.testing.assert_allclose(ref.r_cpu[0], planner.sol_c[on_j], rtol=1e-6)
        np.testing.assert_allclose(ref.r_mem[0], planner.sol_m[on_j], rtol=1e-6)


# ----------------------------------------------------------------------------
# incremental re-plans
# ----------------------------------------------------------------------------
def test_incremental_replan_touches_only_changed_nodes():
    apps, node_caps = make_fleet(8, 6, seed=3)
    planner = FleetPlanner(apps, node_caps, alpha=ALPHA, beta=BETA)
    planner.plan()
    before_c = planner.sol_c.copy()
    before_n = planner.n.copy()

    target = planner.apps[0].name
    node0 = int(planner.assignment[0])
    plan = planner.replan(lam={target: float(planner.lam[0]) * 1.4})
    assert plan.diagnostics["nodes_solved"] == 1
    untouched = planner.assignment != node0
    assert np.array_equal(planner.sol_c[untouched], before_c[untouched])
    assert np.array_equal(planner.n[untouched], before_n[untouched])
    # the drifted app's own node genuinely re-solved
    assert not np.array_equal(
        planner.sol_c[~untouched], before_c[~untouched]
    )


def test_migration_moves_app_and_resolves_both_nodes():
    apps, node_caps = make_fleet(6, 6, seed=7)
    planner = FleetPlanner(apps, node_caps, alpha=ALPHA, beta=BETA)
    planner.plan()
    name = planner.apps[0].name
    src = int(planner.assignment[0])
    dst = (src + 3) % planner.N
    plan = planner.replan(migrations=[(name, dst)])
    assert int(planner.assignment[0]) == dst
    assert plan.diagnostics["migrations"] == 1
    assert plan.diagnostics["nodes_solved"] == 2  # src + dst
    assert plan.diagnostics["nodes_failed"] == 0


# ----------------------------------------------------------------------------
# crms_fleet policy contract
# ----------------------------------------------------------------------------
def test_crms_fleet_policy_cold_then_incremental():
    apps, node_caps = make_fleet(4, 5, seed=1)
    pol = get_policy("crms_fleet")
    pol.reset()
    req = AllocRequest(
        apps=tuple(apps), caps=ServerCaps(*node_caps[0]), alpha=ALPHA, beta=BETA,
        extra={"node_caps": node_caps},
    )
    r1 = allocate("crms_fleet", req)
    assert r1.diagnostics.extra["cold"] is True
    assert r1.diagnostics.nodes_total == 4
    assert r1.allocation.feasible and r1.allocation.stable
    assert len(r1.allocation.meta["assignment"]) == len(apps)

    drifted = tuple(
        a.with_lam(a.lam * 1.1) if i == 0 else a for i, a in enumerate(apps)
    )
    r2 = allocate("crms_fleet", dataclasses.replace(req, apps=drifted))
    assert r2.diagnostics.extra["cold"] is False
    assert r2.diagnostics.nodes_solved == 1
    pol.reset()


def test_crms_fleet_requires_node_caps():
    apps, _ = make_fleet(2, 4, seed=0)
    with pytest.raises(ValueError, match="node_caps"):
        allocate(
            "crms_fleet",
            AllocRequest(apps=tuple(apps), caps=ServerCaps(60.0, 80.0)),
        )


# ----------------------------------------------------------------------------
# timeline tie-break (satellite 2)
# ----------------------------------------------------------------------------
def test_same_epoch_events_apply_in_pinned_order():
    """Join, migrate, resize, set, scale and leave pinned to ONE epoch must
    apply join -> ... -> leave no matter the construction order, so a join
    and a λ-set for the same new app at the same epoch always compose."""
    base = [
        App(name="a0", lam=6.0, xbar=5.0, kappa=(350.0, 0.1, 60.0), r_min=0.5, r_max=2.0),
        App(name="a1", lam=7.0, xbar=5.0, kappa=(350.0, 0.1, 60.0), r_min=0.5, r_max=2.0),
    ]
    joiner = App(name="a2", lam=5.0, xbar=5.0, kappa=(350.0, 0.1, 60.0), r_min=0.5, r_max=2.0)
    events = (
        AppLeave(1, "a1"),                 # deliberately listed first
        LambdaScale(1, {"a2": 2.0}),
        LambdaSet(1, {"a2": 4.0}),
        CapResize(1, 25.0, 9.0),
        AppJoin(1, joiner),
    )
    for order in (events, events[::-1]):
        sc = Scenario(
            name="tiebreak", apps=tuple(base), caps=ServerCaps(30.0, 10.0),
            n_epochs=2, events=order,
        )
        state = sc.timeline()[1]
        names = [a.name for a in state.apps]
        assert names == ["a0", "a2"]            # join applied, leave applied
        lam = {a.name: a.lam for a in state.apps}
        assert lam["a2"] == pytest.approx(8.0)  # join -> set(4.0) -> scale(x2)
        assert state.caps.r_cpu == 25.0
        # the emitted event descriptions are sorted by the pinned order too
        assert list(state.events) == sorted(
            state.events,
            key=lambda s: ["app_join", "app_migrate", "cap_resize",
                           "lam_set", "lam_scale", "app_leave"].index(
                s.split(":")[0]),
        )


def test_migrate_tiebreak_follows_join():
    """A join and a migrate of the SAME app at the same epoch: the join must
    land first so the migrate sees the app."""
    base = (App(name="a0", lam=6.0, xbar=5.0, kappa=(350.0, 0.1, 60.0), r_min=0.5, r_max=2.0),)
    joiner = App(name="a1", lam=5.0, xbar=5.0, kappa=(350.0, 0.1, 60.0), r_min=0.5, r_max=2.0)
    sc = FleetScenario(
        name="mig", apps=base, caps=ServerCaps(30.0, 10.0), n_epochs=2,
        events=(AppMigrate(1, "a1", 0), AppJoin(1, joiner)),
        node_caps=((30.0, 10.0), (30.0, 10.0)),
    )
    state = sc.timeline()[1]
    assert [a.name for a in state.apps] == ["a0", "a1"]
    assert state.migrations == (("a1", 0),)


def test_migrate_unknown_app_rejected():
    base = (App(name="a0", lam=6.0, xbar=5.0, kappa=(350.0, 0.1, 60.0), r_min=0.5, r_max=2.0),)
    sc = Scenario(
        name="bad", apps=base, caps=ServerCaps(30.0, 10.0), n_epochs=2,
        events=(AppMigrate(1, "ghost", 1),),
    )
    with pytest.raises(ValueError, match="ghost"):
        sc.timeline()


# ----------------------------------------------------------------------------
# fleet scenario runner: migrations + sampled DES validation
# ----------------------------------------------------------------------------
@pytest.mark.slow
def test_fleet_scenario_runner_migration_and_des_sample():
    sc = FleetScenario.from_fleet(
        "fleet_smoke", 6, 5, seed=2, n_epochs=3,
        events=(LambdaScale(1, 1.2), AppMigrate(2, "app00000", 3)),
        validate_nodes=2,
    )
    doc = FleetScenarioRunner(sc, epoch_s=30.0).run()
    assert doc["schema_version"] == "fleet-1"
    assert doc["summary"]["n_cold"] == 1
    assert doc["summary"]["migrations_total"] == 1
    assert doc["summary"]["all_nodes_ok"]
    for epoch in doc["epochs"]:
        assert 0 < epoch["validated_nodes"] <= 2
        for v in epoch["validation"]:
            assert v["n_completed"] > 0
            if v["gap_rel"] is not None:
                assert v["gap_rel"] < 0.6  # short-horizon DES, loose gate
    # the analytic model tracks the DES on average much tighter than per-node
    assert doc["summary"]["validation_gap_rel_mean"] < 0.25


# ----------------------------------------------------------------------------
# The sharded row solve (mesh= path) on four virtual CPU devices
# ----------------------------------------------------------------------------
_MESH_PLAN = """
import json
import jax
import numpy as np
from jax.sharding import Mesh
from repro.core.placement import FleetPlanner, make_fleet

apps, node_caps = make_fleet(64, 8, seed=5)
mesh = Mesh(np.array(jax.devices()[:4]), ("nodes",))
out = {}
for key, m in (("mesh", mesh), ("single", None)):
    planner = FleetPlanner(apps, node_caps, alpha=1.4, beta=0.2, mesh=m)
    cold = planner.plan()
    drift = planner.replan(lam={apps[3].name: apps[3].lam * 1.1})
    move = planner.replan(migrations=[(apps[7].name, 40)])
    out[key] = {
        "assignment": planner.assignment.tolist(),
        "sol": np.concatenate([planner.sol_c, planner.sol_m, planner.sol_ws,
                               planner.node_utility]).tolist(),
        "utility": [cold.utility, drift.utility, move.utility],
        "solved": [drift.diagnostics["nodes_solved"], move.diagnostics["nodes_solved"]],
    }
out["devices"] = len(jax.devices())
print(json.dumps(out))
"""


def test_sharded_row_solve_matches_single_device_plan():
    """FleetPlanner(mesh=...) shard_maps every row solve over a 4-device
    "nodes" axis — including the 1- and 2-node replan batches, which the
    planner pads to the axis size — and must reproduce the one-device plan."""
    import json
    import os
    import subprocess
    import sys
    from pathlib import Path

    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = str(Path(__file__).resolve().parent.parent / "src")
    proc = subprocess.run([sys.executable, "-c", _MESH_PLAN], capture_output=True,
                          text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4
    sharded, single = out["mesh"], out["single"]
    assert sharded["assignment"] == single["assignment"]
    assert sharded["solved"] == single["solved"] == [1, 2]
    np.testing.assert_allclose(sharded["sol"], single["sol"], rtol=1e-9, atol=0.0)
    np.testing.assert_allclose(sharded["utility"], single["utility"], rtol=1e-9, atol=0.0)
