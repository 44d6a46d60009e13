"""Fleet-of-fleets placement throughput: the ISSUE-6 acceptance gates.

Full mode builds a 1000-node x M=16 fleet (16k apps), runs one compile-warming
plan, then measures

  cold re-plan    — a FRESH FleetPlanner's plan() wall-clock (greedy placement
                    + exchange refinement + the full 1024-row batched P1 row
                    solve; jit caches are process-global so a fresh planner is
                    the honest "re-plan from scratch" cost).  Gate: < 1 s CPU.
  incremental     — replan() after λ drift on a handful of apps plus one
                    migration: only touched nodes re-solve.  Gate: >= 10x
                    faster than cold (the second replan is timed; the first
                    compiles the touched-batch jit entry).
  parity          — sampled nodes' rows vs a standalone p1_solve_batch on the
                    node's own (apps, caps, counts, recorded phase-1 hint):
                    max relative difference over (c, m, utility).
                    Gate: <= 1e-6 (measured ~1e-15; the padded/masked/width-
                    narrowed fleet row IS the standalone solve).

plus a migration-scenario record: a small FleetScenario driven through
FleetScenarioRunner with per-epoch vector-DES validation of sampled nodes.

--smoke shrinks the fleet to 64 nodes x M=8 with one migration event and
relaxes the incremental floor to 3x (CI hosts share cores); the parity gate
stays at 1e-6.  Records land in BENCH_fleet.json either way.

CLI:  python benchmarks/fleet_placement.py [--smoke] [--nodes N] [--m M]
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import numpy as np

from benchmarks.common import ALPHA, BETA, emit
from repro.core.engine import PackedApps, p1_solve_batch
from repro.core.placement import FleetPlanner, make_fleet

PARITY_TOL = 1e-6
COLD_BUDGET_S = 1.0
INCR_FLOOR_FULL = 10.0
INCR_FLOOR_SMOKE = 3.0


def _parity(planner: FleetPlanner, nodes) -> float:
    """Max relative diff between the fleet row solve and standalone
    p1_solve_batch on each sampled node's own problem."""
    worst = 0.0
    for j in nodes:
        j = int(j)
        if not planner.node_ok[j]:
            continue
        on_j, apps, caps, n_row, c_hint = planner.node_problem(j)
        ref = p1_solve_batch(
            PackedApps.from_apps(apps), caps, n_row, planner.alpha, planner.beta,
            c_hint=c_hint, profile=planner.profile, max_servers=planner._width,
        )
        if not bool(ref.converged[0]):
            continue
        c, m = planner.sol_c[on_j], planner.sol_m[on_j]
        worst = max(
            worst,
            float(np.max(np.abs(ref.r_cpu[0] - c) / np.maximum(np.abs(c), 1e-12))),
            float(np.max(np.abs(ref.r_mem[0] - m) / np.maximum(np.abs(m), 1e-12))),
            abs(float(ref.utility[0]) - float(planner.node_utility[j]))
            / max(abs(float(planner.node_utility[j])), 1e-12),
        )
    return worst


def _drift(planner: FleetPlanner, rng, idx):
    """A λ-drift dict over the apps at ``idx`` (bounded nodes re-solve)."""
    return {
        planner.apps[int(i)].name: float(planner.lam[int(i)]) * float(rng.uniform(0.85, 1.2))
        for i in idx
    }


def bench_fleet(n_nodes: int, m_per_node: int, incr_floor: float, seed: int = 0) -> dict:
    apps, node_caps = make_fleet(n_nodes, m_per_node, seed=seed)
    rng = np.random.default_rng(seed + 1)

    # compile-warming pass: pays every jit compile (row solve at the padded
    # batch size, phase-1, Erlang width) so the timed planners measure compute
    warm = FleetPlanner(apps, node_caps, alpha=ALPHA, beta=BETA)
    warm.plan()

    # cold re-plan: fresh planner, warm jit caches
    cold = FleetPlanner(apps, node_caps, alpha=ALPHA, beta=BETA)
    plan_cold = cold.plan()
    t_cold = float(plan_cold.diagnostics["wall_clock_s"])

    # incremental: λ drift on a fixed app set + one migration.  The same app
    # set drifts (to fresh values) on every replan so the touched-node batch
    # keeps one shape: replan #1 exercises the migration path, #2 pays the
    # drift-only jit compile, #3 is the steady-state cost we time.
    n_drift = max(2, n_nodes // 250)
    drift_idx = rng.choice(cold.A, size=n_drift, replace=False)
    mig_app = cold.apps[int(rng.integers(cold.A))].name
    mig_dst = int(rng.integers(n_nodes))
    cold.replan(lam=_drift(cold, rng, drift_idx), migrations=[(mig_app, mig_dst)])
    cold.replan(lam=_drift(cold, rng, drift_idx))
    plan_incr = cold.replan(lam=_drift(cold, rng, drift_idx))
    t_incr = float(plan_incr.diagnostics["wall_clock_s"])
    speedup = t_cold / max(t_incr, 1e-12)

    sample = rng.choice(n_nodes, size=min(8, n_nodes), replace=False)
    parity = _parity(cold, sample)

    rec = {
        "n_nodes": int(n_nodes),
        "apps_per_node": int(m_per_node),
        "apps_total": int(cold.A),
        "M_pad": int(cold.M_pad),
        "erlang_width": int(cold._width),
        "cold_plan_s": t_cold,
        "incremental_replan_s": t_incr,
        "incremental_nodes_solved": int(plan_incr.diagnostics["nodes_solved"]),
        "speedup_incremental": speedup,
        "parity_max_rel": parity,
        "parity_nodes_sampled": int(sample.size),
        "nodes_failed": int(plan_cold.diagnostics["nodes_failed"]),
        "exchange_accepted": int(plan_cold.diagnostics.get("exchange_accepted", 0)),
        "utility": float(plan_cold.utility),
        "gates": {
            "cold_budget_s": COLD_BUDGET_S,
            "incr_floor": incr_floor,
            "parity_tol": PARITY_TOL,
        },
        "cold_ok": t_cold < COLD_BUDGET_S,
        "incr_ok": speedup >= incr_floor,
        "parity_ok": parity <= PARITY_TOL,
        "placement_ok": plan_cold.diagnostics["nodes_failed"] == 0,
    }
    rec["ok"] = bool(rec["cold_ok"] and rec["incr_ok"] and rec["parity_ok"]
                     and rec["placement_ok"])
    return rec


def bench_scenario(n_nodes: int, m_per_node: int, seed: int = 0) -> dict:
    """Migration trace through FleetScenarioRunner with vector-DES sampling."""
    from repro.api.scenario import AppMigrate, FleetScenario, FleetScenarioRunner, LambdaScale

    sc = FleetScenario.from_fleet(
        "fleet_migration", n_nodes, m_per_node, seed=seed, n_epochs=4,
        events=(
            LambdaScale(1, 1.25),
            AppMigrate(2, "app00001", n_nodes - 1),
        ),
        validate_nodes=3,
    )
    doc = FleetScenarioRunner(sc, epoch_s=40.0).run()
    s = doc["summary"]
    gap = s["validation_gap_rel_mean"]
    return {
        "n_nodes": int(n_nodes),
        "apps_per_node": int(m_per_node),
        "n_epochs": s["n_epochs"],
        "migrations_total": s["migrations_total"],
        "replan_time_s_mean": s["replan_time_s_mean"],
        "des_validation_gap_rel_mean": gap,
        "all_nodes_ok": s["all_nodes_ok"],
        # DES-vs-Erlang gap is stochastic; 25% matches the des-smoke gate
        "ok": bool(s["all_nodes_ok"] and s["migrations_total"] >= 1
                   and gap is not None and gap < 0.25),
    }


def run(smoke: bool = False, n_nodes: int | None = None, m_per_node: int | None = None) -> bool:
    if smoke:
        n_nodes = n_nodes or 64
        m_per_node = m_per_node or 8
        incr_floor = INCR_FLOOR_SMOKE
    else:
        n_nodes = n_nodes or 1000
        m_per_node = m_per_node or 16
        incr_floor = INCR_FLOOR_FULL

    t0 = time.perf_counter()
    fleet = bench_fleet(n_nodes, m_per_node, incr_floor)
    scenario = bench_scenario(min(n_nodes, 16), min(m_per_node, 8))
    ok = bool(fleet["ok"] and scenario["ok"])

    print(
        f"fleet {n_nodes}x{m_per_node}: cold {fleet['cold_plan_s']*1e3:7.1f}ms "
        f"({'OK' if fleet['cold_ok'] else 'FAIL'} vs {COLD_BUDGET_S:.1f}s) | "
        f"incremental {fleet['incremental_replan_s']*1e3:6.1f}ms "
        f"({fleet['speedup_incremental']:.1f}x, floor {incr_floor:.0f}x "
        f"{'OK' if fleet['incr_ok'] else 'FAIL'}) | "
        f"parity {fleet['parity_max_rel']:.2e} "
        f"({'OK' if fleet['parity_ok'] else 'FAIL'})"
    )
    print(
        f"scenario {scenario['n_nodes']}x{scenario['apps_per_node']}: "
        f"{scenario['migrations_total']} migration(s), DES gap "
        f"{scenario['des_validation_gap_rel_mean']:.3f} "
        f"({'OK' if scenario['ok'] else 'FAIL'})"
    )

    out = Path(__file__).resolve().parent.parent / "BENCH_fleet.json"
    out.write_text(
        json.dumps(
            {
                "mode": "smoke" if smoke else "full",
                "ok": ok,
                "fleet": fleet,
                "migration_scenario": scenario,
                "total_bench_s": time.perf_counter() - t0,
            },
            indent=2,
        )
        + "\n"
    )
    emit(
        "fleet_placement",
        fleet["cold_plan_s"] * 1e6,
        f"incr={fleet['speedup_incremental']:.1f}x;"
        f"parity={fleet['parity_max_rel']:.1e};ok={ok}",
    )
    return ok


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="64 nodes x M=8, one migration, 3x incremental floor")
    ap.add_argument("--nodes", type=int, default=None, help="override node count")
    ap.add_argument("--m", type=int, default=None, help="override apps per node")
    args = ap.parse_args()
    return 0 if run(smoke=args.smoke, n_nodes=args.nodes, m_per_node=args.m) else 1


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    raise SystemExit(main())
