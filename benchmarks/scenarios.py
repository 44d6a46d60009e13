"""Cross-policy scenario benchmark: the paper's dynamic-workload comparison
(§VI) as a LIBRARY of declarative traces driven through the policy registry,
scored by either the analytic model or the fleet discrete-event simulator.

Scenario library (benchmarks/scenarios.py --scenarios a,b,...):

    paper_constrained_dynamic — the four §VI apps at the constrained point
        under drifting λ with a tenant join / cap resize / tenant leave.
    burst    — flash-crowd step: the lightest tenant's λ jumps 2.5x, reverts.
    failover — a node dies (CPU+mem budget drops 25%), later recovers.
    diurnal  — common-mode day/night sinusoid (all tenants peak together).
    priority — one tenant carries a 4x latency weight (crms_priority honors
        it through the weighted objective; unweighted policies replay the
        same trace as controls).

Backends (--backend): "analytic" scores each epoch with the Erlang-C model
the solver optimizes; "des" ALSO replays each epoch's Poisson arrivals
through the fleet simulator against the chosen allocation and records the
achieved mean/p95 next to the prediction (the closed-loop model-error gap).

Gates: the bundle validates against the api.scenario schema, every epoch of
every policy is budget-feasible, CRMS-family policies stay queue-stable, and
under --backend des the CRMS analytic-vs-simulated mean-latency gap must be
< 25% per scenario. DRF is *expected* to go unstable — that is the paper's
point — so stability only gates the CRMS family. SNFC is selectable via
--policies but excluded from the defaults: at the constrained operating
point it honestly reports infeasible (the §VI SNFC pathology).

CLI:  PYTHONPATH=src:. python -m benchmarks.scenarios
      [--backend analytic|des] [--des-engine event|vector]
      [--scenarios burst,failover,...]
      [--policies crms,predictive_crms,...] [--epochs N] [--epoch-s SEC]
      [--smoke]
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
from pathlib import Path

if __package__ in (None, ""):  # run as a plain script: repo root + src on sys.path
    _root = Path(__file__).resolve().parent.parent
    sys.path.insert(0, str(_root / "src"))
    sys.path.insert(0, str(_root))

from benchmarks.common import ALPHA, BETA, CONSTRAINED_CAPS, CONSTRAINED_LAM, emit, paper_apps
from repro.api import (
    AppJoin,
    AppLeave,
    CapResize,
    LambdaDrift,
    Scenario,
    ScenarioRunner,
    compact_scenarios_doc,
    dumps_scenarios_doc,
    validate_scenarios_doc,
)
from repro.core.problem import ServerCaps

DEFAULT_POLICIES = ("crms", "predictive_crms", "crms_priority", "drf")
# policies whose contract includes queue stability (gate all_stable on these)
STABLE_POLICIES = frozenset({"crms", "predictive_crms", "crms_priority"})
# cheap budgets for the search baselines when they are requested explicitly
POLICY_EXTRA = {
    "random_search": {"n_samples": 8000},
    "gpbo": {"n_init": 8, "n_iters": 24},
    "tpebo": {"n_init": 8, "n_iters": 24},
}
N_EPOCHS = 10
EPOCH_S = 60.0  # simulated seconds per decision epoch (des backend)
MAX_GAP_REL = 0.25  # CI gate: CRMS analytic-vs-simulated mean-latency gap
OUT = Path(__file__).resolve().parent.parent / "BENCH_scenarios.json"


def default_scenario(n_epochs: int = N_EPOCHS) -> Scenario:
    if n_epochs < 1:
        raise ValueError(f"n_epochs must be >= 1, got {n_epochs}")
    apps = paper_apps(lam=CONSTRAINED_LAM, fitted=False)
    # the joining tenant: a second MobileNet-class workload with its own rate
    burst = dataclasses.replace(apps[2], name="MobileNet_v2_burst", lam=6.0)
    # short (smoke) traces compress the epochs but keep all three event
    # kinds; epochs clamp into [0, n_epochs) and same-epoch events apply in
    # order (join before leave), so any n_epochs >= 1 yields a valid trace
    e_join, e_resize, e_leave = (3, 5, 7) if n_epochs > 7 else (1, 2, 3)
    events = (
        AppJoin(epoch=min(e_join, n_epochs - 1), app=burst),
        CapResize(epoch=min(e_resize, n_epochs - 1), r_cpu=34.0, r_mem=11.5),
        AppLeave(epoch=min(e_leave, n_epochs - 1), name="MobileNet_v2_burst"),
    )
    return Scenario(
        name="paper_constrained_dynamic",
        apps=tuple(apps),
        caps=CONSTRAINED_CAPS,
        n_epochs=n_epochs,
        alpha=ALPHA,
        beta=BETA,
        events=events,
        drift=LambdaDrift(),
    )


def scenario_library(n_epochs: int = N_EPOCHS) -> dict[str, Scenario]:
    """The named trace library. Caps per scenario are sized so the CRMS
    family stays feasible at every epoch (the benchmark's gate): traces that
    push the load/budget envelope (burst, diurnal peak, failover trough) run
    against a proportionally larger base budget."""
    apps = tuple(paper_apps(lam=CONSTRAINED_LAM, fitted=False))
    roomy = ServerCaps(r_cpu=CONSTRAINED_CAPS.r_cpu * 1.3, r_mem=CONSTRAINED_CAPS.r_mem * 1.3)
    return {
        "paper_constrained_dynamic": default_scenario(n_epochs),
        "burst": Scenario.burst(
            apps, roomy, n_epochs=n_epochs, app="MobileNet_v2", factor=2.5,
            alpha=ALPHA, beta=BETA,
        ),
        "failover": Scenario.failover(
            apps, roomy, n_epochs=n_epochs, drop=0.2, alpha=ALPHA, beta=BETA
        ),
        "diurnal": Scenario.diurnal(
            apps, roomy, n_epochs=max(n_epochs, 4), amplitude=0.22,
            alpha=ALPHA, beta=BETA,
        ),
        "priority": Scenario.priority_tenants(
            apps, CONSTRAINED_CAPS, n_epochs=n_epochs, alpha=ALPHA, beta=BETA
        ),
    }


def smoke_scenario(n_epochs: int = 3) -> Scenario:
    """Tiny-horizon CI trace: M=3 of the §VI apps at a scaled-down budget,
    still covering all three event kinds (join, cap resize, leave)."""
    apps = paper_apps(lam=CONSTRAINED_LAM, fitted=False)[:3]
    joiner = dataclasses.replace(apps[2], name="MobileNet_v2_burst", lam=5.0)
    caps = ServerCaps(r_cpu=26.0, r_mem=9.0)
    events = (
        AppJoin(epoch=min(1, n_epochs - 1), app=joiner),
        CapResize(epoch=min(2, n_epochs - 1), r_cpu=28.0, r_mem=9.5),
        AppLeave(epoch=min(2, n_epochs - 1), name="MobileNet_v2_burst"),
    )
    return Scenario(
        name="smoke",
        apps=tuple(apps),
        caps=caps,
        n_epochs=n_epochs,
        alpha=ALPHA,
        beta=BETA,
        events=events,
        drift=LambdaDrift(),
    )


def run(
    policies=DEFAULT_POLICIES,
    scenarios=None,
    n_epochs: int = N_EPOCHS,
    backend: str = "analytic",
    epoch_s: float = EPOCH_S,
    smoke: bool = False,
    out: Path = OUT,
    des_engine: str = "event",
) -> bool:
    if smoke:
        selected = {"smoke": smoke_scenario()}
    else:
        lib = scenario_library(n_epochs)
        names = tuple(scenarios) if scenarios else tuple(lib)
        unknown = sorted(set(names) - set(lib))
        if unknown:
            raise SystemExit(
                f"unknown scenario(s): {', '.join(unknown)}; "
                f"library: {', '.join(lib)}"
            )
        selected = {n: lib[n] for n in names}

    doc = {"schema_version": 2, "backend": backend, "scenarios": {}}
    ok = True
    for name, scenario in selected.items():
        runner = ScenarioRunner(
            scenario, policies, extra=POLICY_EXTRA, backend=backend,
            epoch_s=epoch_s, des_engine=des_engine,
        )
        sub = runner.run()
        doc["scenarios"][name] = sub

        print(f"\nscenario {name}: {scenario.n_epochs} epochs, "
              f"{len(scenario.events)} events, backend={backend}"
              f"{f' (engine={des_engine})' if backend == 'des' else ''}, "
              f"policies: {', '.join(sub['policies'])}")
        print(f"{'policy':16s} {'replans':>7s} {'replan_s':>9s} {'pred_s':>8s} "
              f"{'achieved_s':>10s} {'gap':>6s} {'power_W':>8s} {'feas':>5s} {'stable':>6s}")
        for pname, row in sub["matrix"].items():
            rt = row["replan_time_s_mean"]
            lat = row["mean_latency_s"]
            ach = row["achieved_mean_s"]
            gap = row["mean_gap_rel"]
            pwr = row["total_power_w_mean"]
            print(f"{pname:16s} {row['n_replans']:7d} "
                  f"{rt if rt is None else round(rt, 3)!s:>9s} "
                  f"{lat if lat is None else round(lat, 4)!s:>8s} "
                  f"{ach if ach is None else round(ach, 4)!s:>10s} "
                  f"{gap if gap is None else round(gap, 3)!s:>6s} "
                  f"{pwr if pwr is None else round(pwr, 1)!s:>8s} "
                  f"{str(row['all_feasible']):>5s} {str(row['all_stable']):>6s}")
            ok &= row["all_feasible"]  # every epoch budget-feasible, all policies
            if pname in STABLE_POLICIES:
                ok &= row["all_stable"]  # the CRMS family must stay queue-stable
            if backend == "des" and pname == "crms":
                gap_ok = gap is not None and gap < MAX_GAP_REL
                if not gap_ok:
                    print(f"  !! crms analytic-vs-simulated gap {gap} exceeds "
                          f"{MAX_GAP_REL} on scenario {name}")
                ok &= gap_ok

    validate_scenarios_doc(doc)
    # persist the compact parallel-array shape (schema 2.1) — same data,
    # a fraction of the lines; the validator gates both shapes
    compact = compact_scenarios_doc(doc)
    validate_scenarios_doc(compact)
    out.write_text(dumps_scenarios_doc(compact) + "\n")

    # headline row: CRMS on the first scenario when present
    first = next(iter(doc["scenarios"].values()))
    head = first["matrix"].get("crms") or next(iter(first["matrix"].values()))
    emit(
        "scenarios",
        (head["replan_time_s_mean"] or 0.0) * 1e6,
        f"scenarios={len(doc['scenarios'])};policies={len(first['policies'])};"
        f"backend={backend};replans={head['n_replans']}",
    )
    return bool(ok)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--policies", default=",".join(DEFAULT_POLICIES),
                    help="comma-separated registered policy names")
    ap.add_argument("--scenarios", default=None,
                    help="comma-separated scenario names (default: whole library)")
    ap.add_argument("--backend", default="analytic", choices=("analytic", "des"),
                    help="evaluation backend: analytic model or fleet DES replay")
    ap.add_argument("--des-engine", default="event", choices=("event", "vector"),
                    help="DES implementation: heapq event loop or the "
                         "Kiefer-Wolfowitz vectorized segment fast path")
    ap.add_argument("--epochs", type=int, default=N_EPOCHS)
    ap.add_argument("--epoch-s", type=float, default=EPOCH_S,
                    help="simulated seconds per decision epoch (des backend)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI trace: M=3, 3 epochs, join/resize/leave")
    args = ap.parse_args(argv)
    policies = tuple(p for p in args.policies.split(",") if p)
    scenarios = (
        tuple(s for s in args.scenarios.split(",") if s) if args.scenarios else None
    )
    return 0 if run(
        policies=policies,
        scenarios=scenarios,
        n_epochs=args.epochs,
        backend=args.backend,
        epoch_s=args.epoch_s,
        smoke=args.smoke,
        des_engine=args.des_engine,
    ) else 1


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    raise SystemExit(main())
