"""Cold-start benchmark: what container spin-up lag costs the paper's CRMS,
and how much the lifecycle-aware ``crms_lifecycle`` policy recovers.

Three legs, all scored by the closed-loop DES backend (CRN arrivals shared
across policies and sweep points, so every comparison is paired):

* **Sweep** — a two-spike flash-crowd trace (``Scenario.flash_crowd``)
  replayed at every cold-start lag t_cold ∈ {0, 0.5, 2, 5} s, with
  ``crms_lifecycle`` additionally run at fixed keep-warm pool sizes next to
  its default auto sizing. Gates: at every t_cold > 0 the lifecycle policy's
  achieved mean AND p95 must not exceed plain ``crms``'s (the switching-cost
  accept rule never trades latency away, and the pool parked after the first
  spike absorbs the second); at t_cold = 0 its allocations must be
  BYTE-IDENTICAL to ``crms`` (the derived re-plan bar collapses to zero).

* **Scale-to-zero** — one tenant's traffic collapses to near-zero and
  returns (``Scenario.scale_to_zero``): the lifecycle policy parks the
  decommissioned capacity warm (idle power now) and reabsorbs it instantly
  on the return, versus a full re-ramp for plain CRMS.

* **Parity** — the event-vs-vector CRN oracle check THROUGH a mid-ramp
  reconfigure (a second ``configure()`` landing while cold containers are
  still booting, plus a scale-to-zero + rejoin), MMPP arrivals included:
  per-request response times must agree to ≤ 1e-9 between the engines.

Artifact: BENCH_coldstart.json (sweep curves + economics + gate booleans).

CLI:  PYTHONPATH=src:. python -m benchmarks.coldstart
      [--smoke] [--engine event|vector] [--epochs N] [--epoch-s SEC]
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

if __package__ in (None, ""):  # run as a plain script: repo root + src on sys.path
    _root = Path(__file__).resolve().parent.parent
    sys.path.insert(0, str(_root / "src"))
    sys.path.insert(0, str(_root))

import numpy as np

from benchmarks.common import ALPHA, BETA, emit, paper_apps
from repro.api import (
    AllocRequest,
    LifecycleSpec,
    Scenario,
    ScenarioRunner,
    get_policy,
    mmpp2,
    validate_scenarios_doc,
)
from repro.core.des import FleetSimulator
from repro.core.problem import ServerCaps

POLICIES = ("crms", "crms_lifecycle")
T_COLDS = (0.0, 0.5, 2.0, 5.0)  # cold-start lag sweep [s]; 0 = paper model
POOLS = (0, 2)  # fixed keep-warm variants next to the default auto sizing
FACTOR = 3.0  # flash-crowd spike factor
# roomy caps (the burst_robustness convention, scaled up so the x3 spike
# epochs stay FEASIBLE): lifecycle economics need provisioning headroom —
# at a memory-starved point there is nothing to park
ROOMY = ServerCaps(r_cpu=80.0, r_mem=32.0)
N_EPOCHS, EPOCH_S = 12, 30.0
SEED = 11
PARITY_TOL = 1e-9  # event-vs-vector CRN gate through a mid-ramp reconfig
OUT = Path(__file__).resolve().parent.parent / "BENCH_coldstart.json"


def _score(doc: dict, policy: str) -> dict:
    eps = doc["policies"][policy]["epochs"]
    s = doc["policies"][policy]["summary"]
    p95 = [e["achieved_p95_s"] for e in eps if e["achieved_p95_s"] is not None]
    return {
        "achieved_mean_s": s["achieved_mean_s"],
        "achieved_p95_s": float(np.mean(p95)) if p95 else None,
        "total_power_w_mean": s["total_power_w_mean"],
        "n_replans": s["n_replans"],
        "all_feasible": s["all_feasible"],
        "all_stable": s["all_stable"],
    }


def _run(sc: Scenario, policies, engine: str, epoch_s: float, extra=None) -> dict:
    runner = ScenarioRunner(
        sc, list(policies), quasi_dynamic=False, extra=extra,
        backend="des", epoch_s=epoch_s, des_engine=engine,
    )
    doc = runner.run()
    validate_scenarios_doc(doc)
    return doc


def _flash_crowd(t_cold: float, n_epochs: int, apps=None) -> Scenario:
    return Scenario.flash_crowd(
        tuple(apps if apps is not None else paper_apps()), ROOMY,
        name=f"flash_tc{t_cold:g}", n_epochs=n_epochs, factor=FACTOR,
        t_cold=t_cold, alpha=ALPHA, beta=BETA, seed=SEED,
    )


def sweep_point(
    t_cold: float, engine: str, n_epochs: int = N_EPOCHS, epoch_s: float = EPOCH_S
) -> dict:
    sc = _flash_crowd(t_cold, n_epochs)
    doc = _run(sc, POLICIES, engine, epoch_s)  # crms + auto-pool lifecycle
    row = {p: _score(doc, p) for p in POLICIES}
    for w in POOLS:  # fixed-pool variants, CRN-paired (same scenario seed)
        vdoc = _run(
            sc, ("crms_lifecycle",), engine, epoch_s,
            extra={"crms_lifecycle": {"keep_warm": w}},
        )
        row[f"crms_lifecycle_w{w}"] = _score(vdoc, "crms_lifecycle")
    row["t_cold"] = t_cold
    return row


def scale_to_zero_leg(
    t_cold: float, engine: str, n_epochs: int = N_EPOCHS, epoch_s: float = EPOCH_S
) -> dict:
    sc = Scenario.scale_to_zero(
        tuple(paper_apps()), ROOMY, name=f"s2z_tc{t_cold:g}",
        n_epochs=n_epochs, t_cold=t_cold, alpha=ALPHA, beta=BETA, seed=SEED,
    )
    doc = _run(sc, POLICIES, engine, epoch_s)
    row = {p: _score(doc, p) for p in POLICIES}
    row["t_cold"] = t_cold
    return row


def identity_check(n_epochs: int = N_EPOCHS, apps=None) -> bool:
    """At t_cold = 0 the derived re-plan bar is zero and pools are empty:
    crms_lifecycle must produce BYTE-IDENTICAL allocations to plain crms at
    every epoch of the trace (same draws, same arrays, same utility)."""
    sc = _flash_crowd(0.0, n_epochs, apps=apps)
    lifec = get_policy("crms_lifecycle")
    lifec.reset()
    plain = get_policy("crms")
    extra = {"replan_horizon_s": EPOCH_S}
    for state in sc.timeline():
        req = AllocRequest(
            apps=state.apps, caps=state.caps, alpha=sc.alpha, beta=sc.beta,
            options=sc.options, seed=sc.seed, extra=extra,
        )
        a = lifec.allocate(req).allocation
        b = plain.allocate(req).allocation
        if not (
            np.array_equal(a.n, b.n)
            and np.array_equal(a.r_cpu, b.r_cpu)
            and np.array_equal(a.r_mem, b.r_mem)
            and a.utility == b.utility
        ):
            return False
    return True


def parity_check(t_cold: float = 2.0) -> float:
    """Max |event − vector| over per-request response times of a trace that
    reconfigures MID-RAMP (while cold containers are still booting), retargets
    the warm pool, scales an app to zero and rejoins it — MMPP arrivals on
    one app so the modulated stream crosses every boundary too."""
    out = {}
    for eng in ("event", "vector"):
        sim = FleetSimulator(
            seed=5, engine=eng, lifecycle=LifecycleSpec(t_cold, warm_pool=1)
        )
        sim.add_app("a", 8.0, 2.5, 4, arrival=mmpp2(2.5, 0.25, 40.0))
        sim.add_app("b", 5.0, 2.0, 3)
        sim.run_until(30.0)
        sim.configure("a", lam=16.0, n_servers=10)  # flash crowd: ramp starts
        sim.run_until(30.0 + 0.4 * t_cold)
        sim.configure("a", n_servers=8, warm_pool=2)  # MID-RAMP supersede
        sim.configure("b", n_servers=0)  # scale-to-zero (parks one warm)
        sim.run_until(45.0)
        sim.configure("b", n_servers=3)  # rejoin: 1 warm instant, 2 booting
        sim.run_until(60.0)
        sim.drain()
        out[eng] = tuple(
            np.sort(sim.responses(nm, 0.0, 1e9)) for nm in ("a", "b")
        )
    gap = 0.0
    for ev, vec in zip(out["event"], out["vector"]):
        if ev.shape != vec.shape:
            return float("inf")
        if ev.size:
            gap = max(gap, float(np.max(np.abs(ev - vec))))
    return gap


def _gate(ok: bool, label: str, detail: str = "") -> bool:
    if not ok:
        print(f"  !! gate FAILED: {label} {detail}")
    return ok


def run(
    smoke: bool = False,
    engine: str = "vector",
    n_epochs: int = N_EPOCHS,
    epoch_s: float = EPOCH_S,
    out: Path = OUT,
) -> bool:
    if smoke:
        # tiny CI run: M=3, one flash-crowd ramp at t_cold=2 through BOTH
        # engines; gates: crms_lifecycle never loses to crms, exact parity
        # at t_cold=0, and the mid-ramp CRN oracle check
        apps3 = tuple(paper_apps()[:3])
        ok = _gate(identity_check(n_epochs=4, apps=apps3), "t_cold=0 identity")
        gap = parity_check(2.0)
        ok &= _gate(
            gap <= PARITY_TOL, "mid-ramp CRN parity", f"(gap={gap:.3g})"
        )
        for eng in ("event", "vector"):
            sc = _flash_crowd(2.0, 6, apps=apps3)
            doc = _run(sc, POLICIES, eng, 20.0)
            c = _score(doc, "crms")
            lc = _score(doc, "crms_lifecycle")
            print(f"smoke[{eng}]  crms mean={c['achieved_mean_s']:.4f}  "
                  f"lifecycle mean={lc['achieved_mean_s']:.4f}")
            ok &= _gate(lc["all_feasible"] and lc["all_stable"],
                        f"{eng}: lifecycle feasible+stable")
            ok &= _gate(
                lc["achieved_mean_s"] <= c["achieved_mean_s"],
                f"{eng}: crms_lifecycle must not lose at t_cold=2",
                f"({lc['achieved_mean_s']:.4f} vs {c['achieved_mean_s']:.4f})",
            )
        emit("coldstart", 0.0, f"smoke;engines=2;gate={'ok' if ok else 'FAIL'}")
        return bool(ok)

    points = [sweep_point(tc, engine, n_epochs, epoch_s) for tc in T_COLDS]
    s2z = scale_to_zero_leg(2.0, engine, n_epochs, epoch_s)
    identity = identity_check(n_epochs)
    parity_gap = parity_check(2.0)

    print(f"\ncold-start sweep (engine={engine}, flash-crowd x{FACTOR:g}, "
          f"{n_epochs}x{epoch_s:g}s epochs):")
    print(f"{'t_cold':>6s} {'crms_mean':>10s} {'crms_p95':>10s} "
          f"{'lc_mean':>9s} {'lc_p95':>9s} {'lc_replans':>10s} {'lc_power':>9s}")
    for row in points:
        c, lc = row["crms"], row["crms_lifecycle"]
        print(f"{row['t_cold']:6.1f} {c['achieved_mean_s']:10.4f} "
              f"{c['achieved_p95_s']:10.4f} {lc['achieved_mean_s']:9.4f} "
              f"{lc['achieved_p95_s']:9.4f} {lc['n_replans']:10d} "
              f"{lc['total_power_w_mean']:9.1f}")
    c, lc = s2z["crms"], s2z["crms_lifecycle"]
    print(f"scale-to-zero (t_cold={s2z['t_cold']:g}): "
          f"crms mean={c['achieved_mean_s']:.4f} p95={c['achieved_p95_s']:.4f}"
          f"  lifecycle mean={lc['achieved_mean_s']:.4f} "
          f"p95={lc['achieved_p95_s']:.4f}")
    print(f"t_cold=0 identity: {identity}   mid-ramp parity gap: {parity_gap:.3g}")

    # ---- gates -------------------------------------------------------------
    ok = _gate(identity, "crms_lifecycle byte-identical to crms at t_cold=0")
    ok &= _gate(
        parity_gap <= PARITY_TOL,
        "event-vs-vector CRN parity through a mid-ramp reconfig",
        f"(gap={parity_gap:.3g})",
    )
    for row in points:
        if row["t_cold"] <= 0.0:
            continue
        for key in ("achieved_mean_s", "achieved_p95_s"):
            ok &= _gate(
                row["crms_lifecycle"][key] <= row["crms"][key],
                f"crms_lifecycle wins {key} at t_cold={row['t_cold']:g}",
                f"({row['crms_lifecycle'][key]:.4f} vs {row['crms'][key]:.4f})",
            )
    for key in ("achieved_mean_s", "achieved_p95_s"):
        ok &= _gate(
            s2z["crms_lifecycle"][key] <= s2z["crms"][key],
            f"crms_lifecycle wins {key} on scale-to-zero",
            f"({s2z['crms_lifecycle'][key]:.4f} vs {s2z['crms'][key]:.4f})",
        )
    for row in points + [s2z]:
        lc = row["crms_lifecycle"]
        ok &= _gate(
            lc["all_feasible"] and lc["all_stable"],
            "crms_lifecycle feasible+stable everywhere",
        )

    doc = {
        "schema_version": 1,
        "engine": engine,
        "sweep": {
            "factor": FACTOR, "n_epochs": n_epochs, "epoch_s": epoch_s,
            "seed": SEED, "pools_fixed": list(POOLS),
            "caps": {"r_cpu": ROOMY.r_cpu, "r_mem": ROOMY.r_mem},
            "points": points,
        },
        "scale_to_zero": s2z,
        "identity_t_cold0": bool(identity),
        "parity_gap": parity_gap,
        "parity_tol": PARITY_TOL,
        "gates_ok": bool(ok),
    }
    out.write_text(json.dumps(doc, indent=2, sort_keys=True) + "\n")

    worst = points[-1]
    lag_cost = worst["crms"]["achieved_mean_s"] / points[0]["crms"]["achieved_mean_s"]
    recov = worst["crms"]["achieved_mean_s"] / worst["crms_lifecycle"]["achieved_mean_s"]
    emit(
        "coldstart", 0.0,
        f"points={len(points)};crms_lag_cost={lag_cost:.2f}x;"
        f"lifecycle_recovers={recov:.2f}x;gate={'ok' if ok else 'FAIL'}",
    )
    return bool(ok)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI run: one flash-crowd ramp, both engines")
    ap.add_argument("--engine", default="vector", choices=("event", "vector"))
    ap.add_argument("--epochs", type=int, default=N_EPOCHS)
    ap.add_argument("--epoch-s", type=float, default=EPOCH_S)
    args = ap.parse_args(argv)
    return 0 if run(
        smoke=args.smoke, engine=args.engine,
        n_epochs=args.epochs, epoch_s=args.epoch_s,
    ) else 1


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    raise SystemExit(main())
