"""Failure-resilience benchmark: what container crashes, stragglers and
profiling error cost the paper's CRMS, and how much the failure-aware
``crms_failover`` policy (N+k hedging + bounded crash re-plans + the
load-shedding ladder) recovers.

Four legs, all scored by the closed-loop DES backend (CRN arrivals shared
across policies and sweep points, so every comparison is paired):

* **MTBF sweep** — the paper mix replayed under per-container exponential
  crash–repair dynamics at every MTBF ∈ {off, 300, 120, 60} s (MTTR 10 s,
  cold restart 1 s). Gates: with failures ON, ``crms_failover``'s achieved
  mean AND p95 must not exceed plain ``crms``'s at every MTBF point (the
  availability hedge and crash-replacement capacity pay for themselves);
  with failures OFF its allocations must be BYTE-IDENTICAL to ``crms``.

* **Straggler leg** — failure events degrade the cluster's service rate to
  ``frac·μ`` instead of removing containers (the co-located-interference
  mode); hedged capacity must still win the achieved mean.

* **Parity** — the event-vs-vector CRN oracle check THROUGH a stochastic
  crash/repair sequence plus scripted crash/repair and a mid-outage
  reconfigure: per-request response times must agree to ≤ 1e-9.

* **No silent infeasibility** — at a capacity-crunch operating point the
  failover policy must DEGRADE GRACEFULLY: a feasible+stable allocation of
  the admitted subset with the shed set reported in ``diagnostics.shed``,
  while plain ``crms`` at the same point must RECORD its infeasibility in
  ``diagnostics.extra["infeasible"]`` — nothing fails silently.

Artifact: BENCH_failure.json (sweep curves + gate booleans).

CLI:  PYTHONPATH=src:. python -m benchmarks.failure_resilience
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
    Scenario,
    ScenarioRunner,
    get_policy,
    mmpp2,
    validate_scenarios_doc,
)
from repro.core.des import FleetSimulator
from repro.core.problem import ServerCaps

POLICIES = ("crms", "crms_failover")
MTBFS = (None, 300.0, 120.0, 60.0)  # per-container MTBF sweep [s]; None = off
MTTR = 10.0  # repair time scale [s]
T_COLD = 1.0  # cold restart lag a repaired container pays [s]
STRAGGLER_FRAC = 0.5  # straggler leg: episodes degrade μ to frac·μ
# roomy caps (the coldstart convention): hedged capacity needs provisioning
# headroom — at a memory-starved point there is nothing to hedge with
ROOMY = ServerCaps(r_cpu=80.0, r_mem=32.0)
# capacity crunch for the graceful-degradation gate: far too tight for M=4
CRUNCH = ServerCaps(r_cpu=4.0, r_mem=1.2)
N_EPOCHS, EPOCH_S = 12, 30.0
SEED = 11
PARITY_TOL = 1e-9  # event-vs-vector CRN gate through crash/repair sequences
OUT = Path(__file__).resolve().parent.parent / "BENCH_failure.json"


def _spec(mtbf, straggler_frac: float = 1.0):
    if mtbf is None:
        return None
    return {"mtbf": float(mtbf), "mttr": MTTR, "straggler_frac": straggler_frac}


def _scenario(mtbf, n_epochs: int, apps=None, straggler_frac: float = 1.0) -> Scenario:
    tag = "off" if mtbf is None else f"{mtbf:g}"
    return Scenario(
        name=f"fail_mtbf{tag}",
        apps=tuple(apps if apps is not None else paper_apps()),
        caps=ROOMY,
        n_epochs=n_epochs,
        alpha=ALPHA,
        beta=BETA,
        seed=SEED,
        lifecycle={"t_cold": T_COLD},
        failures=_spec(mtbf, straggler_frac),
    )


def _score(doc: dict, policy: str) -> dict:
    eps = doc["policies"][policy]["epochs"]
    s = doc["policies"][policy]["summary"]
    p95 = [e["achieved_p95_s"] for e in eps if e["achieved_p95_s"] is not None]
    stats = s.get("failure_stats", {})
    return {
        "achieved_mean_s": s["achieved_mean_s"],
        "achieved_p95_s": float(np.mean(p95)) if p95 else None,
        "total_power_w_mean": s["total_power_w_mean"],
        "n_replans": s["n_replans"],
        "all_feasible": s["all_feasible"],
        "all_stable": s["all_stable"],
        "epochs_shed": int(s.get("epochs_shed", 0)),
        "crashes": int(sum(v.get("crashes", 0) for v in stats.values())),
    }


def _run(sc: Scenario, policies, engine: str, epoch_s: float) -> dict:
    runner = ScenarioRunner(
        sc, list(policies), quasi_dynamic=False,
        backend="des", epoch_s=epoch_s, des_engine=engine,
    )
    doc = runner.run()
    validate_scenarios_doc(doc)
    return doc


def sweep_point(
    mtbf, engine: str, n_epochs: int = N_EPOCHS, epoch_s: float = EPOCH_S,
    apps=None, straggler_frac: float = 1.0,
) -> dict:
    sc = _scenario(mtbf, n_epochs, apps=apps, straggler_frac=straggler_frac)
    doc = _run(sc, POLICIES, engine, epoch_s)
    row = {p: _score(doc, p) for p in POLICIES}
    row["mtbf"] = mtbf
    row["straggler_frac"] = straggler_frac
    return row


def identity_check(n_epochs: int = N_EPOCHS, apps=None) -> bool:
    """With failures OFF nothing is hedged and nothing is shed: crms_failover
    must produce BYTE-IDENTICAL allocations to plain crms at every epoch
    (same arrays, same utility) — resilience is free when nothing fails."""
    sc = _scenario(None, n_epochs, apps=apps)
    failover = get_policy("crms_failover")
    failover.reset()
    plain = get_policy("crms")
    for state in sc.timeline():
        req = AllocRequest(
            apps=state.apps, caps=state.caps, alpha=sc.alpha, beta=sc.beta,
            options=sc.options, seed=sc.seed,
        )
        a = failover.allocate(req).allocation
        b = plain.allocate(req).allocation
        if not (
            np.array_equal(a.n, b.n)
            and np.array_equal(a.r_cpu, b.r_cpu)
            and np.array_equal(a.r_mem, b.r_mem)
            and a.utility == b.utility
        ):
            return False
    return True


def parity_check(mtbf: float = 40.0) -> float:
    """Max |event − vector| over per-request response times of a trace under
    STOCHASTIC crash/repair dynamics (MTBF low enough that several crashes
    land) plus scripted crash/repair and a mid-outage reconfigure — MMPP
    arrivals on one app so the modulated stream crosses outages too."""
    out = {}
    failures = {"mtbf": mtbf, "mttr": 6.0}
    for eng in ("event", "vector"):
        sim = FleetSimulator(
            seed=5, engine=eng, lifecycle={"t_cold": T_COLD}, failures=failures,
        )
        sim.add_app("a", 8.0, 2.5, 5, arrival=mmpp2(2.5, 0.25, 40.0))
        sim.add_app("b", 5.0, 2.0, 4)
        sim.run_until(25.0)
        sim.crash("b", 2)  # scripted outage on top of the stochastic process
        sim.run_until(32.0)
        sim.configure("a", lam=12.0, n_servers=7)  # mid-outage reconfigure
        sim.run_until(45.0)
        sim.repair("b", 2)
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


def shed_check(apps=None) -> dict:
    """Graceful degradation at a capacity crunch: the failover policy must
    return a feasible+stable allocation of the admitted subset WITH the shed
    set reported, and plain crms at the same point must record its
    infeasibility — no silent failures on either path."""
    apps = tuple(apps if apps is not None else paper_apps())
    req = AllocRequest(
        apps=apps, caps=CRUNCH, alpha=ALPHA, beta=BETA, seed=SEED,
        extra={"failures": {
            "spec": _spec(120.0), "down": {}, "t_cold": T_COLD,
        }},
    )
    failover = get_policy("crms_failover")
    failover.reset()
    fo = failover.allocate(req)
    plain = get_policy("crms").allocate(
        AllocRequest(apps=apps, caps=CRUNCH, alpha=ALPHA, beta=BETA, seed=SEED)
    )
    return {
        "failover_feasible": bool(fo.feasible),
        "failover_stable": bool(fo.stable),
        "failover_shed": list(fo.diagnostics.shed),
        "failover_admission": dict(
            fo.allocation.meta.get("admission", {})
        ),
        "crms_feasible": bool(plain.feasible),
        "crms_infeasible_recorded": "infeasible" in plain.diagnostics.extra,
    }


def _shed_ok(shed: dict) -> bool:
    return (
        shed["failover_feasible"]
        and shed["failover_stable"]
        and len(shed["failover_shed"]) > 0
        and (not shed["crms_feasible"]) == shed["crms_infeasible_recorded"]
        and not shed["crms_feasible"]  # CRUNCH really is a crunch
    )


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
        # tiny CI run: M=3, one CRASHY point (MTBF short against the horizon
        # so several outages actually land) through BOTH engines; gates:
        # byte-identity with failures off, the crash/repair CRN oracle,
        # the win under crashes, and the graceful-degradation report
        apps3 = tuple(paper_apps()[:3])
        ok = _gate(identity_check(n_epochs=4, apps=apps3), "failures-off identity")
        gap = parity_check(40.0)
        ok &= _gate(
            gap <= PARITY_TOL, "crash/repair CRN parity", f"(gap={gap:.3g})"
        )
        for eng in ("event", "vector"):
            row = sweep_point(30.0, eng, n_epochs=8, epoch_s=20.0, apps=apps3)
            c, fo = row["crms"], row["crms_failover"]
            print(f"smoke[{eng}]  crms mean={c['achieved_mean_s']:.4f}  "
                  f"failover mean={fo['achieved_mean_s']:.4f}  "
                  f"crashes={fo['crashes']}")
            ok &= _gate(fo["all_feasible"] and fo["all_stable"],
                        f"{eng}: failover feasible+stable")
            ok &= _gate(fo["crashes"] > 0, f"{eng}: smoke point saw crashes")
            ok &= _gate(
                fo["achieved_mean_s"] <= c["achieved_mean_s"],
                f"{eng}: crms_failover must not lose at mtbf=30",
                f"({fo['achieved_mean_s']:.4f} vs {c['achieved_mean_s']:.4f})",
            )
        shed = shed_check(apps3)
        ok &= _gate(_shed_ok(shed), "graceful degradation reported", f"({shed})")
        emit("failure_resilience", 0.0,
             f"smoke;engines=2;gate={'ok' if ok else 'FAIL'}")
        return bool(ok)

    points = [sweep_point(m, engine, n_epochs, epoch_s) for m in MTBFS]
    straggler = sweep_point(
        120.0, engine, n_epochs, epoch_s, straggler_frac=STRAGGLER_FRAC
    )
    identity = identity_check(n_epochs)
    parity_gap = parity_check(40.0)
    shed = shed_check()

    print(f"\nfailure sweep (engine={engine}, mttr={MTTR:g}s, "
          f"t_cold={T_COLD:g}s, {n_epochs}x{epoch_s:g}s epochs):")
    print(f"{'mtbf':>6s} {'crms_mean':>10s} {'crms_p95':>10s} "
          f"{'fo_mean':>9s} {'fo_p95':>9s} {'fo_replans':>10s} "
          f"{'fo_power':>9s} {'crashes':>8s}")
    for row in points:
        c, fo = row["crms"], row["crms_failover"]
        tag = "off" if row["mtbf"] is None else f"{row['mtbf']:g}"
        print(f"{tag:>6s} {c['achieved_mean_s']:10.4f} "
              f"{c['achieved_p95_s']:10.4f} {fo['achieved_mean_s']:9.4f} "
              f"{fo['achieved_p95_s']:9.4f} {fo['n_replans']:10d} "
              f"{fo['total_power_w_mean']:9.1f} {fo['crashes']:8d}")
    c, fo = straggler["crms"], straggler["crms_failover"]
    print(f"straggler (mtbf=120, frac={STRAGGLER_FRAC:g}): "
          f"crms mean={c['achieved_mean_s']:.4f}  "
          f"failover mean={fo['achieved_mean_s']:.4f}")
    print(f"failures-off identity: {identity}   "
          f"crash/repair parity gap: {parity_gap:.3g}")
    print(f"graceful degradation: shed={shed['failover_shed']} "
          f"admission={shed['failover_admission']}")

    # ---- gates -------------------------------------------------------------
    ok = _gate(identity, "crms_failover byte-identical to crms with failures off")
    ok &= _gate(
        parity_gap <= PARITY_TOL,
        "event-vs-vector CRN parity through crash/repair sequences",
        f"(gap={parity_gap:.3g})",
    )
    for row in points:
        if row["mtbf"] is None:
            continue
        for key in ("achieved_mean_s", "achieved_p95_s"):
            ok &= _gate(
                row["crms_failover"][key] <= row["crms"][key],
                f"crms_failover wins {key} at mtbf={row['mtbf']:g}",
                f"({row['crms_failover'][key]:.4f} vs {row['crms'][key]:.4f})",
            )
    ok &= _gate(
        straggler["crms_failover"]["achieved_mean_s"]
        <= straggler["crms"]["achieved_mean_s"],
        "crms_failover wins the straggler leg",
        f"({straggler['crms_failover']['achieved_mean_s']:.4f} vs "
        f"{straggler['crms']['achieved_mean_s']:.4f})",
    )
    for row in points + [straggler]:
        fo = row["crms_failover"]
        ok &= _gate(
            fo["all_feasible"] and fo["all_stable"],
            "crms_failover feasible+stable everywhere",
        )
    ok &= _gate(_shed_ok(shed), "graceful degradation reported, never silent",
                f"({shed})")

    doc = {
        "schema_version": 1,
        "engine": engine,
        "sweep": {
            "mttr": MTTR, "t_cold": T_COLD, "n_epochs": n_epochs,
            "epoch_s": epoch_s, "seed": SEED,
            "caps": {"r_cpu": ROOMY.r_cpu, "r_mem": ROOMY.r_mem},
            "points": points,
        },
        "straggler": straggler,
        "identity_failures_off": bool(identity),
        "parity_gap": parity_gap,
        "parity_tol": PARITY_TOL,
        "shed": shed,
        "gates_ok": bool(ok),
    }
    out.write_text(json.dumps(doc, indent=2, sort_keys=True) + "\n")

    worst = points[-1]
    crash_cost = (
        worst["crms"]["achieved_mean_s"] / points[0]["crms"]["achieved_mean_s"]
    )
    recov = (
        worst["crms"]["achieved_mean_s"]
        / worst["crms_failover"]["achieved_mean_s"]
    )
    emit(
        "failure_resilience", 0.0,
        f"points={len(points)};crms_crash_cost={crash_cost:.2f}x;"
        f"failover_recovers={recov:.2f}x;gate={'ok' if ok else 'FAIL'}",
    )
    return bool(ok)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI run: one crashy point, both engines")
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
