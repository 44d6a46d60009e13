"""Burst-robustness benchmark: how much the paper's Poisson-optimal CRMS
loses under Markov-modulated (bursty) arrivals, and how much of it the
burstiness-aware ``robust_crms`` policy recovers.

Two legs, both scored by the closed-loop DES backend (CRN arrivals shared
across policies, so every comparison is paired):

* **Sweep** — a canonical MMPP2 burstiness ladder (burst factor 1 → 3 at
  fixed burst fraction/cycle) replayed at a roomy operating point. ``crms``
  provisions for the mean rate, so its achieved latency must degrade
  monotonically with the burst factor; ``robust_crms`` provisions against the
  top of each app's [λ_mean, λ_hi] interval and must win on achieved mean AND
  p95 once bursts are material, while staying within 2% of ``crms`` at the
  pure-Poisson point (there the interval collapses and the policies are
  numerically identical).

* **Trace** — the committed synthetic Azure-Functions-style invocation log
  (``benchmarks/data/azure_synth.csv``: per-minute counts, diurnal envelope +
  square-wave bursts with sojourns ≥ 2 bins) ingested by
  ``Scenario.from_trace``: per-epoch λ re-estimation drives the drift
  trigger, the fitted per-app MMPP2 drives the DES replay, and the estimated
  peak ratios feed ``robust_crms`` — the full measure → model → provision
  loop on data the optimizer never saw. Same gate: robust wins mean and p95.

Artifact: BENCH_burst.json (degradation curve + trace leg + gate booleans).

CLI:  PYTHONPATH=src:. python -m benchmarks.burst_robustness
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
from repro.api import Scenario, ScenarioRunner, mmpp2, validate_scenarios_doc
from repro.core.problem import ServerCaps

POLICIES = ("crms", "robust_crms")
BURSTS = (1.0, 1.5, 2.0, 2.5, 3.0)  # 1.0 = the paper's Poisson model
FRAC, CYCLE = 0.2, 600.0  # burst phase: 20% of the time, 120 s mean sojourn
# roomy caps: robustness needs provisioning headroom — at the paper's
# constrained point robust_crms honestly backs off to plain CRMS instead
ROOMY = ServerCaps(r_cpu=60.0, r_mem=20.0)
N_EPOCHS, EPOCH_S = 3, 1200.0  # per-policy sim horizon: 2 cycles per epoch
SEED = 11
POISSON_TOL = 0.02  # gate: |robust - crms| at the Poisson point
MONO_TOL = 0.98  # gate: crms mean may dip at most 2% between adjacent points
TRACE = Path(__file__).resolve().parent / "data" / "azure_synth.csv"
OUT = Path(__file__).resolve().parent.parent / "BENCH_burst.json"


def _score(doc: dict, policy: str) -> dict:
    """Achieved latency for one policy: mean over epochs of the DES-measured
    per-epoch mean and p95 (CRN-paired across policies)."""
    eps = doc["policies"][policy]["epochs"]
    s = doc["policies"][policy]["summary"]
    p95 = [e["achieved_p95_s"] for e in eps if e["achieved_p95_s"] is not None]
    return {
        "achieved_mean_s": s["achieved_mean_s"],
        "achieved_p95_s": float(np.mean(p95)) if p95 else None,
        "predicted_mean_s": s["mean_latency_s"],
        "total_power_w_mean": s["total_power_w_mean"],
        "all_feasible": s["all_feasible"],
        "all_stable": s["all_stable"],
    }


def _run_scenario(sc: Scenario, engine: str, epoch_s: float) -> dict:
    runner = ScenarioRunner(
        sc, POLICIES, backend="des", epoch_s=epoch_s, des_engine=engine
    )
    doc = runner.run()
    validate_scenarios_doc(doc)
    return {p: _score(doc, p) for p in POLICIES}


def sweep_point(
    burst: float, engine: str, n_epochs: int = N_EPOCHS, epoch_s: float = EPOCH_S
) -> dict:
    arrival = None if burst <= 1.0 else mmpp2(burst, FRAC, CYCLE)
    sc = Scenario(
        name=f"mmpp_b{burst:g}", apps=tuple(paper_apps()), caps=ROOMY,
        n_epochs=n_epochs, alpha=ALPHA, beta=BETA, arrival=arrival, seed=SEED,
    )
    row = _run_scenario(sc, engine, epoch_s)
    row["burst"] = burst
    return row


def trace_leg(engine: str, epoch_s: float = EPOCH_S) -> dict:
    apps = tuple(paper_apps())
    sc = Scenario.from_trace(
        apps, ROOMY, trace=TRACE, name="azure_synth", n_epochs=8,
        alpha=ALPHA, beta=BETA, seed=SEED,
    )
    row = _run_scenario(sc, engine, epoch_s)
    row["trace"] = TRACE.name
    row["n_epochs"] = sc.n_epochs
    row["ratios"] = {
        a.name: round(sc.arrival_for(a.name).lam_hi_ratio(), 4) for a in apps
    }
    return row


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
        # small MMPP scenario through BOTH engines: the CI gate is that the
        # robust policy's achieved latency never loses at high burstiness
        ok = True
        for eng in ("event", "vector"):
            row = sweep_point(3.0, eng, n_epochs=2, epoch_s=400.0)
            c, r = row["crms"], row["robust_crms"]
            print(f"smoke[{eng}]  crms mean={c['achieved_mean_s']:.4f}  "
                  f"robust mean={r['achieved_mean_s']:.4f}")
            ok &= _gate(
                r["all_feasible"] and r["all_stable"], f"{eng}: robust un-feasible"
            )
            ok &= _gate(
                r["achieved_mean_s"] <= c["achieved_mean_s"],
                f"{eng}: robust_crms must not lose at burst=3",
                f"({r['achieved_mean_s']:.4f} vs {c['achieved_mean_s']:.4f})",
            )
        emit("burst_robustness", 0.0, f"smoke;engines=2;gate={'ok' if ok else 'FAIL'}")
        return bool(ok)

    points = [sweep_point(b, engine, n_epochs, epoch_s) for b in BURSTS]
    trace = trace_leg(engine, epoch_s)

    print(f"\nburstiness sweep (engine={engine}, frac={FRAC}, cycle={CYCLE}s, "
          f"{n_epochs}x{epoch_s:g}s epochs):")
    print(f"{'burst':>5s} {'crms_mean':>10s} {'crms_p95':>10s} "
          f"{'robust_mean':>11s} {'robust_p95':>10s} {'win':>6s}")
    for row in points:
        c, r = row["crms"], row["robust_crms"]
        win = c["achieved_mean_s"] / r["achieved_mean_s"]
        print(f"{row['burst']:5.2f} {c['achieved_mean_s']:10.4f} "
              f"{c['achieved_p95_s']:10.4f} {r['achieved_mean_s']:11.4f} "
              f"{r['achieved_p95_s']:10.4f} {win:5.1f}x")
    c, r = trace["crms"], trace["robust_crms"]
    print(f"trace {trace['trace']} (ratios {trace['ratios']}):")
    print(f"      crms mean={c['achieved_mean_s']:.4f} p95={c['achieved_p95_s']:.4f}"
          f"  robust mean={r['achieved_mean_s']:.4f} p95={r['achieved_p95_s']:.4f}")

    # ---- gates -------------------------------------------------------------
    ok = True
    c0, r0 = points[0]["crms"], points[0]["robust_crms"]
    ok &= _gate(
        abs(r0["achieved_mean_s"] - c0["achieved_mean_s"])
        <= POISSON_TOL * c0["achieved_mean_s"],
        "robust_crms within 2% of crms under pure Poisson",
        f"({r0['achieved_mean_s']:.4f} vs {c0['achieved_mean_s']:.4f})",
    )
    means = [p["crms"]["achieved_mean_s"] for p in points]
    ok &= _gate(
        all(b >= MONO_TOL * a for a, b in zip(means, means[1:])),
        "crms achieved mean degrades monotonically with burstiness",
        f"({[round(m, 3) for m in means]})",
    )
    hi = points[-1]
    for key in ("achieved_mean_s", "achieved_p95_s"):
        ok &= _gate(
            hi["robust_crms"][key] < hi["crms"][key],
            f"robust_crms wins {key} at burst={hi['burst']:g}",
            f"({hi['robust_crms'][key]:.4f} vs {hi['crms'][key]:.4f})",
        )
        ok &= _gate(
            trace["robust_crms"][key] < trace["crms"][key],
            f"robust_crms wins {key} on the ingested trace",
            f"({trace['robust_crms'][key]:.4f} vs {trace['crms'][key]:.4f})",
        )
    for row in points + [trace]:
        ok &= _gate(
            row["robust_crms"]["all_feasible"] and row["robust_crms"]["all_stable"],
            "robust_crms feasible+stable everywhere",
        )

    doc = {
        "schema_version": 1,
        "engine": engine,
        "sweep": {
            "frac": FRAC, "cycle_s": CYCLE, "n_epochs": n_epochs,
            "epoch_s": epoch_s, "seed": SEED,
            "caps": {"r_cpu": ROOMY.r_cpu, "r_mem": ROOMY.r_mem},
            "points": points,
        },
        "trace": trace,
        "gates_ok": bool(ok),
    }
    out.write_text(json.dumps(doc, indent=2, sort_keys=True) + "\n")

    worst = means[-1] / means[0]
    recov = means[-1] / points[-1]["robust_crms"]["achieved_mean_s"]
    emit(
        "burst_robustness", 0.0,
        f"points={len(points)};crms_degrades={worst:.0f}x;"
        f"robust_recovers={recov:.0f}x;gate={'ok' if ok else 'FAIL'}",
    )
    return bool(ok)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI run: one high-burstiness point, both engines")
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
