"""Candidate-batched DES rollouts + p95-aware CRMS (DESIGN.md §14).

Two gates into BENCH_p95.json (exit non-zero when either breaks):

1. **Rollout throughput** — ``des_vector.rollout_candidates`` simulates all B
   candidate allocations in ONE Kiefer–Wolfowitz ``lax.scan`` over shared CRN
   draws; the baseline is B sequential vector-DES runs (``FleetSimulator``,
   engine="vector") of the same configs over the same horizon. Floor: >= 10x
   at B=32 full mode, >= 3x at smoke scale (2-core CI hosts). A CRN parity
   check rides along: the B=1 rollout's pooled mean must match the sequential
   engine to 1e-9 relative (same streams -> sample-path identical).

2. **p95 policy quality** — ``crms_p95`` vs ``crms`` through the DES-backed
   ScenarioRunner under common random numbers, equal alpha/beta/caps:

   * burst + flash-crowd traces: crms_p95's achieved p95 (mean over measured
     epochs) must be <= crms's, and its p95-SLO violation rate <= crms's;
   * plain Poisson (steady trace): crms_p95's model mean latency must stay
     within ``MAX_MEAN_REL`` (2%) of crms's — the tail objective must not
     buy its tail by wrecking the mean.

CLI:  PYTHONPATH=src python -m benchmarks.p95_slo [--B 32] [--smoke]
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

if __package__ in (None, ""):  # run as a plain script: repo root + src on sys.path
    _root = Path(__file__).resolve().parent.parent
    sys.path.insert(0, str(_root / "src"))
    sys.path.insert(0, str(_root))

import numpy as np

from benchmarks.common import ALPHA, BETA, CONSTRAINED_CAPS, emit, paper_apps
from repro.api.scenario import Scenario, ScenarioRunner, validate_scenarios_doc
from repro.core.des import FleetSimulator
from repro.core.des_vector import rollout_candidates

B_FULL = 32
M_FULL = 8
HORIZON_FULL = 40.0  # the refinement's default rollout_horizon_s
FLOOR_FULL = 10.0
B_SMOKE = 8
M_SMOKE = 4
HORIZON_SMOKE = 40.0
FLOOR_SMOKE = 3.0
REPS = 3  # best-of-N walls for both engines (de-noises shared hosts)
MAX_PARITY_REL = 1e-9  # B=1 rollout vs sequential vector engine (shared CRN)
MAX_MEAN_REL = 0.02  # Poisson mean-latency regression budget for crms_p95
SLO_P95_S = 0.6  # p95 response-time SLO the scenario gate scores against
LAM_SCALE = 0.85  # paper constrained rates scaled to stay stable through bursts
OUT = Path(__file__).resolve().parent.parent / "BENCH_p95.json"


def tenant_mix(m: int) -> list[tuple[str, float, float, int]]:
    """Deterministic (name, lam, mu, n_servers) mix at utilization 0.72-0.78
    (the des_throughput convention). Server counts stay in 3..7 so the +-1
    candidate moves stay inside one pow2 server-slot pad (n_pad = 8) — a
    grid that straddles a pow2 boundary pays double scan width for every
    candidate."""
    out = []
    for i in range(m):
        lam = 16.0 + 2.0 * (i % 8)
        n = 3 + (i % 5)
        rho = 0.72 + 0.02 * (i % 4)
        out.append((f"app{i:02d}", lam, lam / (n * rho), n))
    return out


def candidate_grid(mix, b: int):
    """B candidate (mu, n_servers) allocations around the base mix: server
    counts stepped -1/0/+1 and service rates scaled a few percent, the shape
    of a refinement move batch. Row 0 is the base config exactly (the CRN
    parity anchor)."""
    m = len(mix)
    mu = np.array([mu_i for _, _, mu_i, _ in mix])
    n = np.array([n_i for _, _, _, n_i in mix], dtype=int)
    mu_all = np.empty((b, m))
    n_all = np.empty((b, m), dtype=int)
    for k in range(b):
        step = (k % 3) - 1 if k else 0  # row 0: the base config
        scale = 1.0 + 0.03 * (((k * 7) % 5) - 2) / 2.0 if k else 1.0
        n_all[k] = np.maximum(n + step, n // 2 + 1)
        mu_all[k] = mu * scale
    return mu_all, n_all


def sequential_runs(mix, mu_all, n_all, horizon: float, seed: int):
    """The baseline: one vector-engine FleetSimulator run per candidate.
    Returns (wall_s, pooled mean of candidate 0)."""
    names = [name for name, *_ in mix]
    lam = [lam_i for _, lam_i, _, _ in mix]
    mean0 = None
    t0 = time.perf_counter()
    for k in range(mu_all.shape[0]):
        sim = FleetSimulator(seed=seed, engine="vector")
        for i, name in enumerate(names):
            sim.add_app(name, lam[i], float(mu_all[k, i]), int(n_all[k, i]))
        sim.run_until(horizon)
        sim.drain()
        if k == 0:
            resp = np.concatenate([sim.responses(n_, 0.0, horizon) for n_ in names])
            mean0 = float(resp.mean())
    return time.perf_counter() - t0, mean0


def throughput_part(b: int, m: int, horizon: float, floor: float, seed: int = 0):
    mix = tenant_mix(m)
    names = [name for name, *_ in mix]
    lam = np.array([lam_i for _, lam_i, _, _ in mix])
    mu_all, n_all = candidate_grid(mix, b)

    # warmup at the FULL shapes and over ALL candidates: the scans
    # jit-compile per padded shape and candidates differ in n_pad, so a
    # partial warmup pass would leave compiles on the sequential clock
    rollout_candidates(names, lam, mu_all, n_all, horizon, seed=seed)
    sequential_runs(mix, mu_all, n_all, horizon, seed)

    # Both sides time the SAME work — completing the simulation of all B
    # candidates (the rollout blocks on its wait matrix; per-candidate
    # scoring stats are lazy on its side and never computed on the
    # sequential side). Best-of-REPS: single-shot walls on shared CI hosts
    # swing ~20% and a throughput floor should gate the engine, not the
    # scheduler.
    wall_batch = np.inf
    for _ in range(REPS):
        t0 = time.perf_counter()
        ro = rollout_candidates(names, lam, mu_all, n_all, horizon, seed=seed)
        wall_batch = min(wall_batch, time.perf_counter() - t0)
    wall_seq = np.inf
    for _ in range(REPS):
        w, mean_seq = sequential_runs(mix, mu_all, n_all, horizon, seed)
        wall_seq = min(wall_seq, w)

    # CRN parity: candidate 0 is the sequential base config over the SAME
    # arrival/service streams -> pooled means agree to float noise
    ro1 = rollout_candidates(names, lam, mu_all[:1], n_all[:1], horizon, seed=seed)
    parity_rel = abs(float(ro1.pooled_mean_s[0]) - mean_seq) / mean_seq

    speedup = wall_seq / wall_batch
    return {
        "B": b,
        "M": m,
        "horizon_s": horizon,
        "n_events": int(ro.n_events),
        "wall_batched_s": wall_batch,
        "wall_sequential_s": wall_seq,
        "speedup": speedup,
        "floor": floor,
        "parity_rel": parity_rel,
        "max_parity_rel": MAX_PARITY_REL,
        "pass": bool(speedup >= floor and parity_rel < MAX_PARITY_REL),
    }


def _summary(doc: dict, policy: str) -> dict:
    return doc["policies"][policy]["summary"]


def scenario_part(smoke: bool, rollout_budget: int):
    lam = tuple(LAM_SCALE * l for l in (8.0, 7.0, 10.0, 15.0))
    apps = paper_apps(lam=lam)
    caps = CONSTRAINED_CAPS
    n_epochs = 4 if smoke else 10
    epoch_s = 20.0 if smoke else 60.0
    extra = {"crms_p95": {"rollout_budget": rollout_budget}}
    policies = ["crms", "crms_p95"]

    def des_run(sc):
        runner = ScenarioRunner(
            sc, policies, backend="des", epoch_s=epoch_s, des_engine="vector",
            slo_p95_s=SLO_P95_S, extra=extra,
        )
        doc = runner.run()
        validate_scenarios_doc(doc)
        return doc

    traces = {
        "burst": des_run(
            Scenario.burst(apps, caps, name="p95-burst", n_epochs=n_epochs,
                           factor=1.8, seed=11)
        )
    }
    if not smoke:
        traces["flash_crowd"] = des_run(
            Scenario.flash_crowd(apps, caps, name="p95-flash", n_epochs=12,
                                 factor=2.0, t_cold=0.0, seed=11)
        )
    # plain Poisson: no events — the mean-latency non-regression gate. Model
    # mean (mean_latency_s) is deterministic, so the 2% budget is exact.
    steady = ScenarioRunner(
        Scenario(name="p95-poisson", apps=tuple(apps), caps=caps,
                 n_epochs=max(2, n_epochs // 2), seed=11),
        policies, backend="analytic", slo_p95_s=SLO_P95_S, extra=extra,
    ).run()
    validate_scenarios_doc(steady)

    gates = {}
    out = {"slo_p95_s": SLO_P95_S, "rollout_budget": rollout_budget, "traces": {}}
    for name, doc in traces.items():
        s_crms, s_p95 = _summary(doc, "crms"), _summary(doc, "crms_p95")
        gates[f"{name}_p95"] = bool(
            s_p95["achieved_p95_s_mean"] is not None
            and s_crms["achieved_p95_s_mean"] is not None
            and s_p95["achieved_p95_s_mean"] <= s_crms["achieved_p95_s_mean"]
        )
        gates[f"{name}_violation"] = bool(
            s_p95["p95_violation_rate"] is not None
            and s_crms["p95_violation_rate"] is not None
            and s_p95["p95_violation_rate"] <= s_crms["p95_violation_rate"]
        )
        out["traces"][name] = {
            p: {
                "achieved_p95_s_mean": _summary(doc, p)["achieved_p95_s_mean"],
                "p95_violation_rate": _summary(doc, p)["p95_violation_rate"],
                "achieved_mean_s": _summary(doc, p)["achieved_mean_s"],
                "mean_latency_s": _summary(doc, p)["mean_latency_s"],
                "total_power_w_mean": _summary(doc, p)["total_power_w_mean"],
            }
            for p in policies
        }
    mean_crms = _summary(steady, "crms")["mean_latency_s"]
    mean_p95 = _summary(steady, "crms_p95")["mean_latency_s"]
    mean_rel = (mean_p95 - mean_crms) / mean_crms
    gates["poisson_mean"] = bool(mean_rel <= MAX_MEAN_REL)
    out["poisson"] = {
        "mean_latency_crms_s": mean_crms,
        "mean_latency_crms_p95_s": mean_p95,
        "mean_rel_excess": mean_rel,
        "max_mean_rel": MAX_MEAN_REL,
    }
    out["gates"] = gates
    out["pass"] = all(gates.values())
    return out


def run(b: int = B_FULL, smoke: bool = False, out: Path = OUT) -> bool:
    if smoke:
        tp = throughput_part(B_SMOKE, M_SMOKE, HORIZON_SMOKE, FLOOR_SMOKE)
        sp = scenario_part(smoke=True, rollout_budget=0)
    else:
        tp = throughput_part(b, M_FULL, HORIZON_FULL, FLOOR_FULL)
        sp = scenario_part(smoke=False, rollout_budget=6)

    doc = {
        "schema_version": 1,
        "mode": "smoke" if smoke else "full",
        "alpha": ALPHA,
        "beta": BETA,
        "rollout_throughput": tp,
        "p95_scenarios": sp,
        "pass": bool(tp["pass"] and sp["pass"]),
    }
    out.write_text(json.dumps(doc, indent=2) + "\n")

    print(f"rollout throughput: B={tp['B']} M={tp['M']} "
          f"batched {tp['wall_batched_s']:.2f}s vs sequential "
          f"{tp['wall_sequential_s']:.2f}s -> {tp['speedup']:.1f}x "
          f"(floor {tp['floor']}x), CRN parity {tp['parity_rel']:.1e}")
    for name, row in sp["traces"].items():
        a, b_ = row["crms"], row["crms_p95"]
        print(f"{name}: p95 {b_['achieved_p95_s_mean']:.3f}s vs "
              f"{a['achieved_p95_s_mean']:.3f}s  viol "
              f"{b_['p95_violation_rate']:.2f} vs {a['p95_violation_rate']:.2f}  "
              f"power {b_['total_power_w_mean']:.1f}W vs "
              f"{a['total_power_w_mean']:.1f}W")
    print(f"poisson mean excess: {sp['poisson']['mean_rel_excess']:+.4f} "
          f"(budget {MAX_MEAN_REL})")
    failed = [k for k, v in {**sp["gates"], "throughput": tp["pass"]}.items() if not v]
    if failed:
        print(f"  !! failed gates: {', '.join(failed)}")
    emit(
        "p95_slo",
        tp["wall_batched_s"] * 1e6 / tp["B"],
        f"B={tp['B']};speedup={tp['speedup']:.1f}x;"
        f"burst_p95={sp['traces']['burst']['crms_p95']['achieved_p95_s_mean']:.3f}s",
    )
    return bool(doc["pass"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--B", type=int, default=B_FULL, help="rollout batch width")
    ap.add_argument("--smoke", action="store_true",
                    help=f"tiny CI gate: B={B_SMOKE}, M={M_SMOKE}, "
                         f">= {FLOOR_SMOKE}x floor, burst trace only")
    args = ap.parse_args(argv)
    return 0 if run(b=args.B, smoke=args.smoke) else 1


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    raise SystemExit(main())
