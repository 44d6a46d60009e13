"""Figs. 11-14: constrained comparison — CRMS vs RS / GPBO / TPEBO / DRF at
lam=(8,7,10,15), R_cpu=30, R_mem=10GB — and the resource-reallocation view
(unconstrained ideal vs constrained final)."""
from __future__ import annotations

import numpy as np

from benchmarks.common import ALPHA, BETA, CONSTRAINED_CAPS, emit, mean_latency, paper_apps, timed
from repro.core.baselines import drf, gpbo, random_search, tpebo
from repro.core.crms import algorithm1, crms


def run(seeds=(0, 1, 2)) -> bool:
    apps = paper_apps()
    caps = CONSTRAINED_CAPS
    crms_alloc, us_crms = timed(crms, apps, caps, ALPHA, BETA)
    w_crms = mean_latency(apps, crms_alloc)

    rows = {"CRMS": (w_crms, crms_alloc)}
    reductions = {}
    for name, fn in (
        ("RS", lambda s: random_search(apps, caps, ALPHA, BETA, n_samples=20000, seed=s)),
        ("GPBO", lambda s: gpbo(apps, caps, ALPHA, BETA, seed=s)),
        ("TPEBO", lambda s: tpebo(apps, caps, ALPHA, BETA, seed=s)),
    ):
        ws = [mean_latency(apps, fn(s)) for s in seeds]
        finite = [w for w in ws if np.isfinite(w)]
        w = float(np.mean(finite)) if finite else float("inf")
        rows[name] = (w, None)
        reductions[name] = 100.0 * (1.0 - w_crms / w) if np.isfinite(w) else 100.0
    drf_alloc = drf(apps, caps, ALPHA, BETA)
    rows["DRF"] = (mean_latency(apps, drf_alloc), drf_alloc)

    print("\nFigs 11-13 — constrained resources (lam=(8,7,10,15), caps=(30,10GB))")
    print(f"{'scheme':7s} {'meanW(s)':>9s} {'reduction by CRMS':>18s}")
    for k, (w, _) in rows.items():
        red = f"{reductions.get(k, 0.0):6.1f}%" if k in reductions else "   -"
        print(f"{k:7s} {w:9.4f} {red:>18s}")
    print(f"DRF stable: {drf_alloc.stable} (paper: DRF leaves APP queues with rho>1)")

    # Fig. 14: reallocation under constraints
    ideal = algorithm1(apps, caps, ALPHA, BETA)
    print("\nFig 14 — reallocation (ideal -> constrained)")
    print(f"{'app':18s} {'cpu*':>6s} {'cpu':>6s} {'mem*':>6s} {'mem':>6s} {'N':>3s}")
    for app, ic, c, m, n in zip(apps, ideal, crms_alloc.r_cpu, crms_alloc.r_mem, crms_alloc.n):
        print(f"{app.name:18s} {ic.r_cpu:6.2f} {c:6.2f} {ic.r_mem:6.2f} {m:6.2f} {n:3d}")

    min_red = min(reductions.values())
    ok = np.isfinite(w_crms) and crms_alloc.feasible and min_red >= 14.0
    emit(
        "fig11_14_constrained", us_crms,
        f"min_reduction={min_red:.1f}%;drf_unstable={not drf_alloc.stable}",
    )
    return ok


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
