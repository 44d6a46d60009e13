"""Beyond-paper benchmark: CRMS allocating a 256-chip TPU v5e pod across the
ten assigned architectures (chips/replica, HBM/replica, replica count) vs the
search baselines — the DESIGN.md §3 binding, fed by the dry-run roofline model
(results/dryrun.json when present, analytic fallback otherwise)."""
from __future__ import annotations

from pathlib import Path

import numpy as np

from benchmarks.common import ALPHA, BETA, emit, mean_latency, timed
from repro.core.baselines import drf, random_search, tpebo
from repro.core.crms import crms
from repro.core.fleet import default_workloads, pod_caps, build_fleet_apps, workloads_from_roofline


def run() -> bool:
    # The analytic cost model reflects the OPTIMIZED serving layout of
    # EXPERIMENTS.md §Perf (model-only weights, owner-shard cache); the
    # baseline dry-run JSON (collective-bound naive layout) is available via
    # `workloads_from_roofline("results/dryrun.json")` for ablations.
    workloads = default_workloads()
    apps = build_fleet_apps(workloads, seed=0)
    caps = pod_caps(256)
    alloc, us = timed(crms, apps, caps, ALPHA, BETA)

    print("\nTPU fleet allocation (256 chips, 4 TB HBM) — CRMS")
    print(f"{'arch':26s} {'lam':>5s} {'N':>3s} {'chips':>7s} {'HBM GB':>8s} {'Ws ms':>9s}")
    for i, app in enumerate(apps):
        print(
            f"{app.name:26s} {app.lam:5.1f} {alloc.n[i]:3d} {alloc.r_cpu[i]:7.1f} "
            f"{alloc.r_mem[i]:8.1f} {alloc.ws[i]*1e3:9.2f}"
        )
    print(f"chips used {alloc.total_cpu():.0f}/256, HBM {alloc.total_mem():.0f}/4096 GB, "
          f"U={alloc.utility:.3f} feasible={alloc.feasible} stable={alloc.stable}")

    w_crms = mean_latency(apps, alloc)
    rs = random_search(apps, caps, ALPHA, BETA, n_samples=20000, seed=0)
    tp = tpebo(apps, caps, ALPHA, BETA, seed=0)
    w_rs, w_tp = mean_latency(apps, rs), mean_latency(apps, tp)
    red_rs = 100 * (1 - w_crms / w_rs) if np.isfinite(w_rs) else 100.0
    red_tp = 100 * (1 - w_crms / w_tp) if np.isfinite(w_tp) else 100.0
    print(f"mean latency: CRMS {w_crms*1e3:.2f}ms vs RS {w_rs*1e3:.2f}ms ({red_rs:.0f}% lower) "
          f"vs TPEBO {w_tp*1e3:.2f}ms ({red_tp:.0f}% lower)")

    ok = alloc.feasible and alloc.stable and w_crms <= min(w_rs, w_tp)
    emit("fleet_tpu", us, f"crms_ms={w_crms*1e3:.2f};red_vs_rs={red_rs:.0f}%;red_vs_tpebo={red_tp:.0f}%")
    return ok


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
