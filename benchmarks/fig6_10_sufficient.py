"""Figs. 6-10: sufficient-resource comparison — CRMS vs SNFC1 (c=1.8,
m=0.35GB) and SNFC2 (c=1.0, m=r_max): per-app delay, power, utility,
CPU/memory usage."""
from __future__ import annotations

import numpy as np

from benchmarks.common import ALPHA, BETA, SUFFICIENT_CAPS, SUFFICIENT_LAM, emit, mean_latency, paper_apps, timed, total_power
from repro.core.baselines import snfc
from repro.core.crms import crms


def run() -> bool:
    apps = paper_apps(lam=SUFFICIENT_LAM)
    caps = SUFFICIENT_CAPS
    results = {}
    results["CRMS"], us_crms = timed(crms, apps, caps, ALPHA, BETA)
    results["SNFC1"], _ = timed(snfc, apps, caps, ALPHA, BETA, 1.8, 0.35)
    results["SNFC2"], _ = timed(snfc, apps, caps, ALPHA, BETA, 1.0, "rmax")

    print("\nFigs 6-10 — sufficient resources (lam=6, x=5)")
    print(f"{'scheme':8s} {'U_p':>8s} {'meanW(s)':>9s} {'power(W)':>9s} {'cpu':>6s} {'mem(GB)':>8s}  per-app Ws")
    for k, al in results.items():
        print(
            f"{k:8s} {al.utility:8.3f} {mean_latency(apps, al):9.4f} {total_power(al):9.1f} "
            f"{al.total_cpu():6.1f} {al.total_mem():8.2f}  {np.round(al.ws, 3)}"
        )
    crms_wins_delay = all(
        mean_latency(apps, results["CRMS"]) <= mean_latency(apps, results[k]) + 1e-9
        for k in ("SNFC1", "SNFC2")
    )
    crms_wins_utility = all(
        results["CRMS"].utility <= results[k].utility + 1e-9 for k in ("SNFC1", "SNFC2")
    )
    emit(
        "fig6_10_sufficient", us_crms,
        f"crms_lowest_delay={crms_wins_delay};crms_lowest_utility={crms_wins_utility}",
    )
    return crms_wins_delay and crms_wins_utility


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
