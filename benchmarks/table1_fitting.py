"""Table I: RMSE / R^2 of the five candidate fitting families on profiled data
for the four paper applications. Eq.(1) must win (lowest RMSE)."""
from __future__ import annotations

import numpy as np

from benchmarks.common import emit, timed
from repro.core.perf_model import FAMILIES, fit_best_family
from repro.core.profiler import PAPER_APPS_TRUE, profile_all


def run() -> bool:
    profiles = profile_all(seed=0)
    table: dict[str, dict] = {f: {} for f in FAMILIES}
    total_us = 0.0
    for name, p in profiles.items():
        fits, us = timed(fit_best_family, p.cpu, p.mem, p.latency_ms, n_starts=10)
        total_us += us
        for fam, fr in fits.items():
            table[fam][name] = (fr.rmse, fr.r2)

    print("\nTable I — RMSE / R² per fitting family (rows) x application (cols)")
    apps = list(PAPER_APPS_TRUE)
    print(f"{'family':12s} " + " ".join(f"{a[:14]:>20s}" for a in apps))
    for fam, row in table.items():
        cells = " ".join(f"{row[a][0]:8.3f}/{row[a][1]:5.3f} " for a in apps)
        print(f"{fam:12s} {cells}")

    eq1_wins = all(
        min(table[f][a][0] for f in FAMILIES) == table["eq1"][a][0] for a in apps
    )
    mean_r2 = float(np.mean([table["eq1"][a][1] for a in apps]))
    emit("table1_fitting", total_us, f"eq1_wins={eq1_wins};mean_r2={mean_r2:.4f}")
    return eq1_wins


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
