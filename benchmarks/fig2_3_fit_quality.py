"""Figs. 2-3: fitted-surface quality for MobileNet_v2 — adjusted R², residual
statistics and an approximate-normality (Q-Q) check, as in §III-D."""
from __future__ import annotations

import numpy as np

from benchmarks.common import emit, timed
from repro.core.perf_model import fit_family
from repro.core.profiler import profile_app


def run() -> bool:
    p = profile_app("MobileNet_v2", seed=0, noise_rel=0.02)
    fr, us = timed(fit_family, "eq1", p.cpu, p.mem, p.latency_ms, n_starts=10)
    resid = fr.residuals
    n = len(resid)
    # residual diagnostics
    mean_resid = float(np.mean(resid))
    # Q-Q correlation against normal quantiles (close to 1 = normal residuals)
    from scipy.stats import norm

    qs = norm.ppf((np.arange(1, n + 1) - 0.5) / n)
    r_sorted = np.sort((resid - resid.mean()) / (resid.std() + 1e-12))
    qq_corr = float(np.corrcoef(qs, r_sorted)[0, 1])
    print(f"fig2_3: adj_R2={fr.adj_r2:.4f} MSE={fr.mse:.4f} RMSE={fr.rmse:.4f} "
          f"resid_mean={mean_resid:.4f} qq_corr={qq_corr:.4f}")
    ok = fr.adj_r2 > 0.99 and qq_corr > 0.95
    emit("fig2_3_fit_quality", us, f"adj_r2={fr.adj_r2:.4f};qq_corr={qq_corr:.3f}")
    return ok


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
