"""§IV-B validation: analytic Erlang-C Ws vs the discrete-event simulator."""
from __future__ import annotations

import numpy as np

from benchmarks.common import emit, timed
from repro.core.des import simulate_mmn
from repro.core.queueing import erlang_ws_np


CASES = [(8.0, 1.8, 6), (15.0, 3.3, 7), (2.0, 5.0, 1), (4.0, 1.0, 6), (10.0, 2.0, 8)]


def run() -> bool:
    print("\nM/M/N analytic vs DES")
    max_rel = 0.0
    total_us = 0.0
    for lam, mu, n in CASES:
        s, us = timed(simulate_mmn, lam, mu, n, 4000.0, 400.0, 11)
        total_us += us
        w = erlang_ws_np(n, lam, mu)
        rel = abs(s.mean_response_s - w) / w
        max_rel = max(max_rel, rel)
        print(f"  lam={lam:5.1f} mu={mu:4.1f} N={n:2d}: DES={s.mean_response_s:.4f}s "
              f"analytic={w:.4f}s rel_err={rel:.3f} util={s.utilization:.2f}")
    ok = max_rel < 0.1
    emit("mmn_validation", total_us, f"max_rel_err={max_rel:.4f}")
    return ok


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
