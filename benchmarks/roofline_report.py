"""Roofline table from the dry-run JSON: three terms per (arch x shape x
mesh), dominant bottleneck, MODEL_FLOPS ratio (DESIGN.md §7)."""
from __future__ import annotations

import json
from pathlib import Path

from benchmarks.common import emit


def fmt_row(r) -> str:
    if r.get("status") != "ok":
        return f"{r['arch']:26s} {r['shape']:12s} {r['mesh']:10s} {r.get('status', '?')}"
    return (
        f"{r['arch']:26s} {r['shape']:12s} {r['mesh']:10s} "
        f"c={r['compute_term_s']*1e3:9.2f}ms m={r['memory_term_s']*1e3:9.2f}ms "
        f"x={r['collective_term_s']*1e3:9.2f}ms -> {r['dominant']:10s} "
        f"useful={100*(r.get('model_flops_ratio') or 0):5.1f}%"
    )


def run(path: str = "results/dryrun.json") -> bool:
    p = Path(path)
    if not p.exists():
        print(f"roofline_report: {path} not found — run repro.launch.dryrun first")
        emit("roofline_report", 0, "missing")
        return False
    rows = json.loads(p.read_text())
    print("\nRoofline terms per cell (TPU v5e: 197 TF/s bf16, 819 GB/s HBM, 50 GB/s link)")
    n_ok = n_skip = n_fail = 0
    for r in rows:
        print(fmt_row(r))
        st = str(r.get("status", ""))
        n_ok += st == "ok"
        n_skip += st.startswith("SKIP")
        n_fail += not (st == "ok" or st.startswith("SKIP"))
    print(f"\n{n_ok} ok / {n_skip} skipped / {n_fail} failed of {len(rows)} cells")
    emit("roofline_report", 0, f"ok={n_ok};skip={n_skip};fail={n_fail}")
    return n_fail == 0 and n_ok > 0


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
