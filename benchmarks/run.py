# One function per paper table. Prints ``name,us_per_call,derived`` CSV lines
# (plus human-readable detail) for: Table I, Figs 2-3, 6-10, 11-14, 15-22, the
# M/M/N validation, the solver throughput sweep, the quasi-dynamic trace, the
# cross-policy scenario matrix, the burst-robustness curve, the DES engine
# throughput gate, the TPU fleet benchmark, the multi-node placement gates and
# the roofline report.
#
# CLI filters (CI and local runs can execute a single section):
#   --only <section>[,<section>...]   run only the named sections (repeatable)
#   --policy <name>                   restrict the scenarios section to one
#                                     registered allocation policy
#   --list                            print registered benchmark sections and
#                                     allocation policies, then exit
from __future__ import annotations

import argparse
import sys
from pathlib import Path

if __package__ in (None, ""):  # run as a plain script: repo root + src on sys.path
    _root = Path(__file__).resolve().parent.parent
    sys.path.insert(0, str(_root / "src"))
    sys.path.insert(0, str(_root))

SECTIONS = (
    "table1_fitting",
    "fig2_3_fit_quality",
    "fig6_10_sufficient",
    "fig11_14_constrained",
    "fig15_22_sweeps",
    "mmn_validation",
    "solver_throughput",
    "quasidynamic_trace",
    "scenarios",
    "burst_robustness",
    "coldstart",
    "failure_resilience",
    "des_throughput",
    "fleet_tpu",
    "fleet_placement",
    "p95_slo",
    "roofline_report",
)

# Expected artifact files per section, so CI gates and docs can read the
# mapping from --list instead of hard-coding BENCH_*.json names.
ARTIFACTS = {
    "solver_throughput": ("BENCH_solver.json",),
    "quasidynamic_trace": ("BENCH_quasidynamic.json",),
    "scenarios": ("BENCH_scenarios.json",),
    "burst_robustness": ("BENCH_burst.json",),
    "coldstart": ("BENCH_coldstart.json",),
    "failure_resilience": ("BENCH_failure.json",),
    "des_throughput": ("BENCH_des.json",),
    "fleet_placement": ("BENCH_fleet.json",),
    "p95_slo": ("BENCH_p95.json",),
}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description="paper-table benchmark driver")
    ap.add_argument(
        "--only",
        action="append",
        default=None,
        metavar="SECTION",
        help=f"run only these sections (repeatable or comma-separated); "
        f"one of: {', '.join(SECTIONS)}",
    )
    ap.add_argument(
        "--policy",
        default=None,
        metavar="NAME",
        help="restrict the scenarios section to one registered policy",
    )
    ap.add_argument(
        "--list",
        action="store_true",
        help="print registered benchmark sections and allocation policies, then exit",
    )
    args = ap.parse_args(argv)

    if args.list:
        from repro.api import list_policies

        print("benchmark sections (with expected artifacts):")
        for name in SECTIONS:
            arts = ", ".join(ARTIFACTS.get(name, ())) or "-"
            print(f"  {name:24s} {arts}")
        print("registered policies (repro.api.registry):")
        for name in list_policies():
            print(f"  {name}")
        return

    selected = None
    if args.only:
        selected = [s for chunk in args.only for s in chunk.split(",") if s]
        unknown = sorted(set(selected) - set(SECTIONS))
        if unknown:
            ap.error(f"unknown section(s): {', '.join(unknown)}; "
                     f"choose from: {', '.join(SECTIONS)}")

    import importlib

    print("name,us_per_call,derived")
    results = {}
    for name in SECTIONS:
        if selected is not None and name not in selected:
            continue
        try:
            mod = importlib.import_module(f"benchmarks.{name}")
            if name == "scenarios" and args.policy:
                results[name] = bool(mod.run(policies=(args.policy,)))
            else:
                results[name] = bool(mod.run())
        except Exception as e:  # noqa: BLE001 — report, keep going
            print(f"{name},0,ERROR:{type(e).__name__}:{e}")
            results[name] = False

    print("\nsummary:")
    for k, v in results.items():
        print(f"  {k:24s} {'PASS' if v else 'FAIL'}")
    sys.exit(0 if all(results.values()) else 1)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
