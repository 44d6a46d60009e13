#!/usr/bin/env python3
"""Readings for the limits of a cell's check: the program's, and the
lower-precision control's, on several seeds in one process.

    python3 perfbench/control.py --workload <cell> --seeds 1,2,3 --seconds <s>

For each seed it builds the cell, runs a window of ``--seconds`` at the
cell's own load, and prints one JSON line with the numbers the check
compares (the program against the float64 reference) and the same numbers
with the reference computed in float32 put in the program's place. The
benchmark's own runs never run this; the limits in the load loops are set from
its readings (PERF.md, "How correct is decided"). A workload that is not a
cell may be named ``<config>.<traffic>``: a configuration and a traffic mix
of BENCHMARK.json run together, as a cell left out of it would run.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from perfbench import core  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    spec = core.load_spec(ROOT)
    if args.workload not in {w["name"] for w in spec["workloads"]}:
        config, traffic = args.workload.split(".", 1)
        spec["workloads"].append({"name": args.workload, "config": config,
                                  "traffic": traffic, "chips": 1})
    cell = core.resolve(spec, args.workload, ROOT)
    core.check_devices(int(cell.entry["chips"]))
    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    compiles = core.CompileCounter()
    for seed in (int(s) for s in args.seeds.split(",")):
        state = cell.loop.setup(cell.config, cell.traffic, seed)
        cell.loop.window(state, args.seconds, compiles)
        checks, counts = cell.loop.check(state)
        print(json.dumps({
            "workload": args.workload, "seed": seed, "attempted": counts["attempted"],
            "program": {c.name: c.value for c in checks},
            "control": cell.loop.control(state),
            "limits": {c.name: c.limit for c in checks},
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
