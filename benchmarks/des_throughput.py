"""DES engine throughput: Kiefer–Wolfowitz vector fast path vs event oracle.

Simulates the same M-app fleet (synthetic tenant mix, per-app utilization
0.72-0.78) through both ``FleetSimulator`` engines under common random
numbers and records event throughput (arrivals + departures per wall-clock
second), the speedup, and the CRN mean-response parity into BENCH_des.json.

Gates (exit non-zero when either breaks):

* speedup >= ``--floor`` (default 20x full mode at M=16 with >= 1e6 arrivals;
  3x in ``--smoke`` so the 2-core CI host gates regressions without minutes
  of event-loop time);
* vector-vs-event mean response within ``MAX_MEAN_REL`` (2%) under CRN — on
  a stationary segment the two engines are sample-path identical, so any
  drift here is an engine bug, not Monte-Carlo noise.

The vector engine is timed on its second run: the first pays the one-off
``lax.scan`` compile, which amortizes across segments in real use.

CLI:  PYTHONPATH=src python -m benchmarks.des_throughput
      [--M 16] [--arrivals 1050000] [--floor 20] [--smoke]
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

from benchmarks.common import emit
from repro.core.des import FleetSimulator

M = 16
N_ARRIVALS = 1_050_000  # lam_total * horizon; >= 1e6 per the acceptance gate
FLOOR = 20.0  # full-mode speedup floor (vector vs event)
SMOKE_M = 6
SMOKE_ARRIVALS = 60_000
SMOKE_FLOOR = 3.0  # conservative: CI hosts are 2-core and noisy
MAX_MEAN_REL = 0.02  # CRN mean-response parity gate
OUT = Path(__file__).resolve().parent.parent / "BENCH_des.json"


def tenant_mix(m: int) -> list[tuple[str, float, float, int]]:
    """Deterministic (name, lam, mu, n_servers) fleet: heterogeneous rates
    and cluster sizes, every cluster stable at utilization 0.72-0.78."""
    out = []
    for i in range(m):
        lam = 16.0 + 2.0 * (i % 8)
        n = 4 + (i % 5)
        rho = 0.72 + 0.02 * (i % 4)
        out.append((f"app{i:02d}", lam, lam / (n * rho), n))
    return out


def simulate(engine: str, mix, horizon: float, seed: int = 0):
    """One full run (build, run_until, drain); returns (wall_s, n_events,
    pooled mean response). Events = arrivals + departures, the unit the
    heapq loop pays Python cost per."""
    sim = FleetSimulator(seed=seed, engine=engine)
    for name, lam, mu, n in mix:
        sim.add_app(name, lam, mu, n)
    t0 = time.perf_counter()
    sim.run_until(horizon)
    sim.drain()
    wall = time.perf_counter() - t0
    resp = np.concatenate([sim.responses(name, 0.0, horizon) for name, *_ in mix])
    n_events = 2 * sum(cl.n_arrived for cl in sim._clusters.values())
    return wall, int(n_events), float(resp.mean())


def run(
    m: int = M,
    n_arrivals: int = N_ARRIVALS,
    floor: float = FLOOR,
    smoke: bool = False,
    out: Path = OUT,
) -> bool:
    if smoke:
        m, n_arrivals, floor = SMOKE_M, SMOKE_ARRIVALS, SMOKE_FLOOR
    mix = tenant_mix(m)
    lam_total = sum(lam for _, lam, _, _ in mix)
    horizon = n_arrivals / lam_total

    simulate("vector", mix, horizon)  # warmup: pay the scan compile off-clock
    wall_v, ev_v, mean_v = simulate("vector", mix, horizon)
    wall_e, ev_e, mean_e = simulate("event", mix, horizon)

    speedup = (ev_v / wall_v) / (ev_e / wall_e)
    mean_rel = abs(mean_v - mean_e) / mean_e
    ok_floor = speedup >= floor
    ok_parity = mean_rel < MAX_MEAN_REL

    doc = {
        "schema_version": 1,
        "mode": "smoke" if smoke else "full",
        "M": m,
        "lam_total": lam_total,
        "horizon_s": horizon,
        "event": {"wall_s": wall_e, "n_events": ev_e, "events_per_s": ev_e / wall_e},
        "vector": {"wall_s": wall_v, "n_events": ev_v, "events_per_s": ev_v / wall_v},
        "speedup": speedup,
        "floor": floor,
        "mean_response_event_s": mean_e,
        "mean_response_vector_s": mean_v,
        "mean_rel_err": mean_rel,
        "max_mean_rel_err": MAX_MEAN_REL,
        "pass": bool(ok_floor and ok_parity),
    }
    out.write_text(json.dumps(doc, indent=2) + "\n")

    print(f"M={m} fleet, {ev_e} events "
          f"(lam_total={lam_total:.0f}/s x {horizon:.0f}s horizon)")
    print(f"  event : {wall_e:8.2f}s  {ev_e / wall_e / 1e3:9.0f}k events/s")
    print(f"  vector: {wall_v:8.2f}s  {ev_v / wall_v / 1e3:9.0f}k events/s")
    print(f"  speedup {speedup:.1f}x (floor {floor}x)  "
          f"CRN mean parity {mean_rel:.2e} (< {MAX_MEAN_REL})")
    if not ok_floor:
        print(f"  !! vector speedup {speedup:.1f}x below the {floor}x floor")
    if not ok_parity:
        print(f"  !! CRN mean-response gap {mean_rel:.3e} exceeds {MAX_MEAN_REL}")
    emit(
        "des_throughput",
        wall_v / max(ev_v, 1) * 1e6,
        f"M={m};events={ev_e};speedup={speedup:.1f}x;floor={floor}x",
    )
    return bool(ok_floor and ok_parity)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--M", type=int, default=M, help="fleet size (apps)")
    ap.add_argument("--arrivals", type=int, default=N_ARRIVALS,
                    help="total arrivals to simulate (lam_total * horizon)")
    ap.add_argument("--floor", type=float, default=FLOOR,
                    help="minimum vector-vs-event speedup")
    ap.add_argument("--smoke", action="store_true",
                    help=f"tiny CI gate: M={SMOKE_M}, {SMOKE_ARRIVALS} arrivals, "
                         f">= {SMOKE_FLOOR}x floor")
    args = ap.parse_args(argv)
    return 0 if run(
        m=args.M, n_arrivals=args.arrivals, floor=args.floor, smoke=args.smoke
    ) else 1


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    raise SystemExit(main())
