#!/usr/bin/env python3
"""Smoke run of the CRMS allocator's main path on one TPU chip.

Runs every phase in this one process, on ``jax.devices()[0]``, through the
entry points a user calls, at deployment sizes:

1. paper_node  — the paper's 4-app edge node: ``make_paper_apps(fitted=True)``
   at λ = (8, 7, 10, 15) on ServerCaps(30, 10) through ``allocate("crms")``
   with default options, so grid seeding runs the Pallas ``crms_grid`` kernel.
2. tenant64    — a 64-tenant node (``make_tenant_mix(64)``), same checks.
3. vector_des  — the ``benchmarks/des_throughput.py`` M = 16 mix at about 10⁶
   arrivals through the vector DES (``lax.scan`` backend) and the event DES on
   the same common-random-number streams.
4. rollouts    — ``rollout_candidates`` at B = 32 candidates × M = 16 apps.
5. region      — ``FleetPlanner`` over 1000 nodes × 16 apps: a cold plan, then
   one incremental ``replan``.

Each phase prints one line: its compile and wall seconds (smoke timings, not
benchmark numbers) and its check against a plain reference. A failed check
raises. The last line is one JSON object naming the device.

``--chips 4`` runs only the region phase, once on a ``Mesh`` over four chips
and once without one, and checks that the plans agree and that the row
solve's outputs are sharded over all four devices.

The script exits non-zero, before any phase, where JAX finds no TPU.

    python chip_smoke.py [--chips 4]
"""
from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent

PAPER_LAM = (8.0, 7.0, 10.0, 15.0)
# Bounds. Where a CPU test pins a tighter one, the comment says why the chip
# needs more: v5e has no float64 units, and XLA's emulation rounds to about
# 1e-14 per operation (up to 1e-12 under cancellation) with float32's
# exponent range.
KERNEL_RTOL = 1e-4  # tests/test_kernels.py: f32 kernel vs the f64 oracle ...
KERNEL_RHO_MAX = 0.99  # ... where rho <= 0.99. The chip's f32 exp is off by
# up to 3.9e-6 relative (measured on v5e; 6e-8 on a CPU), and Ws amplifies
# an error in mu by 1/(1 - rho), so nearer saturation an f32 kernel on the
# chip cannot meet 1e-4 ...
KERNEL_RTOL_ALL = 1e-2  # ... and every stable cell is held to this instead
WS_RTOL = 1e-9  # Ws vs the float64 pure-Python Erlang-C oracle
KAPPA_RTOL = 1e-8  # Eq. (1) fit on the chip vs the same fit on the host CPU:
# 1.2e-9 measured on v5e (the CPU fit vs the earlier LU fit is 5e-11, pinned
# at 1e-9 by tests/test_perf_model.py)
DES_RTOL = 1e-9  # vector vs event DES mean response on shared CRN streams
ROLLOUT_RTOL = 1e-9  # B = 1 rollout vs one vector-DES run
REGION_RTOL = 1e-6  # fleet row solve vs standalone p1_solve_batch per node
MESH_RTOL = 1e-9  # four-chip plan vs one-device plan

# JAX records each backend compile (or persistent-cache read) under this name
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_COMPILE_S = [0.0]  # backend compile seconds, summed by a monitoring listener


def _on_duration(event: str, duration: float, **_):
    if event == _BACKEND_COMPILE_EVENT:
        _COMPILE_S[0] += duration


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def _rel(a, b) -> float:
    a = np.asarray(a, dtype=float)
    b = np.asarray(b, dtype=float)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-300)))


class _Spy:
    """Records the calls made through ``module.<name>`` without changing
    them, for checks on what the entry point ran."""

    def __init__(self, module, name: str):
        self.module, self.name = module, name
        self.calls: list = []

    def __enter__(self):
        self.orig = getattr(self.module, self.name)

        def record(*args, **kwargs):
            out = self.orig(*args, **kwargs)
            self.calls.append((args, kwargs, out))
            return out

        setattr(self.module, self.name, record)
        return self

    def __exit__(self, *exc):
        setattr(self.module, self.name, self.orig)


# ----------------------------------------------------------------------------
# phases 1-2: one edge node through allocate("crms")
# ----------------------------------------------------------------------------
def _lowers_to_mosaic(args, kwargs) -> bool:
    """Whether this crms_grid call lowers to a compiled Mosaic kernel."""
    import jax

    from repro.kernels.crms_grid import crms_grid_eval

    text = jax.jit(functools.partial(crms_grid_eval, **kwargs)).lower(*args).as_text()
    return "tpu_custom_call" in text


def _check_node(apps, caps, result, spy) -> str:
    """Feasible and stable; Ws against the float64 Erlang-C oracle; the grid
    kernel's per-app terms against the jnp oracle on the grids it was run on."""
    import jax.numpy as jnp

    from repro.core.batch_eval import utility_terms_batch
    from repro.core.engine import PackedApps, _eq1_np
    from repro.core.queueing import erlang_ws_np

    alloc = result.allocation
    _check(result.feasible and result.stable, f"allocation infeasible or unstable: {alloc}")
    _check(alloc.total_cpu() <= caps.r_cpu * (1 + 1e-9), "CPU budget exceeded")
    _check(alloc.total_mem() <= caps.r_mem * (1 + 1e-9), "memory budget exceeded")
    packed = PackedApps.from_apps(apps)
    mu = 1000.0 / (packed.xbar * _eq1_np(packed.kappa, alloc.r_cpu, alloc.r_mem))
    ws_ref = [erlang_ws_np(int(n), lam, m) for n, lam, m in zip(alloc.n, packed.lam, mu)]
    ws_gap = _rel(alloc.ws, ws_ref)
    _check(ws_gap <= WS_RTOL, f"Ws vs f64 oracle {ws_gap:.3e} > {WS_RTOL}")
    rho_max = float(np.max(packed.lam / (alloc.n * mu)))

    _check(len(spy.calls) > 0, "allocate() never called the crms_grid kernel")
    _check(not any(kw.get("interpret") for _, kw, _ in spy.calls),
           "crms_grid ran in interpret mode")
    args0, kw0, _ = spy.calls[0]
    _check(_lowers_to_mosaic(args0, kw0), "crms_grid did not lower to tpu_custom_call")
    kern_gap = kern_gap_all = 0.0
    for (kappa, lam, xbar, n, c, m), kw, out in spy.calls:
        pdict = {"kappa": kappa, "lam": lam, "xbar": xbar,
                 "r_min": packed.r_min, "r_max": packed.r_max}
        ref = np.asarray(utility_terms_batch(
            pdict, jnp.asarray(n), jnp.asarray(c), jnp.asarray(m),
            kw["caps_cpu"], kw["power_span"], kw["alpha"], kw["beta"],
        ))
        got = np.asarray(out, dtype=float)
        stable = np.isfinite(ref) & (ref < 1e8)
        _check(bool(np.all(got[~stable] > 1e6)), "kernel missed an unstable cell")
        rho = np.asarray(lam) * np.asarray(xbar) * _eq1_np(np.asarray(kappa), c, m) / (
            1000.0 * np.asarray(n))
        held = stable & (rho <= KERNEL_RHO_MAX)
        if stable.any():
            kern_gap_all = max(kern_gap_all, _rel(got[stable], ref[stable]))
        if held.any():
            kern_gap = max(kern_gap, _rel(got[held], ref[held]))
    _check(kern_gap <= KERNEL_RTOL, f"kernel vs oracle {kern_gap:.3e} > {KERNEL_RTOL}")
    _check(kern_gap_all <= KERNEL_RTOL_ALL,
           f"kernel vs oracle {kern_gap_all:.3e} > {KERNEL_RTOL_ALL} over all stable cells")
    d = result.diagnostics
    return (f"feasible+stable, {d.refine_iters} refine iters, max rho {rho_max:.6f}; "
            f"Ws vs f64 Erlang-C oracle {ws_gap:.3e} <= {WS_RTOL:g}; crms_grid compiled Pallas "
            f"(tpu_custom_call), {len(spy.calls)} calls, per-app terms vs jnp oracle "
            f"{kern_gap:.3e} <= {KERNEL_RTOL:g} where rho <= {KERNEL_RHO_MAX}, "
            f"{kern_gap_all:.3e} <= {KERNEL_RTOL_ALL:g} over all stable cells")


def paper_node() -> str:
    import jax

    from repro.api import AllocRequest, allocate
    from repro.core.problem import ServerCaps
    from repro.core.profiler import make_paper_apps
    from repro.kernels import crms_grid

    apps = make_paper_apps(lam=PAPER_LAM, fitted=True)
    with jax.default_device(jax.devices("cpu")[0]):  # the host CPU's fit
        apps_cpu = make_paper_apps(lam=PAPER_LAM, fitted=True)
    kappa_gap = _rel([a.kappa for a in apps], [a.kappa for a in apps_cpu])
    _check(kappa_gap <= KAPPA_RTOL, f"fitted kappa vs CPU fit {kappa_gap:.3e} > {KAPPA_RTOL}")
    caps = ServerCaps(30.0, 10.0)
    with _Spy(crms_grid, "crms_grid_eval") as spy:
        result = allocate("crms", AllocRequest(apps, caps))
    return (f"fitted kappa vs host-CPU fit {kappa_gap:.3e} <= {KAPPA_RTOL:g}; "
            + _check_node(apps, caps, result, spy))


def tenant_node(m: int = 64) -> str:
    from repro.api import AllocRequest, allocate
    from repro.core.profiler import make_tenant_mix
    from repro.kernels import crms_grid

    apps, caps, _ = make_tenant_mix(m)
    with _Spy(crms_grid, "crms_grid_eval") as spy:
        result = allocate("crms", AllocRequest(apps, caps))
    return f"M={m}: " + _check_node(apps, caps, result, spy)


# ----------------------------------------------------------------------------
# phases 3-4: the simulators
# ----------------------------------------------------------------------------
def vector_des(m: int = 16, n_arrivals: int | None = None) -> str:
    from benchmarks.des_throughput import N_ARRIVALS, simulate, tenant_mix

    mix = tenant_mix(m)
    horizon = (n_arrivals or N_ARRIVALS) / sum(lam for _, lam, _, _ in mix)
    _, ev_v, mean_v = simulate("vector", mix, horizon)
    _, ev_e, mean_e = simulate("event", mix, horizon)
    _check(ev_v == ev_e, f"event counts differ: vector {ev_v}, event {ev_e}")
    gap = _rel(mean_v, mean_e)
    _check(gap <= DES_RTOL, f"vector vs event DES mean {gap:.3e} > {DES_RTOL}")
    return (f"M={m}, {ev_e // 2} arrivals; vector (lax.scan) vs event DES mean "
            f"response {gap:.3e} <= {DES_RTOL:g}")


def rollouts(b: int = 32, m: int = 16) -> str:
    from benchmarks.p95_slo import HORIZON_FULL, candidate_grid, sequential_runs, tenant_mix
    from repro.core.des_vector import rollout_candidates

    mix = tenant_mix(m)
    names = [name for name, *_ in mix]
    lam = np.array([lam_i for _, lam_i, _, _ in mix])
    mu_all, n_all = candidate_grid(mix, b)
    ro = rollout_candidates(names, lam, mu_all, n_all, HORIZON_FULL, seed=0)
    _check(ro.mean_s.shape == (b, m) and bool(np.all(np.isfinite(ro.mean_s))),
           "rollout means not finite (B, M)")
    ro1 = rollout_candidates(names, lam, mu_all[:1], n_all[:1], HORIZON_FULL, seed=0)
    _, mean_seq = sequential_runs(mix, mu_all[:1], n_all[:1], HORIZON_FULL, 0)
    gap = _rel(ro1.pooled_mean_s[0], mean_seq)
    _check(gap <= ROLLOUT_RTOL, f"B=1 rollout vs vector DES {gap:.3e} > {ROLLOUT_RTOL}")
    return (f"B={b} x M={m}, {ro.n_events} customers per candidate; B=1 pooled mean vs "
            f"vector DES {gap:.3e} <= {ROLLOUT_RTOL:g}")


# ----------------------------------------------------------------------------
# phase 5: the region planner
# ----------------------------------------------------------------------------
def _plan_region(n_nodes: int, m: int, mesh=None):
    from benchmarks.common import ALPHA, BETA
    from benchmarks.fleet_placement import _drift
    from repro.core.placement import FleetPlanner, make_fleet

    apps, node_caps = make_fleet(n_nodes, m, seed=0)
    planner = FleetPlanner(apps, node_caps, alpha=ALPHA, beta=BETA, mesh=mesh)
    cold = planner.plan()
    rng = np.random.default_rng(1)
    drift_idx = rng.choice(planner.A, size=max(2, n_nodes // 250), replace=False)
    mig = (planner.apps[int(rng.integers(planner.A))].name, int(rng.integers(n_nodes)))
    incr = planner.replan(lam=_drift(planner, rng, drift_idx), migrations=[mig])
    for plan in (cold, incr):
        _check(plan.diagnostics["nodes_failed"] == 0, f"failed nodes: {plan.diagnostics}")
    return planner, cold, incr


def region(n_nodes: int = 1000, m: int = 16) -> str:
    from benchmarks.fleet_placement import _parity

    planner, cold, incr = _plan_region(n_nodes, m)
    # sample among the nodes holding the modal app count, so the standalone
    # reference compiles one program rather than one per distinct count
    counts = np.bincount(planner.assignment, minlength=n_nodes)
    modal = np.flatnonzero(counts == np.bincount(counts).argmax())
    sample = np.random.default_rng(2).choice(modal, size=min(8, modal.size), replace=False)
    gap = _parity(planner, sample)
    _check(gap <= REGION_RTOL, f"row solve vs p1_solve_batch {gap:.3e} > {REGION_RTOL}")
    return (f"{n_nodes} nodes x {m} apps, cold plan then replan of "
            f"{incr.diagnostics['nodes_solved']} nodes; per-node parity vs "
            f"p1_solve_batch {gap:.3e} <= {REGION_RTOL:g}")


def region_mesh(n_nodes: int = 1000, m: int = 16, chips: int = 4) -> str:
    import jax
    from jax.sharding import Mesh

    from repro.core import placement

    mesh = Mesh(np.array(jax.devices()[:chips]), ("nodes",))
    with _Spy(placement, "ip_solve_rows") as spy:
        sharded, *plans_s = _plan_region(n_nodes, m, mesh=mesh)
    single, *plans_1 = _plan_region(n_nodes, m)
    spans = {len(out[0].sharding.device_set) for _, _, out in spy.calls}
    _check(spans == {chips}, f"row-solve outputs span {spans} devices, not {chips}")
    _check(np.array_equal(sharded.assignment, single.assignment), "assignments differ")
    gap = max(
        _rel(getattr(sharded, f), getattr(single, f))
        for f in ("sol_c", "sol_m", "sol_ws", "node_utility")
    )
    for ps, p1 in zip(plans_s, plans_1):
        gap = max(gap, _rel(ps.utility, p1.utility))
    _check(gap <= MESH_RTOL, f"{chips}-chip plan vs one-device plan {gap:.3e} > {MESH_RTOL}")
    return (f"{n_nodes} nodes x {m} apps, cold plan + replan on a {chips}-chip mesh vs one "
            f"device: {gap:.3e} <= {MESH_RTOL:g}; {len(spy.calls)} row solves, each "
            f"output sharded over {chips} devices")


PHASES = {
    "paper_node": paper_node,
    "tenant64": tenant_node,
    "vector_des": vector_des,
    "rollouts": rollouts,
    "region": region,
}


def run_phase(name: str, fn) -> None:
    c0, t0 = _COMPILE_S[0], time.perf_counter()
    check = fn()
    wall = time.perf_counter() - t0
    print(f"{name}: compile_s={_COMPILE_S[0] - c0:.3f} wall_s={wall:.3f} "
          f"(smoke timings, not benchmark numbers) | check: {check}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the region phase on a four-chip mesh")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found {len(devices)} devices",
              file=sys.stderr)
        return 1
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    if args.chips == 4:
        run_phase("region_4chip", region_mesh)
    else:
        for name, fn in PHASES.items():
            run_phase(name, fn)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
