"""Problem P (paper §IV-C): joint latency+energy MINLP over container configs.

    min_{N_i, r_cpu_i, r_mem_i}  Σ_i  α·Ws(N_i, λ_i, μ_i) + β·ΔP_i/λ_i
    s.t.  Σ N_i r_cpu_i ≤ R̄cpu,  Σ N_i r_mem_i ≤ R̄mem,
          r_min_i ≤ r_mem_i ≤ r_max_i.

Latency d is in ms (perf_model), Ws in seconds, power in W. μ = 1000/(x̄·d).
NP-hardness (Theorem 1) is by reduction from unbounded multi-dim knapsack;
`tests/test_theorems.py::test_np_hardness_reduction` exercises the constructed
special case.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import queueing
from repro.core.perf_model import eq1_latency
from repro.core.power import EDGE_POWER, PowerModel, delta_power


@dataclasses.dataclass(frozen=True)
class App:
    """One heterogeneous application (paper: a container cluster workload)."""

    name: str
    lam: float  # request arrival rate [req/s]
    xbar: float  # mean images (TPU binding: kilo-tokens) per request
    kappa: tuple  # (k1, k2, k3) of Eq. (1), k1>0 convention
    r_min: float  # memory lower bound [GB] (OOM threshold)
    r_max: float  # memory saturation point [GB]
    cpu_min: float = 0.05  # smallest meaningful CPU quota [cores]
    cpu_max: float = 16.0  # largest per-container quota [cores]

    def with_lam(self, lam: float) -> "App":
        return dataclasses.replace(self, lam=lam)

    def with_xbar(self, xbar: float) -> "App":
        return dataclasses.replace(self, xbar=xbar)


@dataclasses.dataclass(frozen=True)
class ServerCaps:
    """Global resource budget (edge server or TPU pod)."""

    r_cpu: float  # total CPU capacity [cores]  (TPU: chips)
    r_mem: float  # total memory [GB]           (TPU: HBM GB)
    power: PowerModel = EDGE_POWER


@dataclasses.dataclass
class Allocation:
    """A full solution to Problem P."""

    n: np.ndarray  # (M,) int container counts
    r_cpu: np.ndarray  # (M,) per-container CPU quota
    r_mem: np.ndarray  # (M,) per-container memory [GB]
    utility: float = np.nan
    ws: np.ndarray | None = None  # (M,) per-app response time [s]
    power_w: np.ndarray | None = None  # (M,) per-app incremental power [W]
    feasible: bool = True
    stable: bool = True
    meta: dict = dataclasses.field(default_factory=dict)

    def total_cpu(self) -> float:
        return float(np.sum(self.n * self.r_cpu))

    def total_mem(self) -> float:
        return float(np.sum(self.n * self.r_mem))


def latency_ms(app: App, r_cpu, r_mem):
    """Eq. (1) per-image latency for an app at a given allocation."""
    return eq1_latency(jnp.asarray(app.kappa, jnp.float64), r_cpu, r_mem)


def service_rate(app: App, r_cpu, r_mem):
    """Eq. (6): μ = 1/(x̄ d) with d converted ms→s."""
    d_s = latency_ms(app, r_cpu, r_mem) * 1e-3
    return 1.0 / (app.xbar * d_s)


def p95_surrogate_s(apps: Sequence[App], n, r_cpu, r_mem, q: float = 0.95) -> np.ndarray:
    """Per-app analytic response-time quantile surrogate at an allocation —
    the model-side tail prediction the DES's ``achieved_p95_s`` is compared
    against. +inf for unstable apps."""
    out = []
    for i, app in enumerate(apps):
        mu = float(service_rate(app, np.asarray(r_cpu)[i], np.asarray(r_mem)[i]))
        out.append(
            queueing.erlang_wait_quantile_np(int(np.asarray(n)[i]), app.lam, mu, q=q)
        )
    return np.asarray(out, dtype=float)


def utility(
    apps: Sequence[App],
    n,
    r_cpu,
    r_mem,
    caps: ServerCaps,
    alpha: float,
    beta: float,
    weights: Sequence[float] | None = None,
    tail_q: float = 0.0,
):
    """Objective U_p of Eq. (8). Returns (U_p, per-app Ws, per-app ΔP).

    ``weights``: optional per-app priority weights w_i scaling the latency
    term to α·w_i·Ws_i (the priority-weighted CRMS objective); None keeps
    the paper's unweighted objective. ``tail_q``: quantile-surrogate latency
    term (0.0 = mean; the returned per-app latency vector then holds the
    surrogate quantiles, not mean Ws).

    Every app is evaluated at once as an (M,) vector: on a TPU, float64
    arithmetic on rank-0 arrays is far less precise than on vectors (exp
    4.6e-8 against 6.7e-15 relative, measured on v5e)."""
    f64 = jnp.float64
    kappa = jnp.asarray([a.kappa for a in apps], f64).T
    lam = jnp.asarray([a.lam for a in apps], f64)
    xbar = jnp.asarray([a.xbar for a in apps], f64)
    n = jnp.asarray(n, f64)
    r_cpu = jnp.asarray(r_cpu, f64)
    mu = 1.0 / (xbar * eq1_latency(kappa, r_cpu, jnp.asarray(r_mem, f64)) * 1e-3)
    if tail_q:
        ws = jax.vmap(partial(queueing.erlang_wait_quantile, q=tail_q))(n, lam, mu)
    else:
        ws = jax.vmap(queueing.erlang_ws)(n, lam, mu)
    dp = delta_power(n, r_cpu, caps.r_cpu, caps.power)
    a = alpha if weights is None else alpha * jnp.asarray(weights, f64)
    return jnp.sum(a * ws + beta * dp / lam), ws, dp


def check_feasible(apps, n, r_cpu, r_mem, caps: ServerCaps, tol: float = 1e-6):
    """Constraints (9)-(11) + queue stability. Returns dict of booleans."""
    n = np.asarray(n)
    r_cpu = np.asarray(r_cpu)
    r_mem = np.asarray(r_mem)
    cpu_ok = float(np.sum(n * r_cpu)) <= caps.r_cpu * (1 + tol)
    mem_ok = float(np.sum(n * r_mem)) <= caps.r_mem * (1 + tol)
    bounds_ok = all(
        (a.r_min - tol <= m <= a.r_max + tol) and (c > 0) for a, c, m in zip(apps, r_cpu, r_mem)
    )
    stable = all(
        app.lam < nn * float(service_rate(app, c, m))
        for app, nn, c, m in zip(apps, n, r_cpu, r_mem)
    )
    return {
        "cpu": cpu_ok,
        "mem": mem_ok,
        "bounds": bounds_ok,
        "stable": stable,
        "all": cpu_ok and mem_ok and bounds_ok,
    }


def evaluate(apps, n, r_cpu, r_mem, caps, alpha, beta, weights=None,
             tail_q: float = 0.0) -> Allocation:
    """Package a candidate solution with metrics + feasibility flags.
    ``weights`` (optional, per-app) selects the priority-weighted objective.
    ``tail_q`` makes ``utility`` the quantile-surrogate objective while the
    ``ws`` field KEEPS the mean response (the reporting contract —
    ``mean_latency_s`` and the scenario gap metrics read it); the per-app
    surrogate lands in ``meta["p95_surrogate_s"]``."""
    u, ws, dp = utility(
        apps, np.asarray(n), np.asarray(r_cpu), np.asarray(r_mem), caps, alpha, beta,
        weights=weights, tail_q=tail_q,
    )
    feas = check_feasible(apps, n, r_cpu, r_mem, caps)
    meta = {}
    if tail_q:
        # ws above holds the surrogate quantiles; recompute the mean view
        _, ws_mean, _ = utility(
            apps, np.asarray(n), np.asarray(r_cpu), np.asarray(r_mem), caps, alpha,
            beta, weights=weights,
        )
        meta["p95_surrogate_s"] = np.asarray(ws, dtype=float).tolist()
        meta["tail_q"] = float(tail_q)
        ws = ws_mean
    return Allocation(
        n=np.asarray(n, dtype=int),
        r_cpu=np.asarray(r_cpu, dtype=float),
        r_mem=np.asarray(r_mem, dtype=float),
        utility=float(u),
        ws=np.asarray(ws, dtype=float),
        power_w=np.asarray(dp, dtype=float),
        feasible=feas["all"],
        stable=feas["stable"],
        meta=meta,
    )
