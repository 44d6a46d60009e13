"""Batched allocation engine (DESIGN.md §5): one packed-apps representation
and vectorized solver paths shared by the whole stack.

The first-class unit of work is a *batch of candidate allocations*: a (B, M)
matrix of per-app container counts, solved jointly.

PackedApps
    The single array-of-structs packing of an ``App`` sequence, used by
    ``solvers.py``, ``batch_eval.py``, ``baselines.py`` and the fleet binding.
find_feasible_start_batch
    The P1 phase-1 heuristic (memory waterfill + CPU scaling + stability
    repair) vectorized in NumPy over the batch; infeasible rows are masked
    out rather than short-circuited.
p1_solve_batch
    The log-barrier interior-point Newton of Theorem 4 under one jit(vmap)
    over the batch. Serial ``solvers.p1_solve`` is the B=1 special case of
    this path, so the batched and serial solvers cannot drift apart.
ideal_configs_batch
    Algorithm 1's inner solves — the SP1 bisection-on-dF/dc and the SP2
    integer argmin over Φ(N) — vmapped over apps.

All JAX paths run in float64 (enabled by repro.core).
"""
from __future__ import annotations

import dataclasses
from functools import cached_property, partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import queueing
from repro.core.perf_model import eq1_latency
from repro.core.problem import App, ServerCaps


# ----------------------------------------------------------------------------
# PackedApps — the shared array-of-structs representation
# ----------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class PackedApps:
    """Array-of-structs packing of a Sequence[App] (all float64 NumPy)."""

    kappa: np.ndarray  # (M, 3) Eq.(1) parameters
    lam: np.ndarray  # (M,) arrival rates [req/s]
    xbar: np.ndarray  # (M,) work units per request
    r_min: np.ndarray  # (M,) memory floor [GB]
    r_max: np.ndarray  # (M,) memory saturation [GB]
    cpu_min: np.ndarray  # (M,) smallest CPU quota
    cpu_max: np.ndarray  # (M,) largest CPU quota

    @classmethod
    def from_apps(cls, apps: Sequence[App]) -> "PackedApps":
        return cls(
            kappa=np.asarray([a.kappa for a in apps], dtype=np.float64),
            lam=np.asarray([a.lam for a in apps], dtype=np.float64),
            xbar=np.asarray([a.xbar for a in apps], dtype=np.float64),
            r_min=np.asarray([a.r_min for a in apps], dtype=np.float64),
            r_max=np.asarray([a.r_max for a in apps], dtype=np.float64),
            cpu_min=np.asarray([a.cpu_min for a in apps], dtype=np.float64),
            cpu_max=np.asarray([a.cpu_max for a in apps], dtype=np.float64),
        )

    @property
    def M(self) -> int:
        return int(self.lam.shape[0])

    @cached_property
    def jax_dict(self) -> dict:
        """The pytree the jitted kernels take (cached: pack once, solve many)."""
        return {
            f.name: jnp.asarray(getattr(self, f.name), jnp.float64)
            for f in dataclasses.fields(self)
        }

    def as_dict(self) -> dict:
        # fresh shell over the cached leaves: callers may rebind keys for
        # what-if evaluations without poisoning the shared packing
        return dict(self.jax_dict)


def as_packed(apps) -> PackedApps:
    """Coerce a Sequence[App] (or an already-packed instance) to PackedApps."""
    return apps if isinstance(apps, PackedApps) else PackedApps.from_apps(apps)


def _eq1_np(kappa: np.ndarray, c, m):
    """Eq. (1) in NumPy, broadcasting kappa (..., M, 3) against (..., M)
    quotas — the trailing-axis indexing also accepts the fleet layer's
    per-node (N, M, 3) parameter stacks."""
    k1, k2, k3 = kappa[..., 0], kappa[..., 1], kappa[..., 2]
    return k1 / (1.0 - np.exp(-k2 * c)) + np.exp(k3 / m)


def _mask_counts(packed, n):
    """(n_eff, n_ws) under the optional packed["mask"] sentinel-slot pattern.

    Fleet rows pad heterogeneous per-node app counts to one static M with
    masked slots (mask = 0). Padded slots carry n = 0 so ``n_eff`` zeroes
    their budget/power contributions for free, while ``n_ws`` sanitizes them
    to 1 server so the Erlang-C evaluations at the sentinel app parameters
    stay finite (their ws values are masked out of every sum afterwards).
    """
    mask = packed.get("mask") if isinstance(packed, dict) else None
    if mask is None:
        return n, n
    return n * mask, jnp.where(mask > 0, n, jnp.ones_like(n))


def _alpha_arg(alpha):
    """Normalize the latency weight: a scalar stays a python float (keeps the
    historical jit trace), a per-app priority-weighted (M,) vector becomes a
    float64 array — every objective/derivative expression in this module
    multiplies alpha elementwise against per-app terms, so the vector form
    broadcasts through the interior point, SP1 and the grid sweep unchanged."""
    a = np.asarray(alpha, dtype=float)
    return float(a) if a.ndim == 0 else a


# ----------------------------------------------------------------------------
# P1 objective / barrier (Theorem 4) — shared by serial and batched paths
# ----------------------------------------------------------------------------
def p1_objective(x, packed, n, caps_cpu, caps_mem, power_span, alpha, beta,
                 width: int | None = None, tail_q: float = 0.0):
    """Σ_i α Ws_i + β ΔP_i/λ_i as a function of x = [c_1..c_M, m_1..m_M].

    Honors the optional ``packed["mask"]`` sentinel-slot pattern (masked
    slots contribute exactly 0) and the optional static Erlang sum ``width``.
    ``tail_q`` (static) swaps the latency term: 0.0 is the paper's mean Ws,
    a quantile in (0, 1) substitutes the analytic response-time quantile
    surrogate (queueing.erlang_wait_quantile) — the ``crms_p95`` objective.
    """
    M = packed["lam"].shape[0]
    c, m = x[:M], x[M:]
    mask = packed.get("mask")
    n_eff, n_ws = _mask_counts(packed, n)
    d_ms = eq1_latency(
        (packed["kappa"][..., 0], packed["kappa"][..., 1], packed["kappa"][..., 2]), c, m
    )
    mu = 1000.0 / (packed["xbar"] * d_ms)
    if tail_q:
        ws = jax.vmap(partial(queueing.erlang_wait_quantile, q=tail_q, width=width))(
            n_ws, packed["lam"], mu
        )
    else:
        ws = jax.vmap(partial(queueing.erlang_ws, width=width))(n_ws, packed["lam"], mu)
    dp = power_span * n_eff * c / caps_cpu
    terms = alpha * ws + beta * dp / packed["lam"]
    if mask is not None:
        terms = jnp.where(mask > 0, terms, 0.0)
    return jnp.sum(terms)


def p1_slacks(x, packed, n, caps_cpu, caps_mem):
    """The barrier constraint slacks (budgets, memory box, CPU floor) — the
    single definition shared by the barrier value and the line search's cheap
    feasibility check, so the two cannot drift. Masked slots (n = 0 via
    ``packed["mask"]``) leave the budget slacks untouched; their box slacks
    stay a positive constant because the Newton direction freezes their
    coordinates, so they shift the barrier by a constant that cancels out of
    every line-search comparison."""
    M = packed["lam"].shape[0]
    c, m = x[:M], x[M:]
    n_eff, _ = _mask_counts(packed, n)
    return jnp.concatenate(
        [
            jnp.asarray([caps_cpu - jnp.sum(n_eff * c), caps_mem - jnp.sum(n_eff * m)]),
            m - packed["r_min"],
            packed["r_max"] - m,
            c - packed["cpu_min"],
        ]
    )


def p1_barrier(x, t, packed, n, caps_cpu, caps_mem, power_span, alpha, beta,
               width: int | None = None, tail_q: float = 0.0):
    f = p1_objective(x, packed, n, caps_cpu, caps_mem, power_span, alpha, beta,
                     width, tail_q)
    slacks = p1_slacks(x, packed, n, caps_cpu, caps_mem)
    barrier = -jnp.sum(jnp.log(slacks))
    return t * f + barrier, slacks


def p1_rho(x, packed, n):
    M = packed["lam"].shape[0]
    c, m = x[:M], x[M:]
    mask = packed.get("mask")
    _, n_ws = _mask_counts(packed, n)
    d_ms = eq1_latency(
        (packed["kappa"][..., 0], packed["kappa"][..., 1], packed["kappa"][..., 2]), c, m
    )
    mu = 1000.0 / (packed["xbar"] * d_ms)
    rho = packed["lam"] / (n_ws * mu)
    # masked slots report rho = 0 so the stability predicate never freezes a
    # whole row on a sentinel lane
    return rho if mask is None else jnp.where(mask > 0, rho, 0.0)


_NEWTON_DAMP = 1e-9  # diagonal damping shared by the dense and structured paths


def _check_solver(solver: str) -> None:
    """The dense Newton is a CPU test reference: its f64 ``jnp.linalg.solve``
    has no TPU lowering (XLA's TPU LU decomposition takes F32/C64 only), so
    asking for it on a TPU fails here rather than deep in the compiler."""
    if solver not in ("structured", "dense"):
        raise ValueError(f"solver must be 'structured' or 'dense', got {solver!r}")
    if solver == "dense" and jax.default_backend() == "tpu":
        raise NotImplementedError(
            "solver='dense' is a CPU test reference: its float64 LU solve does "
            "not compile for TPU; use solver='structured'"
        )


def _newton_direction_structured(x, t, packed, n, caps_cpu, caps_mem, power_span, alpha, beta,
                                 width: int | None = None, tail_q: float = 0.0):
    """Analytic Newton direction H⁻¹g for the P1 barrier in O(M).

    The barrier Hessian has exploitable structure (DESIGN.md §5): the
    objective and all box barriers are separable per app — each (c_i, m_i)
    pair contributes one 2×2 block — and only the two budget barriers couple
    apps, each as a rank-1 term (1/s²)·nnᵀ on its own resource block. So

        H = B + uuᵀ + vvᵀ,   B block-diagonal (2×2), u = [n/s_cpu; 0],
                             v = [0; n/s_mem]

    and H⁻¹g follows from per-app 2×2 solves plus a 2×2 Woodbury
    (Sherman-Morrison-Woodbury) capacitance solve — no O((2M)³) dense
    factorization and no forward-over-reverse autodiff Hessian. All
    derivatives are closed-form: Eq. (1) latency, mu = 1000/(x̄ d), Erlang-C
    Ws via queueing.erlang_ws_derivs, the linear power term and the log
    barriers. With the same _NEWTON_DAMP on the block diagonals this is the
    exact same damped-Hessian solve as the dense path.
    """
    M = packed["lam"].shape[0]
    c, m = x[:M], x[M:]
    k1, k2, k3 = packed["kappa"][..., 0], packed["kappa"][..., 1], packed["kappa"][..., 2]
    lam, xbar = packed["lam"], packed["xbar"]
    mask = packed.get("mask")
    n_eff, n_ws = _mask_counts(packed, n)

    # Eq. (1): d = k1/(1-e^{-k2 c}) + e^{k3/m}, separable so d_cm = 0
    e = jnp.exp(-k2 * c)
    s = 1.0 - e
    B_m = jnp.exp(k3 / m)
    d = k1 / s + B_m
    d_c = -k1 * k2 * e / s**2
    d_cc = k1 * k2**2 * e * (s + 2.0 * e) / s**3
    d_m = -(k3 / m**2) * B_m
    d_mm = B_m * (k3**2 / m**4 + 2.0 * k3 / m**3)

    # mu = K/d with K = 1000/x̄ (Eq. 6)
    K = 1000.0 / xbar
    mu = K / d
    mu_c = -K * d_c / d**2
    mu_m = -K * d_m / d**2
    mu_cc = K * (2.0 * d_c**2 / d**3 - d_cc / d**2)
    mu_mm = K * (2.0 * d_m**2 / d**3 - d_mm / d**2)
    mu_cm = 2.0 * K * d_c * d_m / d**3

    if tail_q:
        # tail objective: frozen-Erlang-C quantile derivatives (DESIGN.md §14)
        _, ws1, ws2 = jax.vmap(
            partial(queueing.erlang_wait_quantile_derivs, q=tail_q, width=width)
        )(n_ws, lam, mu)
    else:
        _, ws1, ws2 = jax.vmap(partial(queueing.erlang_ws_derivs, width=width))(n_ws, lam, mu)
    P = beta * power_span * n_eff / (caps_cpu * lam)  # linear power slope in c

    f_c = alpha * ws1 * mu_c + P
    f_m = alpha * ws1 * mu_m
    f_cc = alpha * (ws2 * mu_c**2 + ws1 * mu_cc)
    f_cm = alpha * (ws2 * mu_c * mu_m + ws1 * mu_cm)
    f_mm = alpha * (ws2 * mu_m**2 + ws1 * mu_mm)
    if mask is not None:
        # masked-slot objective terms are constants (0): drop their (finite,
        # sentinel-app) derivatives so the frozen coordinates carry no pull
        f_c = f_c * mask
        f_m = f_m * mask
        f_cc = f_cc * mask
        f_cm = f_cm * mask
        f_mm = f_mm * mask

    s_cpu = caps_cpu - jnp.sum(n_eff * c)
    s_mem = caps_mem - jnp.sum(n_eff * m)
    sc_lo = c - packed["cpu_min"]
    sm_lo = m - packed["r_min"]
    sm_hi = packed["r_max"] - m

    g_c = t * f_c + n_eff / s_cpu - 1.0 / sc_lo
    g_m = t * f_m + n_eff / s_mem - 1.0 / sm_lo + 1.0 / sm_hi

    bcc = t * f_cc + 1.0 / sc_lo**2 + _NEWTON_DAMP
    bmm = t * f_mm + 1.0 / sm_lo**2 + 1.0 / sm_hi**2 + _NEWTON_DAMP
    bcm = t * f_cm
    det = bcc * bmm - bcm**2

    def bsolve(rc, rm):  # per-app 2×2 solve B_i y_i = r_i, vectorized over apps
        return (bmm * rc - bcm * rm) / det, (bcc * rm - bcm * rc) / det

    u = n_eff / s_cpu  # rank-1 factors of the two budget-barrier Hessians
    v = n_eff / s_mem
    yg_c, yg_m = bsolve(g_c, g_m)
    yu_c, yu_m = bsolve(u, jnp.zeros_like(u))
    yv_c, yv_m = bsolve(jnp.zeros_like(v), v)

    # 2×2 capacitance solve: (I + Uᵀ B⁻¹ U) w = Uᵀ B⁻¹ g, U = [u | v]
    S11 = 1.0 + jnp.dot(u, yu_c)
    S12 = jnp.dot(u, yv_c)
    S21 = jnp.dot(v, yu_m)
    S22 = 1.0 + jnp.dot(v, yv_m)
    bu = jnp.dot(u, yg_c)
    bv = jnp.dot(v, yg_m)
    detS = S11 * S22 - S12 * S21
    w1 = (S22 * bu - S12 * bv) / detS
    w2 = (S11 * bv - S21 * bu) / detS
    dx_c = yg_c - (yu_c * w1 + yv_c * w2)
    dx_m = yg_m - (yu_m * w1 + yv_m * w2)
    if mask is not None:
        # freeze masked coordinates at their box-center start: their barrier
        # contribution stays a CONSTANT shift of every line-search value, so
        # acceptance decisions match the unpadded solve exactly
        dx_c = dx_c * mask
        dx_m = dx_m * mask
    return jnp.concatenate([dx_c, dx_m])


def _ip_core(x0, packed, n, caps_cpu, caps_mem, power_span, alpha, beta, n_outer, n_inner,
             solver: str = "structured", t0: float = 1.0, width: int | None = None,
             tail_q: float = 0.0):
    """Log-barrier interior point: t <- t*mu_t, damped Newton inner loop with a
    feasibility-preserving backtracking line search (rejects steps that leave
    the barrier domain or the queue-stability region).

    ``solver`` picks the Newton direction: "structured" (default) is the
    analytic block-diagonal + Woodbury O(M) solve; "dense" is the autodiff
    jax.hessian + O((2M)³) jnp.linalg.solve escape hatch kept for parity
    testing (tests/test_structured_newton.py pins the two within 1e-6).

    ``width`` (static) narrows every Erlang-C recurrence from MAX_SERVERS
    steps to the given width — exact whenever all container counts stay
    below it (queueing._erlang_c), and the dominant term in fleet-scale wall
    clock."""

    def strictly_feasible(x):
        _, slacks = p1_barrier(x, 1.0, packed, n, caps_cpu, caps_mem, power_span, alpha, beta,
                               width, tail_q)
        rho = p1_rho(x, packed, n)
        return jnp.logical_and(jnp.all(slacks > 0), jnp.all(rho < 1.0 - 1e-7))

    def feasible_cheap(x):
        # same predicate as strictly_feasible without evaluating the objective:
        # slacks are linear/box terms, rho needs only the Eq. (1) latency
        slacks = p1_slacks(x, packed, n, caps_cpu, caps_mem)
        rho = p1_rho(x, packed, n)
        return jnp.logical_and(jnp.all(slacks > 0), jnp.all(rho < 1.0 - 1e-7))

    _ALPHAS = (1.0, 0.5, 0.25, 0.1, 0.03, 0.01, 3e-3, 1e-3)

    def inner_dense(x, t):
        # the PR-1 newton step, verbatim: autodiff Hessian, dense solve, and a
        # line search paying a full barrier evaluation per trial step — the
        # escape hatch the structured path is parity-tested and benchmarked
        # against
        def newton_step(x, _):
            val_fn = lambda xx: p1_barrier(
                xx, t, packed, n, caps_cpu, caps_mem, power_span, alpha, beta, width,
                tail_q
            )[0]
            g = jax.grad(val_fn)(x)
            H = jax.hessian(val_fn)(x)
            dim = x.shape[0]
            H = H + _NEWTON_DAMP * jnp.eye(dim, dtype=x.dtype)
            dx = jnp.linalg.solve(H, g)
            cur = val_fn(x)

            def try_alpha(acc, a):
                best_x, best_val, found = acc
                cand = x - a * dx
                ok = strictly_feasible(cand)
                v = jnp.where(ok, val_fn(cand), jnp.inf)
                better = jnp.logical_and(v < best_val, ~found)
                best_x = jnp.where(better, cand, best_x)
                best_val = jnp.where(better, v, best_val)
                found = jnp.logical_or(found, better)
                return (best_x, best_val, found), None

            alphas = jnp.asarray(_ALPHAS, x.dtype)
            (x_new, _, found), _ = jax.lax.scan(try_alpha, (x, cur, jnp.asarray(False)), alphas)
            return jnp.where(found, x_new, x), None

        x, _ = jax.lax.scan(newton_step, x, None, length=n_inner)
        return x

    def inner_structured(x, t):
        # analytic O(M) direction + a two-stage line search with the SAME
        # acceptance rule as inner_dense (largest alpha that is strictly
        # feasible and decreases the barrier): feasibility of all trial
        # alphas is prechecked without touching the objective (the feasible
        # set is convex, so feasibility is monotone in the step size), then
        # barrier values are evaluated on demand, largest-first, stopping at
        # the first improvement — 1-2 heavy evaluations per step instead of
        # 2 per trial alpha
        val_fn = lambda xx: p1_barrier(
            xx, t, packed, n, caps_cpu, caps_mem, power_span, alpha, beta, width,
            tail_q
        )[0]

        def newton_step(carry, _):
            # the barrier value at x rides the carry: the accepted candidate's
            # value IS the next step's baseline, so each step costs one heavy
            # evaluation per tried alpha and none for the current point
            x, cur = carry
            dx = _newton_direction_structured(
                x, t, packed, n, caps_cpu, caps_mem, power_span, alpha, beta, width,
                tail_q
            )
            alphas = jnp.asarray(_ALPHAS, x.dtype)
            feas = jax.vmap(lambda a: feasible_cheap(x - a * dx))(alphas)
            k = alphas.shape[0]
            start = jnp.where(jnp.any(feas), jnp.argmax(feas), k)

            def cond(state):
                i, accepted, _, _ = state
                return jnp.logical_and(~accepted, i < k)

            def body(state):
                i, _, xb, vb = state
                cand = x - alphas[i] * dx
                v = jnp.where(feas[i], val_fn(cand), jnp.inf)
                acc = v < cur
                return (
                    i + 1,
                    acc,
                    jnp.where(acc, cand, xb),
                    jnp.where(acc, v, vb),
                )

            _, _, x_new, cur_new = jax.lax.while_loop(
                cond, body, (start, jnp.asarray(False), x, cur)
            )
            return (x_new, cur_new), None

        (x, _), _ = jax.lax.scan(newton_step, (x, val_fn(x)), None, length=n_inner)
        return x

    inner = inner_structured if solver == "structured" else inner_dense

    def outer(carry, _):
        x, t = carry
        x = inner(x, t)
        return (x, t * 6.0), None

    (x, _), _ = jax.lax.scan(outer, (x0, jnp.asarray(t0, x0.dtype)), None, length=n_outer)
    return x


@partial(jax.jit, static_argnames=("n_outer", "n_inner", "solver", "t0", "width",
                                   "tail_q"))
def _ip_solve_batched(
    x0, packed, n, caps_cpu, caps_mem, power_span, alpha, beta,
    n_outer=14, n_inner=24, solver="structured", t0=1.0, width=None, tail_q=0.0,
):
    """One jitted vmap over a (B, 2M) batch of starts + (B, M) counts. Returns
    (x* (B, 2M), utility (B,)) — the utility is the tail objective when
    ``tail_q`` is set, so candidate ranking and the reported optimum agree."""

    def one(x0_i, n_i):
        x = _ip_core(x0_i, packed, n_i, caps_cpu, caps_mem, power_span, alpha, beta,
                     n_outer, n_inner, solver=solver, t0=t0, width=width, tail_q=tail_q)
        u = p1_objective(x, packed, n_i, caps_cpu, caps_mem, power_span, alpha, beta,
                         width, tail_q)
        return x, u

    return jax.vmap(one)(x0, n)


# ----------------------------------------------------------------------------
# Row-wise P1 solve — the fleet placement layer's inner engine
# ----------------------------------------------------------------------------
def p1_app_ws(x, packed, n, width: int | None = None):
    """Per-app response times at a solution x (masked sentinel slots -> 0)."""
    M = packed["lam"].shape[0]
    c, m = x[:M], x[M:]
    mask = packed.get("mask")
    _, n_ws = _mask_counts(packed, n)
    d_ms = eq1_latency(
        (packed["kappa"][..., 0], packed["kappa"][..., 1], packed["kappa"][..., 2]), c, m
    )
    mu = 1000.0 / (packed["xbar"] * d_ms)
    ws = jax.vmap(partial(queueing.erlang_ws, width=width))(n_ws, packed["lam"], mu)
    return ws if mask is None else jnp.where(mask > 0, ws, 0.0)


def _rows_core(x0, packed_rows, n, caps_cpu, caps_mem, power_span, alpha, beta,
               n_outer, n_inner, solver, t0, width):
    """vmap over FULL per-row problems: unlike ``_ip_solve_batched`` (one
    shared packing, many count vectors), every row here carries its own
    packed-field stack AND its own (caps_cpu, caps_mem) budget — one row per
    fleet node. Returns (x* (N, 2M), utility (N,), ws (N, M))."""

    def one(x0_i, packed_i, n_i, ccpu_i, cmem_i):
        x = _ip_core(x0_i, packed_i, n_i, ccpu_i, cmem_i, power_span, alpha, beta,
                     n_outer, n_inner, solver=solver, t0=t0, width=width)
        u = p1_objective(x, packed_i, n_i, ccpu_i, cmem_i, power_span, alpha, beta, width)
        ws = p1_app_ws(x, packed_i, n_i, width)
        return x, u, ws

    return jax.vmap(one)(x0, packed_rows, n, caps_cpu, caps_mem)


_ROWS_STATICS = ("n_outer", "n_inner", "solver", "t0", "width")
_ip_solve_rows = partial(jax.jit, static_argnames=_ROWS_STATICS)(_rows_core)


@partial(jax.jit, static_argnames=_ROWS_STATICS + ("mesh", "axis"))
def _ip_solve_rows_sharded(
    x0, packed_rows, n, caps_cpu, caps_mem, power_span, alpha, beta,
    *, n_outer, n_inner, solver, t0, width, mesh, axis,
):
    """shard_map wrapper: row-stacked operands split along ``axis`` of
    ``mesh`` (the mesh idiom of launch/mesh.py), scalars replicated. Rows are
    independent, so out_specs is a plain gather — no collectives. The node
    count must be divisible by the axis size (``ip_solve_rows`` checks)."""
    from jax.sharding import PartitionSpec as P

    row = P(axis)  # pytree prefix: applies to every leaf of packed_rows too
    rep = P()
    fn = jax.shard_map(
        partial(_rows_core, n_outer=n_outer, n_inner=n_inner, solver=solver,
                t0=t0, width=width),
        mesh=mesh,
        in_specs=(row, row, row, row, row, rep, rep, rep),
        out_specs=(row, row, row),
        check_vma=False,
    )
    return fn(
        x0, packed_rows, n, caps_cpu, caps_mem,
        jnp.asarray(power_span), jnp.asarray(alpha), jnp.asarray(beta),
    )


def ip_solve_rows(
    x0, packed_rows, n, caps_cpu, caps_mem, power_span, alpha, beta,
    n_outer=8, n_inner=3, solver="structured", t0=1.0, width=None,
    mesh=None, mesh_axis: str = "nodes",
):
    """Public row-wise solver: jit(vmap) on one device, or shard_map over
    ``mesh_axis`` of ``mesh`` when a mesh is given. Both paths share
    ``_rows_core``, so sharding cannot change the math. All operands are
    row-stacked along the leading node axis: x0 (N, 2M), packed_rows a dict
    of (N, M)/(N, M, 3) arrays (plus the (N, M) "mask" sentinel field),
    n (N, M), caps_cpu/caps_mem (N,); power_span/alpha/beta are fleet-wide
    scalars. Returns (x* (N, 2M), utility (N,), ws (N, M))."""
    _check_solver(solver)
    if mesh is None:
        return _ip_solve_rows(
            x0, packed_rows, n, caps_cpu, caps_mem, power_span, alpha, beta,
            n_outer=n_outer, n_inner=n_inner, solver=solver, t0=t0, width=width,
        )
    if x0.shape[0] % mesh.shape[mesh_axis]:
        raise ValueError(
            f"{x0.shape[0]} rows do not split evenly over the {mesh.shape[mesh_axis]} "
            f"devices of mesh axis {mesh_axis!r}; pad the row batch"
        )
    return _ip_solve_rows_sharded(
        x0, packed_rows, n, caps_cpu, caps_mem, power_span, alpha, beta,
        n_outer=n_outer, n_inner=n_inner, solver=solver, t0=t0, width=width,
        mesh=mesh, axis=mesh_axis,
    )


# ----------------------------------------------------------------------------
# Phase-1 feasible start, vectorized over the batch (NumPy)
# ----------------------------------------------------------------------------
def find_feasible_start_batch(packed, caps: ServerCaps, n_batch, c_hint=None, mask=None):
    """Phase-1 heuristic over a (B, M) batch of container-count vectors:
    memory waterfill + CPU proportional scaling + a stability repair pass.
    Rows with no strictly feasible interior point are masked (ok=False) and
    their x0 contents are unspecified. Returns (x0 (B, 2M), ok (B,)).

    Generalizations used by the fleet placement layer (all transparent to the
    single-server callers): packed fields may be per-row (B, M[, 3]) stacks,
    ``caps`` fields may be (B,) arrays (one budget per row/node), and ``mask``
    (B, M) marks sentinel slots — masked lanes are exempted from every
    feasibility predicate (their latency cap is +inf, so the repair loop and
    the hard-cap check ignore them) and land on their box center, matching
    the frozen-coordinate convention of the masked interior point."""
    packed = as_packed(packed)
    n = np.asarray(n_batch, dtype=float)
    B, M = n.shape
    r_min, r_max = packed.r_min, packed.r_max
    cpu_min = packed.cpu_min
    k1, k3 = packed.kappa[..., 0], packed.kappa[..., 2]
    lam, xbar = packed.lam, packed.xbar
    if mask is not None:
        mask = np.asarray(mask, dtype=bool)
        n = n * mask  # sentinel slots budget nothing regardless of caller's n
    ok = np.ones(B, dtype=bool)

    with np.errstate(all="ignore"):
        # memory: m = r_min + phi (r_max - r_min), largest phi in [0, .95]
        # fitting the budget
        base = np.sum(n * r_min, axis=1)
        spread = np.sum(n * (r_max - r_min), axis=1)
        ok &= ~(base > 0.98 * caps.r_mem)
        phi_frac = np.minimum(
            0.95, np.maximum(0.0, (0.95 * caps.r_mem - base) / np.maximum(spread, 1e-9))
        )
        m0 = r_min + phi_frac[:, None] * (r_max - r_min)

        # cpu: scale the hint (sufficient-resource optimum) into the budget
        if c_hint is None:
            c_hint = np.ones(M)
        c_hint = np.asarray(c_hint, dtype=float)
        c_hint = np.broadcast_to(c_hint, (B, M)) if c_hint.ndim == 1 else c_hint
        scale = np.minimum(
            1.0, 0.95 * caps.r_cpu / np.maximum(np.sum(n * c_hint, axis=1), 1e-9)
        )
        c0 = np.maximum(c_hint * scale[:, None], cpu_min * 1.5 + 1e-5)

        # memory repair: two-tier waterfill — a hard floor (mem term <= 90% of
        # the latency cap, bare stabilizability) plus proportional headroom
        # toward a comfortable 60%-of-cap target, within the global budget
        d_cap_ms = 0.92 * n * 1000.0 / (lam * xbar)  # (B, M)
        if mask is not None:
            # sentinel lanes have no queue: no latency cap, never "bad"
            d_cap_ms = np.where(mask, d_cap_ms, np.inf)
        d_cap_ms = np.broadcast_to(d_cap_ms, (B, M))
        hard, soft = 0.9 * d_cap_ms, 0.6 * d_cap_ms
        ok &= ~np.any(hard <= 1.05, axis=1)  # latency cap below the e^0 floor
        floor = k3 / np.log(np.maximum(hard, 1.0 + 1e-12))
        ok &= ~np.any(floor > r_max + 1e-9, axis=1)  # no memory can stabilize
        m_bare = np.clip(np.maximum(floor * 1.01, r_min), r_min, r_max)
        pref = k3 / np.log(np.maximum(soft, 1.06))
        m_pref = np.clip(np.maximum(pref * 1.01, m0), m_bare, r_max)
        bare_need = np.sum(n * m_bare, axis=1)
        ok &= ~(bare_need > 0.98 * caps.r_mem)
        spread2 = np.sum(n * (m_pref - m_bare), axis=1)
        phi2 = np.where(
            spread2 <= 1e-12,
            1.0,
            np.minimum(1.0, (0.98 * caps.r_mem - bare_need) / np.where(spread2 <= 1e-12, 1.0, spread2)),
        )
        m0 = m_bare + phi2[:, None] * (m_pref - m_bare)

        # stability repair: each app needs d(c, m0) < N/(λ x̄) * 1000 ms.
        # Typical rows settle in 1-3 rounds; genuinely borderline rows can
        # oscillate between the lift and the budget shrink, so the round
        # budget is tight and survivors are masked by the hard-cap check
        # below instead of burning 40 vectorized-bisection rounds (this loop
        # sits on the per-refinement-iteration hot path)
        for _ in range(12):
            d_now = _eq1_np(packed.kappa, c0, m0)
            bad = d_now >= d_cap_ms  # (B, M)
            active = np.any(bad, axis=1)  # rows still being repaired
            if not np.any(active & ok):
                break
            mem_term = np.exp(k3 / m0)
            ok &= ~np.any(bad & (k1 + mem_term >= d_cap_ms), axis=1)  # infinite cpu won't do
            # bisect the cpu needed for d = d_cap (d decreasing in c), all
            # (B, M) lanes at once — non-bad lanes are discarded by the mask
            lo = np.broadcast_to(cpu_min, (B, M)).copy()
            hi = np.broadcast_to(packed.cpu_max, (B, M)).copy()
            for _ in range(44):  # 8 cores / 2^44 ≈ 5e-13 — still fp-exact
                mid = 0.5 * (lo + hi)
                too_slow = _eq1_np(packed.kappa, mid, m0) >= d_cap_ms
                lo = np.where(too_slow, mid, lo)
                hi = np.where(too_slow, hi, mid)
            c0 = np.where(bad, np.maximum(c0, hi), c0)
            # over-budget rows shrink the non-binding apps proportionally
            total = np.sum(n * c0, axis=1)
            over = active & (total > 0.98 * caps.r_cpu)
            fixed = np.sum(np.where(bad, n * c0, 0.0), axis=1)
            ok &= ~(over & (fixed > 0.98 * caps.r_cpu))
            room = 0.98 * caps.r_cpu - fixed
            cur = np.sum(np.where(bad, 0.0, n * c0), axis=1)
            shrink_row = over & (cur > room)
            shrink = np.where(cur > 0, room / np.maximum(cur, 1e-300), 1.0)
            c0 = np.where(
                shrink_row[:, None] & ~bad,
                np.maximum(c0 * shrink[:, None], cpu_min * 1.5),
                c0,
            )

        # rows whose repair budget ran out with still-unstable lanes (rho >=
        # 1, i.e. d at/above the hard cap, not just the 0.92 repair target)
        # never reached a strictly feasible interior point — mask them instead
        # of handing the solver a start outside the barrier domain
        d_hard_ms = d_cap_ms / 0.92
        ok &= ~np.any(
            _eq1_np(packed.kappa, c0, m0) >= d_hard_ms * (1.0 - 1e-7), axis=1
        )

    if mask is not None:
        # sentinel lanes start (and stay frozen) at their box center, keeping
        # their barrier terms a finite constant for the masked interior point
        c_mid = np.broadcast_to(0.5 * (cpu_min + packed.cpu_max), (B, M))
        m_mid = np.broadcast_to(0.5 * (r_min + r_max), (B, M))
        c0 = np.where(mask, c0, c_mid)
        m0 = np.where(mask, m0, m_mid)
    x0 = np.concatenate([c0, m0], axis=1)
    return x0, ok


# ----------------------------------------------------------------------------
# Grid-seeded phase-1 CPU hints (ROADMAP: Pallas grid seeding)
# ----------------------------------------------------------------------------
def grid_seed_chints(
    packed,
    caps: ServerCaps,
    n_batch,
    alpha: float,
    beta: float,
    n_c: int = 6,
    n_m: int = 3,
    backend: str | None = None,
) -> np.ndarray:
    """Coarse per-app (c, m) utility sweep per candidate count vector; returns
    the argmin-cell CPU quotas as (B, M) phase-1 ``c_hint``s.

    Each app gets a log-spaced CPU grid × linear memory grid over its own box;
    grid cell g assigns every app its g-th quota simultaneously, so the
    per-app utility terms of one batched evaluation decouple and a single
    argmin over G recovers each app's grid-optimal cell at its actual
    container count. The global budget coupling is deliberately ignored here —
    ``find_feasible_start_batch`` scales the hint into the budget, exactly as
    it does the SP1 ideal-config hints.

    ``backend``: None/'auto' routes through the Pallas kernel on TPU
    (kernels.ops.crms_grid, per-app mode) and the f64 jnp oracle
    (batch_eval.utility_terms_batch) elsewhere; 'pallas'/'interpret'/
    'reference' force the kernel path, 'oracle' forces the jnp oracle.
    Apps with no stable grid cell fall back to cpu_max (the most
    stabilizing quota the box allows).
    """
    packed = as_packed(packed)
    n = np.asarray(n_batch, dtype=float)
    B, M = n.shape

    # Per-app terms depend on the app's own count only, so the sweep needs the
    # per-COLUMN unique counts, not all B rows: a CRMS refinement batch has at
    # most 3 distinct counts per app (n0, n0±1), collapsing the (B·G, M)
    # candidate matrix to (K·G, M) with K = max distinct counts per app.
    uniq = [np.unique(n[:, i]) for i in range(M)]
    K = max(u.shape[0] for u in uniq)
    Kp = _pad_pow2(K)  # keep the jit cache warm as the CRMS move set shrinks
    V = np.stack(  # (Kp, M) pseudo-rows; short columns repeat their last count
        [np.concatenate([u, np.full(Kp - u.shape[0], u[-1])]) for u in uniq], axis=1
    )
    # row index of each (b, i)'s count among its column's unique values
    kidx = np.stack([np.searchsorted(u, n[:, i]) for i, u in enumerate(uniq)], axis=1)

    cgrid = np.geomspace(packed.cpu_min * 1.25 + 1e-3, packed.cpu_max, n_c)  # (n_c, M)
    span = packed.r_max - packed.r_min
    mgrid = np.linspace(packed.r_min + 0.02 * span, packed.r_max, n_m)  # (n_m, M)
    cg = np.repeat(cgrid, n_m, axis=0)  # (G, M) cell -> cpu quota
    mg = np.tile(mgrid, (n_c, 1))  # (G, M) cell -> mem quota
    G = n_c * n_m

    n_rep = np.repeat(V, G, axis=0)  # (Kp*G, M)
    c_rep = np.tile(cg, (Kp, 1))
    m_rep = np.tile(mg, (Kp, 1))

    alpha = _alpha_arg(alpha)
    # the Pallas kernel takes a scalar alpha; priority-weighted (vector-alpha)
    # sweeps always route through the jnp oracle, which broadcasts per app
    use_oracle = backend == "oracle" or np.ndim(alpha) > 0 or (
        backend in (None, "auto") and jax.default_backend() != "tpu"
    )
    if use_oracle:
        from repro.core.batch_eval import utility_terms_batch

        terms = utility_terms_batch(
            packed.as_dict(),
            jnp.asarray(n_rep),
            jnp.asarray(c_rep),
            jnp.asarray(m_rep),
            jnp.asarray(float(caps.r_cpu)),
            jnp.asarray(float(caps.power.span)),
            alpha,
            float(beta),
        )
    else:
        from repro.kernels import ops

        terms = ops.crms_grid(
            packed.kappa, packed.lam, packed.xbar, n_rep, c_rep, m_rep,
            caps_cpu=float(caps.r_cpu), power_span=float(caps.power.span),
            alpha=float(alpha), beta=float(beta),
            backend=backend or "auto", reduce="per_app",
        )
    terms = np.asarray(terms, dtype=float).reshape(Kp, G, M)
    # unstable cells: +inf from the f64 oracle, the ws=1e9 sentinel from the
    # f32 Pallas kernel (emitted as alpha·1e9 + power term) — map both to inf
    # so argmin/fallback agree across backends; the threshold scales with
    # alpha so small latency weights don't slip the sentinel past the filter
    thresh = max(float(np.max(alpha)), 1e-3) * 1e8
    terms = np.where(np.isfinite(terms) & (terms < thresh), terms, np.inf)
    gstar = np.argmin(terms, axis=1)  # (Kp, M) argmin cell per (count, app)
    cols = np.arange(M)
    c_hint_k = cg[gstar, cols[None, :]]  # (Kp, M)
    no_stable_cell = ~np.isfinite(np.min(terms, axis=1))
    c_hint_k = np.where(no_stable_cell, packed.cpu_max[None, :], c_hint_k)
    return c_hint_k[kidx, cols[None, :]]  # scatter back to the (B, M) batch


# ----------------------------------------------------------------------------
# Batched P1 solve
# ----------------------------------------------------------------------------
@dataclasses.dataclass
class P1Result:
    r_cpu: np.ndarray
    r_mem: np.ndarray
    utility: float
    converged: bool
    info: dict


@dataclasses.dataclass
class P1BatchResult:
    """A (B,)-batch of P1 solutions; ``row(i)`` views one as a P1Result."""

    r_cpu: np.ndarray  # (B, M)
    r_mem: np.ndarray  # (B, M)
    utility: np.ndarray  # (B,)
    converged: np.ndarray  # (B,) bool
    started: np.ndarray  # (B,) bool — phase-1 found a feasible interior point
    info: dict

    def row(self, i: int) -> P1Result:
        info = dict(self.info)
        if not self.started[i]:
            info.setdefault("reason", "no_feasible_start")
        elif not self.converged[i]:
            info.setdefault("reason", "diverged")
        return P1Result(
            r_cpu=self.r_cpu[i].copy(),
            r_mem=self.r_mem[i].copy(),
            utility=float(self.utility[i]),
            converged=bool(self.converged[i]),
            info=info,
        )


def _pad_pow2(B: int) -> int:
    return 1 << max(B - 1, 0).bit_length()


class InfeasibleAllocation(RuntimeError):
    """Every row of a ``p1_solve_batch`` batch lacks a feasible interior
    point (opt-in via ``on_infeasible="raise"``). Carries the binding
    constraint — ``.binding`` ∈ {"stability", "memory", "cpu"} — so callers
    can ACT (shed load, add capacity, relax the SLO) instead of
    pattern-matching an all-masked result with ``ok=False`` rows."""

    def __init__(self, binding: str, detail: dict):
        self.binding = binding
        self.detail = dict(detail)
        super().__init__(
            f"no feasible allocation for any of the "
            f"{detail.get('batch', '?')} candidate count vectors; "
            f"binding constraint: {binding}"
        )


def _diagnose_infeasible(packed, caps: ServerCaps, n_np: np.ndarray) -> tuple:
    """Name the constraint that kills an all-masked batch, by re-running the
    cheap phase-1 impossibility predicates per row and taking the modal
    label: "stability" (some app cannot be stabilized at ANY (c, m) in its
    box at these counts), "memory" (the bare r_min footprint alone busts the
    budget), else "cpu" (the repair loop ran out of CPU headroom). Returns
    (binding, {label: n_rows}) — diagnostic naming only, no solve."""
    with np.errstate(all="ignore"):
        B, M = n_np.shape
        k3 = packed.kappa[..., 2]
        mem_floor = np.sum(n_np * packed.r_min, axis=1)
        memory = mem_floor > 0.98 * np.asarray(caps.r_mem)
        d_cap_ms = 0.92 * n_np * 1000.0 / (packed.lam * packed.xbar)
        hard = 0.9 * d_cap_ms
        floor = k3 / np.log(np.maximum(hard, 1.0 + 1e-12))
        stability = np.any(hard <= 1.05, axis=1) | np.any(
            floor > packed.r_max + 1e-9, axis=1
        )
    counts = {
        "stability": int(np.sum(stability)),
        "memory": int(np.sum(memory & ~stability)),
        "cpu": int(np.sum(~memory & ~stability)),
    }
    binding = max(counts, key=counts.get)
    return binding, counts


# Barrier-schedule profiles (n_outer, n_inner). "reference" mirrors the seed
# serial solver — heavily over-converged (duality gap ~1e-10 relative).
# "refine" is the schedule the CRMS greedy refinement and the throughput
# benchmark use: ~7x less Newton work for ≤2e-9 relative utility drift on the
# evaluation scenarios (pinned by tests/test_engine.py and BENCH_solver.json).
# "fleet" is the placement layer's schedule: t0 covers 8 rounds of t *= 6 to
# the same final barrier weight ballpark, and with per-node problems already
# warm-started from ideal configs the remaining drift is ~1e-6 relative —
# well inside the exchange loop's move-acceptance margins.
P1_PROFILES = {"reference": (14, 24), "refine": (12, 4), "fleet": (8, 3)}


def p1_solve_batch(
    apps,
    caps: ServerCaps,
    n_batch,
    alpha: float,
    beta: float,
    c_hint=None,
    n_outer: int | None = None,
    n_inner: int | None = None,
    pad: bool = True,
    profile: str = "reference",
    solver: str = "structured",
    seed_grid: bool = False,
    max_servers: int | None = None,
    tail_q: float = 0.0,
    on_infeasible: str = "mask",
) -> P1BatchResult:
    """Solve Problem P1 (Eq. 26) for every row of a (B, M) batch of container
    counts in ONE vmapped interior-point call.

    ``apps`` may be a Sequence[App] or an already-built PackedApps. Rows with
    no phase-1 feasible start come back with utility=inf / converged=False;
    the remaining lanes are solved jointly (infeasible lanes are filled with a
    feasible row's data so the vmap stays dense, then masked out). ``pad``
    rounds B up to a power of two so the jit cache stays warm as the CRMS
    move set shrinks between refinement iterations. ``profile`` picks the
    barrier schedule (see P1_PROFILES); explicit n_outer/n_inner override it.
    ``solver`` picks the Newton direction ("structured" O(M) analytic default,
    "dense" autodiff escape hatch). ``seed_grid`` puts phase-1 CPU hints from
    the coarse per-app (c, m) utility grid sweep (grid_seed_chints) at the
    head of the hint chain; rows where a hinted phase-1 fails fall back to
    the caller's ``c_hint`` and finally the plain waterfill, so hint sources
    only ever add feasible rows. ``max_servers`` narrows every Erlang-C
    recurrence from queueing.MAX_SERVERS to the given static width — EXACT
    (not approximate) because every count in the batch must stay ≤ it, which
    is validated eagerly; callers should pass a pow2 so distinct fleets share
    one jit cache entry. ``tail_q`` (static) swaps the per-app latency term
    for the analytic quantile surrogate (see p1_objective); the phase-1
    start and grid seeding stay mean-based — they are advisory hints, and
    the tail surrogate shares the mean's feasible region. ``on_infeasible``
    names the all-masked-batch behavior: ``"mask"`` (default, back-compat)
    returns ok=False rows with ``info["binding"]`` naming the constraint;
    ``"raise"`` raises a structured ``InfeasibleAllocation`` carrying it —
    no caller has to pattern-match a silent all-False ``started`` vector.
    """
    if on_infeasible not in ("mask", "raise"):
        raise ValueError(
            f"on_infeasible must be 'mask' or 'raise', got {on_infeasible!r}"
        )
    _check_solver(solver)
    prof_outer, prof_inner = P1_PROFILES[profile]
    n_outer = prof_outer if n_outer is None else n_outer
    n_inner = prof_inner if n_inner is None else n_inner
    packed = as_packed(apps)
    n_np = np.asarray(n_batch, dtype=float)
    if n_np.ndim != 2:
        raise ValueError(f"n_batch must be (B, M), got shape {n_np.shape}")
    if max_servers is not None and n_np.size and float(n_np.max()) > max_servers:
        raise ValueError(
            f"max_servers={max_servers} is below the largest container count "
            f"{int(n_np.max())} in the batch — the narrowed Erlang sum would "
            "no longer be exact"
        )
    B, M = n_np.shape
    # Phase-1 hint chain: grid-seeded cells first (when enabled), then the
    # caller's hint (SP1 ideal / warm quotas), then the plain waterfill.
    # Hints are advisory — rows where a hinted phase-1 fails (e.g. a
    # budget-oblivious hint starves a CPU-hungry app) retry down the chain,
    # so adding a hint source can only ever ADD feasible rows, and each
    # retry touches only the still-failing row subset.
    hint_chain: list = [c_hint] if c_hint is not None else []
    if seed_grid:
        hint_chain.insert(0, grid_seed_chints(packed, caps, n_np, alpha, beta))
    if not hint_chain or hint_chain[-1] is not None:
        hint_chain.append(None)
    x0, ok = find_feasible_start_batch(packed, caps, n_np, c_hint=hint_chain[0])
    n_rescued = 0  # rows the hint fallback chain recovered after a failed start
    for fb in hint_chain[1:]:
        if np.all(ok):
            break
        idx = np.where(~ok)[0]
        fb_np = np.asarray(fb, dtype=float) if fb is not None else None
        sub = fb_np[idx] if fb_np is not None and fb_np.ndim == 2 else fb_np
        x0_fb, ok_fb = find_feasible_start_batch(packed, caps, n_np[idx], c_hint=sub)
        x0[idx[ok_fb]] = x0_fb[ok_fb]
        ok[idx[ok_fb]] = True
        n_rescued += int(np.sum(ok_fb))

    r_cpu = np.zeros((B, M))
    r_mem = np.broadcast_to(packed.r_min, (B, M)).copy()
    utility = np.full(B, np.inf)
    converged = np.zeros(B, dtype=bool)
    if not np.any(ok):
        binding, bind_counts = _diagnose_infeasible(packed, caps, n_np)
        if on_infeasible == "raise":
            raise InfeasibleAllocation(
                binding, {"batch": B, "rows_by_binding": bind_counts}
            )
        return P1BatchResult(
            r_cpu, r_mem, utility, converged, started=ok,
            info={"n_feasible_start": 0, "n_rescued": n_rescued, "n_masked": B,
                  "binding": binding, "rows_by_binding": bind_counts},
        )

    sub = int(np.argmax(ok))  # donor row for masked-out lanes
    x0 = np.where(ok[:, None], x0, x0[sub])
    n_solve = np.where(ok[:, None], n_np, n_np[sub])
    Bp = _pad_pow2(B) if pad else B
    if Bp > B:
        x0 = np.concatenate([x0, np.broadcast_to(x0[sub], (Bp - B, 2 * M))], axis=0)
        n_solve = np.concatenate([n_solve, np.broadcast_to(n_solve[sub], (Bp - B, M))], axis=0)

    x, u = _ip_solve_batched(
        jnp.asarray(x0),
        packed.as_dict(),
        jnp.asarray(n_solve),
        jnp.asarray(float(caps.r_cpu)),
        jnp.asarray(float(caps.r_mem)),
        jnp.asarray(float(caps.power.span)),
        _alpha_arg(alpha),
        float(beta),
        n_outer=n_outer,
        n_inner=n_inner,
        solver=solver,
        width=max_servers,
        tail_q=float(tail_q),
    )
    x = np.asarray(x)[:B]
    u = np.asarray(u)[:B]
    r_cpu = np.where(ok[:, None], x[:, :M], r_cpu)
    r_mem = np.where(ok[:, None], x[:, M:], r_mem)
    utility = np.where(ok, u, np.inf)
    converged = ok & np.isfinite(utility)
    return P1BatchResult(
        r_cpu, r_mem, utility, converged, started=ok,
        info={
            "n_feasible_start": int(ok.sum()),
            "n_rescued": n_rescued,
            "n_masked": int(B - ok.sum()),
            "batch": B,
            "padded_to": Bp,
        },
    )


# ----------------------------------------------------------------------------
# Algorithm 1 inner solves, vmapped over apps
# ----------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("iters",))
def _sp1_batch(packed, caps_cpu, power_span, alpha, beta, iters=100):
    """SP1 for every app at once: m* = r_max (Theorem-2 monotonicity), c* by
    bisection on dF/dc with the box edges handled by masks."""
    k1, k2 = packed["kappa"][:, 0], packed["kappa"][:, 1]
    lam, xbar = packed["lam"], packed["xbar"]

    def dF_dc(c):
        e = jnp.exp(-k2 * c)
        d_latency = -k1 * k2 * e / (1.0 - e) ** 2
        return alpha * xbar * 1e-3 * d_latency + beta * power_span / (caps_cpu * lam)

    lo0, hi0 = packed["cpu_min"], packed["cpu_max"]
    g_lo, g_hi = dF_dc(lo0), dF_dc(hi0)

    def body(_, bounds):
        lo, hi = bounds
        mid = 0.5 * (lo + hi)
        g = dF_dc(mid)
        lo = jnp.where(g < 0, mid, lo)
        hi = jnp.where(g < 0, hi, mid)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo0, hi0))
    c = 0.5 * (lo + hi)
    # still decreasing at cpu_max -> box edge; increasing at cpu_min -> floor
    c = jnp.where(g_hi < 0, hi0, jnp.where(g_lo > 0, lo0, c))
    return c, packed["r_max"]


def sp1_solve_batch(apps, caps: ServerCaps, alpha: float, beta: float, iters: int = 100):
    """Vectorized SP1: returns (r_cpu* (M,), r_mem* (M,)) as NumPy arrays."""
    packed = as_packed(apps)
    c, m = _sp1_batch(
        packed.as_dict(),
        jnp.asarray(float(caps.r_cpu)),
        jnp.asarray(float(caps.power.span)),
        _alpha_arg(alpha),
        float(beta),
        iters=iters,
    )
    return np.asarray(c), np.asarray(m)


@partial(jax.jit, static_argnames=("width",))
def _phi_grid(lam, mu, c, power_span, caps_cpu, alpha, beta, ns, width=None):
    """Φ(N) of Eq. (23) on an (M, K) grid of container counts. ``alpha`` is a
    per-app (M,) latency weight (a scalar is broadcast by the caller).
    ``width``: static Erlang recurrence width — K itself is exact, since no
    grid count exceeds K (see queueing._erlang_c)."""

    def per_app(lam_i, mu_i, c_i, alpha_i):
        def per_n(n):
            ws = queueing.erlang_ws(n, lam_i, mu_i, width)
            dp = power_span * n * c_i / caps_cpu
            return alpha_i * ws + beta * dp / lam_i

        return jax.vmap(per_n)(ns)

    return jax.vmap(per_app)(lam, mu, c, alpha)


def sp2_argmin_batch(apps, caps: ServerCaps, alpha, beta, mu_star, c_star, m_star,
                     n_cap: int | None = None):
    """Vectorized SP2: per-app argmin of convex Φ over the stable feasible
    range [stability floor, cap-implied ceiling] — the exhaustive oracle the
    serial ternary search is tested against, evaluated as one (M, K) grid.

    ``n_cap`` clamps the ceiling (and with it the grid width K and the Erlang
    sum width): Φ is convex in N, so whenever the unconstrained argmin is
    ≤ n_cap the result is identical, and a count that would exceed it comes
    back clamped to n_cap. The fleet placement layer passes a small cap —
    its per-app counts live far below the cap-implied single-server ceiling
    — which turns the (M, K) sweep from K=512 to K=64."""
    packed = as_packed(apps)
    mu_star = np.asarray(mu_star, dtype=float)
    c_star = np.asarray(c_star, dtype=float)
    m_star = np.asarray(m_star, dtype=float)
    lo = np.array(
        [queueing.stability_lower_bound(l, mu) for l, mu in zip(packed.lam, mu_star)],
        dtype=int,
    )
    hi = np.minimum(caps.r_cpu / c_star, caps.r_mem / m_star).astype(int)
    cap = queueing.MAX_SERVERS - 1 if n_cap is None else min(n_cap, queueing.MAX_SERVERS - 1)
    hi = np.minimum(np.maximum(hi, lo), cap)
    K = _pad_pow2(int(hi.max()))
    ns = jnp.arange(1, K + 1, dtype=jnp.float64)
    alpha_vec = np.broadcast_to(_alpha_arg(alpha), packed.lam.shape)
    vals = np.asarray(
        _phi_grid(
            jnp.asarray(packed.lam),
            jnp.asarray(mu_star),
            jnp.asarray(c_star),
            jnp.asarray(float(caps.power.span)),
            jnp.asarray(float(caps.r_cpu)),
            jnp.asarray(alpha_vec),
            float(beta),
            ns,
            width=K,
        )
    )
    grid = np.arange(1, K + 1)
    mask = (grid[None, :] >= lo[:, None]) & (grid[None, :] <= hi[:, None])
    vals = np.where(mask & np.isfinite(vals), vals, np.inf)
    return grid[np.argmin(vals, axis=1)].astype(int)


def ideal_configs_batch(apps, caps: ServerCaps, alpha: float, beta: float,
                        n_cap: int | None = None):
    """Algorithm 1's per-app ideal configs, vectorized over apps. Returns
    (r_cpu* (M,), r_mem* (M,), n* (M,) int, mu* (M,)). ``n_cap`` bounds the
    SP2 count search (see sp2_argmin_batch)."""
    packed = as_packed(apps)
    c_star, m_star = sp1_solve_batch(packed, caps, alpha, beta)
    d_ms = _eq1_np(packed.kappa, c_star, m_star)
    mu_star = 1000.0 / (packed.xbar * d_ms)
    n_star = sp2_argmin_batch(packed, caps, alpha, beta, mu_star, c_star, m_star,
                              n_cap=n_cap)
    return c_star, m_star, n_star, mu_star
