"""M/M/N queueing (Eqs. 4-7 of the paper), numerically stable and differentiable.

The paper's Eq. (4)-(5) use factorials directly; for container counts beyond ~20
that overflows. The solver-path quantities (Ws, Ls, the waiting probability C
and their derivatives) therefore come from the Erlang-B recurrence

    B_0 = 1,   B_k = a·B_{k-1} / (k + a·B_{k-1}),   C = B_N / (1 - rho·(1 - B_N))

which needs only multiplies, adds and divides, all on values in [0, 1]. A
float64 transcendental (log, exp, lgamma) expands into a long f32 sequence on a
TPU, which has no float64 units, and the log-space form of Eq. (5) puts about a
dozen of them into every Erlang evaluation; the recurrence puts in none, so the
solver programs compile in seconds rather than minutes there. All functions are
jit/vmap/grad-safe: ``N`` may be a traced integer-valued float, and the
recurrence runs a fixed number of masked steps (``width``).

Conventions
-----------
lam : request arrival rate [req/s]
mu  : per-container service rate [req/s]  (mu = 1000/(xbar * d_ms), Eq. 6)
N   : container count
rho : lam / (N mu) — must be < 1 for stability; unstable inputs return +inf.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.scipy.special import gammaln

# Fixed width of the masked k-sum. Edge scenarios use N <= ~64; the TPU fleet
# binding can deploy up to 256 replica groups per app in principle.
MAX_SERVERS = 512


@partial(jax.jit, static_argnames=("width",))
def _erlang_c(N, a, rho_s, width: int | None = None):
    """Erlang-C waiting probability C(N, a) from the Erlang-B recurrence,
    evaluated at the clamped load ``rho_s`` = min(a/N, 1 - eps).

    The recurrence runs ``width`` steps (MAX_SERVERS by default) and masks
    every step k > N, so it is EXACT, not an approximation, whenever
    N <= width: masked steps carry B through unchanged. The fleet placement
    layer passes the pow2 ceiling of its largest container count (~16
    instead of 512) — every Erlang evaluation in the interior point pays
    this width.
    """
    ks = jnp.arange(1, (MAX_SERVERS if width is None else width) + 1, dtype=a.dtype)

    def step(B, k):
        return jnp.where(k <= N, a * B / (k + a * B), B), None

    B, _ = jax.lax.scan(step, jnp.ones_like(a), ks)
    return B / (1.0 - rho_s * (1.0 - B))


def _log_sum_k(N, log_a, width: int | None = None):
    """log Σ_{k=0}^{N-1} a^k / k!  as a masked logsumexp (fixed width) — the
    log-space head of Eq. (5), used by ``erlang_pi0``. Exact for N <= width:
    masked terms contribute exp(-inf) = 0."""
    ks = jnp.arange(MAX_SERVERS if width is None else width, dtype=log_a.dtype)
    logs = ks * log_a - gammaln(ks + 1.0)
    mask = ks < N
    neg_inf = jnp.asarray(-jnp.inf, dtype=log_a.dtype)
    logs = jnp.where(mask, logs, neg_inf)
    return jax.scipy.special.logsumexp(logs)


def erlang_pi0(N, lam, mu, width: int | None = None):
    """pi0 of Eq. (5): probability of an empty M/M/N system (log-space)."""
    N = jnp.asarray(N, dtype=jnp.result_type(float))
    lam = jnp.asarray(lam, dtype=N.dtype)
    mu = jnp.asarray(mu, dtype=N.dtype)
    log_a = jnp.log(lam) - jnp.log(mu)
    rho = lam / (N * mu)
    rho_safe = jnp.minimum(rho, 1.0 - 1e-9)
    log_head = _log_sum_k(N, log_a, width)
    log_tail = N * log_a - gammaln(N + 1.0) - jnp.log1p(-rho_safe)
    log_pi0 = -jnp.logaddexp(log_head, log_tail)
    return jnp.exp(log_pi0)


def erlang_ls(N, lam, mu, width: int | None = None):
    """Eq. (4): expected number of requests in the system. +inf when rho >= 1.

    Lq = C·rho/(1 - rho) with C the Erlang-C waiting probability."""
    dtype = jnp.result_type(float)
    N = jnp.asarray(N, dtype=dtype)
    lam = jnp.asarray(lam, dtype=dtype)
    mu = jnp.asarray(mu, dtype=dtype)
    a = lam / mu
    rho = a / N
    rho_s = jnp.minimum(rho, 1.0 - 1e-9)
    lq = _erlang_c(N, a, rho_s, width) * rho_s / (1.0 - rho_s)
    return jnp.where(rho < 1.0, lq + a, jnp.inf)


def erlang_ws(N, lam, mu, width: int | None = None):
    """Eq. (7): expected response time per request (Little's law). +inf if unstable.

    Differentiable in ``lam``/``mu`` on the stable region. ``width`` bounds
    the recurrence (exact for N <= width; see _erlang_c).
    """
    return erlang_ls(N, lam, mu, width) / lam


def erlang_ws_derivs(N, lam, mu, width: int | None = None):
    """Closed-form (Ws, dWs/dmu, d²Ws/dmu²) on the stable region, for the
    structured Newton path of the P1 solver (engine._newton_direction_structured).

    Uses the Erlang-C identity Lq = C·rho/(1-rho) with C the probability of
    waiting (the same recurrence as ``erlang_ws``), and the exact
    a-derivatives

        dC/da  = C·[(1-rho)/rho + (1-C)/(N(1-rho))]
        dLq/da = C'·rho/(1-rho) + C/(N(1-rho)²)

    (valid for integer N, where d/da Σ_{k<N} a^k/k! = Σ_{k<N-1} a^k/k!),
    then chains through a = lam/mu. Ws = Lq/lam + 1/mu. Matches
    jax.grad/jax.hessian of ``erlang_ws`` to fp precision on the stable
    region (pinned by tests/test_structured_newton.py); unstable inputs
    (rho >= 1) return +inf value with unspecified derivatives.
    """
    dtype = jnp.result_type(float)
    N = jnp.asarray(N, dtype=dtype)
    lam = jnp.asarray(lam, dtype=dtype)
    mu = jnp.asarray(mu, dtype=dtype)
    a = lam / mu
    rho = a / N
    rho_s = jnp.minimum(rho, 1.0 - 1e-9)
    one_m = 1.0 - rho_s  # (1 - rho), the only small quantity here
    C = _erlang_c(N, a, rho_s, width)

    lq = C * rho_s / one_m
    # first derivatives w.r.t. a
    h = one_m / rho_s + (1.0 - C) / (N * one_m)
    dC = C * h
    dlq = dC * rho_s / one_m + C / (N * one_m**2)
    # second derivatives w.r.t. a
    dh = -N / a**2 + (-dC * one_m + (1.0 - C) / N) / (N * one_m**2)
    d2C = dC * h + C * dh
    d2lq = d2C * rho_s / one_m + 2.0 * dC / (N * one_m**2) + 2.0 * C / (N**2 * one_m**3)

    # chain rule through a(mu) = lam/mu:  da/dmu = -a/mu, d²a/dmu² = 2a/mu²
    ws = lq / lam + 1.0 / mu
    dws = -dlq * a / (mu * lam) - 1.0 / mu**2
    d2ws = (d2lq * (a / mu) ** 2 + dlq * 2.0 * a / mu**2) / lam + 2.0 / mu**3
    ws = jnp.where(rho < 1.0, ws, jnp.inf)
    return ws, dws, d2ws


def erlang_wait_prob(N, lam, mu, width: int | None = None):
    """Erlang-C probability of waiting C = P(W_q > 0) (Eq. 5's tail mass).

    Same recurrence as ``erlang_ws``; returns 1.0 on the unstable branch
    (every request waits). Differentiable on the stable region; ``width``
    bounds the recurrence (exact for N <= width).
    """
    dtype = jnp.result_type(float)
    N = jnp.asarray(N, dtype=dtype)
    lam = jnp.asarray(lam, dtype=dtype)
    mu = jnp.asarray(mu, dtype=dtype)
    a = lam / mu
    rho = a / N
    rho_s = jnp.minimum(rho, 1.0 - 1e-9)
    C = _erlang_c(N, a, rho_s, width)
    return jnp.where(rho < 1.0, C, 1.0)


def erlang_wait_quantile(N, lam, mu, q: float = 0.95, width: int | None = None):
    """Analytic q-quantile surrogate for the M/M/N response time (DESIGN.md §14).

    The conditional wait given delay is exactly Exp(N·mu − lam), so the
    waiting-time tail is P(W_q > t) = C·e^{−(Nμ−λ)t} with C the Erlang-C
    probability of waiting. Inverting at q gives the exact W_q quantile
    max(0, ln(C/(1−q)))/(Nμ−λ); the *response*-time surrogate reported here
    rides it on the mean response:

        T_q ≈ Ws + max(ln(C/(1−q)), 0) / (Nμ − λ)

    (q = 0.95 → ln(20·C)). The wait part is exact for M/M/N; adding the mean
    service 1/mu (inside Ws) instead of convolving the service distribution
    makes the surrogate slightly conservative in light traffic and slightly
    optimistic when service variability dominates — the DES-vs-surrogate
    test documents the H2/MMPP bias (achieved tails exceed the surrogate).
    +inf on the unstable branch. Differentiable in lam/mu on the stable
    region (C's dependence included — this is the dense-path objective).
    """
    dtype = jnp.result_type(float)
    N = jnp.asarray(N, dtype=dtype)
    lam = jnp.asarray(lam, dtype=dtype)
    mu = jnp.asarray(mu, dtype=dtype)
    rho = lam / (N * mu)
    ws = erlang_ws(N, lam, mu, width)
    C = erlang_wait_prob(N, lam, mu, width)
    gap = jnp.maximum(N * mu - lam, 1e-300)
    L = jnp.maximum(jnp.log(C) - jnp.log1p(-q), 0.0)
    return jnp.where(rho < 1.0, ws + L / gap, jnp.inf)


def erlang_wait_quantile_derivs(N, lam, mu, q: float = 0.95,
                                width: int | None = None):
    """(T_q, dT_q/dmu, d²T_q/dmu²) for the structured Newton path, with the
    Erlang-C coefficient FROZEN (DESIGN.md §14).

    T_q = Ws + L/g with g = Nμ−λ and L = max(ln(C/(1−q)), 0). The exact
    dC/dμ term is small (C varies slowly next to the 1/g pole) and carrying
    it would forfeit the closed-form 2×2 block structure, so the tail excess
    is differentiated holding L as data:

        d(L/g)/dμ = −L·N/g²,   d²(L/g)/dμ² = 2·L·N²/g³

    L itself is recomputed from the current iterate at every Newton step, so
    the frozen-C direction is a quasi-Newton step toward the true surrogate
    optimum — the dense autodiff path (`solver="dense"`) differentiates C
    exactly and is the parity escape hatch. Unstable inputs return +inf
    value with unspecified derivatives.
    """
    ws, dws, d2ws = erlang_ws_derivs(N, lam, mu, width)
    dtype = jnp.result_type(float)
    N = jnp.asarray(N, dtype=dtype)
    lam = jnp.asarray(lam, dtype=dtype)
    mu = jnp.asarray(mu, dtype=dtype)
    C = erlang_wait_prob(N, lam, mu, width)
    g = jnp.maximum(N * mu - lam, 1e-300)
    L = jnp.maximum(jnp.log(C) - jnp.log1p(-q), 0.0)
    T = ws + L / g
    dT = dws - L * N / g**2
    d2T = d2ws + 2.0 * L * N**2 / g**3
    return T, dT, d2T


def erlang_ws_finite(N, lam, mu, cap: float = 1e9):
    """Ws with the unstable branch mapped to a large finite cap (for optimizers
    that dislike inf, e.g. line searches probing the boundary)."""
    ws = erlang_ws(N, lam, mu)
    return jnp.where(jnp.isfinite(ws), ws, cap)


def stability_lower_bound(lam, mu) -> int:
    """Smallest integer N with lam < N*mu (paper uses ceil(lam/mu); we bump the
    exact-integer case where rho would be exactly 1)."""
    import math

    ratio = float(lam) / float(mu)
    n = math.ceil(ratio)
    if n <= ratio + 1e-12:  # ratio integral -> rho == 1, not stable
        n += 1
    return max(n, 1)


# ----------------------------------------------------------------------------
# NumPy float64 reference (oracle for tests; mirrors the formulas verbatim)
# ----------------------------------------------------------------------------
def erlang_ws_np(N: int, lam: float, mu: float) -> float:
    import numpy as np
    from math import lgamma, log, exp, inf

    a = lam / mu
    rho = lam / (N * mu)
    if rho >= 1.0:
        return inf
    log_a = log(a)
    head = [k * log_a - lgamma(k + 1) for k in range(int(N))]
    tail = N * log_a - lgamma(N + 1) - log(1.0 - rho)
    m = max(max(head), tail)
    log_denom = m + log(sum(exp(h - m) for h in head) + exp(tail - m))
    log_pi0 = -log_denom
    log_lq = N * log_a - lgamma(N + 1) + log(rho) - 2.0 * log(1.0 - rho) + log_pi0
    ls = exp(log_lq) + a
    return ls / lam


def erlang_wait_prob_np(N: int, lam: float, mu: float) -> float:
    """NumPy oracle for the Erlang-C waiting probability (mirrors Eq. 5)."""
    from math import lgamma, log, exp, fsum

    a = lam / mu
    rho = lam / (N * mu)
    if rho >= 1.0:
        return 1.0
    log_a = log(a)
    head = [k * log_a - lgamma(k + 1) for k in range(int(N))]
    tail = N * log_a - lgamma(N + 1) - log(1.0 - rho)
    m = max(max(head), tail)
    return exp(tail - m) / (fsum(exp(h - m) for h in head) + exp(tail - m))


def erlang_wait_quantile_np(N: int, lam: float, mu: float, q: float = 0.95) -> float:
    """NumPy oracle for the response-time quantile surrogate (tests pin the
    jitted ``erlang_wait_quantile`` against this verbatim transcription)."""
    from math import log, inf

    rho = lam / (N * mu)
    if rho >= 1.0:
        return inf
    C = erlang_wait_prob_np(N, lam, mu)
    gap = N * mu - lam
    L = max(log(C / (1.0 - q)), 0.0)
    return erlang_ws_np(N, lam, mu) + L / gap
