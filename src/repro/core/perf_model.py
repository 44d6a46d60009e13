"""Container-level latency-resource performance model (paper §III).

Implements the five candidate fitting families of Table I and a
Levenberg-Marquardt nonlinear-least-squares fitter in pure JAX (the paper uses
scipy's; we keep a scipy cross-check in tests). Eq. (1) — the winner — is:

    d(c, m) = k1 / (1 - exp(-k2 * c)) + exp(k3 / m)          [d in ms]

with c = CPU quota [cores] (TPU binding: chips per replica) and m = memory
[GB] (TPU binding: HBM per replica).

Sign convention: the paper states k1 < 0 but its own derivative algebra
(Eqs. 18/20) uses the rewritten denominator (1 - e^{+k2 c}) which is negative;
with the literal Eq. (1) form, positivity + monotone-decreasing latency +
convexity require k1 > 0 (see DESIGN.md §3). We therefore fit/hold k1 > 0 and
verify Theorems 2-4 numerically under this convention.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np


# ----------------------------------------------------------------------------
# Candidate families (Table I). Each maps (params, cpu, mem) -> latency [ms].
# ----------------------------------------------------------------------------
def eq1_latency(params, cpu, mem):
    """Eq. (1): k1/(1-e^{-k2 c}) + e^{k3/m}.  params = (k1, k2, k3), all > 0."""
    k1, k2, k3 = params[0], params[1], params[2]
    return k1 / (1.0 - jnp.exp(-k2 * cpu)) + jnp.exp(k3 / mem)


def family2(params, cpu, mem):
    """k1/c + k2 m^2 + k3 m."""
    k1, k2, k3 = params[0], params[1], params[2]
    return k1 / cpu + k2 * mem**2 + k3 * mem


def family3(params, cpu, mem):
    """1 / (k1 log(1+c) + k2 log(1+m))."""
    k1, k2 = params[0], params[1]
    return 1.0 / (k1 * jnp.log1p(cpu) + k2 * jnp.log1p(mem))


def family4(params, cpu, mem):
    """k1 / (k2 + k3 c^2 + k4 m^2)."""
    k1, k2, k3, k4 = params[0], params[1], params[2], params[3]
    return k1 / (k2 + k3 * cpu**2 + k4 * mem**2)


def family5(params, cpu, mem):
    """k1 c^3 + k2 m^3 + k3 c m."""
    k1, k2, k3 = params[0], params[1], params[2]
    return k1 * cpu**3 + k2 * mem**3 + k3 * cpu * mem


@dataclasses.dataclass(frozen=True)
class Family:
    name: str
    fn: Callable
    n_params: int
    positive: bool  # constrain params > 0 via softplus reparametrization


FAMILIES: Dict[str, Family] = {
    "eq1": Family("eq1", eq1_latency, 3, True),
    "inv_quad": Family("inv_quad", family2, 3, False),
    "log_inv": Family("log_inv", family3, 2, True),
    "rational": Family("rational", family4, 4, True),
    "cubic": Family("cubic", family5, 3, False),
}


# ----------------------------------------------------------------------------
# Levenberg-Marquardt NLLS in JAX
# ----------------------------------------------------------------------------
def _softplus(x):
    return jnp.logaddexp(x, 0.0)


def _inv_softplus(y):
    y = jnp.maximum(y, 1e-8)
    return y + jnp.log(-jnp.expm1(-y))


def _solve_small(A, b):
    """Solve A x = b for a small static n by Gaussian elimination with partial
    pivoting, unrolled over rows. The LM systems are 2×2 to 4×4 per start,
    and an f64 LU call does not compile on TPU; this lowers to elementwise
    ops and selects that run in any precision."""
    n = A.shape[0]
    Ab = jnp.concatenate([A, b[:, None]], axis=1)
    rows = jnp.arange(n)
    for k in range(n):
        p = k + jnp.argmax(jnp.abs(Ab[k:, k]))  # pivot row
        swap = jnp.where(rows == k, p, jnp.where(rows == p, k, rows))
        Ab = Ab[swap]
        f = jnp.where(rows > k, Ab[:, k] / Ab[k, k], 0.0)
        Ab = Ab - f[:, None] * Ab[k][None, :]
    x = jnp.zeros_like(b)
    for k in reversed(range(n)):
        x = x.at[k].set((Ab[k, n] - jnp.dot(Ab[k, :n], x)) / Ab[k, k])
    return x


@dataclasses.dataclass
class FitResult:
    family: str
    params: np.ndarray
    rmse: float
    mse: float
    r2: float
    adj_r2: float
    residuals: np.ndarray
    converged: bool

    def predict(self, cpu, mem):
        return np.asarray(FAMILIES[self.family].fn(jnp.asarray(self.params), jnp.asarray(cpu), jnp.asarray(mem)))


@partial(jax.jit, static_argnames=("fn", "positive", "iters"))
def _lm_fit(theta0, cpu, mem, y, fn=None, positive=True, iters=200):
    """Levenberg-Marquardt on residuals r(theta) = fn(map(theta)) - y."""

    def unmap(theta):
        return _softplus(theta) if positive else theta

    def resid(theta):
        return fn(unmap(theta), cpu, mem) - y

    def loss(theta):
        r = resid(theta)
        return 0.5 * jnp.sum(r * r)

    jac = jax.jacfwd(resid)

    def step(carry, _):
        theta, lam_damp, best_theta, best_loss = carry
        r = resid(theta)
        J = jac(theta)
        JTJ = J.T @ J
        g = J.T @ r
        n = theta.shape[0]

        def try_lambda(lam):
            delta = _solve_small(JTJ + lam * jnp.eye(n, dtype=theta.dtype), g)
            cand = theta - delta
            return cand, loss(cand)

        cand1, l1 = try_lambda(lam_damp)
        cand2, l2 = try_lambda(lam_damp * 10.0)
        cur = loss(theta)
        # accept best improving candidate; adapt damping
        use1 = l1 < cur
        use2 = jnp.logical_and(~use1, l2 < cur)
        theta_new = jnp.where(use1, cand1, jnp.where(use2, cand2, theta))
        lam_new = jnp.where(use1, lam_damp * 0.5, jnp.where(use2, lam_damp * 10.0, lam_damp * 10.0))
        lam_new = jnp.clip(lam_new, 1e-12, 1e12)
        new_loss = loss(theta_new)
        better = new_loss < best_loss
        best_theta = jnp.where(better, theta_new, best_theta)
        best_loss = jnp.where(better, new_loss, best_loss)
        return (theta_new, lam_new, best_theta, best_loss), None

    init = (theta0, jnp.asarray(1e-2, theta0.dtype), theta0, loss(theta0))
    (theta, _, best_theta, best_loss), _ = jax.lax.scan(step, init, None, length=iters)
    return unmap(best_theta), best_loss


def fit_family(
    family: str,
    cpu: np.ndarray,
    mem: np.ndarray,
    y: np.ndarray,
    n_starts: int = 16,
    seed: int = 0,
    iters: int = 200,
) -> FitResult:
    """Multi-start LM fit of one candidate family; returns metrics per Table I."""
    fam = FAMILIES[family]
    cpu = jnp.asarray(cpu, jnp.float64)
    mem = jnp.asarray(mem, jnp.float64)
    y = jnp.asarray(y, jnp.float64)

    key = jax.random.PRNGKey(seed)
    # data-informed starting scales
    y_scale = float(jnp.maximum(jnp.mean(y), 1e-3))
    starts = []
    for i in range(n_starts):
        key, k = jax.random.split(key)
        raw = jax.random.uniform(k, (fam.n_params,), jnp.float64, 0.05, 3.0)
        raw = raw * jnp.asarray([y_scale, 1.0, 1.0, 1.0][: fam.n_params])
        starts.append(_inv_softplus(raw) if fam.positive else raw)
    starts = jnp.stack(starts)

    fit_one = lambda t0: _lm_fit(t0, cpu, mem, y, fn=fam.fn, positive=fam.positive, iters=iters)
    params_all, losses = jax.vmap(fit_one)(starts)
    best = int(jnp.argmin(losses))
    params = params_all[best]

    pred = fam.fn(params, cpu, mem)
    resid = np.asarray(pred - y)
    n = y.shape[0]
    mse = float(np.mean(resid**2))
    rmse = float(np.sqrt(mse))
    ss_res = float(np.sum(resid**2))
    ss_tot = float(np.sum((np.asarray(y) - np.mean(np.asarray(y))) ** 2))
    r2 = 1.0 - ss_res / max(ss_tot, 1e-12)
    p = fam.n_params
    adj_r2 = 1.0 - (1.0 - r2) * (n - 1) / max(n - p - 1, 1)
    return FitResult(
        family=family,
        params=np.asarray(params),
        rmse=rmse,
        mse=mse,
        r2=r2,
        adj_r2=adj_r2,
        residuals=resid,
        converged=bool(np.isfinite(rmse)),
    )


def fit_best_family(cpu, mem, y, **kw) -> Dict[str, FitResult]:
    """Fit all Table-I families; caller compares RMSE (Table I reproduction)."""
    return {name: fit_family(name, cpu, mem, y, **kw) for name in FAMILIES}


# ----------------------------------------------------------------------------
# Sensitivity (the quantity the paper's allocator exploits)
# ----------------------------------------------------------------------------
def cpu_sensitivity(params, cpu, mem):
    """-∂d/∂c at the operating point (>0: latency improves with more CPU)."""
    g = jax.grad(lambda c: eq1_latency(params, c, mem))(jnp.asarray(cpu, jnp.float64))
    return -g


def mem_sensitivity(params, cpu, mem):
    """-∂d/∂m at the operating point."""
    g = jax.grad(lambda m: eq1_latency(params, cpu, m))(jnp.asarray(mem, jnp.float64))
    return -g


def validate_eq1_shape(params) -> dict:
    """Checks the fitted Eq.1 surface has the Theorem-2 shape: positive,
    decreasing, convex in both resources over a probe grid."""
    c = jnp.linspace(0.25, 8.0, 64, dtype=jnp.float64)
    m = jnp.linspace(0.15, 1.0, 64, dtype=jnp.float64)
    C, M = jnp.meshgrid(c, m)
    d = eq1_latency(jnp.asarray(params), C, M)
    dc = jax.vmap(jax.vmap(jax.grad(lambda cc, mm: eq1_latency(params, cc, mm), 0)))(C, M)
    dm = jax.vmap(jax.vmap(jax.grad(lambda cc, mm: eq1_latency(params, cc, mm), 1)))(C, M)
    d2c = jax.vmap(jax.vmap(jax.grad(jax.grad(lambda cc, mm: eq1_latency(params, cc, mm), 0), 0)))(C, M)
    d2m = jax.vmap(jax.vmap(jax.grad(jax.grad(lambda cc, mm: eq1_latency(params, cc, mm), 1), 1)))(C, M)
    return {
        "positive": bool(jnp.all(d > 0)),
        "decreasing_cpu": bool(jnp.all(dc < 0)),
        "decreasing_mem": bool(jnp.all(dm < 0)),
        "convex_cpu": bool(jnp.all(d2c > 0)),
        "convex_mem": bool(jnp.all(d2m > 0)),
    }
