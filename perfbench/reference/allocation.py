"""Plain reference for one edge node's allocation (the paper's Sec. IV).

Straight transcriptions of the model, in scalar NumPy arithmetic of one
chosen precision, with nothing taken from the program under test:

* Eq. (1): per-image latency d = k1 / (1 - exp(-k2 c)) + exp(k3 / m)  [ms];
  Eq. (6): service rate mu = 1000 / (xbar d)  [1/s].
* M/M/N mean response Ws from the Erlang-C formula, the sum over k < N of
  a^k / k! taken in log space.
* Eq. (8): U = sum_i alpha Ws_i + beta dP_i / lam_i, dP_i = span n_i c_i / R_cpu.
* Feasibility (Eqs. 9-11) and stability (lam_i < n_i mu_i).
* The quasi-dynamic rule of Sec. V-B: re-plan on the first request, on a
  change of caps, or when some app's rate drifted past the threshold
  relative to the rates of the last re-plan.

``dtype`` is float64 for the reference and float32 for the lower-precision
control the benchmark's limits are set against.
"""
from __future__ import annotations

import math

import numpy as np

FEAS_RTOL = 1e-9  # budget and box constraints, relative: the program's
# quotas round-trip through emulated float64 on the chip


def latency_ms(kappa, c, m, dtype=np.float64):
    k1, k2, k3 = (dtype(v) for v in kappa)
    c, m = dtype(c), dtype(m)
    one = dtype(1.0)
    return k1 / (one - np.exp(-k2 * c)) + np.exp(k3 / m)


def service_rate(app: dict, c, m, dtype=np.float64):
    return dtype(1000.0) / (dtype(app["xbar"]) * latency_ms(app["kappa"], c, m, dtype))


def erlang_ws(n: int, lam, mu, dtype=np.float64):
    """Mean response time of an M/M/n queue (inf when lam >= n mu)."""
    lam, mu = dtype(lam), dtype(mu)
    nn = dtype(n)
    a = lam / mu
    rho = a / nn
    if not rho < 1.0:
        return dtype(np.inf)
    one = dtype(1.0)
    log_a = np.log(a)
    log_fact = dtype(0.0)  # log k!
    head = []
    for k in range(int(n)):
        if k:
            log_fact = log_fact + np.log(dtype(k))
        head.append(dtype(k) * log_a - log_fact)
    log_nfact = log_fact + np.log(nn)
    tail = nn * log_a - log_nfact - np.log(one - rho)
    top = max(max(head), tail)
    total = dtype(0.0)
    for h in head:
        total = total + np.exp(h - top)
    log_p0 = -(top + np.log(total + np.exp(tail - top)))
    log_lq = nn * log_a - log_nfact + np.log(rho) - dtype(2.0) * np.log(one - rho) + log_p0
    return (np.exp(log_lq) + a) / lam


def evaluate(cfg: dict, lam, r_cpu_cap: float, r_mem_cap: float, n, c, m,
             dtype=np.float64) -> dict:
    """Ws per app, the objective, and the constraint checks of one allocation."""
    apps = cfg["apps"]
    span = dtype(cfg["power_w"]["p_full"]) - dtype(cfg["power_w"]["p_idle"])
    alpha, beta = dtype(cfg["alpha"]), dtype(cfg["beta"])
    ws, util, stable = [], dtype(0.0), True
    for app, lam_i, n_i, c_i, m_i in zip(apps, lam, n, c, m):
        mu = service_rate(app, c_i, m_i, dtype)
        w = erlang_ws(int(n_i), lam_i, mu, dtype)
        stable &= bool(dtype(lam_i) < dtype(n_i) * mu)
        dp = span * dtype(n_i) * dtype(c_i) / dtype(r_cpu_cap)
        util = util + alpha * w + beta * dp / dtype(lam_i)
        ws.append(w)
    n = np.asarray(n, dtype=float)
    c = np.asarray(c, dtype=float)
    m = np.asarray(m, dtype=float)
    feasible = (
        float(np.sum(n * c)) <= r_cpu_cap * (1.0 + FEAS_RTOL)
        and float(np.sum(n * m)) <= r_mem_cap * (1.0 + FEAS_RTOL)
        and all(
            a["r_min"] * (1.0 - FEAS_RTOL) <= mi <= a["r_max"] * (1.0 + FEAS_RTOL)
            for a, mi in zip(apps, m)
        )
        and bool(np.all(c > 0.0))
        and bool(np.all(n >= 1))
    )
    return {"ws": np.asarray(ws, dtype=float), "utility": float(util),
            "feasible": bool(feasible), "stable": bool(stable)}


class QuasiDynamicRule:
    """Which requests must re-plan (Sec. V-B), and at which rates and caps the
    allocation served for each request was solved."""

    def __init__(self, threshold: float):
        self.threshold = float(threshold)
        self.solved = None  # (lam, r_cpu, r_mem) of the last re-plan

    def observe(self, lam, r_cpu: float, r_mem: float) -> tuple[bool, tuple]:
        lam = np.asarray(lam, dtype=float)
        replan = (
            self.solved is None
            or (r_cpu, r_mem) != self.solved[1:]
            or bool(np.any(np.abs(lam - self.solved[0]) / np.maximum(self.solved[0], 1e-9)
                           > self.threshold))
        )
        if replan:
            self.solved = (lam, float(r_cpu), float(r_mem))
        return replan, self.solved


def rel_gap(got, ref) -> float:
    """Widest relative gap of ``got`` from ``ref`` (inf where one is not finite
    and the other is)."""
    got = np.asarray(got, dtype=float)
    ref = np.asarray(ref, dtype=float)
    if got.shape != ref.shape:
        return math.inf
    both_inf = np.isinf(got) & np.isinf(ref)
    with np.errstate(invalid="ignore", divide="ignore"):
        gap = np.abs(got - ref) / np.abs(ref)
    gap = np.where(both_inf, 0.0, gap)
    return float(np.max(np.nan_to_num(gap, nan=math.inf), initial=0.0))


# --- Problem P1 at fixed container counts (the paper's Eq. 26) -------------
#
# min over c, m of Eq. (8) subject to n.c <= R_cpu, n.m <= R_mem,
# r_min <= m <= r_max, c >= cpu_min and stability, solved by a plain
# log-barrier method: Newton steps on the dense (2M x 2M) system with exact
# first derivatives (the Erlang-C term by complex step, Eq. (1) in closed
# form) and a backtracking line search. A strictly stable start comes from a
# phase-1 barrier that drives max_i rho_i below 1. In float64 the barrier
# runs to t = 1e11 (duality gap (3M+2)/t); the float32 control runs the same
# schedule in float32 and stalls where its arithmetic does.

P1_T_MAX = 1e11
_NEWTON_TOL = {np.float64: 1e-13, np.float32: 1e-6}


class _P1:
    """One instance of Problem P1 in one precision, arrays over the apps."""

    def __init__(self, cfg: dict, lam, r_cpu_cap: float, r_mem_cap: float, n, dtype):
        apps = cfg["apps"]
        dt = self.dt = dtype
        self.ct = np.complex128 if dt is np.float64 else np.complex64

        def arr(values):
            return np.asarray(values, dtype=dt)

        self.k1, self.k2, self.k3 = (arr([a["kappa"][j] for a in apps]) for j in range(3))
        self.xbar = arr([a["xbar"] for a in apps])
        self.lam = arr(lam)
        self.n_int = np.asarray(n, dtype=int)
        self.n = arr(self.n_int)
        self.r_min, self.r_max = arr([a["r_min"] for a in apps]), arr([a["r_max"] for a in apps])
        self.c_min = arr([a["cpu_min"] for a in apps])
        self.cap_c, self.cap_m = dt(r_cpu_cap), dt(r_mem_cap)
        span = dt(cfg["power_w"]["p_full"]) - dt(cfg["power_w"]["p_idle"])
        self.alpha = dt(cfg["alpha"])
        self.cost_c = dt(cfg["beta"]) * span * self.n / (self.cap_c * self.lam)  # per core
        self.d_max = dt(1000.0) * self.n / (self.lam * self.xbar)  # latency at rho = 1, ms
        big = int(self.n_int.max()) + 1
        log_k = np.log(arr(np.arange(1, big + 1)))
        self.log_fact = np.concatenate([np.zeros(1, dt), np.cumsum(log_k)])  # log k!
        self.k = np.arange(big)
        self.head = self.k[None, :] < self.n_int[:, None]
        self.m_ = len(apps)

    # Eq. (1) split as d = f(c) + g(m), with first and second derivatives
    def f(self, c):
        e = np.exp(-self.k2 * c)
        one = self.dt(1.0)
        return (self.k1 / (one - e), -self.k1 * self.k2 * e / (one - e) ** 2,
                self.k1 * self.k2 ** 2 * e * (one + e) / (one - e) ** 3)

    def g(self, m):
        e = np.exp(self.k3 / m)
        k3 = self.k3
        return e, -k3 / m ** 2 * e, e * (k3 ** 2 / m ** 4 + self.dt(2.0) * k3 / m ** 3)

    def ws_of_latency(self, d):
        """Erlang-C mean response of every app at per-image latency d (ms);
        d may be complex (complex step)."""
        a = self.lam * self.xbar * d / self.dt(1000.0)
        rho = a / self.n
        log_a = np.log(a)
        one = self.dt(1.0)
        lf = self.log_fact
        head = self.k[None, :] * log_a[:, None] - lf[self.k][None, :]
        tail = self.n * log_a - lf[self.n_int] - np.log(one - rho)
        top = np.maximum(np.max(np.where(self.head, head.real, -np.inf), axis=1), tail.real)
        total = np.sum(np.where(self.head, np.exp(head - top[:, None]), 0), axis=1)
        log_p0 = -(top + np.log(total + np.exp(tail - top)))
        log_lq = (self.n * log_a - lf[self.n_int] + np.log(rho)
                  - self.dt(2.0) * np.log(one - rho) + log_p0)
        return (np.exp(log_lq) + a) / self.lam

    def ws_derivs(self, d):
        """W(d), W'(d) by complex step, W''(d) by central differences of W'."""
        h = d * self.dt(1e-20 if self.dt is np.float64 else 1e-10)
        step = d * self.dt(1e-4 if self.dt is np.float64 else 1e-3)

        def first(x):
            z = x.astype(self.ct) + 1j * h.astype(self.ct)
            return (self.ws_of_latency(z).imag / h).astype(self.dt)

        w = self.ws_of_latency(d)
        return w, first(d), (first(d + step) - first(d - step)) / (self.dt(2.0) * step)

    def split(self, x):
        return x[: self.m_], x[self.m_:]

    def box_slacks(self, x):
        c, m = self.split(x)
        return np.concatenate([[self.cap_c - self.n @ c, self.cap_m - self.n @ m],
                               m - self.r_min, self.r_max - m, c - self.c_min])

    def latency(self, x):
        c, m = self.split(x)
        return self.f(c)[0] + self.g(m)[0]

    def utility(self, x):
        c, _ = self.split(x)
        d = self.latency(x)
        if not np.all(d < self.d_max):
            return self.dt(np.inf)
        return np.sum(self.alpha * self.ws_of_latency(d) + self.cost_c * c)

    def _barrier_terms(self, x, grad, hess):
        """Add -sum log(slack) of budgets and boxes to grad and hess."""
        mm = self.m_
        s = self.box_slacks(x)
        n = self.n
        one = self.dt(1.0)
        grad[:mm] += n / s[0] - one / s[2 + 2 * mm:]
        grad[mm:] += n / s[1] - one / s[2:2 + mm] + one / s[2 + mm:2 + 2 * mm]
        hess[:mm, :mm] += np.outer(n, n) / s[0] ** 2 + np.diag(one / s[2 + 2 * mm:] ** 2)
        hess[mm:, mm:] += np.outer(n, n) / s[1] ** 2 + np.diag(
            one / s[2:2 + mm] ** 2 + one / s[2 + mm:2 + 2 * mm] ** 2)
        return -np.sum(np.log(s))

    def p1_model(self, x, t):
        """Value, gradient and Hessian of t * U(x) - sum log(slack)."""
        mm = self.m_
        c, m = self.split(x)
        fc, fc1, fc2 = self.f(c)
        gm, gm1, gm2 = self.g(m)
        w, w1, w2 = self.ws_derivs(fc + gm)
        a = self.alpha
        grad = np.concatenate([a * w1 * fc1 + self.cost_c, a * w1 * gm1]) * t
        hess = np.zeros((2 * mm, 2 * mm), self.dt)
        idx = np.arange(mm)
        hess[idx, idx] = t * a * (w2 * fc1 ** 2 + w1 * fc2)
        hess[mm + idx, mm + idx] = t * a * (w2 * gm1 ** 2 + w1 * gm2)
        hess[idx, mm + idx] = hess[mm + idx, idx] = t * a * w2 * fc1 * gm1
        value = t * np.sum(a * w + self.cost_c * c) + self._barrier_terms(x, grad, hess)
        return value, grad, hess

    def p1_value(self, x, t):
        s = self.box_slacks(x)
        if not np.all(s > 0):
            return self.dt(np.inf)
        return t * self.utility(x) - np.sum(np.log(s))

    def phase1_model(self, z, t):
        """Barrier model of: min s subject to d_i / d_max_i - 1 <= s, the
        budgets and the boxes; z = (c, m, s)."""
        mm = self.m_
        x, s = z[:-1], z[-1]
        c, m = self.split(x)
        fc, fc1, fc2 = self.f(c)
        gm, gm1, gm2 = self.g(m)
        q = s - ((fc + gm) / self.d_max - self.dt(1.0))
        grad = np.zeros(2 * mm + 1, self.dt)
        hess = np.zeros((2 * mm + 1, 2 * mm + 1), self.dt)
        grad[-1] = t
        value = t * s + self._barrier_terms(x, grad[:-1], hess[:-1, :-1])
        v = np.zeros((mm, 2 * mm + 1), self.dt)  # gradients of q_i
        idx = np.arange(mm)
        v[idx, idx] = -fc1 / self.d_max
        v[idx, mm + idx] = -gm1 / self.d_max
        v[:, -1] = self.dt(1.0)
        grad -= (v / q[:, None]).sum(axis=0)
        hess += (v / q[:, None]).T @ (v / q[:, None])
        hess[idx, idx] += fc2 / self.d_max / q
        hess[mm + idx, mm + idx] += gm2 / self.d_max / q
        return value - np.sum(np.log(q)), grad, hess

    def phase1_value(self, z, t):
        x, s = z[:-1], z[-1]
        q = s - (self.latency(x) / self.d_max - self.dt(1.0))
        b = self.box_slacks(x)
        if not (np.all(b > 0) and np.all(q > 0)):
            return self.dt(np.inf)
        return t * s - np.sum(np.log(b)) - np.sum(np.log(q))


def _newton(model, value, z, t, tol, max_steps=60):
    """Damped Newton on one barrier function from a strictly feasible z."""
    dt = z.dtype.type
    for _ in range(max_steps):
        v0, g, h = model(z, t)
        try:
            dz = -np.linalg.solve(h, g)
        except np.linalg.LinAlgError:
            return z
        dec = -(g @ dz)
        if not dec > 2 * tol:
            return z
        # below the rounding of the barrier's value, a decrease cannot be
        # seen: take the largest feasible step and stop
        flat = dec <= 64 * np.finfo(dt).eps * abs(v0)
        step = dt(1.0)
        while step > 1e-12:
            v1 = value(z + step * dz, t)
            if (flat and np.isfinite(v1)) or v1 <= v0 - dt(0.25) * step * dec:
                break
            step = dt(step * 0.5)
        else:
            return z
        z = (z + step * dz).astype(z.dtype)
        if flat:
            return z
    return z


def solve_p1(cfg: dict, lam, r_cpu_cap: float, r_mem_cap: float, n,
             dtype=np.float64) -> dict:
    """The optimal quotas at container counts ``n``: ``{"utility", "c", "m"}``,
    utility inf where no stable allocation fits the caps."""
    p = _P1(cfg, lam, r_cpu_cap, r_mem_cap, n, dtype)
    none = {"utility": np.inf, "c": None, "m": None}
    dt = dtype
    if not (p.n @ p.r_min < p.cap_m and p.n @ p.c_min < p.cap_c and np.all(p.n >= 1)):
        return none
    frac = min(dt(0.5), dt(0.5) * (p.cap_m - p.n @ p.r_min) / (p.n @ (p.r_max - p.r_min)))
    m0 = p.r_min + frac * (p.r_max - p.r_min)
    c0 = p.c_min + dt(0.5) * (p.cap_c - p.n @ p.c_min) / np.sum(p.n)
    x = np.concatenate([c0, m0]).astype(dt)
    s0 = np.max(p.latency(x) / p.d_max - dt(1.0)) + dt(1.0)
    z = np.concatenate([x, [s0]]).astype(dt)
    tol = _NEWTON_TOL[dt]
    t = dt(1.0)
    while z[-1] >= dt(-1e-3) and t <= 1e8:
        z = _newton(p.phase1_model, p.phase1_value, z, t, tol)
        t = dt(t * 10.0)
    if not z[-1] < 0:
        return none
    x = z[:-1]
    t = dt(1.0)
    while t <= P1_T_MAX:
        x = _newton(p.p1_model, p.p1_value, x, t, tol)
        t = dt(t * 10.0)
    c, m = p.split(x)
    return {"utility": float(p.utility(x)), "c": c.astype(float), "m": m.astype(float)}
