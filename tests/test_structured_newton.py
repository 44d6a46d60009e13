"""Structured O(M) Newton path and grid-seeded phase-1 (ISSUE 2).

Pins, headless and hypothesis-free:
  - closed-form Erlang-C Ws derivatives vs autodiff (queueing.erlang_ws_derivs)
  - the analytic block-diagonal + Woodbury Newton direction vs the dense
    autodiff-Hessian solve at the same point
  - structured-vs-dense converged-utility parity at M = 8 / 32 / 64
  - grid-seeded phase-1 starts never worsening (and possibly rescuing)
    converged utility vs the waterfill
  - phase-1 honesty: every ok row is a strictly feasible interior point
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import queueing
from repro.core.engine import (
    PackedApps,
    _newton_direction_structured,
    find_feasible_start_batch,
    grid_seed_chints,
    p1_barrier,
    p1_rho,
    p1_solve_batch,
)
from repro.core.problem import ServerCaps
from repro.core.profiler import make_paper_apps, make_tenant_mix

ALPHA, BETA = 1.4, 0.2


def neighbors(n0):
    M = len(n0)
    return np.stack(
        [n0 + d * np.eye(M, dtype=int)[i] for i in range(M) for d in (-1, +1)]
    ).astype(float)


# ----------------------------------------------------------------------------
# closed-form Erlang derivatives
# ----------------------------------------------------------------------------
@pytest.mark.parametrize(
    "N,lam,mu",
    [
        (7.0, 8.0, 1.4),
        (3.0, 10.0, 3.5),
        (2.0, 0.3, 0.2),
        (40.0, 30.0, 0.8),
        (128.0, 64.0, 0.6),
        (1.0, 0.5, 0.7),
    ],
)
def test_erlang_ws_derivs_match_autodiff(N, lam, mu):
    ws, d1, d2 = queueing.erlang_ws_derivs(N, lam, mu)
    f = lambda m: queueing.erlang_ws(N, lam, m)
    mu64 = jnp.asarray(mu, jnp.float64)
    assert float(ws) == pytest.approx(float(f(mu64)), rel=1e-12)
    assert float(d1) == pytest.approx(float(jax.grad(f)(mu64)), rel=1e-9)
    assert float(d2) == pytest.approx(float(jax.grad(jax.grad(f))(mu64)), rel=1e-9)


def test_erlang_ws_derivs_unstable_is_inf():
    ws, _, _ = queueing.erlang_ws_derivs(2.0, 10.0, 1.0)  # rho = 5
    assert not np.isfinite(float(ws))


# ----------------------------------------------------------------------------
# analytic Newton direction vs dense autodiff solve
# ----------------------------------------------------------------------------
@pytest.mark.parametrize("t", [1.0, 36.0, 6.0**6])
def test_structured_direction_matches_dense_solve(t):
    apps, caps, n0 = make_tenant_mix(8)
    packed = PackedApps.from_apps(apps)
    n_b = np.asarray(n0, dtype=float)[None, :]
    x0, ok = find_feasible_start_batch(packed, caps, n_b)
    assert ok[0]
    x = jnp.asarray(x0[0])
    n = jnp.asarray(n_b[0])
    args = (
        packed.jax_dict,
        n,
        jnp.asarray(float(caps.r_cpu)),
        jnp.asarray(float(caps.r_mem)),
        jnp.asarray(float(caps.power.span)),
        ALPHA,
        BETA,
    )
    val = lambda xx: p1_barrier(xx, jnp.asarray(t), *args)[0]
    g = jax.grad(val)(x)
    H = jax.hessian(val)(x) + 1e-9 * jnp.eye(x.shape[0], dtype=x.dtype)
    dx_dense = jnp.linalg.solve(H, g)
    dx_struct = _newton_direction_structured(x, jnp.asarray(t), *args)
    np.testing.assert_allclose(np.asarray(dx_struct), np.asarray(dx_dense), rtol=1e-6)


# ----------------------------------------------------------------------------
# structured vs dense converged parity (same starts -> utility diff <= 1e-6)
# ----------------------------------------------------------------------------
def _parity_check(M, rows=None, profile="refine"):
    apps, caps, n0 = make_tenant_mix(M)
    packed = PackedApps.from_apps(apps)
    n_cands = neighbors(n0)
    if rows is not None:
        n_cands = n_cands[rows]
    dense = p1_solve_batch(packed, caps, n_cands, ALPHA, BETA, profile=profile, solver="dense")
    struct = p1_solve_batch(
        packed, caps, n_cands, ALPHA, BETA, profile=profile, solver="structured"
    )
    np.testing.assert_array_equal(dense.converged, struct.converged)
    conv = dense.converged
    assert np.any(conv)
    np.testing.assert_allclose(struct.utility[conv], dense.utility[conv], rtol=1e-6)
    np.testing.assert_allclose(struct.r_cpu[conv], dense.r_cpu[conv], rtol=1e-4)
    np.testing.assert_allclose(struct.r_mem[conv], dense.r_mem[conv], rtol=1e-4)


def test_structured_vs_dense_parity_m8():
    _parity_check(8)


def test_structured_vs_dense_parity_m32():
    # a subset of the 64 neighbor moves keeps the dense side affordable
    _parity_check(32, rows=[0, 1, 17, 30, 45, 63])


@pytest.mark.slow
def test_structured_vs_dense_parity_m64():
    _parity_check(64, rows=[0, 1, 33, 66, 95, 127])


# ----------------------------------------------------------------------------
# grid seeding
# ----------------------------------------------------------------------------
CAPS4 = ServerCaps(r_cpu=30.0, r_mem=10.0)
APPS4 = make_paper_apps(lam=(8, 7, 10, 15), fitted=False)


def test_grid_seed_chints_shape_and_bounds():
    packed = PackedApps.from_apps(APPS4)
    n_b = neighbors(np.array([6, 7, 3, 7]))
    hints = grid_seed_chints(packed, CAPS4, n_b, ALPHA, BETA)
    assert hints.shape == n_b.shape
    assert np.all(hints >= packed.cpu_min - 1e-12)
    assert np.all(hints <= packed.cpu_max + 1e-12)
    # one pseudo-row per distinct count: every (b, i) with the same count must
    # get the same hint
    for i in range(hints.shape[1]):
        for cnt in np.unique(n_b[:, i]):
            assert np.unique(hints[n_b[:, i] == cnt, i]).size == 1


@pytest.mark.parametrize("M", [8, 16])
def test_grid_seeded_starts_never_worse(M):
    apps, caps, n0 = make_tenant_mix(M)
    packed = PackedApps.from_apps(apps)
    n_cands = neighbors(n0)
    plain = p1_solve_batch(packed, caps, n_cands, ALPHA, BETA, profile="refine")
    seeded = p1_solve_batch(
        packed, caps, n_cands, ALPHA, BETA, profile="refine", seed_grid=True
    )
    # the hint fallback guarantees seeding never loses feasible rows
    assert np.all(seeded.converged >= plain.converged)
    conv = plain.converged & seeded.converged
    assert np.any(conv)
    assert np.all(seeded.utility[conv] <= plain.utility[conv] * (1 + 1e-6) + 1e-12)


def test_grid_seed_backends_agree():
    packed = PackedApps.from_apps(APPS4)
    n_b = neighbors(np.array([6, 7, 3, 7]))
    h_oracle = grid_seed_chints(packed, CAPS4, n_b, ALPHA, BETA, backend="oracle")
    h_interp = grid_seed_chints(packed, CAPS4, n_b, ALPHA, BETA, backend="interpret")
    # f32 kernel vs f64 oracle may flip near-tied argmin cells; the chosen
    # quotas must still agree for the overwhelming majority of (b, i) slots
    agree = np.isclose(h_oracle, h_interp, rtol=1e-5)
    assert agree.mean() >= 0.9


# ----------------------------------------------------------------------------
# phase-1 honesty
# ----------------------------------------------------------------------------
@pytest.mark.parametrize("M", [8, 16])
def test_phase1_ok_rows_are_strictly_feasible(M):
    apps, caps, n0 = make_tenant_mix(M)
    packed = PackedApps.from_apps(apps)
    n_cands = neighbors(n0)
    x0, ok = find_feasible_start_batch(packed, caps, n_cands)
    assert np.any(ok)
    for b in np.where(ok)[0]:
        x = jnp.asarray(x0[b])
        n = jnp.asarray(n_cands[b])
        _, slacks = p1_barrier(
            x, 1.0, packed.jax_dict, n,
            jnp.asarray(float(caps.r_cpu)), jnp.asarray(float(caps.r_mem)),
            jnp.asarray(float(caps.power.span)), ALPHA, BETA,
        )
        rho = p1_rho(x, packed.jax_dict, n)
        assert np.all(np.asarray(slacks) > 0), b
        assert np.all(np.asarray(rho) < 1.0), b


def test_dense_newton_refused_on_tpu(monkeypatch):
    """The dense Newton's f64 LU solve has no TPU lowering: asking for it on a
    TPU raises a clear error up front instead of failing in the compiler."""
    from repro.core.engine import p1_solve_batch

    apps = make_paper_apps(lam=(8.0, 7.0, 10.0, 15.0), fitted=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(NotImplementedError, match="dense"):
        p1_solve_batch(apps, CAPS4, np.array([[7.0, 8.0, 3.0, 7.0]]), 1.4, 0.2,
                       solver="dense")
