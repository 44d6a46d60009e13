"""Candidate-batched CRN rollouts (``des_vector.rollout_candidates``,
DESIGN.md §14).

The engine simulates B candidate allocations × M apps in ONE batched
Kiefer–Wolfowitz scan over SHARED arrival/service draws. Two contracts are
pinned here: (1) with B = 1 the sample path is IDENTICAL to the sequential
vector engine (same streams, same recurrence — parity ≤ 1e-12, far stronger
than Monte-Carlo agreement), and (2) the shared draws make candidate
comparisons paired — pathwise monotone in server count and lower-variance
than independent-seed comparisons, which is what lets SHORT rollouts rank
refinement moves."""
import numpy as np
import pytest

from repro.core.des import FleetSimulator
from repro.core.des_vector import (
    _CRN_CACHE,
    _CRN_CACHE_MAX,
    rollout_candidates,
)

MIX = [("a", 8.0, 1.8, 6), ("b", 15.0, 3.3, 7), ("c", 2.0, 5.0, 1)]
NAMES = [m[0] for m in MIX]
LAM = np.array([m[1] for m in MIX])
MU = np.array([[m[2] for m in MIX]])  # (1, M)
NS = np.array([[m[3] for m in MIX]])  # (1, M)


def _sequential_means(mu_row, n_row, horizon, seed):
    """Per-app response means from the sequential vector engine on the SAME
    (seed, name)-keyed streams the rollout consumes."""
    sim = FleetSimulator(seed=seed, engine="vector")
    for i, name in enumerate(NAMES):
        sim.add_app(name, float(LAM[i]), float(mu_row[i]), int(n_row[i]))
    sim.run_until(horizon)
    sim.drain()
    return [sim.responses(name, 0.0, horizon) for name in NAMES]


# ----------------------------------------------------------------------------
# B=1 parity with the sequential vector engine (the CRN anchor)
# ----------------------------------------------------------------------------
def test_b1_parity_with_sequential_vector_engine():
    horizon = 200.0
    ro = rollout_candidates(NAMES, LAM, MU, NS, horizon, seed=7)
    resp = _sequential_means(MU[0], NS[0], horizon, seed=7)
    for i, r in enumerate(resp):
        assert ro.n_arrivals[i] == r.shape[0]  # identical arrival streams
        assert ro.mean_s[0, i] == pytest.approx(float(np.mean(r)), rel=1e-12)
        assert ro.p95_s[0, i] == pytest.approx(
            float(np.percentile(r, 95)), rel=1e-12
        )
    pooled = np.concatenate(resp)
    assert ro.pooled_mean_s[0] == pytest.approx(float(np.mean(pooled)), rel=1e-12)
    assert ro.pooled_p95_s[0] == pytest.approx(
        float(np.percentile(pooled, 95)), rel=1e-12
    )
    assert ro.n_events == int(ro.n_arrivals.sum())


def test_warmup_scores_only_post_warmup_arrivals():
    horizon, warm = 200.0, 50.0
    ro = rollout_candidates(NAMES, LAM, MU, NS, horizon, seed=7, warmup_s=warm)
    resp = _sequential_means(MU[0], NS[0], horizon, seed=7)
    sim = FleetSimulator(seed=7, engine="vector")
    for i, name in enumerate(NAMES):
        sim.add_app(name, float(LAM[i]), float(MU[0, i]), int(NS[0, i]))
    sim.run_until(horizon)
    sim.drain()
    for i, name in enumerate(NAMES):
        late = sim.responses(name, warm, horizon)
        assert ro.n_scored[i] == late.shape[0] < resp[i].shape[0]
        assert ro.mean_s[0, i] == pytest.approx(float(np.mean(late)), rel=1e-12)


def test_numpy_backend_matches_jax_backend():
    mu_all = np.vstack([MU[0], MU[0] * 1.1, MU[0] * 0.95])
    n_all = np.vstack([NS[0], NS[0] + 1, np.maximum(NS[0] - 1, 1)])
    a = rollout_candidates(NAMES, LAM, mu_all, n_all, 120.0, seed=3,
                           backend="jax")
    b = rollout_candidates(NAMES, LAM, mu_all, n_all, 120.0, seed=3,
                           backend="numpy")
    np.testing.assert_allclose(a.mean_s, b.mean_s, rtol=1e-12)
    np.testing.assert_allclose(a.p95_s, b.p95_s, rtol=1e-12)


# ----------------------------------------------------------------------------
# The paired-CRN property (why short rollouts can rank moves)
# ----------------------------------------------------------------------------
def test_paired_crn_is_pathwise_monotone_and_lower_variance():
    """Identical draws make 'one more server' a pathwise improvement (the KW
    workload vector with n+1 servers dominates the n-server one customer by
    customer), so EVERY paired difference has the right sign — and the
    paired difference estimator's variance drops well below independent-seed
    comparisons of the same two candidates."""
    mu2 = np.array([[2.0], [2.0]])
    n2 = np.array([[4], [5]])
    d_shared, d_indep = [], []
    for s in range(16):
        ro = rollout_candidates(["a"], [6.0], mu2, n2, 400.0, seed=s,
                                warmup_s=20.0)
        d_shared.append(ro.mean_s[0, 0] - ro.mean_s[1, 0])
        ra = rollout_candidates(["a"], [6.0], mu2[:1], n2[:1], 400.0, seed=s,
                                warmup_s=20.0)
        rb = rollout_candidates(["a"], [6.0], mu2[1:], n2[1:], 400.0,
                                seed=s + 500, warmup_s=20.0)
        d_indep.append(ra.mean_s[0, 0] - rb.mean_s[0, 0])
    assert np.all(np.asarray(d_shared) > 0.0)  # pathwise, not just on average
    assert np.var(d_indep) > 1.5 * np.var(d_shared)


# ----------------------------------------------------------------------------
# Masking, degenerate lanes, laziness, memoization
# ----------------------------------------------------------------------------
def test_zero_server_candidate_scores_inf_not_garbage():
    mu_all = np.vstack([MU[0], MU[0]])
    n_all = np.vstack([NS[0], NS[0]])
    n_all[1, 0] = 0  # candidate 1 starves app "a"
    ro = rollout_candidates(NAMES, LAM, mu_all, n_all, 80.0, seed=1)
    assert np.isfinite(ro.mean_s[0]).all()
    assert ro.mean_s[1, 0] == np.inf and ro.p95_s[1, 0] == np.inf
    assert np.isfinite(ro.mean_s[1, 1:]).all()  # other apps unaffected
    assert ro.pooled_mean_s[1] == np.inf  # never-served taints the pooled view
    assert np.isfinite(ro.pooled_mean_s[0])


def test_no_arrivals_is_nan_not_inf():
    ro = rollout_candidates(["idle"], [1e-9], np.array([[2.0]]),
                            np.array([[1]]), 1.0, seed=0)
    assert ro.n_events == 0
    assert np.isnan(ro.mean_s).all() and np.isnan(ro.p95_s).all()
    assert np.isnan(ro.pooled_mean_s).all()


def test_stats_are_lazy_and_cached():
    ro = rollout_candidates(NAMES, LAM, MU, NS, 60.0, seed=2)
    assert ro._raw is not None  # simulation done, scoring deferred
    m1 = ro.mean_s
    assert ro._raw is None  # first access consumed (and released) the raws
    assert ro.mean_s is m1  # cached, not recomputed
    assert np.isfinite(ro.p95_s).all()
    assert np.isfinite(ro.pooled_p95_s).all()


def test_crn_cache_reuses_draws_and_stays_bounded():
    a = rollout_candidates(NAMES, LAM, MU, NS, 60.0, seed=5)
    b = rollout_candidates(NAMES, LAM, MU, NS, 60.0, seed=5)
    np.testing.assert_array_equal(a.mean_s, b.mean_s)  # same memoized world
    for s in range(2 * _CRN_CACHE_MAX):  # distinct keys evict oldest-first
        rollout_candidates(NAMES, LAM, MU, NS, 60.0, seed=100 + s)
    assert len(_CRN_CACHE) <= _CRN_CACHE_MAX


def test_validation_errors():
    with pytest.raises(ValueError, match=r"\(B, M\)"):
        rollout_candidates(NAMES, LAM, MU[0], NS[0], 10.0)  # 1-D shapes
    with pytest.raises(ValueError, match=r"\(B, M\)"):
        rollout_candidates(NAMES, LAM, MU, NS[:, :2], 10.0)
    with pytest.raises(ValueError, match="names"):
        rollout_candidates(NAMES[:2], LAM, MU, NS, 10.0)
    with pytest.raises(ValueError, match="mu"):
        rollout_candidates(NAMES, LAM, 0.0 * MU, NS, 10.0)
    with pytest.raises(ValueError, match="warmup"):
        rollout_candidates(NAMES, LAM, MU, NS, 10.0, warmup_s=10.0)
    with pytest.raises(ValueError, match="backend"):
        rollout_candidates(NAMES, LAM, MU, NS, 10.0, backend="fortran")
