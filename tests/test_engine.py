"""Batched allocation engine: batched-vs-serial parity, masking, packing."""
import numpy as np
import pytest

from repro.core.batch_eval import pack_apps
from repro.core.engine import (
    PackedApps,
    as_packed,
    find_feasible_start_batch,
    ideal_configs_batch,
    p1_solve_batch,
    sp1_solve_batch,
)
from repro.core.problem import ServerCaps, service_rate
from repro.core.profiler import make_paper_apps
from repro.core.solvers import p1_solve, p1_solve_scipy, sp1_solve, sp2_ternary

CAPS = ServerCaps(r_cpu=30.0, r_mem=10.0)
APPS = make_paper_apps(lam=(8, 7, 10, 15), fitted=False)


def test_packed_apps_matches_apps():
    packed = PackedApps.from_apps(APPS)
    assert packed.M == len(APPS)
    for i, a in enumerate(APPS):
        assert packed.lam[i] == a.lam
        assert packed.xbar[i] == a.xbar
        assert tuple(packed.kappa[i]) == a.kappa
        assert packed.r_min[i] == a.r_min and packed.r_max[i] == a.r_max
        assert packed.cpu_min[i] == a.cpu_min and packed.cpu_max[i] == a.cpu_max
    # the historical batch_eval entry point serves the same packing
    d = pack_apps(APPS)
    assert set(d) >= {"kappa", "lam", "xbar", "r_min", "r_max", "cpu_min"}
    np.testing.assert_array_equal(np.asarray(d["lam"]), packed.lam)
    assert as_packed(packed) is packed


# Scenarios: (caps, batch of container-count rows). Each batch mixes feasible
# rows with an infeasible one (memory demand alone blows the budget).
SCENARIOS = [
    (CAPS, [[6, 7, 3, 7], [5, 7, 3, 7], [6, 6, 3, 7], [40, 40, 40, 40]]),
    (ServerCaps(28.0, 9.0), [[5, 6, 3, 6], [5, 6, 4, 6], [30, 30, 30, 30]]),
    (ServerCaps(120.0, 40.0), [[8, 10, 4, 9], [7, 10, 4, 9], [8, 9, 4, 9], [80, 80, 80, 80]]),
]


@pytest.mark.parametrize("caps,rows", SCENARIOS)
def test_batched_p1_matches_serial(caps, rows):
    n_batch = np.asarray(rows, dtype=float)
    batch = p1_solve_batch(APPS, caps, n_batch, 1.4, 0.2)
    for b, n_row in enumerate(rows):
        serial = p1_solve(APPS, caps, n_row, 1.4, 0.2)
        assert bool(batch.converged[b]) == serial.converged, n_row
        if not serial.converged:
            assert not np.isfinite(batch.utility[b])
            continue
        assert batch.utility[b] == pytest.approx(serial.utility, rel=1e-6)
        np.testing.assert_allclose(batch.r_cpu[b], serial.r_cpu, rtol=1e-5)
        np.testing.assert_allclose(batch.r_mem[b], serial.r_mem, rtol=1e-5)


def test_batched_p1_all_refinement_neighbors():
    """The CRMS hot path: all 2M neighbor moves of one refinement iteration in
    a single batched solve must match per-move serial solves."""
    n0 = np.array([6, 7, 3, 7])
    M = len(APPS)
    moves = [(i, d) for i in range(M) for d in (-1, +1) if n0[i] + d >= 1]
    n_cands = np.stack([n0 + d * np.eye(M, dtype=int)[i] for i, d in moves]).astype(float)
    batch = p1_solve_batch(APPS, CAPS, n_cands, 1.4, 0.2)
    assert len(moves) == 2 * M
    for b in range(len(moves)):
        serial = p1_solve(APPS, CAPS, n_cands[b], 1.4, 0.2)
        assert bool(batch.converged[b]) == serial.converged, moves[b]
        if serial.converged:
            assert batch.utility[b] == pytest.approx(serial.utility, rel=1e-6)


def test_refine_profile_matches_reference():
    """The tuned barrier schedule CRMS refinement runs on must stay within
    1e-6 relative utility of the reference schedule (it measures ~1e-9)."""
    n0 = np.array([6, 7, 3, 7])
    M = len(APPS)
    n_cands = np.stack(
        [n0 + d * np.eye(M, dtype=int)[i] for i in range(M) for d in (-1, +1)]
    ).astype(float)
    ref = p1_solve_batch(APPS, CAPS, n_cands, 1.4, 0.2, profile="reference")
    fast = p1_solve_batch(APPS, CAPS, n_cands, 1.4, 0.2, profile="refine")
    np.testing.assert_array_equal(ref.converged, fast.converged)
    conv = ref.converged
    np.testing.assert_allclose(fast.utility[conv], ref.utility[conv], rtol=1e-6)


# Both paper nodes (caps, λ) and counts CRMS settles on there; "count_17"
# moves around a row with 16 containers, so one move reaches 17 (width 32).
PAPER_NODES = {
    "paper_node": (ServerCaps(30.0, 10.0), (8, 7, 10, 15), [6, 7, 3, 7]),
    "paper_sufficient": (ServerCaps(120.0, 40.0), (6, 6, 6, 6), [6, 8, 3, 5]),
}


def _moves(n0):
    M = len(n0)
    return np.stack([np.asarray(n0) + d * np.eye(M, dtype=int)[i]
                     for i in range(M) for d in (-1, +1)]).astype(float)


@pytest.mark.parametrize("node", PAPER_NODES)
@pytest.mark.parametrize("case,tail_q", [
    ("reference_row", 0.0), ("refine_moves", 0.0),
    ("reference_row", 0.95), ("refine_moves", 0.95), ("count_17", 0.0),
])
def test_derived_erlang_width_matches_full_width(node, case, tail_q):
    """The width p1_solve_batch derives from its counts (16 or 32 here)
    leaves every result bit-identical to the full MAX_SERVERS = 512 width:
    masked recurrence steps are identities."""
    caps, lam, n0 = PAPER_NODES[node]
    apps = make_paper_apps(lam=lam, fitted=False)
    if case == "reference_row":
        n_batch, profile = np.asarray([n0], dtype=float), "reference"
    elif case == "refine_moves":
        n_batch, profile = _moves(n0), "refine"
    else:
        n_batch, profile = _moves([n0[0], 16, *n0[2:]]), "refine"
    derived = p1_solve_batch(apps, caps, n_batch, 1.4, 0.2, profile=profile, tail_q=tail_q)
    full = p1_solve_batch(apps, caps, n_batch, 1.4, 0.2, profile=profile, tail_q=tail_q,
                          max_servers=512)
    assert derived.converged.any()
    if case == "count_17":
        assert derived.converged[n_batch[:, 1] == 17].all()
    for key in ("r_cpu", "r_mem", "utility", "converged"):
        np.testing.assert_array_equal(getattr(derived, key), getattr(full, key))


def test_feasible_start_batch_masks_infeasible_rows():
    n_batch = np.asarray([[6, 7, 3, 7], [80, 80, 80, 80]], dtype=float)
    x0, ok = find_feasible_start_batch(APPS, CAPS, n_batch)
    assert ok[0] and not ok[1]
    M = len(APPS)
    c0, m0 = x0[0, :M], x0[0, M:]
    # the feasible row's start is a strict interior point
    assert float(np.sum(n_batch[0] * c0)) < CAPS.r_cpu
    assert float(np.sum(n_batch[0] * m0)) < CAPS.r_mem
    for a, c, m in zip(APPS, c0, m0):
        assert a.r_min <= m <= a.r_max
        assert c > a.cpu_min


def test_p1_solve_vs_scipy_cross_check():
    """Interior-point (batched engine) vs the paper's own SLSQP solver."""
    caps = ServerCaps(34.0, 11.0)
    n = [8, 9, 3, 7]
    res = p1_solve(APPS, caps, n, 1.4, 0.2)
    res_sp = p1_solve_scipy(APPS, caps, n, 1.4, 0.2)
    assert res.converged and res_sp.converged
    assert res.utility <= res_sp.utility * 1.01 + 1e-6
    np.testing.assert_allclose(res.r_mem, res_sp.r_mem, rtol=0.05)


def test_sp1_batch_matches_serial():
    c_batch, m_batch = sp1_solve_batch(APPS, CAPS, 1.4, 0.2)
    for i, app in enumerate(APPS):
        c_star, m_star = sp1_solve(app, CAPS, 1.4, 0.2)
        assert c_batch[i] == pytest.approx(c_star, rel=1e-9), app.name
        assert m_batch[i] == pytest.approx(m_star), app.name


def test_ideal_configs_batch_matches_serial_algorithm1():
    c_b, m_b, n_b, mu_b = ideal_configs_batch(APPS, CAPS, 1.4, 0.2)
    for i, app in enumerate(APPS):
        c_star, m_star = sp1_solve(app, CAPS, 1.4, 0.2)
        mu_star = float(service_rate(app, c_star, m_star))
        n_star = sp2_ternary(app, CAPS, 1.4, 0.2, mu_star, c_star, m_star)
        assert mu_b[i] == pytest.approx(mu_star, rel=1e-9), app.name
        assert int(n_b[i]) == n_star, app.name


def test_crms_warm_start_quasi_dynamic():
    """Warm-started re-optimization stays feasible/stable and reuses the mix."""
    from repro.core.crms import crms

    caps = ServerCaps(34.0, 11.0)
    cold = crms(APPS, caps, 1.4, 0.2)
    drifted = [a.with_lam(a.lam * 1.2) for a in APPS]
    warm = crms(drifted, caps, 1.4, 0.2, warm=cold)
    assert warm.feasible and warm.stable
    stages = [h["stage"] for h in warm.meta["history"]]
    assert stages[0] == "warm_start" and "p1_warm" in stages
    # warm result must not be worse than a cold re-optimization (here the
    # refinement converges to the same point)
    cold2 = crms(drifted, caps, 1.4, 0.2)
    assert warm.utility <= cold2.utility * 1.05 + 1e-9
