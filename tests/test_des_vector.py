"""CRN-matched parity suite: the Kiefer–Wolfowitz workload-vector engine
(``core/des_vector.py``) against the heapq event oracle on identical draws.

Both engines consume the same chunked ``(seed, name)``-keyed streams, and
FCFS makes service-start order equal arrival order, so for stationary
segments and λ/n-only reconfiguration histories the two engines must be
sample-path IDENTICAL up to float round-off — far stronger than the
Monte-Carlo agreement the acceptance gate asks for. μ-boundary hand-off
(different draw instants by design) is checked statistically against the
analytic model, mirroring tests/test_des.py."""
import numpy as np
import pytest

from repro.core.arrivals import ArrivalSpec, ArrivalStream, estimate_arrival, idc_at, mmpp2
from repro.core.des import FleetSimulator, simulate_mmn
from repro.core.des_vector import VectorFleetSimulator
from repro.core.queueing import erlang_ws_np

BACKENDS = ("numpy", "jax")


def paired_paths(event_sim, vector_sim, name):
    """(arrivals, responses) of both engines sorted by arrival time — the
    event engine logs in completion order, the vector engine in arrival
    order, so pairing must key on the (shared) arrival stream."""
    ce = event_sim._clusters[name]
    te = np.asarray(ce.arr_log)
    re = np.asarray(ce.resp_log)
    oe = np.argsort(te)
    tv, wv, sv = vector_sim._clusters[name].logs()
    ov = np.argsort(tv)
    return te[oe], re[oe], tv[ov], (wv + sv)[ov]


def assert_exact_parity(event_sim, vector_sim, name):
    ta, ra, tb, rb = paired_paths(event_sim, vector_sim, name)
    assert ta.shape == tb.shape  # identical arrival streams, nothing lost
    np.testing.assert_allclose(ta, tb, rtol=1e-9, atol=1e-9)
    np.testing.assert_allclose(ra, rb, rtol=1e-7, atol=1e-9)


# ----------------------------------------------------------------------------
# Dispatch
# ----------------------------------------------------------------------------
def test_engine_dispatch():
    ev = FleetSimulator(seed=0)
    vec = FleetSimulator(seed=0, engine="vector")
    assert ev.engine == "event" and type(ev) is FleetSimulator
    assert vec.engine == "vector" and isinstance(vec, VectorFleetSimulator)
    assert isinstance(vec, FleetSimulator)  # one contract
    with pytest.raises(ValueError):
        FleetSimulator(engine="simpy")
    with pytest.raises(ValueError):
        FleetSimulator(engine="vector", backend="fortran")


# ----------------------------------------------------------------------------
# Stationary-segment parity (the acceptance gate, checked per customer)
# ----------------------------------------------------------------------------
@pytest.mark.parametrize("backend", BACKENDS)
def test_stationary_crn_parity_per_customer(backend):
    ev = FleetSimulator(seed=7)
    vec = FleetSimulator(seed=7, engine="vector", backend=backend)
    for sim in (ev, vec):
        sim.add_app("x", lam=8.0, mu=1.8, n_servers=6)
        sim.add_app("y", lam=15.0, mu=3.3, n_servers=7)
        sim.add_app("z", lam=2.0, mu=5.0, n_servers=1)  # single server lane
        sim.run_until(600.0)
        sim.drain()
    for name in ("x", "y", "z"):
        assert (
            ev._clusters[name].n_arrived == vec._clusters[name].n_arrived
        )  # identical arrival streams
        assert_exact_parity(ev, vec, name)
    # and the means (what the benchmark gates at 2%) are therefore equal
    for name in ("x", "y"):
        me = float(np.mean(ev.responses(name, 0.0, 600.0)))
        mv = float(np.mean(vec.responses(name, 0.0, 600.0)))
        assert mv == pytest.approx(me, rel=1e-6)


def test_vector_matches_analytic():
    s = simulate_mmn(8.0, 1.8, 6, horizon_s=4000.0, warmup_s=400.0, seed=7,
                     engine="vector")
    assert s.mean_response_s == pytest.approx(erlang_ws_np(6, 8.0, 1.8), rel=0.08)
    # sample-path occupancy integrals: utilization tracks rho
    assert s.utilization == pytest.approx(8.0 / (1.8 * 6), rel=0.1)


def test_numpy_backend_matches_jax_backend():
    a = FleetSimulator(seed=3, engine="vector", backend="numpy")
    b = FleetSimulator(seed=3, engine="vector", backend="jax")
    for sim in (a, b):
        sim.add_app("x", lam=9.0, mu=2.0, n_servers=6)
        sim.run_until(400.0)
        sim.drain()
    ra = a.responses("x", 0.0, 400.0)
    rb = b.responses("x", 0.0, 400.0)
    np.testing.assert_allclose(ra, rb, rtol=1e-9)


# ----------------------------------------------------------------------------
# Reconfiguration-boundary hand-off (mirrors tests/test_des.py)
# ----------------------------------------------------------------------------
def test_grow_reconfig_carries_backlog_exactly():
    """The test_fleet_reconfigure_carries_inflight_work trace: a rho=1.5
    backlog drained by a mid-run scale-out. λ/n-only history ⇒ the vector
    engine must reproduce the oracle per customer, across the boundary."""
    ev = FleetSimulator(seed=3)
    vec = FleetSimulator(seed=3, engine="vector")
    for sim in (ev, vec):
        sim.add_app("hot", lam=6.0, mu=1.0, n_servers=4)
        sim.run_until(120.0)
        sim.configure("hot", n_servers=12)
        sim.run_until(400.0)
        sim.drain()
    assert_exact_parity(ev, vec, "hot")
    cl = vec._clusters["hot"]
    assert cl.queue_t.shape[0] == 0  # backlog fully drained
    t_arr, wait, svc = cl.logs()
    assert t_arr.shape[0] == cl.n_arrived  # nothing lost across the reconfig
    late = vec.responses("hot", 250.0, 400.0)
    assert np.mean(late) == pytest.approx(1.0, rel=0.35)  # ~1/mu post-scale-out


def test_shrink_is_non_preemptive_limit():
    """Shrinking below the busy count: the queue must resume exactly at the
    (b - n' + 1)-th in-flight completion — dropping the smallest workload
    entries reproduces the oracle's retire-as-they-finish rule exactly."""
    ev = FleetSimulator(seed=5)
    vec = FleetSimulator(seed=5, engine="vector")
    for sim in (ev, vec):
        sim.add_app("s", lam=6.0, mu=1.2, n_servers=8)
        sim.run_until(300.0)
        sim.configure("s", n_servers=4)  # shrink below the busy count
        sim.run_until(600.0)
        sim.configure("s", n_servers=9)  # recover
        sim.run_until(900.0)
        sim.drain()
    assert_exact_parity(ev, vec, "s")


def test_lambda_reconfig_crn_redraw_matches_oracle():
    """A λ change supersedes the pending arrival and re-draws from a fresh
    chunk at the new rate in BOTH engines — the streams stay aligned."""
    ev = FleetSimulator(seed=11)
    vec = FleetSimulator(seed=11, engine="vector")
    for sim in (ev, vec):
        sim.add_app("a", lam=4.0, mu=2.0, n_servers=6)
        sim.run_until(200.0)
        sim.configure("a", lam=10.0)
        sim.run_until(500.0)
        sim.configure("a", lam=2.5)
        sim.run_until(800.0)
        sim.drain()
    assert ev._clusters["a"].n_arrived == vec._clusters["a"].n_arrived
    assert_exact_parity(ev, vec, "a")


def test_mu_change_congested_boundary_is_unbiased():
    """A congested boundary followed by a μ scale-up — the exact case the
    closed loop exists to measure. The oracle re-draws queued work at the
    new rate at service start; the vector engine rescales its queued draws
    by mu_old/mu_new (the same new-rate law), so the engines must agree
    statistically, not just on quiet traces."""
    ev = FleetSimulator(seed=1)
    vec = FleetSimulator(seed=1, engine="vector")
    for sim in (ev, vec):
        sim.add_app("c", lam=9.0, mu=1.0, n_servers=5)  # rho=1.8: backlog
        sim.run_until(120.0)
        sim.configure("c", mu=3.0)  # scale-up serves the backlog fast
        sim.run_until(400.0)
        sim.drain()
    me = float(np.mean(ev.responses("c", 0.0, 400.0)))
    mv = float(np.mean(vec.responses("c", 0.0, 400.0)))
    assert mv == pytest.approx(me, rel=0.15)  # was 240% off pre-rescale


def test_zero_server_cluster_never_fabricates_responses():
    """n_servers=0 queues forever in the oracle; the vector engine must not
    finalize the masked-slot sentinel as a real wait, even through drain()."""
    ev = FleetSimulator(seed=2)
    vec = FleetSimulator(seed=2, engine="vector")
    for sim in (ev, vec):
        sim.add_app("z", lam=3.0, mu=1.0, n_servers=0)
        sim.run_until(10.0)
        sim.drain()
    assert ev.responses("z", 0.0, 10.0).shape[0] == 0
    assert vec.responses("z", 0.0, 10.0).shape[0] == 0
    zc = vec._clusters["z"]
    assert zc.queue_t.shape[0] == zc.n_arrived  # everything still queued


def test_mu_change_statistical_hand_off():
    """μ re-draws happen at service START in the oracle but are rescaled
    at-arrival draws here (same law, different draws), so μ-boundary parity
    is statistical: both windows must track the analytic Erlang-C value,
    mirroring test_fleet_mu_change_preserves_inflight_service."""
    sim = FleetSimulator(seed=11, engine="vector")
    sim.add_app("a", lam=4.0, mu=2.0, n_servers=8)
    sim.run_until(500.0)
    sim.configure("a", mu=4.0)
    sim.run_until(1500.0)
    sim.drain()
    before = sim.responses("a", 100.0, 500.0)
    after = sim.responses("a", 600.0, 1500.0)
    assert np.mean(before) == pytest.approx(erlang_ws_np(8, 4.0, 2.0), rel=0.15)
    assert np.mean(after) == pytest.approx(erlang_ws_np(8, 4.0, 4.0), rel=0.15)


def test_retire_and_rejoin_vector():
    ev = FleetSimulator(seed=7)
    vec = FleetSimulator(seed=7, engine="vector")
    for sim in (ev, vec):
        sim.add_app("t", lam=5.0, mu=2.0, n_servers=5)
        sim.add_app("u", lam=3.0, mu=2.0, n_servers=3)
        sim.run_until(200.0)
        sim.retire("t")
        sim.run_until(600.0)
        sim.activate("t")
        sim.run_until(800.0)
        sim.drain()
    for name in ("t", "u"):
        assert ev._clusters[name].n_arrived == vec._clusters[name].n_arrived
        assert_exact_parity(ev, vec, name)


def test_crn_arrivals_shared_across_allocations():
    """Same seed ⇒ same arrival process even under different (mu, n) — the
    paired-comparison property, engine-independent."""
    a = FleetSimulator(seed=42, engine="vector")
    a.add_app("x", lam=8.0, mu=2.0, n_servers=6)
    b = FleetSimulator(seed=42, engine="vector")
    b.add_app("x", lam=8.0, mu=3.5, n_servers=3)
    a.run_until(300.0)
    b.run_until(300.0)
    assert a._clusters["x"].n_arrived == b._clusters["x"].n_arrived


# ----------------------------------------------------------------------------
# Occupancy integrals (snapshot sample-path identities)
# ----------------------------------------------------------------------------
def test_window_integrals_match_oracle():
    ev = FleetSimulator(seed=5)
    vec = FleetSimulator(seed=5, engine="vector")
    stats = []
    for sim in (ev, vec):
        sim.add_app("a", lam=11.5, mu=1.6, n_servers=8)
        sim.run_until(500.0)
        snap = sim.snapshot("a")
        sim.run_until(1500.0)
        q1, b1 = sim.snapshot("a")
        stats.append((q1 - snap[0], b1 - snap[1]))
    (qe, be), (qv, bv) = stats
    # identical sample path ⇒ identical integrals (the vector engine computes
    # them from per-customer intervals, the oracle from piecewise advance)
    assert qv == pytest.approx(qe, rel=1e-6)
    assert bv == pytest.approx(be, rel=1e-6)


# ----------------------------------------------------------------------------
# H2 service (the first non-Poisson knob) through the vector engine
# ----------------------------------------------------------------------------
def test_h2_crn_parity_and_off_model_degradation():
    ev = FleetSimulator(seed=9, service="h2", h2_scv=4.0)
    vec = FleetSimulator(seed=9, engine="vector", service="h2", h2_scv=4.0)
    for sim in (ev, vec):
        sim.add_app("h", lam=10.0, mu=1.5, n_servers=8)
        sim.run_until(500.0)
        sim.drain()
    assert_exact_parity(ev, vec, "h")
    # heavier-tailed service at the same mean must congest beyond Erlang-C
    h2 = simulate_mmn(10.0, 1.5, 8, horizon_s=3000.0, warmup_s=300.0, seed=2,
                      engine="vector", service="h2", h2_scv=4.0)
    exp = simulate_mmn(10.0, 1.5, 8, horizon_s=3000.0, warmup_s=300.0, seed=2,
                       engine="vector")
    assert h2.mean_response_s > 1.08 * exp.mean_response_s
    assert h2.p95_response_s > 1.2 * exp.p95_response_s


# ----------------------------------------------------------------------------
# Bursty (MMPP) arrivals: the same CRN contract off the Poisson model
# ----------------------------------------------------------------------------
# Conditioned on its modulating chain an MMPP is piecewise-Poisson, and both
# engines consume ONE shared ArrivalStream, so every λ/n-only parity guarantee
# above extends verbatim to bursty arrivals — checked here per customer.
MMPP = mmpp2(burst=4.0, frac=0.15, cycle=40.0)


def test_mmpp_stationary_crn_parity():
    ev = FleetSimulator(seed=3)
    vec = FleetSimulator(seed=3, engine="vector")
    for sim in (ev, vec):
        sim.add_app("b", lam=8.0, mu=1.8, n_servers=8, arrival=MMPP)
        sim.add_app("p", lam=8.0, mu=1.8, n_servers=8)  # Poisson control lane
        sim.run_until(600.0)
        sim.drain()
    for name in ("b", "p"):
        assert ev._clusters[name].n_arrived == vec._clusters[name].n_arrived
        assert_exact_parity(ev, vec, name)
    # same seed/name streams, different law: the bursty lane is NOT the
    # Poisson lane relabelled — the modulating chain really reshapes the path
    tb, _, _, _ = paired_paths(ev, vec, "b")
    tp, _, _, _ = paired_paths(ev, vec, "p")
    assert tb.shape != tp.shape or not np.allclose(tb, tp)


def test_mmpp_mid_burst_configure_parity():
    """λ and n reconfigurations land at arbitrary modulating-chain positions
    (including mid-burst): the phase is carried across the boundary and the
    pending draw superseded identically in both engines."""
    ev = FleetSimulator(seed=3, arrival=MMPP)
    vec = FleetSimulator(seed=3, engine="vector", arrival=MMPP)
    for sim in (ev, vec):
        sim.add_app("a", lam=6.0, mu=1.5, n_servers=7)
        sim.run_until(150.0)
        sim.configure("a", lam=12.0, n_servers=12)
        sim.run_until(400.0)
        sim.configure("a", lam=4.0)
        sim.run_until(700.0)
        sim.drain()
    assert ev._clusters["a"].n_arrived == vec._clusters["a"].n_arrived
    assert_exact_parity(ev, vec, "a")


def test_mmpp_retire_rejoin_parity():
    """The modulating chain keeps evolving while a tenant is retired; on
    rejoin both engines resolve the missed transitions and resume from the
    same chain state and draw position."""
    ev = FleetSimulator(seed=7)
    vec = FleetSimulator(seed=7, engine="vector")
    for sim in (ev, vec):
        sim.add_app("t", lam=5.0, mu=2.0, n_servers=6, arrival=MMPP)
        sim.add_app("u", lam=3.0, mu=2.0, n_servers=3, arrival=MMPP)
        sim.run_until(200.0)
        sim.retire("t")
        sim.run_until(600.0)
        sim.activate("t")
        sim.run_until(800.0)
        sim.drain()
    for name in ("t", "u"):
        assert ev._clusters[name].n_arrived == vec._clusters[name].n_arrived
        assert_exact_parity(ev, vec, name)


def test_mmpp_three_phase_off_phase_parity():
    """R=3 chain with a silent phase (interrupted Poisson): exercises the
    routing-uniform draws and the off-phase fast-forward in both engines."""
    spec = ArrivalSpec(kind="mmpp", rates=(1.0, 3.0, 0.0), sojourn=(30.0, 8.0, 10.0))
    ev = FleetSimulator(seed=5)
    vec = FleetSimulator(seed=5, engine="vector")
    for sim in (ev, vec):
        sim.add_app("w", lam=7.0, mu=1.6, n_servers=8, arrival=spec)
        sim.run_until(300.0)
        sim.configure("w", n_servers=5)
        sim.run_until(900.0)
        sim.drain()
    assert ev._clusters["w"].n_arrived == vec._clusters["w"].n_arrived
    assert_exact_parity(ev, vec, "w")


def test_mmpp_estimator_round_trip():
    """Simulate an MMPP arrival stream, bin it like an invocation log, and
    recover the law: mean rate within 10%, bin-window IDC tracking the
    closed-form idc_at, and a fitted MMPP2 whose peak ratio is in the right
    range (burst sojourn = 2 bins, so the threshold fit is not diluted)."""
    spec = mmpp2(burst=3.0, frac=0.2, cycle=600.0)
    lam, horizon, bin_s = 20.0, 24 * 3600.0, 60.0
    arr = ArrivalStream(spec, lam, seed=1, name="rt", t0=0.0)
    ts = arr.times_until(horizon)
    counts, _ = np.histogram(ts, bins=int(horizon / bin_s), range=(0.0, horizon))
    est = estimate_arrival(counts, bin_s)
    assert est["lam"] == pytest.approx(lam, rel=0.10)
    assert est["idc"] > 10.0  # strongly overdispersed — nothing like Poisson
    assert est["idc"] == pytest.approx(idc_at(spec, lam, bin_s), rel=0.25)
    assert est["spec"].kind == "mmpp"
    ratio = est["spec"].lam_hi_ratio()
    assert 1.5 <= ratio <= 3.5  # true peak ratio is 3.0; threshold fit is coarse


# ----------------------------------------------------------------------------
# Guard rails
# ----------------------------------------------------------------------------
def test_vector_run_until_needs_finite_horizon():
    sim = FleetSimulator(seed=0, engine="vector")
    sim.add_app("x", lam=2.0, mu=1.0, n_servers=4)
    with pytest.raises(ValueError):
        sim.run_until(np.inf)
    sim.drain()  # the supported unbounded operation
