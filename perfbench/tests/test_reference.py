"""The plain references agree with the program on the CPU at small sizes,
so that a gap on the chip is the chip's and not the reference's."""
import json

import numpy as np
import pytest

from perfbench import core, node
from perfbench.reference import allocation as ref_alloc
from perfbench.reference import des as ref_des

CFG = json.loads((core.BENCH_DIR / "configs" / "paper_node.json").read_text())


@pytest.mark.parametrize("n,lam,mu", [(1, 0.5, 1.0), (3, 7.0, 2.9), (8, 15.0, 2.1), (12, 40.0, 3.5)])
def test_erlang_ws_matches_the_program_oracle(n, lam, mu):
    from repro.core.queueing import erlang_ws_np

    assert ref_alloc.erlang_ws(n, lam, mu) == pytest.approx(erlang_ws_np(n, lam, mu), rel=1e-13)


def test_evaluate_matches_the_program_on_a_crms_allocation():
    from repro.api import allocate

    lam = [a["lam"] for a in CFG["apps"]]
    request = node.request(CFG, node.apps(CFG), lam)
    alloc = allocate("crms", request).allocation
    caps = request.caps
    got = ref_alloc.evaluate(CFG, lam, caps.r_cpu, caps.r_mem, alloc.n, alloc.r_cpu, alloc.r_mem)
    assert got["feasible"] and got["stable"]
    assert ref_alloc.rel_gap(alloc.ws, got["ws"]) < 1e-12
    assert ref_alloc.rel_gap([alloc.utility], [got["utility"]]) < 1e-12
    f32 = ref_alloc.evaluate(CFG, lam, caps.r_cpu, caps.r_mem, alloc.n, alloc.r_cpu,
                             alloc.r_mem, dtype=np.float32)
    assert ref_alloc.rel_gap(f32["ws"], got["ws"]) > 1e-9  # float32 is visibly off


@pytest.mark.parametrize("config,cpu", [("paper_node", 30.0), ("paper_node", 27.0),
                                        ("paper_sufficient", 108.0)])
def test_p1_reference_matches_the_program_interior_point(config, cpu):
    """At fixed counts the plain barrier and the program's interior point
    (its over-converged "reference" schedule) find the same quotas; the
    float32 reference does not."""
    from repro.core.engine import as_packed, p1_solve_batch

    cfg = json.loads((core.BENCH_DIR / "configs" / f"{config}.json").read_text())
    lam = [a["lam"] for a in cfg["apps"]]
    request = node.request(cfg, node.apps(cfg), lam, cpu)
    n = [7, 9, 3, 7] if config == "paper_node" else [5, 5, 4, 6]
    prog = p1_solve_batch(as_packed(request.apps), request.caps, np.asarray([n], dtype=float),
                          request.alpha, request.beta)
    want = ref_alloc.solve_p1(cfg, lam, cpu, cfg["caps"]["r_mem"], n)
    got = np.concatenate([prog.r_cpu[0], prog.r_mem[0]])
    assert ref_alloc.rel_gap(got, np.concatenate([want["c"], want["m"]])) < 1e-8
    assert want["utility"] == pytest.approx(float(prog.utility[0]), rel=1e-10)
    low = ref_alloc.solve_p1(cfg, lam, cpu, cfg["caps"]["r_mem"], n, dtype=np.float32)
    assert ref_alloc.rel_gap(np.concatenate([low["c"], low["m"]]),
                             np.concatenate([want["c"], want["m"]])) > 1e-6


def test_p1_reference_says_infeasible_where_nothing_fits():
    lam = [a["lam"] for a in CFG["apps"]]
    assert ref_alloc.solve_p1(CFG, lam, 30.0, 10.0, [1, 1, 1, 1])["utility"] == np.inf  # unstable
    assert ref_alloc.solve_p1(CFG, lam, 30.0, 10.0, [20, 20, 20, 20])["utility"] == np.inf  # memory


def test_quasi_dynamic_rule():
    rule = ref_alloc.QuasiDynamicRule(0.15)
    assert rule.observe([10.0, 5.0], 30.0, 10.0)[0]  # first request
    assert not rule.observe([11.4, 5.0], 30.0, 10.0)[0]  # 14% drift
    assert rule.observe([11.6, 5.0], 30.0, 10.0)[0]  # 16% drift
    assert rule.observe([11.6, 5.0], 27.0, 10.0)[0]  # caps resized
    assert rule.observe([11.6, 5.0], 30.0, 10.0)[0]  # and back


EPOCHS = [  # (lam, mu, n): rate, service and server changes, a shrink under load
    (7.0, 2.0, 4), (9.0, 2.0, 5), (9.0, 1.6, 7), (11.0, 2.4, 5), (6.0, 2.4, 3), (6.0, 2.4, 3),
]


@pytest.mark.parametrize("seed", [0, 2**31 + 5])
def test_reference_des_matches_the_vector_engine_through_reconfigurations(seed):
    from repro.core.des import FleetSimulator

    epoch_s = 300.0
    sim = FleetSimulator(seed=seed, engine="vector")
    sim.add_app("app", *EPOCHS[0])
    for e, (lam, mu, n) in enumerate(EPOCHS):
        if e:
            sim.configure("app", lam=lam, mu=mu, n_servers=n)
        sim.run_until((e + 1) * epoch_s)
    got = sim.responses("app", 0.0, len(EPOCHS) * epoch_s)
    want = ref_des.simulate_cluster(seed, "app", EPOCHS, epoch_s)["response"]
    assert got.shape == want.shape and got.size > 10_000
    assert np.max(np.abs(got - want)) / np.mean(want) < 1e-11
    f32 = ref_des.simulate_cluster(seed, "app", EPOCHS, epoch_s, dtype=np.float32)["response"]
    k = min(f32.size, want.size)
    assert np.max(np.abs(f32[:k] - want[:k])) / np.mean(want) > 1e-6
