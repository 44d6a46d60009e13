"""Validate traffic: replay a drifting-load plan through the vector DES.

Set-up plans every epoch of one cycle of the drift trace with
``QuasiDynamicPolicy`` over the configuration's policy, outside the window.
The window then runs validation jobs back to back, each a fresh
``FleetSimulator(engine="vector")`` seeded from the run's seed: every app is
an M/M/n cluster at its plan's rate, service rate and container count, the
plan is re-applied with ``configure()`` at each epoch boundary, and after
the last epoch the job reads every app's response times back. A job stops
early, after its current epoch, when the window is over.

The check re-simulates a sample of (job, app) pairs, drawn from the seed and
always holding the busiest app of the first job, with the plain reference
of ``reference/des.py`` on the same random streams, and compares the
response time of every customer.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from perfbench import core, node
from perfbench.reference import allocation as ref_alloc
from perfbench.reference import des as ref_des
from perfbench.traffic import RequestStream

SPANS = [("repro.core.des_vector", "segment_scan", "segment_scan")]
RECORD = ()

RESPONSE_RTOL = 1e-6  # widest response-time gap, relative to the reference's
# mean response of that app: the program reads at most 6.7e-11 on the chip,
# the float32 control at least 4.5e-3 (PERF.md, "How correct is decided")
SAMPLE_JOBS = 3  # (job, app) pairs the reference re-simulates


@dataclasses.dataclass
class Job:
    seed: int
    epochs_run: int
    keep: int  # index of the app whose responses are kept for the check
    responses: np.ndarray


@dataclasses.dataclass
class State:
    cfg: dict
    traffic: dict
    seed: int
    names: list
    plans: list  # per epoch: (lam (M,), mu (M,), n (M,))
    jobs: list = dataclasses.field(default_factory=list)
    customers: int = 0
    segments: int = 0
    window_s: float = 0.0
    compiles: int = 0


def _job_seed(seed: int, j: int) -> int:
    return int(np.random.default_rng([int(seed), int(j)]).integers(2**31 - 1))


def _plan(cfg: dict, traffic: dict, seed: int) -> list:
    """One plan per epoch of the cycle, through the public API."""
    apps = node.apps(cfg)
    stream = RequestStream(traffic, [a["lam"] for a in cfg["apps"]],
                           cfg["caps"]["r_cpu"], cfg["caps"]["r_mem"], seed)
    policy = node.policy(cfg, traffic["threshold"])
    plans = []
    for req in stream.cycle():
        alloc = policy.allocate(node.request(cfg, apps, req.lam)).allocation
        if not (alloc.feasible and alloc.stable):
            raise RuntimeError(f"epoch {req.epoch}: the plan is infeasible or unstable")
        mu = np.array([float(ref_alloc.service_rate(a, c, m))
                       for a, c, m in zip(cfg["apps"], alloc.r_cpu, alloc.r_mem)])
        plans.append((np.asarray(req.lam, dtype=float), mu, np.asarray(alloc.n, dtype=int)))
    return plans


def _shapes(plans, epoch_s: float, m: int) -> set:
    """Every (customers, apps, servers) shape the segment scan can be given:
    customers and servers padded to powers of two as the simulator pads
    them, customers within 15% (plus the queue) of each epoch's busiest app."""
    def pow2(k):
        return 1 << max(int(k) - 1, 0).bit_length()

    out = set()
    for lam, _, n in plans:
        k = float(np.max(lam)) * epoch_s
        for kp in {pow2(0.85 * k), pow2(1.15 * k + 64)}:
            out.add((kp, pow2(m), pow2(int(np.max(n)))))
    return out


def _warm_scan(shapes) -> None:
    from repro.core import des_vector

    big = 1e30
    for kp, mp, npad in sorted(shapes):
        des_vector.segment_scan(
            np.full((mp, npad), big), np.zeros((mp, npad), dtype=bool),
            np.zeros((kp, mp)), np.zeros((kp, mp)), np.zeros((kp, mp), dtype=bool))


def _run_job(state: State, j: int, deadline: float | None) -> Job:
    from repro.core.des import FleetSimulator

    epoch_s = float(state.traffic["epoch_s"])
    seed = _job_seed(state.seed, j)
    rng = np.random.default_rng([int(state.seed), int(j), 1])
    lam0, mu0, n0 = state.plans[0]
    keep = int(np.argmax(lam0)) if j == 0 else int(rng.integers(len(state.names)))
    with core.span("job"):
        sim = FleetSimulator(seed=seed, engine="vector", service=state.traffic["service"])
        for i, name in enumerate(state.names):
            sim.add_app(name, float(lam0[i]), float(mu0[i]), int(n0[i]))
        run = 0
        for e, (lam, mu, n) in enumerate(state.plans):
            if e:
                for i, name in enumerate(state.names):
                    sim.configure(name, lam=float(lam[i]), mu=float(mu[i]),
                                  n_servers=int(n[i]))
            sim.run_until((e + 1) * epoch_s)
            run += 1
            if deadline is not None and time.perf_counter() >= deadline:
                break
        horizon = run * epoch_s
        responses = [sim.responses(name, 0.0, horizon) for name in state.names]
    state.customers += sum(r.shape[0] for r in responses)
    state.segments += run
    return Job(seed, run, keep, responses[keep])


def setup(cfg: dict, traffic: dict, seed: int) -> State:
    names = [a["name"] for a in cfg["apps"]]
    plans = _plan(cfg, traffic, seed)
    state = State(cfg, traffic, seed, names, plans)
    _warm_scan(_shapes(plans, float(traffic["epoch_s"]), len(names)))
    warm = State(cfg, traffic, seed + 1, names, plans)
    _run_job(warm, 0, None)  # one whole job from another seed
    return state


def window(state: State, seconds: float, compiles: core.CompileCounter) -> None:
    c0 = compiles.count
    t0 = time.perf_counter()
    deadline = t0 + seconds
    j = 0
    while time.perf_counter() < deadline:
        state.jobs.append(_run_job(state, j, deadline))
        j += 1
    state.window_s = time.perf_counter() - t0
    state.compiles = compiles.count - c0


def report_lines(state: State) -> list[str]:
    return [
        f"validation jobs in window: {len(state.jobs)} ({state.segments} epochs, "
        f"{state.customers} customers) in {state.window_s:.3f} s",
        f"compiles in window: {state.compiles}",
    ]


def end_to_end(state: State) -> dict:
    return {"sim_customers_per_s": state.customers / state.window_s}


def _sample(state: State) -> list:
    rng = np.random.default_rng([int(state.seed), 2])
    rest = list(range(1, len(state.jobs)))
    picked = rng.choice(rest, size=min(SAMPLE_JOBS - 1, len(rest)), replace=False) if rest else []
    return [0, *sorted(int(p) for p in picked)]


def _reference(state: State, job: Job, dtype) -> np.ndarray:
    i = job.keep
    epochs = [(lam[i], mu[i], n[i]) for lam, mu, n in state.plans[:job.epochs_run]]
    out = ref_des.simulate_cluster(job.seed, state.names[i], epochs,
                                   float(state.traffic["epoch_s"]), dtype=dtype)
    return out["response"]


def _gap(got: np.ndarray, want: np.ndarray) -> tuple[float, int]:
    """(widest gap over the customers both hold, relative to the reference's
    mean response; how many customers one holds and the other does not)."""
    k = min(got.shape[0], want.shape[0])
    miss = abs(got.shape[0] - want.shape[0])
    if not k:
        return 0.0, miss
    return float(np.max(np.abs(got[:k] - want[:k])) / np.mean(want[:k])), miss


def check(state: State) -> tuple[list, dict]:
    gap, miss = 0.0, 0
    for j in _sample(state):
        job = state.jobs[j]
        g, m = _gap(job.responses, _reference(state, job, np.float64))
        gap, miss = max(gap, g), miss + m
    checks = [core.Check("response_gap", gap, RESPONSE_RTOL),
              core.Check("customer_count_mismatch", miss, 0)]
    return checks, {"attempted": state.segments, "failed": 0}


def control(state: State) -> dict:
    """The control's reading: the reference in float32 in the program's place."""
    gap = 0.0
    for j in _sample(state):
        job = state.jobs[j]
        want = _reference(state, job, np.float64)
        g, _ = _gap(_reference(state, job, np.float32), want)
        gap = max(gap, g)
    return {"response_gap": gap}


def counters(state: State) -> dict:
    return {"customers": state.customers, "segments": state.segments}
