"""Re-plan traffic: one controller asks the node's allocation policy for a
decision every epoch, in a closed loop, through the public API.

Each request carries the epoch's arrival rates and the node's caps and goes
to ``QuasiDynamicPolicy`` over the configuration's policy, which re-plans
(warm-started, or cold after a resize) or returns the cached allocation. A
request is timed from its issue until its allocation is on the host; the
next one is issued when it returns. Requests the threshold skips count too.

The check holds every decision of the window to the plain reference of
``reference/allocation.py``, at the rates and caps it was solved for: which
requests re-plan; the Ws and objective the program reports for its
allocation; that the allocation is feasible and stable; its quotas against
the optimal quotas at its container counts (Problem P1, which the program
solves by its interior point); and its objective against the best of those
counts and of every count vector one container away (the refinement's
neighbourhood), so that a worse solve, a refinement that stops early or a
move it missed shows.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from perfbench import core, node
from perfbench.reference import allocation as ref
from perfbench.traffic import RequestStream

# (module, attribute, span name): the layers a decision passes through
SPANS = [
    ("repro.api.policies", "crms", "crms"),
    ("repro.core.crms", "algorithm1", "algorithm1"),
    ("repro.core.crms", "p1_solve_batch", "p1_solve_batch"),
    ("repro.core.engine", "find_feasible_start_batch", "phase1"),
    ("repro.core.engine", "grid_seed_chints", "grid_seed"),
    ("repro.core.engine", "_ip_solve_batched", "p1_ip"),
    ("repro.kernels.crms_grid", "crms_grid_eval", "crms_grid"),
]
RECORD = ("crms_grid",)  # spans whose calls' arguments the metrics read

# Limits of the check, between the largest reading of the program's runs on
# the chip and the smallest of the float32 control (PERF.md, "How correct is
# decided").
WS_RTOL = 1e-9  # Ws of each app vs the float64 Erlang-C reference
UTILITY_RTOL = 1e-9  # objective the program reports vs the reference's Eq. (8)
QUOTA_RTOL = 1e-6  # quotas vs the reference's optimum at the same counts
OBJECTIVE_RTOL = 1e-8  # objective above the best of the counts and their neighbours


@dataclasses.dataclass
class Decision:
    request: object  # traffic.Request
    latency_s: float
    result: object  # AllocResult, or None where the call raised
    error: str | None


@dataclasses.dataclass
class State:
    cfg: dict
    traffic: dict
    apps: list
    stream: RequestStream
    decisions: list = dataclasses.field(default_factory=list)
    window_s: float = 0.0
    compiles: int = 0


def _policy(state: State):
    return node.policy(state.cfg, state.traffic["threshold"])


def _request(state: State, req):
    return node.request(state.cfg, state.apps, req.lam, req.r_cpu)


def _decide(policy, request):
    t0 = time.perf_counter()
    try:
        result, error = policy.allocate(request), None
        np.asarray(result.allocation.n)  # the allocation is host data
    except Exception as exc:  # a failed decision is counted, not fatal
        result, error = None, f"{type(exc).__name__}: {exc}"
    return result, error, time.perf_counter() - t0


def setup(cfg: dict, traffic: dict, seed: int) -> State:
    """Build the node from the configuration and warm up on the requests
    ``warmup_requests`` of another seed's stream: cold and warm re-plans at
    each cap level, through the public API only."""
    base = [a["lam"] for a in cfg["apps"]]
    caps = cfg["caps"]
    stream = RequestStream(traffic, base, caps["r_cpu"], caps["r_mem"], seed)
    state = State(cfg, traffic, node.apps(cfg), stream)
    warm = RequestStream(traffic, base, caps["r_cpu"], caps["r_mem"],
                         seed + int(traffic["warmup_seed_offset"]))
    policy = _policy(state)
    for j in traffic["warmup_requests"]:
        _decide(policy, _request(state, warm[int(j)]))
    return state


def window(state: State, seconds: float, compiles: core.CompileCounter) -> None:
    """The closed loop: decisions until ``seconds`` have passed."""
    policy = _policy(state)
    c0 = compiles.count
    t0 = time.perf_counter()
    deadline = t0 + seconds
    j = 0
    while time.perf_counter() < deadline:
        req = state.stream[j]
        request = _request(state, req)
        with core.span("decision"):
            result, error, lat = _decide(policy, request)
        state.decisions.append(Decision(req, lat, result, error))
        j += 1
    state.window_s = time.perf_counter() - t0
    state.compiles = compiles.count - c0


def report_lines(state: State) -> list[str]:
    d = state.decisions
    done = [x for x in d if x.result is not None]
    replans = [x for x in done if not x.result.diagnostics.cache_hit]
    cold = [x for x in replans if not x.result.diagnostics.warm_start]
    return [
        f"decisions in window: {len(d)} ({len(replans)} re-planned, {len(cold)} cold, "
        f"{len(done) - len(replans)} skipped, {len(d) - len(done)} raised) "
        f"in {state.window_s:.3f} s",
        f"compiles in window: {state.compiles}",
    ]


def end_to_end(state: State) -> dict:
    lat_ms = [1e3 * x.latency_s for x in state.decisions]
    return {"replan_p50_ms": float(np.percentile(lat_ms, 50)),
            "replan_p95_ms": float(np.percentile(lat_ms, 95))}


def _neighbours(n) -> list:
    n = np.asarray(n, dtype=int)
    out = []
    for i in range(n.shape[0]):
        for delta in (-1, 1):
            if n[i] + delta >= 1:
                out.append(n + delta * np.eye(n.shape[0], dtype=int)[i])
    return out


def _compare(state: State, dtype) -> dict:
    """Hold every decision to the reference. ``dtype`` float64 reads the
    program's answer; float32 puts the reference, computed in float32, in the
    program's place (the control): its Ws and objective, and its quotas
    solved at the program's counts."""
    rule = ref.QuasiDynamicRule(float(state.traffic["threshold"]))
    out = {"ws_gap": 0.0, "utility_gap": 0.0, "quota_gap": 0.0, "objective_gap": 0.0,
           "infeasible": 0, "flag_mismatch": 0, "rule_mismatch": 0, "failed": 0}
    seen = {}
    for d in state.decisions:
        req = d.request
        replan, (lam_s, cpu_s, mem_s) = rule.observe(req.lam, req.r_cpu, req.r_mem)
        if d.result is None:
            out["failed"] += 1
            out["infeasible"] += 1
            continue
        alloc = d.result.allocation
        out["rule_mismatch"] += int(replan == bool(d.result.diagnostics.cache_hit))
        want = ref.evaluate(state.cfg, lam_s, cpu_s, mem_s, alloc.n, alloc.r_cpu, alloc.r_mem)
        at_now = ref.evaluate(state.cfg, req.lam, req.r_cpu, req.r_mem,
                              alloc.n, alloc.r_cpu, alloc.r_mem)
        out["failed"] += int(not (at_now["feasible"] and at_now["stable"]))
        flags = (bool(alloc.feasible), bool(alloc.stable))
        out["flag_mismatch"] += int(flags != (want["feasible"], want["stable"]))
        key = (tuple(lam_s), cpu_s, mem_s, tuple(alloc.n), tuple(alloc.r_cpu), tuple(alloc.r_mem))
        if key in seen:
            continue
        seen[key] = True
        out["infeasible"] += int(not (want["feasible"] and want["stable"]))
        best = ref.solve_p1(state.cfg, lam_s, cpu_s, mem_s, alloc.n)
        u_best = min([best["utility"]] + [
            ref.solve_p1(state.cfg, lam_s, cpu_s, mem_s, nb)["utility"]
            for nb in _neighbours(alloc.n)])
        if dtype is np.float64:
            ws, utility = alloc.ws, alloc.utility
            c, m = np.asarray(alloc.r_cpu, dtype=float), np.asarray(alloc.r_mem, dtype=float)
            u_got = want["utility"]
        else:
            low = ref.evaluate(state.cfg, lam_s, cpu_s, mem_s, alloc.n, alloc.r_cpu,
                               alloc.r_mem, dtype=dtype)
            ws, utility = low["ws"], low["utility"]
            sol = ref.solve_p1(state.cfg, lam_s, cpu_s, mem_s, alloc.n, dtype=dtype)
            c, m = sol["c"], sol["m"]
            u_got = (ref.evaluate(state.cfg, lam_s, cpu_s, mem_s, alloc.n, c, m)["utility"]
                     if c is not None else np.inf)
        out["ws_gap"] = max(out["ws_gap"], ref.rel_gap(ws, want["ws"]))
        out["utility_gap"] = max(out["utility_gap"], ref.rel_gap([utility], [want["utility"]]))
        if best["c"] is None or c is None:
            out["quota_gap"] = np.inf
        else:
            gap = ref.rel_gap(np.concatenate([c, m]), np.concatenate([best["c"], best["m"]]))
            out["quota_gap"] = max(out["quota_gap"], gap)
        out["objective_gap"] = max(out["objective_gap"],
                                   max(0.0, (u_got - u_best) / abs(u_best)))
    return out


NUMBERS = ("ws_gap", "utility_gap", "quota_gap", "objective_gap")


def check(state: State) -> tuple[list, dict]:
    """The checks with their limits, and the counts for the result line."""
    got = _compare(state, np.float64)
    checks = [
        core.Check("ws_gap", got["ws_gap"], WS_RTOL),
        core.Check("utility_gap", got["utility_gap"], UTILITY_RTOL),
        core.Check("quota_gap", got["quota_gap"], QUOTA_RTOL),
        core.Check("objective_gap", got["objective_gap"], OBJECTIVE_RTOL),
        core.Check("infeasible_decisions", got["infeasible"], 0),
        core.Check("flag_mismatch", got["flag_mismatch"], 0),
        core.Check("replan_rule_mismatch", got["rule_mismatch"], 0),
    ]
    return checks, {"attempted": len(state.decisions), "failed": got["failed"]}


def control(state: State) -> dict:
    """The control's readings: the reference in float32 in the program's place."""
    got = _compare(state, np.float32)
    return {k: got[k] for k in NUMBERS}


def counters(state: State) -> dict:
    """What the per-layer readers count per request."""
    done = [d for d in state.decisions if d.result is not None]
    diags = [d.result.diagnostics for d in done]
    return {
        "requests": len(state.decisions),
        "cold": sum(1 for g in diags if not g.cache_hit and not g.warm_start),
        "refine_iters": [g.refine_iters for g in diags],
    }
