"""Region traffic: one controller asks the region planner for a plan every
epoch, in a closed loop, through the public API.

The region is the configuration's node and apps at every station, each
station's apps at its load factor (``reference/region.py``). Every app's rate
drifts by the traffic generator's law (``traffic.py``, phases from the run's
seed) times its station's factor. Each request carries every app's rate and
the region's node caps to ``get_policy("crms_fleet")``, which re-plans
incrementally: it re-solves the nodes whose apps' rates changed, warm-started,
in one batched row solve, and moves apps off a node that lost feasibility
(emergency offload). The node caps never change, so the cold plan (greedy
placement, exchange) runs once, in set-up. No quasi-dynamic threshold stands
in front: every app's rate moves every epoch, so its region-wide trigger
would fire on every request. A request is timed from its issue until the
plan is on the host; the next is issued when it returns.

The check holds every plan of the window to the plain reference of
``reference/region.py`` at the rates it was solved for: every node the plan
reports ok is feasible and stable; the Ws and node objectives it reports
against the float64 reference's evaluation of its allocation, over every app
and node; nodes the re-plan did not touch kept their allocation bit for bit;
and on a seeded sample of nodes (with the node of least CPU slack and every
node an emergency move touched) the node's objective at its quotas against
the optimum at its counts. A plan that raised or reports a failed node counts
in ``failed``.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from perfbench import core, node
from perfbench.reference import allocation
from perfbench.reference import region as ref
from perfbench.traffic import RequestStream

# (module, attribute, span name): the layers a plan passes through
SPANS = [
    ("repro.core.placement", "find_feasible_start_batch", "region_phase1"),
    ("repro.core.placement", "ip_solve_rows", "region_rows"),
]
RECORD = ()

# Limits of the check, between the largest reading of the program's runs on
# the chip and the smallest of the float32 control (PERF.md, "How correct is
# decided").
WS_RTOL = 1e-9  # Ws of each app vs the float64 Erlang-C reference
UTILITY_RTOL = 1e-9  # each node's objective vs the reference's Eq. (8)
QUOTA_RTOL = 1e-4  # a node's objective above the optimum at its counts


@dataclasses.dataclass
class Plan:
    """What one request was answered with, at the rates it carried."""

    lam: np.ndarray  # (A,) rates solved for
    latency_s: float
    assignment: np.ndarray | None = None  # (A,) node of each app
    n: np.ndarray | None = None
    c: np.ndarray | None = None
    m: np.ndarray | None = None
    ws: np.ndarray | None = None
    node_utility: np.ndarray | None = None  # (N,), inf where the node failed
    diagnostics: dict = dataclasses.field(default_factory=dict)
    error: str | None = None

    def as_dict(self) -> dict:
        return {"lam": self.lam, "assignment": self.assignment, "n": self.n, "c": self.c,
                "m": self.m, "ws": self.ws, "node_utility": self.node_utility}


@dataclasses.dataclass
class State:
    cfg: dict  # the region (reference.region): every station's apps
    traffic: dict
    seed: int
    apps: list  # the program's App objects, in the region's order
    caps: object  # one node's ServerCaps
    node_caps: list  # the region's node caps, one per node
    stream: RequestStream
    policy: object
    requests: dict = dataclasses.field(default_factory=dict)  # epoch -> AllocRequest
    before: Plan | None = None  # the last plan of set-up
    decisions: list = dataclasses.field(default_factory=list)
    window_s: float = 0.0
    compiles: int = 0
    j: int = 0  # requests issued so far

    @property
    def nodes(self) -> int:
        return len(self.node_caps)


def request(state: State, lam, migrations=()):
    """An ``AllocRequest`` for the region at rates ``lam``."""
    from repro.api import AllocRequest

    extra = {"node_caps": state.node_caps,
             "exchange_rounds": int(state.cfg["exchange_rounds"])}
    if migrations:
        extra["migrations"] = list(migrations)
    return AllocRequest([a.with_lam(float(x)) for a, x in zip(state.apps, lam)], state.caps,
                        alpha=float(state.cfg["alpha"]), beta=float(state.cfg["beta"]),
                        extra=extra)


def decide(state: State, lam, migrations=(), req=None) -> Plan:
    """Issue one request (``req``, or one built at rates ``lam``) and time it
    (the span ``decision`` in a traced window)."""
    req = request(state, lam, migrations) if req is None else req
    with core.span("decision"):
        t0 = time.perf_counter()
        try:
            result, error = state.policy.allocate(req), None
            np.asarray(result.allocation.n)  # the plan is host data
        except Exception as exc:  # a failed plan is counted, not fatal
            result, error = None, f"{type(exc).__name__}: {exc}"
        lat = time.perf_counter() - t0
    plan = Plan(np.asarray(lam, dtype=float), lat, error=error)
    if result is not None:
        alloc = result.allocation
        plan.assignment = np.asarray(alloc.meta["assignment"], dtype=int)
        plan.n, plan.c, plan.m = alloc.n, alloc.r_cpu, alloc.r_mem
        plan.ws = alloc.ws
        plan.node_utility = np.asarray(alloc.meta["node_utility"], dtype=float)
        plan.diagnostics = dict(alloc.meta.get("diagnostics", {}))
    return plan


def setup(cfg: dict, traffic: dict, seed: int, stations: int | None = None) -> State:
    """Build the region from the configuration, plan it cold, then re-plan
    one whole cycle of the run's own rates (every count, and so every Erlang
    width, the window will meet) and move one app away and back (the 2-row
    solve of an emergency offload), all through the public API."""
    from repro.api import get_policy

    reg = ref.region(cfg, stations)
    base = np.asarray([a["lam"] for a in reg["apps"]])
    caps = node.caps(cfg)
    stream = RequestStream(traffic, base, caps.r_cpu, caps.r_mem, seed)
    policy = get_policy(cfg["policy"])
    policy.reset()
    state = State(reg, traffic, int(seed), node.apps(reg), caps,
                  [caps] * int(reg["stations"]), stream, policy)
    cycle = int(traffic["cycle_epochs"])
    state.requests = {e: request(state, stream.rates[e]) for e in range(cycle)}
    plan = None
    for j in range(cycle + 1):
        r = stream[j]
        plan = decide(state, r.lam, req=state.requests[r.epoch])
        if plan.error is not None:
            raise RuntimeError(f"set-up plan failed: {plan.error}")
    state.j = cycle + 1
    counts = np.bincount(plan.assignment, minlength=state.nodes)
    src = int(plan.assignment[0])
    dst = int(np.argmin(np.where(np.arange(state.nodes) == src, np.iinfo(int).max, counts)))
    name = state.apps[0].name
    for to in (dst, src):
        plan = decide(state, plan.lam, migrations=[(name, to)])
        if plan.error is not None:
            raise RuntimeError(f"set-up plan failed: {plan.error}")
    state.before = plan
    return state


def window(state: State, seconds: float, compiles: core.CompileCounter) -> None:
    """The closed loop: plans until ``seconds`` have passed. The requests of
    the cycle's epochs were built in set-up, outside the timer."""
    c0 = compiles.count
    t0 = time.perf_counter()
    deadline = t0 + seconds
    while time.perf_counter() < deadline:
        r = state.stream[state.j]
        plan = decide(state, r.lam, req=state.requests[r.epoch])
        state.decisions.append(plan)
        state.j += 1
    state.window_s = time.perf_counter() - t0
    state.compiles = compiles.count - c0


def report_lines(state: State) -> list[str]:
    d = state.decisions
    done = [x for x in d if x.error is None]
    failed = sum(int(x.diagnostics.get("nodes_failed", 0)) > 0 for x in done)
    solved = [int(x.diagnostics.get("nodes_solved", 0)) for x in done]
    return [
        f"plans in window: {len(d)} ({len(d) - len(done)} raised, {failed} with a failed node) "
        f"in {state.window_s:.3f} s, over {state.nodes} nodes and {len(state.apps)} apps",
        f"nodes re-solved per plan: {min(solved, default=0)}-{max(solved, default=0)}; "
        f"emergency moves: {sum(int(x.diagnostics.get('migrations', 0)) for x in done)}",
        f"compiles in window: {state.compiles}",
    ]


def end_to_end(state: State) -> dict:
    lat_ms = [1e3 * x.latency_s for x in state.decisions]
    return {"replan_p50_ms": float(np.percentile(lat_ms, 50)),
            "replan_p95_ms": float(np.percentile(lat_ms, 95))}


def _sample(state: State, k: int, plan: Plan, prev: Plan, ev: dict) -> list[int]:
    """Nodes whose quotas are checked, among the ok nodes that hold an app:
    ``quota_sample_nodes`` drawn from the run's seed, the one with the least
    CPU slack, and every node an emergency move touched."""
    held = np.bincount(plan.assignment, minlength=state.nodes) > 0
    ok = np.where(np.isfinite(plan.node_utility) & held)[0]
    if ok.size == 0:
        return []
    rng = np.random.default_rng([state.seed, 0x7175, k])
    pick = set(rng.choice(ok, size=min(int(state.traffic["quota_sample_nodes"]), ok.size),
                          replace=False).tolist())
    pick.add(int(ok[np.argmin(ev["cpu_slack"][ok])]))
    if prev is not None:
        moved = prev.assignment != plan.assignment
        pick.update(int(j) for j in np.concatenate([prev.assignment[moved],
                                                    plan.assignment[moved]]))
    return sorted(j for j in pick if np.isfinite(plan.node_utility[j]) and held[j])


def _compare(state: State, dtype) -> dict:
    """Hold every plan to the reference. ``dtype`` float64 reads the
    program's answer; float32 puts the reference, computed in float32, in the
    program's place (the control): its Ws and node objectives, and its optimal
    quotas on the sampled nodes."""
    apps = ref.Apps(state.cfg)
    caps_cpu = np.full(state.nodes, float(state.caps.r_cpu))
    caps_mem = np.full(state.nodes, float(state.caps.r_mem))
    out = {"ws_gap": 0.0, "utility_gap": 0.0, "quota_gap": 0.0, "infeasible": 0,
           "incremental": 0, "failed": 0}
    prev = state.before
    for k, plan in enumerate(state.decisions):
        if plan.error is not None:
            out["failed"] += 1
            continue
        ok = np.isfinite(plan.node_utility)
        out["failed"] += int(not ok.all())
        if prev is not None:
            out["incremental"] += ref.incremental_mismatch(prev.as_dict(), plan.as_dict(),
                                                           state.nodes)
        want = ref.evaluate(apps, plan.lam, plan.n, plan.c, plan.m, plan.assignment,
                            caps_cpu, caps_mem)
        out["infeasible"] += int(np.sum(ok & ~want["feasible"]))
        if dtype is np.float64:
            ws, utility = plan.ws, plan.node_utility
        else:
            low = ref.evaluate(apps, plan.lam, plan.n, plan.c, plan.m, plan.assignment,
                               caps_cpu, caps_mem, dtype=dtype)
            ws, utility = low["ws"], low["utility"]
        on_ok = ok[plan.assignment]
        held = ok & (np.bincount(plan.assignment, minlength=state.nodes) > 0)
        out["ws_gap"] = max(out["ws_gap"], allocation.rel_gap(np.asarray(ws)[on_ok],
                                                              want["ws"][on_ok]))
        # a node that holds no app has the objective 0, exactly
        empty = np.asarray(utility)[ok & ~held]
        out["utility_gap"] = max(out["utility_gap"],
                                 allocation.rel_gap(np.asarray(utility)[held],
                                                    want["utility"][held]),
                                 0.0 if np.all(empty == 0.0) else np.inf)
        for j in _sample(state, k, plan, prev, want):
            idx = np.where(plan.assignment == j)[0]
            out["quota_gap"] = max(out["quota_gap"], ref.quota_gap(
                apps, plan.lam, plan.n, plan.c, plan.m, idx, caps_cpu[j], caps_mem[j], dtype))
        prev = plan
    return out


NUMBERS = ("ws_gap", "utility_gap", "quota_gap")


def check(state: State) -> tuple[list, dict]:
    """The checks with their limits, and the counts for the result line."""
    got = _compare(state, np.float64)
    checks = [
        core.Check("ws_gap", got["ws_gap"], WS_RTOL),
        core.Check("utility_gap", got["utility_gap"], UTILITY_RTOL),
        core.Check("quota_gap", got["quota_gap"], QUOTA_RTOL),
        core.Check("infeasible_nodes", got["infeasible"], 0),
        core.Check("incremental_mismatch", got["incremental"], 0),
    ]
    return checks, {"attempted": len(state.decisions), "failed": got["failed"]}


def control(state: State) -> dict:
    """The control's readings: the reference in float32 in the program's place."""
    got = _compare(state, np.float32)
    return {k: got[k] for k in NUMBERS}


def counters(state: State) -> dict:
    """What the per-layer readers count per request. ``rows_solved`` and
    ``rows_dispatched`` sum the planner's own row counters over the window,
    where the program reports them."""
    done = [d for d in state.decisions if d.error is None]
    out = {"requests": len(state.decisions)}
    diags = [d.diagnostics for d in done]
    if diags and all("rows_dispatched" in g for g in diags):
        out["rows_solved"] = sum(int(g["rows_solved"]) for g in diags)
        out["rows_dispatched"] = sum(int(g["rows_dispatched"]) for g in diags)
    return out
