"""Plain reference for a region of edge nodes: the deployment, and the checks
of a region plan (apps placed over nodes, each node's containers sized).

Nothing here comes from the program under test. The region is the
configuration's node and apps at every station: each station's apps run at
their base rate times the station's load factor, log-normal with mean 1,
drawn from the configuration's ``layout_seed``. A plan is held to the model
of ``allocation.py``, vectorised over every app and node of the region:

* feasibility of each node the plan reports ok: its CPU and memory budgets,
  each app's quota box (r_min <= m <= r_max, c >= cpu_min, n >= 1), and
  stability (lam < n mu) at the rates the plan was solved for;
* Ws of every app by the float64 Erlang-C of ``allocation._P1`` (the same
  log-space sums as ``allocation.erlang_ws``), and each node's Eq. (8)
  objective, against what the plan reports;
* the incremental invariant: a node whose apps kept their rates and whose
  app set did not change keeps its allocation bit for bit;
* on a few nodes, the node's objective at its quotas against the optimum at
  its counts (``allocation.solve_p1``).

``dtype`` float32 computes the Ws, the objectives and the optimal quotas in
float32 instead: the lower-precision control the limits are set against.
"""
from __future__ import annotations

import numpy as np

from perfbench.reference import allocation as ref

FEAS_RTOL = ref.FEAS_RTOL


def station_factors(cfg: dict, stations: int | None = None) -> np.ndarray:
    """Each station's load factor: log-normal with mean ``mean`` and log-sd
    ``sigma``, from the configuration's ``layout_seed``."""
    law = cfg["station_factor"]
    s = int(cfg["stations"] if stations is None else stations)
    z = np.random.default_rng(int(law["layout_seed"])).standard_normal(s)
    sigma = float(law["sigma"])
    return float(law["mean"]) * np.exp(sigma * z - 0.5 * sigma ** 2)


def region(cfg: dict, stations: int | None = None) -> dict:
    """The region as a node configuration over all of its apps: every
    station's copy of the configuration's apps (named ``s<station>.<app>``)
    at the station's rate, station by station."""
    factor = station_factors(cfg, stations)
    apps = [dict(app, name=f"s{k:04d}.{app['name']}", lam=float(app["lam"] * f))
            for k, f in enumerate(factor) for app in cfg["apps"]]
    return dict(cfg, apps=apps, stations=len(factor))


class Apps:
    """The region's app parameters as arrays over its apps."""

    def __init__(self, reg: dict):
        self.cfg = reg
        apps = reg["apps"]
        self.r_min = np.asarray([a["r_min"] for a in apps])
        self.r_max = np.asarray([a["r_max"] for a in apps])
        self.cpu_min = np.asarray([a["cpu_min"] for a in apps])
        self.span = float(reg["power_w"]["p_full"]) - float(reg["power_w"]["p_idle"])

    def node_cfg(self, idx) -> dict:
        """The node configuration of the apps ``idx`` (for ``allocation``)."""
        return dict(self.cfg, apps=[self.cfg["apps"][int(i)] for i in idx])


def evaluate(apps: Apps, lam, n, c, m, assignment, caps_cpu, caps_mem,
             dtype=np.float64) -> dict:
    """Ws of every app; each node's objective, its feasibility and stability
    at rates ``lam``, and its CPU slack (share of its cap left), for a plan."""
    n_int = np.asarray(n, dtype=int)
    p = ref._P1(apps.cfg, lam, 1.0, 1.0, np.maximum(n_int, 1), dtype)
    c_t, m_t = np.asarray(c, dtype=dtype), np.asarray(m, dtype=dtype)
    with np.errstate(all="ignore"):
        d = p.f(c_t)[0] + p.g(m_t)[0]  # Eq. (1), ms
        mu = dtype(1000.0) / (p.xbar * d)
        stable = p.lam < p.n * mu
        ws = np.where(stable, p.ws_of_latency(d), np.inf)
    nodes = caps_cpu.shape[0]
    cap_c = np.asarray(caps_cpu, dtype=dtype)[assignment]
    beta = dtype(apps.cfg["beta"])
    terms = p.alpha * ws + beta * dtype(apps.span) * p.n * c_t / (cap_c * p.lam)
    utility = np.bincount(assignment, weights=terms.astype(float), minlength=nodes)
    c64, m64 = np.asarray(c, dtype=float), np.asarray(m, dtype=float)
    cpu_used = np.bincount(assignment, weights=n_int * c64, minlength=nodes)
    mem_used = np.bincount(assignment, weights=n_int * m64, minlength=nodes)
    box = ((apps.r_min * (1.0 - FEAS_RTOL) <= m64) & (m64 <= apps.r_max * (1.0 + FEAS_RTOL))
           & (c64 >= apps.cpu_min * (1.0 - FEAS_RTOL)) & (n_int >= 1) & stable)
    bad_apps = np.bincount(assignment, weights=(~box).astype(float), minlength=nodes)
    feasible = ((cpu_used <= caps_cpu * (1.0 + FEAS_RTOL))
                & (mem_used <= caps_mem * (1.0 + FEAS_RTOL)) & (bad_apps == 0))
    return {"ws": ws.astype(float), "utility": utility, "feasible": feasible,
            "cpu_slack": (caps_cpu - cpu_used) / caps_cpu}


def touched_nodes(prev_lam, lam, prev_assignment, assignment) -> np.ndarray:
    """Nodes a re-plan may re-solve: those of apps whose rate changed (where
    they were), and the two ends of every app that moved."""
    changed = np.asarray(prev_lam) != np.asarray(lam)
    moved = np.asarray(prev_assignment) != np.asarray(assignment)
    return np.unique(np.concatenate([prev_assignment[changed | moved],
                                     assignment[moved]]))


def incremental_mismatch(prev: dict, cur: dict, nodes: int) -> int:
    """Nodes left untouched between two plans whose allocation changed, plus
    apps not placed on exactly one node of the region. A plan is a dict of
    ``lam``, ``assignment``, ``n``, ``c``, ``m``, ``ws``, ``node_utility``."""
    a = np.asarray(cur["assignment"])
    bad = int(a.shape != np.asarray(prev["assignment"]).shape)
    if bad:
        return bad + a.size
    placed = (a >= 0) & (a < nodes)
    bad += int(np.sum(~placed))
    still = np.ones(nodes, dtype=bool)
    still[touched_nodes(prev["lam"], cur["lam"], prev["assignment"], a)] = False
    on_still = still[np.where(placed, a, 0)] & placed
    differs = np.zeros(a.size, dtype=bool)
    for key in ("n", "c", "m", "ws"):
        x, y = np.asarray(prev[key]), np.asarray(cur[key])
        differs |= on_still & ~((x == y) | (np.isnan(x) & np.isnan(y)))
    node_differs = np.bincount(a[differs], minlength=nodes) > 0
    u0, u1 = np.asarray(prev["node_utility"]), np.asarray(cur["node_utility"])
    node_differs |= still & ~(u0 == u1)
    return bad + int(np.sum(node_differs & still))


def quota_gap(apps: Apps, lam, n, c, m, idx, cap_cpu: float, cap_mem: float,
              dtype=np.float64) -> float:
    """One node's objective at its quotas above the optimum at its counts,
    relative (``allocation.solve_p1``). With ``dtype`` float32 the quotas are
    the float32 optimum's instead of the plan's."""
    cfg = apps.node_cfg(idx)
    lam_j, n_j = np.asarray(lam)[idx], np.asarray(n)[idx]
    best = ref.solve_p1(cfg, lam_j, cap_cpu, cap_mem, n_j)
    if dtype is np.float64:
        c_j, m_j = np.asarray(c)[idx], np.asarray(m)[idx]
    else:
        low = ref.solve_p1(cfg, lam_j, cap_cpu, cap_mem, n_j, dtype=dtype)
        c_j, m_j = low["c"], low["m"]
    if best["c"] is None or c_j is None:
        return np.inf
    got = ref.evaluate(cfg, lam_j, cap_cpu, cap_mem, n_j, c_j, m_j)["utility"]
    return max(0.0, (got - best["utility"]) / abs(best["utility"]))
