"""An edge node of the program, built from a configuration file."""
from __future__ import annotations


def apps(cfg: dict) -> list:
    """The configuration's apps as the program's ``App``s, at their base rates."""
    from repro.core.problem import App

    return [App(name=a["name"], lam=a["lam"], xbar=a["xbar"], kappa=tuple(a["kappa"]),
                r_min=a["r_min"], r_max=a["r_max"], cpu_min=a["cpu_min"],
                cpu_max=a["cpu_max"]) for a in cfg["apps"]]


def caps(cfg: dict, r_cpu: float | None = None):
    """The node's ``ServerCaps``, the CPU cap resized to ``r_cpu`` if given."""
    from repro.core.power import PowerModel
    from repro.core.problem import ServerCaps

    power = PowerModel(p_idle=cfg["power_w"]["p_idle"], p_full=cfg["power_w"]["p_full"])
    cpu = cfg["caps"]["r_cpu"] if r_cpu is None else r_cpu
    return ServerCaps(float(cpu), float(cfg["caps"]["r_mem"]), power)


def request(cfg: dict, node_apps: list, lam, r_cpu: float | None = None):
    """An ``AllocRequest`` for the node at rates ``lam``."""
    from repro.api import AllocRequest

    return AllocRequest([a.with_lam(float(x)) for a, x in zip(node_apps, lam)],
                        caps(cfg, r_cpu), alpha=float(cfg["alpha"]), beta=float(cfg["beta"]))


def policy(cfg: dict, threshold: float):
    """The configuration's policy behind the quasi-dynamic cache (§V-B)."""
    from repro.api import QuasiDynamicPolicy, get_policy

    return QuasiDynamicPolicy(get_policy(cfg["policy"]), threshold=float(threshold))
