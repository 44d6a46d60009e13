"""Figs. 15-22: parameter studies — arrival rate λ, request size x̄, resource
caps R̄cpu/R̄mem, and the (α, β) trade-off heatmaps."""
from __future__ import annotations

import numpy as np

from benchmarks.common import ALPHA, BETA, emit, mean_latency, paper_apps, timed, total_power
from repro.core.crms import crms
from repro.core.problem import ServerCaps


def sweep_lambda():
    caps = ServerCaps(30.0, 10.0)
    lams = np.arange(4.0, 10.5, 1.0)
    delays, powers = [], []
    for lam in lams:
        apps = paper_apps(lam=(lam,) * 4)
        al = crms(apps, caps, ALPHA, BETA)
        delays.append(mean_latency(apps, al))
        powers.append(total_power(al))
    print("\nFig 15-16 — lambda sweep (caps 30/10, x=5)")
    for lam, d, p in zip(lams, delays, powers):
        print(f"  lam={lam:4.1f}  meanW={d:7.4f}s  power={p:7.1f}W")
    # power rises then plateaus once resources saturate
    plateau = powers[-1] <= max(powers) * 1.02
    return delays, powers, plateau


def sweep_xbar():
    caps = ServerCaps(30.0, 10.0)
    xs = np.arange(4.0, 8.5, 1.0)
    delays, powers = [], []
    for x in xs:
        apps = paper_apps(lam=(6.0,) * 4, xbar=(x,) * 4)
        al = crms(apps, caps, ALPHA, BETA)
        delays.append(mean_latency(apps, al))
        powers.append(total_power(al))
    print("\nFig 17-18 — request-size sweep (lam=6)")
    for x, d, p in zip(xs, delays, powers):
        print(f"  x={x:4.1f}  meanW={d:7.4f}s  power={p:7.1f}W")
    rising = delays[-1] > delays[0]
    return delays, powers, rising


def sweep_caps():
    delays_cpu = []
    for rcpu in np.arange(28.0, 39.0, 2.0):
        apps = paper_apps()
        al = crms(apps, ServerCaps(rcpu, 10.0), ALPHA, BETA)
        delays_cpu.append((rcpu, mean_latency(apps, al)))
    delays_mem = []
    for rmem in np.arange(6.5, 11.5, 1.0):
        apps = paper_apps()
        al = crms(apps, ServerCaps(30.0, rmem), ALPHA, BETA)
        delays_mem.append((rmem, mean_latency(apps, al)))
    print("\nFig 19-20 — resource-cap sweeps")
    for r, d in delays_cpu:
        print(f"  Rcpu={r:5.1f}  meanW={d:7.4f}s")
    for r, d in delays_mem:
        print(f"  Rmem={r:5.1f}GB  meanW={d:7.4f}s")
    mono_cpu = all(a[1] >= b[1] - 5e-3 for a, b in zip(delays_cpu, delays_cpu[1:]))
    mono_mem = all(a[1] >= b[1] - 5e-3 for a, b in zip(delays_mem, delays_mem[1:]))
    return mono_cpu, mono_mem


def heatmap_alpha_beta():
    apps = paper_apps(lam=(6.0,) * 4)
    caps = ServerCaps(30.0, 10.0)
    alphas = [0.6, 1.0, 1.4, 1.8]
    betas = [0.1, 0.2, 0.4, 0.8]
    delay_grid = np.zeros((len(alphas), len(betas)))
    power_grid = np.zeros_like(delay_grid)
    for i, a in enumerate(alphas):
        for j, b in enumerate(betas):
            al = crms(apps, caps, a, b)
            delay_grid[i, j] = mean_latency(apps, al)
            power_grid[i, j] = total_power(al)
    print("\nFig 21-22 — (alpha, beta) heatmaps (rows=alpha, cols=beta)")
    print("delay (s):")
    for i, a in enumerate(alphas):
        print(f"  a={a:3.1f} " + " ".join(f"{delay_grid[i, j]:7.3f}" for j in range(len(betas))))
    print("power (W):")
    for i, a in enumerate(alphas):
        print(f"  a={a:3.1f} " + " ".join(f"{power_grid[i, j]:7.1f}" for j in range(len(betas))))
    # beta raises delay / lowers power (paper's headline trend)
    delay_up = np.all(delay_grid[:, -1] >= delay_grid[:, 0] - 1e-6)
    power_down = np.all(power_grid[:, -1] <= power_grid[:, 0] + 1e-6)
    return bool(delay_up), bool(power_down)


def run() -> bool:
    (d_l, p_l, plateau), us = timed(sweep_lambda)
    d_x, p_x, rising = sweep_xbar()
    mono_cpu, mono_mem = sweep_caps()
    delay_up, power_down = heatmap_alpha_beta()
    ok = plateau and rising and mono_cpu and mono_mem and delay_up and power_down
    emit(
        "fig15_22_sweeps", us,
        f"power_plateau={plateau};xbar_delay_rises={rising};caps_monotone={mono_cpu and mono_mem};"
        f"beta_tradeoff={delay_up and power_down}",
    )
    return ok


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
