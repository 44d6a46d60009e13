"""The one traffic generator: a drifting arrival-rate trace read from a
traffic file's parameters and drawn from the run's seed.

Every epoch k of a cycle of ``cycle_epochs`` epochs gives app i the rate

    lam_i(k) = base_i * (1 + swing * sin(2 pi k / swing_period + phi_i)
                           + jitter * sin(2 pi k / jitter_period + jitter_phase_mult * phi_i))
                      * (1 + noise * z_ik),

the shape of the quasi-dynamic trace of the paper's Sec. V-B. A seed draws
the phases and the noise: phi_i = 2 pi (p_i + u) / M, with p a permutation
of the apps and u uniform in [0, 1), so the apps' peaks stay spread evenly
over the cycle and every seed offers the node the same swing; and z_ik
standard normal. That seed is the run's, or the traffic file's
``rates_seed`` where it has one: then every run gets the same cycle of
rates, and so the same sizes of work, and the run's seed only picks the
epoch it starts from.

A request stream walks the cycle from that epoch and wraps around for as
long as a window lasts. Every ``resize_every`` requests the node's CPU cap
moves to the next entry of ``resize_cpu_levels`` (a co-located system
reservation); the memory cap never moves.
"""
from __future__ import annotations

import dataclasses

import numpy as np

TWO_PI = 2.0 * np.pi


def cycle_rates(params: dict, base_lam, seed: int) -> np.ndarray:
    """(cycle_epochs, M) arrival rates of one cycle, drawn from ``seed``."""
    base = np.asarray(base_lam, dtype=float)
    m = base.shape[0]
    rng = np.random.default_rng([int(seed), 0x7261])
    phi = TWO_PI * (rng.permutation(m) + rng.uniform()) / m
    k = np.arange(int(params["cycle_epochs"]), dtype=float)[:, None]
    shape = (
        1.0
        + params["swing"] * np.sin(TWO_PI * k / params["swing_period"] + phi[None, :])
        + params["jitter"] * np.sin(
            TWO_PI * k / params["jitter_period"] + params["jitter_phase_mult"] * phi[None, :]
        )
    )
    z = rng.standard_normal(shape.shape)
    return base[None, :] * shape * (1.0 + params["noise"] * z)


@dataclasses.dataclass(frozen=True)
class Request:
    """One epoch of offered load: arrival rates and the node's caps."""

    epoch: int  # position in the cycle
    lam: np.ndarray  # (M,)
    r_cpu: float
    r_mem: float


def start_epoch(params: dict, seed: int) -> int:
    """Where in the cycle a run with this seed starts."""
    return int(np.random.default_rng([int(seed), 0x5354]).integers(int(params["cycle_epochs"])))


class RequestStream:
    """Endless stream of requests for one run: its cycle from the seed's
    start epoch, with the CPU cap stepping through its levels."""

    def __init__(self, params: dict, base_lam, r_cpu: float, r_mem: float, seed: int):
        self.rates = cycle_rates(params, base_lam, params.get("rates_seed", seed))
        self.start = start_epoch(params, seed)
        self.levels = tuple(float(v) for v in params.get("resize_cpu_levels", (1.0,)))
        self.every = int(params.get("resize_every", 0))
        self.r_cpu = float(r_cpu)
        self.r_mem = float(r_mem)

    def __getitem__(self, j: int) -> Request:
        epoch = (self.start + j) % self.rates.shape[0]
        level = self.levels[(j // self.every) % len(self.levels)] if self.every else 1.0
        return Request(epoch, self.rates[epoch].copy(), self.r_cpu * level, self.r_mem)

    def cycle(self) -> list[Request]:
        """One full cycle from the start epoch, at the configured caps."""
        n = self.rates.shape[0]
        return [Request((self.start + j) % n, self.rates[(self.start + j) % n].copy(),
                        self.r_cpu, self.r_mem) for j in range(n)]
