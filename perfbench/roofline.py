"""Peaks of the chip and the work a kernel's call needs, for roofline shares.

The peaks come from ``peaks.json``, keyed by JAX's ``device_kind``; a kind
missing from the table is an error. The work counts are taken from the
shapes and arguments of a call, from what the computation needs and not from
how the kernel under test carries it out.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in {PEAKS_FILE.name}")
    return table[device_kind]


# The grid sweep of CRMS's phase-1 seeding evaluates, for every cell
# (candidate row b, app i) with container count n = n[b, i] and quotas
# (c, m), the app's term of Eq. (8):
#   Eq. (1) and mu: exp, mul, sub, div, div, exp, add; mu = 1000/(xbar d): mul, div  -> 9
#   a = lam/mu, rho = a/n, log a                                                   -> 3
#   Erlang-C head: sum over k < n of exp(k log a - log k!), log k! by one log and
#   one add per term: log, add, mul, sub, exp, add                                 -> 6 per term
#   tail and Lq: log n!, n log a, log(1-rho) x2, log rho, adds and muls, exp       -> 14
#   Ws = (Lq + a)/lam; dP = span n c / R; U = alpha Ws + beta dP/lam               -> 9
GRID_FLOPS_PER_CELL = 9 + 3 + 14 + 9
GRID_FLOPS_PER_TERM = 6
# Each cell reads n, c, m and writes its term, 4 bytes each (float32); each
# call also reads kappa (3), lam and xbar per app once.
GRID_BYTES_PER_CELL = 4 * 4
GRID_BYTES_PER_APP = 4 * 5


def crms_grid_work(n) -> tuple[float, float]:
    """(FLOPs, bytes) one grid sweep needs, from its (B, M) container counts."""
    n = np.asarray(n, dtype=float)
    cells = n.size
    flops = GRID_FLOPS_PER_CELL * cells + GRID_FLOPS_PER_TERM * float(np.sum(n))
    bytes_ = GRID_BYTES_PER_CELL * cells + GRID_BYTES_PER_APP * n.shape[-1]
    return float(flops), float(bytes_)


def roofline_share(flops: float, bytes_: float, seconds: float, device_kind: str):
    """(share of the roofline in %, the bound that applies): the least time the
    chip could take for the work, over the time it took."""
    pk = peaks(device_kind)
    t_flops = flops / pk["flops_per_s"]
    t_bytes = bytes_ / pk["hbm_bytes_per_s"]
    bound = "compute" if t_flops >= t_bytes else "memory"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
