"""Pallas TPU kernel for the paper's own compute hot-spot: batched evaluation
of Problem-P candidate allocations (Eq.(1) latency -> service rate -> Erlang-C
Ws -> utility). RS/GPBO/TPEBO score tens of thousands of candidates per
optimization cycle; each costs an O(MAX_N) masked log-sum per app for pi0.
CRMS phase-1 grid seeding (engine.grid_seed_chints) sweeps coarse (c, m)
quota grids through the same kernel in per-app output mode.

Grid tiles the candidate axis; per tile the kernel evaluates a (CB, M) block
of candidates fully on-chip (VPU transcendentals, no HBM round-trips for the
intermediate N-term series). The k-sum is a streaming logsumexp under one
``lax.fori_loop`` (an unrolled Python loop at MAX_N=128 dominated trace and
compile time). f32 throughout (the oracle runs f64; tests bound the drift).

``reduce`` selects the output: "sum" (B,) totals Eq. (8) over apps;
"per_app" (B, M) keeps each app's utility term — the argmin input for grid
seeding (the budget coupling is handled downstream by phase-1 scaling).

The ``pallas_call`` is built once per static signature (tiles, lane-padded
apps, output mode) and reused: ``caps_cpu``, ``power_span``, ``alpha`` and
``beta`` reach the kernel as one float32 SMEM operand, not as constants of
its closure, so a later call at a seen shape traces, lowers and compiles
nothing. Called eagerly, the kernel is its own device program
(``jit_wrapped``); under an outer ``jax.jit`` it inlines into that program.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import obs

MAX_N = 128  # supported container count in-kernel (edge scenarios: N <= ~40)
_HALF_LOG_2PI = 0.5 * math.log(2.0 * math.pi)  # a Python float: stays weakly typed


def _crms_kernel(s_ref, kappa_ref, lam_ref, xbar_ref, n_ref, c_ref, m_ref, u_ref, *,
                 n_apps: int, per_app: bool):
    obs.retraced("crms_grid", shape=obs.shape(*n_ref.shape))  # the block
    caps_cpu, power_span, alpha, beta = s_ref[0], s_ref[1], s_ref[2], s_ref[3]
    k1 = kappa_ref[0, :]
    k2 = kappa_ref[1, :]
    k3 = kappa_ref[2, :]
    lam = lam_ref[0, :]
    xbar = xbar_ref[0, :]
    n = n_ref[...].astype(jnp.float32)  # (CB, M)
    c = c_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)

    d_ms = k1 / (1.0 - jnp.exp(-k2 * c)) + jnp.exp(k3 / m)
    mu = 1000.0 / (xbar * d_ms)
    a = lam / mu
    rho = lam / (n * mu)
    rho_s = jnp.minimum(rho, 1.0 - 1e-6)
    log_a = jnp.log(a)

    # lgamma(n+1) via Stirling: the start value, kept only for n >= MAX_N
    nn = jnp.maximum(n, 1.0)
    stirling = (nn + 0.5) * jnp.log(nn) - nn + _HALF_LOG_2PI + 1.0 / (12.0 * nn)

    # log sum_{k=0}^{N-1} a^k/k! — streaming logsumexp over k as one fori_loop
    # carry (running max, rescaled running sum, log k!); k=0 term is log 1 = 0.
    # The same carry picks up log n! exactly at k = n: Stirling's series cut
    # after 1/(12n) is off by ~1e-4 in log n! at n = 3.
    def lse_step(kk, carry):
        run_max, run_sum, log_fact, log_nfact = carry
        kf = kk.astype(jnp.float32)
        log_fact = log_fact + jnp.log(kf)
        term = kf * log_a - log_fact
        valid = n > kf
        new_max = jnp.where(valid, jnp.maximum(run_max, term), run_max)
        run_sum = run_sum * jnp.exp(run_max - new_max) + jnp.where(
            valid, jnp.exp(term - new_max), 0.0
        )
        log_nfact = jnp.where(n == kf, log_fact, log_nfact)
        return new_max, run_sum, log_fact, log_nfact

    # int32 bounds: Python-int bounds give an int64 index when x64 is on
    run_max, run_sum, _, log_nfact = jax.lax.fori_loop(
        jnp.int32(1), jnp.int32(MAX_N), lse_step,
        (jnp.zeros_like(a), jnp.ones_like(a), jnp.zeros_like(a), stirling),
    )
    log_head = run_max + jnp.log(run_sum)

    log_tail = n * log_a - log_nfact - jnp.log1p(-rho_s)
    log_pi0 = -jnp.logaddexp(log_head, log_tail)
    log_lq = n * log_a - log_nfact + jnp.log(rho_s) - 2.0 * jnp.log1p(-rho_s) + log_pi0
    ls = jnp.exp(log_lq) + a
    ws = ls / lam
    ws = jnp.where(rho < 1.0, ws, 1e9)  # unstable -> huge

    dp = power_span * n * c / caps_cpu
    util = alpha * ws + beta * dp / lam
    mask = jax.lax.broadcasted_iota(jnp.int32, util.shape, 1) < n_apps
    if per_app:
        u_ref[...] = jnp.where(mask, util, 1e9)
    else:
        u_ref[...] = jnp.sum(jnp.where(mask, util, 0.0), axis=1, keepdims=True)


# Block index maps return int32 explicitly: a Python-int block index becomes
# int64 when x64 is on, which Mosaic cannot lower.
def _scalars(i):
    return (jnp.int32(0),)


def _whole(i):
    return jnp.int32(0), jnp.int32(0)


def _row_tile(i):
    return i, jnp.int32(0)


@functools.lru_cache(maxsize=32)
def _grid_call(nb: int, CB: int, Mp: int, n_apps: int, per_app: bool, interpret: bool):
    """The ``pallas_call`` for one static signature. Its jitted wrapper keeps
    the compiled program, so every later call at these shapes reuses it."""
    out_cols = Mp if per_app else 1
    return pl.pallas_call(
        functools.partial(_crms_kernel, n_apps=n_apps, per_app=per_app),
        grid=(nb,),
        in_specs=[
            # caps_cpu, power_span, alpha, beta
            pl.BlockSpec((4,), _scalars, memory_space=pltpu.SMEM),
            pl.BlockSpec((3, Mp), _whole),
            pl.BlockSpec((1, Mp), _whole),
            pl.BlockSpec((1, Mp), _whole),
            pl.BlockSpec((CB, Mp), _row_tile),
            pl.BlockSpec((CB, Mp), _row_tile),
            pl.BlockSpec((CB, Mp), _row_tile),
        ],
        out_specs=pl.BlockSpec((CB, out_cols), _row_tile),
        out_shape=jax.ShapeDtypeStruct((nb * CB, out_cols), jnp.float32),
        interpret=interpret,
    )


def _xp(*xs):
    """NumPy where every input is on the host, so building the kernel's
    operands dispatches nothing to the device; ``jnp`` for device arrays and
    tracers."""
    return jnp if any(isinstance(x, jax.Array) for x in xs) else np


def crms_grid_eval(kappa, lam, xbar, n, c, m, *, caps_cpu, power_span, alpha, beta,
                   block: int = 256, interpret: bool = False, reduce: str = "sum"):
    """kappa (M,3) f32; lam/xbar (M,); n/c/m (B,M). Returns utility (B,) when
    ``reduce="sum"``, per-app utility terms (B, M) when ``reduce="per_app"``."""
    if reduce not in ("sum", "per_app"):
        raise ValueError(f"reduce must be 'sum' or 'per_app', got {reduce!r}")
    per_app = reduce == "per_app"
    B, M = n.shape
    Mp = max(8 * ((M + 7) // 8), 8)  # lane-pad the app axis
    CB = min(block, B)
    nb = pl.cdiv(B, CB)
    pad_b = nb * CB - B
    xp = _xp(kappa, lam, xbar, n, c, m)

    def pad(x, rows, fill):
        return xp.pad(xp.asarray(x, dtype=np.float32), ((0, rows), (0, Mp - M)),
                      constant_values=fill)

    kpad = pad(xp.transpose(kappa), 0, 1.0)
    lpad = pad(xp.reshape(lam, (1, M)), 0, 1.0)
    xpad = pad(xp.reshape(xbar, (1, M)), 0, 1.0)
    # pad candidates: n=2, c=m=1 keeps padded columns finite; they are masked out
    npad = pad(n, pad_b, 2.0)
    cpad = pad(c, pad_b, 1.0)
    mpad = pad(m, pad_b, 1.0)
    scalars = (caps_cpu, power_span, alpha, beta)
    s = _xp(*scalars).asarray(scalars, dtype=np.float32)  # x64 is on: say float32

    u = _grid_call(nb, CB, Mp, M, per_app, bool(interpret))(
        s, kpad, lpad, xpad, npad, cpad, mpad)
    if per_app:
        return u[:B, :M]
    return u[:B, 0]
