"""jit'd public wrappers for the Pallas kernels with backend dispatch.

backend:
  'auto'      — pallas on TPU, reference elsewhere (the dry-run lowers the
                reference path, which shares the kernels' FLOP/byte structure)
  'pallas'    — compiled Pallas TPU kernel
  'interpret' — Pallas kernel body interpreted on CPU (correctness tests)
  'reference' — pure-jnp oracle (ref.py)
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve(backend: str) -> str:
    if backend in ("auto", None):
        return "pallas" if _on_tpu() else "reference"
    return backend


# ----------------------------------------------------------------------------
# flash attention — q (B,Sq,KV,G,hd), k/v (B,Skv,KV,hd)
# ----------------------------------------------------------------------------
def flash_attention(q, k, v, causal: bool = True, backend: str = "auto"):
    mode = _resolve(backend)
    if mode == "reference":
        return _ref.flash_attention(q, k, v, causal)
    from repro.kernels.flash_attention import flash_attention_fwd

    B, Sq, KV, G, hd = q.shape
    qh = q.transpose(0, 2, 3, 1, 4).reshape(B, KV * G, Sq, hd)
    kh = k.transpose(0, 2, 1, 3)
    vh = v.transpose(0, 2, 1, 3)
    out = flash_attention_fwd(qh, kh, vh, causal=causal, interpret=(mode == "interpret"))
    return out.reshape(B, KV, G, Sq, hd).transpose(0, 3, 1, 2, 4)


# ----------------------------------------------------------------------------
# SSD chunk scan — xh (B,S,H,P), bmat/cmat (B,S,N), da (B,S,H)
# ----------------------------------------------------------------------------
def ssd_chunks(xh, bmat, cmat, da, chunk: int = 128, backend: str = "auto"):
    mode = _resolve(backend)
    if mode == "reference":
        return _ref.ssd_chunks(xh, bmat, cmat, da, chunk)
    from repro.kernels.ssd import ssd_chunk_fwd

    B, S, H, P = xh.shape
    N = bmat.shape[-1]
    Q = min(chunk, S)
    nc = S // Q
    y_diag, states = ssd_chunk_fwd(
        xh.astype(jnp.float32), bmat.astype(jnp.float32), cmat.astype(jnp.float32),
        da.astype(jnp.float32), chunk=Q, interpret=(mode == "interpret"),
    )
    # inter-chunk recurrence + off-diagonal contribution (tiny, stays in jnp)
    da_c = da.reshape(B, nc, Q, H)
    da_cum = jnp.cumsum(da_c, axis=2)
    chunk_decay = jnp.exp(da_cum[:, :, -1, :])  # (B,nc,H)

    def step(s_prev, inp):
        s_new, dec = inp
        carry = s_new + dec[..., None, None] * s_prev
        return carry, s_prev

    s0 = jnp.zeros_like(states[:, 0])
    final_state, s_in = jax.lax.scan(
        step, s0, (states.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(1, 0, 2))
    )
    s_in = s_in.transpose(1, 0, 2, 3, 4)  # (B,nc,H,P,N)
    cc = cmat.reshape(B, nc, Q, N)
    decay_in = jnp.exp(da_cum)
    y_off = jnp.einsum("bnts,bnth,bnhps->bnthp", cc.astype(jnp.float32), decay_in, s_in)
    y = y_diag.reshape(B, nc, Q, H, P) + y_off
    return y.reshape(B, S, H, P), final_state


# ----------------------------------------------------------------------------
# CRMS candidate grid — see crms_grid.py
# ----------------------------------------------------------------------------
def crms_grid(kappa, lam, xbar, n, c, m, *, caps_cpu, power_span, alpha, beta,
              backend: str = "auto", reduce: str = "sum"):
    mode = _resolve(backend)
    if mode == "reference":
        ref_fn = _ref.crms_grid_terms if reduce == "per_app" else _ref.crms_grid_utility
        return ref_fn(
            jnp.asarray(kappa), jnp.asarray(lam), jnp.asarray(xbar),
            jnp.asarray(n), jnp.asarray(c), jnp.asarray(m),
            caps_cpu, power_span, alpha, beta,
        )
    from repro.kernels.crms_grid import crms_grid_eval

    # host arrays stay on the host: the kernel's operands are built in NumPy
    return crms_grid_eval(
        kappa, lam, xbar, n, c, m,
        caps_cpu=caps_cpu, power_span=power_span, alpha=alpha, beta=beta,
        interpret=(mode == "interpret"), reduce=reduce,
    )
