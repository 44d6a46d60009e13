"""Core layers: norms, RoPE, GQA/MQA attention (flash-style chunked reference
with a Pallas TPU kernel behind kernels.ops), gated MLPs.

Dtype discipline: params are created in ``param_dtype`` (f32 for training,
bf16 for serving); compute happens in ``compute_dtype`` (bf16) with f32
softmax/norm accumulations. No implicit f64 anywhere (x64 is enabled globally
for the CRMS math).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig


@dataclasses.dataclass(frozen=True)
class Runtime:
    """Execution context threaded through model apply."""

    mesh: Any = None  # jax Mesh or None (single device)
    data_axes: tuple = ("data",)  # axes sharding batch/tokens ("pod","data") multi-pod
    model_axis: str | None = "model"  # None: pure-DP (tiny models) — no tensor axis
    compute_dtype: Any = jnp.bfloat16
    attn_backend: str = "reference"  # reference | pallas (kernels.ops dispatch)
    seq_shard_acts: bool = False  # sequence-parallel residual stream (SP)

    @property
    def model_axis_size(self) -> int:
        if self.mesh is None or self.model_axis is None:
            return 1
        return self.mesh.shape[self.model_axis]


def constrain(x, runtime: Runtime, spec):
    """with_sharding_constraint if a mesh is active, else identity."""
    if runtime.mesh is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.lax.with_sharding_constraint(x, NamedSharding(runtime.mesh, spec))


def residual_constrain(x, runtime: Runtime):
    """Residual-stream sharding between blocks: batch over the data axes and,
    under sequence parallelism, S over the model axis."""
    from jax.sharding import PartitionSpec as P

    if (
        runtime.seq_shard_acts
        and runtime.model_axis is not None
        and x.ndim >= 3
        and x.shape[1] % max(runtime.model_axis_size, 1) == 0
        and x.shape[1] >= runtime.model_axis_size
    ):
        return constrain(x, runtime, P(runtime.data_axes, runtime.model_axis, None))
    return constrain(x, runtime, P(runtime.data_axes, None, None))


# ----------------------------------------------------------------------------
# Norms
# ----------------------------------------------------------------------------
def init_norm(cfg: ModelConfig, dtype) -> dict:
    init_val = jnp.zeros if cfg.norm_plus_one else jnp.ones
    return {"w": init_val((cfg.d_model,), dtype=dtype)}


def apply_norm(p, x, cfg: ModelConfig, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    w = p["w"].astype(jnp.float32)
    if cfg.norm_plus_one:
        w = 1.0 + w
    if cfg.norm == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + eps) * w
    else:  # rmsnorm
        ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
        out = xf * jax.lax.rsqrt(ms + eps) * w
    return out.astype(x.dtype)


# ----------------------------------------------------------------------------
# RoPE
# ----------------------------------------------------------------------------
def rope_embed(x, positions, theta: float):
    """x: (..., S, H, hd); positions: (..., S) int32 broadcastable."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]  # (..., S, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xr1 = x1.astype(jnp.float32) * cos - x2.astype(jnp.float32) * sin
    xr2 = x2.astype(jnp.float32) * cos + x1.astype(jnp.float32) * sin
    return jnp.concatenate([xr1, xr2], axis=-1).astype(x.dtype)


# ----------------------------------------------------------------------------
# Attention
# ----------------------------------------------------------------------------
def init_attention(key, cfg: ModelConfig, dtype, cross: bool = False) -> dict:
    hd = cfg.resolved_head_dim
    d = cfg.d_model
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s = d**-0.5
    src_d = d  # cross-attn keys/values come from d_model-projected memory
    p = {
        "wq": (jax.random.normal(k1, (d, cfg.n_heads, hd), jnp.float32) * s).astype(dtype),
        "wk": (jax.random.normal(k2, (src_d, cfg.kv_heads, hd), jnp.float32) * s).astype(dtype),
        "wv": (jax.random.normal(k3, (src_d, cfg.kv_heads, hd), jnp.float32) * s).astype(dtype),
        "wo": (jax.random.normal(k4, (cfg.n_heads, hd, d), jnp.float32) * (cfg.n_heads * hd) ** -0.5).astype(dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.n_heads, hd), dtype=dtype)
        p["bk"] = jnp.zeros((cfg.kv_heads, hd), dtype=dtype)
        p["bv"] = jnp.zeros((cfg.kv_heads, hd), dtype=dtype)
    return p


def apply_attention(
    p,
    x,
    cfg: ModelConfig,
    runtime: Runtime,
    *,
    positions,
    causal: bool = True,
    memory=None,  # cross-attention source (B, S_src, d) already normed
    cache=None,  # dict(k=(B,KV,T,hd), v=..., index=scalar) for decode
    use_rope: bool = True,
):
    """Returns (out (B,S,d), new_cache or None)."""
    hd = cfg.resolved_head_dim
    B, S, _ = x.shape
    dt = runtime.compute_dtype
    kv_src = memory if memory is not None else x

    q = jnp.einsum("bsd,dnh->bsnh", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dnh->bsnh", kv_src, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dnh->bsnh", kv_src, p["wv"].astype(dt))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)

    if use_rope and memory is None:
        q = rope_embed(q, positions, cfg.rope_theta)
        k = rope_embed(k, positions, cfg.rope_theta)

    from jax.sharding import PartitionSpec as P

    mdl = runtime.model_axis
    batch_sp = runtime.data_axes
    shard_mode = cfg.attn_shard_mode(runtime.model_axis_size)

    new_cache = None
    if cache is not None and S > 1:
        # prefill-fill: write the fresh k/v into the cache at [0, S), then
        # compute normal (flash) attention below as if cache were absent.
        k_cache = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k.transpose(0, 2, 1, 3).astype(cache["k"].dtype), 0, axis=2
        )
        v_cache = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v.transpose(0, 2, 1, 3).astype(cache["v"].dtype), 0, axis=2
        )
        new_cache = {"k": k_cache, "v": v_cache, "index": cache["index"]}
        cache = None
    if cache is not None:
        # decode: append this step's k/v at cache["index"], attend over prefix.
        # Cache layout (B, KV, T, hd): batch over data axes, T over model axis
        # (flash-decode; the softmax reductions over the sharded T become
        # small psums — see DESIGN.md §5).
        k_new = k.transpose(0, 2, 1, 3).astype(cache["k"].dtype)
        v_new = v.transpose(0, 2, 1, 3).astype(cache["v"].dtype)
        T_full = cache["k"].shape[2]
        axis_n = max(runtime.model_axis_size, 1)
        if runtime.mesh is not None and axis_n > 1 and T_full % axis_n == 0:
            # owner-shard in-place update: a naive dynamic_update_slice along
            # the model-sharded T dim makes GSPMD route the whole cache shard
            # through a collective every layer (~0.27 GB/layer observed);
            # instead each T-shard conditionally writes its own slice.
            from jax import shard_map

            data_n = 1
            for ax in batch_sp:
                data_n *= runtime.mesh.shape[ax]
            bsp = batch_sp if B % data_n == 0 else None

            def upd(kc, vc, kn, vn, idx):
                j = jax.lax.axis_index(mdl)
                t_loc = kc.shape[2]
                li = idx - j * t_loc
                in_range = jnp.logical_and(li >= 0, li < t_loc)
                li_safe = jnp.clip(li, 0, t_loc - 1)

                def write(ops):
                    kc_, vc_ = ops
                    return (
                        jax.lax.dynamic_update_slice_in_dim(kc_, kn, li_safe, 2),
                        jax.lax.dynamic_update_slice_in_dim(vc_, vn, li_safe, 2),
                    )

                return jax.lax.cond(in_range, write, lambda ops: ops, (kc, vc))

            k_cache, v_cache = shard_map(
                upd,
                mesh=runtime.mesh,
                in_specs=(
                    P(bsp, None, mdl, None), P(bsp, None, mdl, None),
                    P(bsp, None, None, None), P(bsp, None, None, None), P(),
                ),
                out_specs=(P(bsp, None, mdl, None), P(bsp, None, mdl, None)),
                check_vma=False,
            )(cache["k"], cache["v"], k_new, v_new, cache["index"])
        else:
            k_cache = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new, cache["index"], axis=2)
            v_cache = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new, cache["index"], axis=2)
        k_cache = constrain(k_cache, runtime, P(batch_sp, None, mdl, None))
        v_cache = constrain(v_cache, runtime, P(batch_sp, None, mdl, None))
        new_cache = {"k": k_cache, "v": v_cache, "index": cache["index"]}
        KV = cfg.kv_heads
        G = cfg.n_heads // KV
        qg = q.reshape(B, S, KV, G, hd)
        kk = k_cache.astype(dt)  # (B, KV, T, hd)
        vv = v_cache.astype(dt)
        scale = hd**-0.5
        s = jnp.einsum("bskgh,bkth->bkgst", qg, kk, preferred_element_type=jnp.float32) * scale
        T = kk.shape[2]
        valid = jnp.arange(T, dtype=jnp.int32) <= cache["index"]  # uniform decode step
        s = jnp.where(valid[None, None, None, None, :], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgst,bkth->bskgh", w.astype(dt), vv, preferred_element_type=jnp.float32)
        out = o.reshape(B, S, cfg.n_heads, hd).astype(dt)
    else:
        KV = cfg.kv_heads
        G = cfg.n_heads // KV
        qg = q.reshape(B, S, KV, G, hd)
        axis_n = max(runtime.model_axis_size, 1)
        if shard_mode == "sequence" and memory is None:
            # sequence-parallel attention: q blocks sharded over model, kv
            # replicated (GSPMD all-gathers kv once per block — ring-lite)
            qg = constrain(qg, runtime, P(batch_sp, mdl, None, None, None))
            k = constrain(k, runtime, P(batch_sp, None, None, None))
            v = constrain(v, runtime, P(batch_sp, None, None, None))
        elif KV % axis_n == 0:
            qg = constrain(qg, runtime, P(batch_sp, None, mdl, None, None))
            k = constrain(k, runtime, P(batch_sp, None, mdl, None))
            v = constrain(v, runtime, P(batch_sp, None, mdl, None))
        elif G % axis_n == 0:
            qg = constrain(qg, runtime, P(batch_sp, None, None, mdl, None))
            k = constrain(k, runtime, P(batch_sp, None, None, None))
            v = constrain(v, runtime, P(batch_sp, None, None, None))
        from repro.kernels import ops as kops

        out5 = kops.flash_attention(
            qg, k, v, causal=causal and memory is None, backend=runtime.attn_backend
        )
        out = out5.reshape(B, S, cfg.n_heads, hd).astype(dt)

    y = jnp.einsum("bsnh,nhd->bsd", out, p["wo"].astype(dt))
    y = constrain(y, runtime, P(batch_sp, None, None))
    return y, new_cache


# ----------------------------------------------------------------------------
# MLP
# ----------------------------------------------------------------------------
def init_mlp(key, cfg: ModelConfig, dtype, d_ff: int | None = None) -> dict:
    d_ff = d_ff or cfg.d_ff
    d = cfg.d_model
    k1, k2, k3 = jax.random.split(key, 3)
    s_in, s_out = d**-0.5, d_ff**-0.5
    p = {
        "w_up": (jax.random.normal(k2, (d, d_ff), jnp.float32) * s_in).astype(dtype),
        "w_down": (jax.random.normal(k3, (d_ff, d), jnp.float32) * s_out).astype(dtype),
    }
    if cfg.act in ("swiglu", "geglu"):
        p["w_gate"] = (jax.random.normal(k1, (d, d_ff), jnp.float32) * s_in).astype(dtype)
    return p


def apply_mlp(p, x, cfg: ModelConfig, runtime: Runtime):
    from jax.sharding import PartitionSpec as P

    dt = runtime.compute_dtype
    mdl = runtime.model_axis
    batch_sp = runtime.data_axes
    up = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(dt))
    up = constrain(up, runtime, P(batch_sp, None, mdl))
    if cfg.act == "swiglu":
        gate = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(dt))
        h = jax.nn.silu(gate) * up
    elif cfg.act == "geglu":
        gate = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(dt))
        h = jax.nn.gelu(gate, approximate=True) * up
    else:
        h = jax.nn.relu(up)
    h = constrain(h, runtime, P(batch_sp, None, mdl))
    y = jnp.einsum("bsf,fd->bsd", h, p["w_down"].astype(dt))
    y = constrain(y, runtime, P(batch_sp, None, None))
    return y
