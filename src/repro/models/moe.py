"""Mixture-of-Experts with expert parallelism.

Router + top-k run as plain pjit ops; the dispatch/compute/combine runs in one
of three modes (DESIGN.md §5):

  local       — single device / model_axis==1: capacity-based scatter->batched
                expert matmul->gather. Also the numerical oracle for tests.
  a2a         — shard_map EP: tokens split across the model axis, scattered
                into fixed-capacity per-expert buffers, exchanged with a tiled
                all_to_all, expert-computed locally (experts sharded over
                'model'), returned with the inverse all_to_all. Used whenever
                the local token count divides the model axis (train/prefill).
  replicated  — decode-sized token counts: every model shard dispatches all
                its data-shard tokens to its local experts; combine via psum.

Capacity-factor drops are standard (tokens over capacity fall through with a
zero update); tests use cf=E/top_k to make the paths exactly dropless and
comparable against the dense oracle.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.layers import Runtime, constrain


def init_moe(key, cfg: ModelConfig, dtype) -> dict:
    m = cfg.moe
    d, f, E = cfg.d_model, m.d_ff_expert, m.n_experts
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s_in, s_out = d**-0.5, f**-0.5
    return {
        "router": (jax.random.normal(k1, (d, E), jnp.float32) * s_in).astype(jnp.float32),
        "w_gate": (jax.random.normal(k2, (E, d, f), jnp.float32) * s_in).astype(dtype),
        "w_up": (jax.random.normal(k3, (E, d, f), jnp.float32) * s_in).astype(dtype),
        "w_down": (jax.random.normal(k4, (E, f, d), jnp.float32) * s_out).astype(dtype),
    }


def _capacity(n_tokens: int, k: int, E: int, cf: float) -> int:
    c = int(math.ceil(n_tokens * k * cf / E))
    return max(8 * ((c + 7) // 8), 8)


def _dispatch_positions(ids_flat, E):
    """Position of each (token, k) slot within its expert's buffer."""
    one_hot = jax.nn.one_hot(ids_flat, E, dtype=jnp.int32)  # (Tk, E)
    pos = jnp.cumsum(one_hot, axis=0) - one_hot
    return jnp.sum(pos * one_hot, axis=-1)  # (Tk,)


def _expert_ffn(xe, w_gate, w_up, w_down, act: str, dt):
    """xe: (E, C, d); weights (E, d, f)/(E, f, d)."""
    gate = jnp.einsum("ecd,edf->ecf", xe, w_gate.astype(dt))
    up = jnp.einsum("ecd,edf->ecf", xe, w_up.astype(dt))
    if act == "geglu":
        h = jax.nn.gelu(gate, approximate=True) * up
    else:
        h = jax.nn.silu(gate) * up
    return jnp.einsum("ecf,efd->ecd", h, w_down.astype(dt))


def _moe_block_local(x2, ids, pk, w_gate, w_up, w_down, E, k, C, act, dt):
    """Scatter -> expert matmul -> gather on one shard. x2: (T, d).

    The dispatch loops over the k routing slots (k <= 6) instead of
    materializing a (T*k, d) repeat buffer — that buffer otherwise becomes a
    per-layer residual under scan+remat and dominates HBM (observed 71 GB/dev
    on moonshot train_4k before this change)."""
    T, d = x2.shape
    ids_flat = ids.reshape(-1)  # (Tk,) — token-major
    pos_flat = _dispatch_positions(ids_flat, E).reshape(T, k)
    keep = pos_flat < C
    xe = jnp.zeros((E, C, d), dtype=x2.dtype)
    for i in range(k):
        xe = xe.at[ids[:, i], jnp.where(keep[:, i], pos_flat[:, i], 0)].add(
            jnp.where(keep[:, i, None], x2, 0), mode="drop"
        )
    ye = _expert_ffn(xe, w_gate, w_up, w_down, act, dt)
    y = jnp.zeros((T, d), dtype=ye.dtype)
    for i in range(k):
        y_i = ye[ids[:, i], jnp.where(keep[:, i], pos_flat[:, i], 0)]
        y = y + jnp.where(keep[:, i, None], y_i, 0) * pk[:, i, None].astype(dt)
    return y


def apply_moe(p, x, cfg: ModelConfig, runtime: Runtime, cf: float = 1.25):
    """Returns (y (B,S,d), aux load-balance loss scalar f32)."""
    m = cfg.moe
    E, k = m.n_experts, m.top_k
    B, S, d = x.shape
    dt = runtime.compute_dtype
    act = cfg.act

    logits = jnp.einsum("bsd,de->bse", x, p["router"].astype(dt)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    pk, ids = jax.lax.top_k(probs, k)  # (B,S,k)
    pk = pk / jnp.maximum(pk.sum(-1, keepdims=True), 1e-9)

    # load-balance aux (Switch-style): E * sum_e f_e * P_e
    f_e = jnp.mean(jnp.sum(jax.nn.one_hot(ids, E, dtype=jnp.float32), axis=2), axis=(0, 1))
    p_e = jnp.mean(probs, axis=(0, 1))
    aux = E * jnp.sum(f_e * p_e)

    mesh = runtime.mesh
    axis_n = runtime.model_axis_size
    mdl = runtime.model_axis
    batch_sp = runtime.data_axes

    if mesh is None or axis_n <= 1:
        C = _capacity(B * S, k, E, cf)
        y = _moe_block_local(
            x.reshape(-1, d), ids.reshape(-1, k), pk.reshape(-1, k),
            p["w_gate"], p["w_up"], p["w_down"], E, k, C, act, dt,
        )
        return y.reshape(B, S, d), aux

    from jax import shard_map

    data_shards = 1
    for ax in batch_sp:
        data_shards *= mesh.shape[ax]
    b_loc = max(B // data_shards, 1)
    t_loc = b_loc * S  # tokens per data shard (model-replicated)

    if t_loc % axis_n == 0 and t_loc >= axis_n:
        # ---- a2a mode: split tokens across the model axis ----
        t_my = t_loc // axis_n
        C_loc = _capacity(t_my, k, E, cf)

        def fn(x_blk, ids_blk, pk_blk, w_gate, w_up, w_down):
            tb = x_blk.shape[0] * x_blk.shape[1]
            x2 = x_blk.reshape(tb, d)
            ids2 = ids_blk.reshape(tb, k)
            pk2 = pk_blk.reshape(tb, k)
            j = jax.lax.axis_index(mdl)
            t_my_ = tb // axis_n
            x_my = jax.lax.dynamic_slice_in_dim(x2, j * t_my_, t_my_, axis=0)
            ids_my = jax.lax.dynamic_slice_in_dim(ids2, j * t_my_, t_my_, axis=0)
            pk_my = jax.lax.dynamic_slice_in_dim(pk2, j * t_my_, t_my_, axis=0)

            ids_flat = ids_my.reshape(-1)
            pos = _dispatch_positions(ids_flat, E).reshape(t_my_, k)
            keep = pos < C_loc
            buf = jnp.zeros((E, C_loc, d), dtype=x_my.dtype)
            for i in range(k):
                buf = buf.at[ids_my[:, i], jnp.where(keep[:, i], pos[:, i], 0)].add(
                    jnp.where(keep[:, i, None], x_my, 0), mode="drop"
                )
            # exchange: (E=axis_n*E_loc, C_loc, d) -> (E_loc, axis_n*C_loc, d)
            recv = jax.lax.all_to_all(buf, mdl, split_axis=0, concat_axis=1, tiled=True)
            ye = _expert_ffn(recv, w_gate, w_up, w_down, act, dt)
            back = jax.lax.all_to_all(ye, mdl, split_axis=1, concat_axis=0, tiled=True)
            y_my = jnp.zeros((t_my_, d), dtype=back.dtype)
            for i in range(k):
                y_i = back[ids_my[:, i], jnp.where(keep[:, i], pos[:, i], 0)]
                y_my = y_my + jnp.where(keep[:, i, None], y_i, 0) * pk_my[:, i, None].astype(dt)
            y = jax.lax.all_gather(y_my, mdl, axis=0, tiled=True)  # (tb, d)
            return y.reshape(x_blk.shape)

        y = shard_map(
            fn,
            mesh=mesh,
            in_specs=(
                P(batch_sp, None, None),
                P(batch_sp, None, None),
                P(batch_sp, None, None),
                P(mdl, None, None),
                P(mdl, None, None),
                P(mdl, None, None),
            ),
            out_specs=P(batch_sp, None, None),
            check_vma=False,
        )(x, ids, pk, p["w_gate"], p["w_up"], p["w_down"])
        return y, aux

    # ---- replicated mode (decode-sized): all local tokens on every model
    # shard, each computes its local experts, combine with psum ----
    # (B=1 long-context decode cannot shard batch at all -> fully replicated)
    tok_sp = batch_sp if B % data_shards == 0 else None
    C = _capacity(max(t_loc, 1), k, E, cf)
    E_loc = E // axis_n

    def fn(x_blk, ids_blk, pk_blk, w_gate, w_up, w_down):
        tb = x_blk.shape[0] * x_blk.shape[1]
        x2 = x_blk.reshape(tb, d)
        ids2 = ids_blk.reshape(tb, k)
        pk2 = pk_blk.reshape(tb, k)
        j = jax.lax.axis_index(mdl)
        # map global expert ids to local slots; non-local -> dropped
        local_ids = ids2 - j * E_loc
        is_mine = (local_ids >= 0) & (local_ids < E_loc)
        ids_loc = jnp.where(is_mine, local_ids, 0)
        pos = _dispatch_positions(ids_loc.reshape(-1), E_loc).reshape(tb, k)
        keep = (pos < C) & is_mine
        buf = jnp.zeros((E_loc, C, d), dtype=x2.dtype)
        for i in range(k):
            buf = buf.at[ids_loc[:, i], jnp.where(keep[:, i], pos[:, i], 0)].add(
                jnp.where(keep[:, i, None], x2, 0), mode="drop"
            )
        ye = _expert_ffn(buf, w_gate, w_up, w_down, act, dt)
        y = jnp.zeros((tb, d), dtype=ye.dtype)
        for i in range(k):
            y_i = ye[ids_loc[:, i], jnp.where(keep[:, i], pos[:, i], 0)]
            y = y + jnp.where(keep[:, i, None], y_i, 0) * pk2[:, i, None].astype(dt)
        y = jax.lax.psum(y, mdl)
        return y.reshape(x_blk.shape)

    x_in = constrain(x, runtime, P(tok_sp, None, None))
    y = shard_map(
        fn,
        mesh=mesh,
        in_specs=(
            P(tok_sp, None, None),
            P(tok_sp, None, None),
            P(tok_sp, None, None),
            P(mdl, None, None),
            P(mdl, None, None),
            P(mdl, None, None),
        ),
        out_specs=P(tok_sp, None, None),
        check_vma=False,
    )(x_in, ids, pk, p["w_gate"], p["w_up"], p["w_down"])
    return y, aux
