"""Training step: microbatched gradient accumulation (lax.scan), remat'd model
forward, optimizer update. Optionally an int8 error-feedback compressed
cross-pod gradient reduction (beyond-paper optimization for the collective-
bound cells, §Perf).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import Runtime
from repro.models.model import lm_loss
from repro.train.optimizer import Optimizer


def make_train_step(cfg: ModelConfig, runtime: Runtime, optimizer: Optimizer,
                    microbatches: int | None = None):
    """Returns train_step(params, opt_state, batch) -> (params, opt_state, metrics).

    batch: dict(tokens (B,S) int32, labels (B,S) int32 [, patches | frames]).
    """
    mb = microbatches if microbatches is not None else cfg.microbatches

    def loss_fn(params, micro):
        extra = {k: v for k, v in micro.items() if k not in ("tokens", "labels")}
        loss, metrics = lm_loss(params, cfg, runtime, micro["tokens"], micro["labels"], extra)
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def train_step(params, opt_state, batch):
        if mb <= 1:
            (loss, metrics), grads = grad_fn(params, batch)
        else:
            def split(x):
                return x.reshape((mb, x.shape[0] // mb) + x.shape[1:])

            micro_all = jax.tree.map(split, batch)

            def body(carry, micro):
                g_acc, l_acc = carry
                (loss, _), grads = grad_fn(params, micro)
                g_acc = jax.tree.map(lambda a, g: a + g.astype(jnp.float32), g_acc, grads)
                return (g_acc, l_acc + loss), None

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, loss), _ = jax.lax.scan(body, (g0, jnp.zeros((), jnp.float32)), micro_all)
            grads = jax.tree.map(lambda g: g / mb, grads)
            loss = loss / mb
            metrics = {"nll": loss, "aux": jnp.zeros((), jnp.float32)}

        params_new, opt_state_new = optimizer.update(grads, opt_state, params)
        gnorm = jnp.sqrt(
            sum(jnp.sum(g.astype(jnp.float32) ** 2) for g in jax.tree.leaves(grads))
        )
        metrics = dict(metrics, loss=loss, grad_norm=gnorm)
        return params_new, opt_state_new, metrics

    return train_step


# ----------------------------------------------------------------------------
# int8 error-feedback compressed cross-pod gradient all-reduce (beyond-paper)
# ----------------------------------------------------------------------------
def compress_allreduce_pod(grads, mesh, error_state, axis: str = "pod"):
    """Quantize each gradient leaf to int8 (per-tensor scale), all-reduce the
    int8 payload across pods, dequantize, and carry the quantization error to
    the next step (error feedback — keeps convergence unbiased in practice).
    Cuts cross-pod gradient bytes 4x vs f32 / 2x vs bf16.

    Runs inside shard_map over the pod axis with other axes left to GSPMD.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    npods = mesh.shape[axis]

    def one(g, err):
        g = g.astype(jnp.float32) + err
        scale = jnp.maximum(jnp.max(jnp.abs(g)), 1e-12) / 127.0
        q = jnp.clip(jnp.round(g / scale), -127, 127).astype(jnp.int8)
        deq = q.astype(jnp.float32) * scale
        new_err = g - deq

        def reduce_fn(qv, sv):
            qsum = jax.lax.psum(qv.astype(jnp.int32), axis)
            ssum = jax.lax.psum(sv, axis)  # scales differ per pod: use mean scale
            return qsum.astype(jnp.float32) * (ssum / npods) / npods

        red = shard_map(
            reduce_fn, mesh=mesh,
            in_specs=(P(), P()), out_specs=P(), check_vma=False,
        )(q, scale[None] if scale.ndim == 0 else scale)
        return red, new_err

    flat_g, tree = jax.tree_util.tree_flatten(grads)
    flat_e = jax.tree_util.tree_leaves(error_state)
    out = [one(g, e) for g, e in zip(flat_g, flat_e)]
    new_g = jax.tree_util.tree_unflatten(tree, [o[0] for o in out])
    new_e = jax.tree_util.tree_unflatten(tree, [o[1] for o in out])
    return new_g, new_e
