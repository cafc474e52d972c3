"""LM train/serve step builders: jitted, sharded, donated, accumulating.

``make_train_step`` returns the jitted update plus the state/batch shardings
the launcher (and dry-run) feed to ``.lower()``. Features:

* gradient accumulation (scan over microbatches — the global batch stays
  the cell's value while per-device live activations shrink);
* optional int8+error-feedback gradient quantize/dequantize at the optimizer
  boundary (wire-format of the cross-pod reduce; see optim/compression.py);
* global-norm clipping, donated state, f32 Adam moments over bf16 params;
* ``sync_axis``: the explicit data-parallel mode — the step assumes it runs
  inside a ``shard_map`` over that mesh axis and reduces gradients across it
  with the hand-written collective (``dist.collectives.sync_grads``; int8
  shared-scale wire when ``compression=True``) between ``value_and_grad``
  and the optimizer. ``make_data_parallel_step`` builds the wrapped step.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.dist.partition import (LM_RULES, batch_shardings, cache_shardings,
                                  param_shardings, state_shardings)
from repro.models.lm import transformer as T
from repro.optim import adamw
from repro.optim.compression import ef_init, ef_compress_update, int8_decompress
from repro.optim.optimizer import apply_updates

Array = Any

__all__ = ["TrainState", "make_train_state", "make_train_step",
           "make_data_parallel_step", "make_prefill_step", "make_decode_step",
           "shaped_batch", "shaped_state", "shaped_cache"]


class TrainState(NamedTuple):
    params: Any
    opt_state: Any
    ef: Any          # error-feedback residuals or None


def make_train_state(cfg: ModelConfig, key, opt, *, compression: bool = False
                     ) -> TrainState:
    params = T.init_params(cfg, key)
    ef = ef_init(params) if compression else None
    return TrainState(params=params, opt_state=opt.init(params), ef=ef)


def _split_microbatches(batch: dict, accum: int) -> dict:
    return {k: v.reshape(accum, v.shape[0] // accum, *v.shape[1:])
            for k, v in batch.items()}


def make_train_step(cfg: ModelConfig, *, lr=3e-4, weight_decay: float = 0.1,
                    clip_norm: float = 1.0, accum: int = 1,
                    compression: bool = False,
                    sync_axis: Optional[str] = None):
    """Returns (step_fn, opt). step_fn(state, batch) -> (state, metrics).

    ``sync_axis`` switches gradient handling to the explicit data-parallel
    mode: the step must then run inside a ``shard_map`` over that axis
    (see :func:`make_data_parallel_step`) and reduces the gradient tree
    across it before ``opt.update`` — exact fp32 psum, or, with
    ``compression=True``, the int8 shared-scale wire of
    ``dist.collectives.compressed_psum`` (the hand-written cross-pod
    collective, not the GSPMD optimizer-boundary emulation). The wire
    quantizer is stateless, so the error-feedback residuals are left
    untouched in that mode; EF composes with the ``sync_axis=None``
    optimizer-boundary path only."""
    opt = adamw(lr, weight_decay=weight_decay, clip_norm=clip_norm,
                state_dtype=jnp.float32)

    def loss_for(params, mb):
        loss, metrics = T.loss_fn(cfg, params, mb)
        return loss, metrics

    def step(state: TrainState, batch: dict):
        if accum == 1:
            (loss, metrics), grads = jax.value_and_grad(
                loss_for, has_aux=True)(state.params, batch)
        else:
            mbs = _split_microbatches(batch, accum)

            def acc_body(carry, mb):
                g_acc, l_acc = carry
                (l, _), g = jax.value_and_grad(loss_for, has_aux=True)(
                    state.params, mb)
                g_acc = jax.tree_util.tree_map(jnp.add, g_acc, g)
                return (g_acc, l_acc + l), None

            g0 = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
            (grads, loss), _ = jax.lax.scan(acc_body, (g0, 0.0), mbs)
            grads = jax.tree_util.tree_map(lambda g: g / accum, grads)
            loss = loss / accum
            metrics = {"xent": loss, "aux": jnp.zeros((), jnp.float32)}

        from repro.models.lm.moe import tie_expert_replica_grads
        grads = tie_expert_replica_grads(cfg, grads)

        ef = state.ef
        if sync_axis is not None:
            from repro.dist.collectives import sync_grads
            grads = sync_grads(grads, sync_axis,
                               wire="int8" if compression else "fp32")
            loss = jax.lax.pmean(loss, sync_axis)
            metrics = {k: jax.lax.pmean(v, sync_axis)
                       for k, v in metrics.items()}
        elif compression:
            qtree, ef = ef_compress_update(grads, ef)
            grads = jax.tree_util.tree_map(
                lambda qs: int8_decompress(*qs), qtree,
                is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2
                and not isinstance(x[0], tuple))

        updates, opt_state = opt.update(grads, state.opt_state, state.params)
        params = apply_updates(state.params, updates)
        metrics = dict(metrics, loss=loss,
                       grad_norm=jnp.sqrt(sum(
                           jnp.sum(jnp.square(g.astype(jnp.float32)))
                           for g in jax.tree_util.tree_leaves(grads))))
        return TrainState(params, opt_state, ef), metrics

    return step, opt


def make_data_parallel_step(cfg: ModelConfig, mesh: Mesh, *,
                            axis: str = "data", **kw):
    """``make_train_step`` wrapped in ``shard_map`` over ``mesh``'s
    ``axis``: state replicated, the batch split on its leading (batch)
    dim, gradients reduced *inside* the step by the hand-written
    collective (fp32 psum, or ``compressed_psum`` with
    ``compression=True``). Returns (step_fn, opt) with the same call
    contract as ``make_train_step`` — jit (with donation) as usual.

    This is pure data parallelism: parameters replicate over the whole
    mesh (the 'model' axis carries no tensor-parallel sharding in this
    mode), which is the configuration whose cross-pod reduce the int8
    wire is for. The ``axis`` size must divide the batch size (the batch
    splits on its leading dim, one slice per shard). The model's
    logical-axis ``shard_constraint`` hints are deactivated inside the
    body (an empty rule set) — every mesh axis is manual under this
    shard_map, so GSPMD constraints have nothing left to place."""
    from repro.dist.sharding import Rules, use_rules
    step, opt = make_train_step(cfg, sync_axis=axis, **kw)

    def body(state, batch):
        with use_rules(Rules(table={})):
            return step(state, batch)

    # check_vma=False: the attention scans start from replicated carries
    # that become shard-varying inside the body
    sharded = jax.shard_map(body, mesh=mesh, in_specs=(P(), P(axis)),
                            out_specs=(P(), P()), check_vma=False)
    return sharded, opt


def make_prefill_step(cfg: ModelConfig, capacity: int):
    def pre(params, batch):
        return T.prefill(cfg, params, batch, capacity)
    return pre


def make_decode_step(cfg: ModelConfig):
    def dec(params, cache, tokens):
        return T.decode_step(cfg, params, cache, tokens)
    return dec


# --------------------------------------------------------------------------
# ShapeDtypeStruct builders (dry-run / AOT compile; no allocation)
# --------------------------------------------------------------------------

def shaped_batch(cfg: ModelConfig, batch_size: int, seq_len: int,
                 mesh: Optional[Mesh] = None, rules=None) -> dict:
    rules = rules or LM_RULES
    i32 = jnp.int32
    dt = jnp.dtype(cfg.dtype)
    b: dict = {}
    if cfg.family == "audio":
        b["frames"] = jax.ShapeDtypeStruct((batch_size, seq_len, cfg.d_model), dt)
        b["targets"] = jax.ShapeDtypeStruct((batch_size, seq_len), i32)
    elif cfg.family == "vlm":
        text = seq_len - cfg.n_prefix_tokens
        b["tokens"] = jax.ShapeDtypeStruct((batch_size, text), i32)
        b["image_emb"] = jax.ShapeDtypeStruct(
            (batch_size, cfg.n_prefix_tokens, cfg.d_model), dt)
        b["targets"] = jax.ShapeDtypeStruct((batch_size, text), i32)
    else:
        b["tokens"] = jax.ShapeDtypeStruct((batch_size, seq_len), i32)
        b["targets"] = jax.ShapeDtypeStruct((batch_size, seq_len), i32)
    if mesh is not None:
        sh = batch_shardings(mesh, b, rules)
        b = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=sh[k])
             for k, v in b.items()}
    return b


def shaped_state(cfg: ModelConfig, opt, mesh: Optional[Mesh] = None,
                 compression: bool = False, rules=None) -> TrainState:
    rules = rules or LM_RULES
    shapes = jax.eval_shape(
        lambda: make_train_state(cfg, jax.random.PRNGKey(0), opt,
                                 compression=compression))
    if mesh is None:
        return shapes
    sh = state_shardings(mesh, shapes, rules)
    return jax.tree_util.tree_map(
        lambda s, d: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=d),
        shapes, sh)


def shaped_cache(cfg: ModelConfig, batch_size: int, capacity: int,
                 mesh: Optional[Mesh] = None, rules=None) -> dict:
    rules = rules or LM_RULES
    shapes = jax.eval_shape(lambda: T.init_cache(cfg, batch_size, capacity))
    if mesh is None:
        return shapes
    sh = cache_shardings(mesh, shapes, rules)
    return {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=sh[k])
            for k, v in shapes.items()}
