"""Plain reference of one data-parallel step stack of GraphSAGE-mean over
sampled blocks.

Hamilton et al. (arXiv:1706.02216), mean aggregator:
``h'_i = W_self h_i + W_neigh mean_{j in S(i)} h_j + b``, relu between layers,
masked cross-entropy over the real seeds of each shard, gradients averaged
over the shards, AdamW. Float32, with the matrix products at the
configuration's precision (``reference/common.py``); ``dtype`` lowers the
whole computation for the control.

A block here is the reference's own view of what the sampler drew, after
``chipbench.lib.blockcheck`` has checked it against the graph: the global
ids of its source rows, and for each destination row its position among the
sources (``self_pos``) and the positions of its sampled neighbours
(``nbr_row``/``nbr_col``). Nothing of the program is imported.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference.common import (adamw_init, adamw_step, cast,
                                        glorot, xent)


def init_params(seed: int, dims: list[int]) -> dict:
    """Per layer: split the seed's key once per layer, then once more for the
    self and neighbour weights (Glorot uniform); zero biases."""
    keys = jax.random.split(jax.random.PRNGKey(seed), len(dims) - 1)
    out = {}
    for i in range(len(dims) - 1):
        k1, k2 = jax.random.split(keys[i])
        shape = (dims[i], dims[i + 1])
        out[f"l{i}"] = {"w_self": glorot(k1, shape),
                        "w_neigh": glorot(k2, shape),
                        "b": jnp.zeros((dims[i + 1],), jnp.float32)}
    return out


def _rows(h, pos):
    """h[pos] with zero rows for positions past the end (absent rows)."""
    return jnp.take(h, pos, axis=0, mode="fill", fill_value=0)


def forward(params, blocks, x):
    h = _rows(x, blocks[0]["src_ids"])
    for i, b in enumerate(blocks):
        n_dst = b["self_pos"].shape[0]
        msgs = _rows(h, b["nbr_col"])
        total = jax.ops.segment_sum(msgs, b["nbr_row"], num_segments=n_dst)
        count = jax.ops.segment_sum(jnp.ones_like(b["nbr_row"], h.dtype),
                                    b["nbr_row"], num_segments=n_dst)
        mean = total / jnp.maximum(count, 1)[:, None]
        p = params[f"l{i}"]
        h = _rows(h, b["self_pos"]) @ p["w_self"] + mean @ p["w_neigh"] + p["b"]
        if i < len(blocks) - 1:
            h = jax.nn.relu(h)
    return h


def shard_loss(params, blocks, x, y, n_real):
    logits = forward(params, blocks, x)
    mask = jnp.arange(logits.shape[0]) < n_real
    return xent(logits, y, mask)


def train(params, steps_shards, x, y_of, hp: dict, precision: str,
          dtype=jnp.float32, loss_share: float = 1.0):
    """One AdamW step per entry of ``steps_shards``: a list (one per step) of
    per-shard ``(blocks, seeds, n_real)``. The step's loss and gradient are
    the means over its shards. ``loss_share`` < 1 keeps only that leading
    share of each shard's real seeds (a planted fault, for calibration).

    Returns the per-step losses, the first gradient and the final params."""
    params, x = cast(params, dtype), x.astype(dtype)
    state = adamw_init(params)
    losses, first_grad = [], None
    with jax.default_matmul_precision(precision):
        vg = jax.jit(jax.value_and_grad(shard_loss))
        for shards in steps_shards:
            lsum, gsum = 0.0, None
            for blocks, seeds, n_real in shards:
                n_keep = int(n_real * loss_share) if loss_share < 1 else n_real
                loss, g = vg(params, blocks, x, y_of(seeds), n_keep)
                lsum = lsum + loss
                gsum = g if gsum is None else jax.tree_util.tree_map(
                    jnp.add, gsum, g)
            k = len(shards)
            grads = jax.tree_util.tree_map(lambda a: a / k, gsum)
            losses.append(float(lsum / k))
            first_grad = grads if first_grad is None else first_grad
            params, state = adamw_step(params, grads, state, lr=hp["lr"],
                                       b1=hp["b1"], b2=hp["b2"],
                                       eps=hp["eps"],
                                       weight_decay=hp["weight_decay"])
    return losses, first_grad, params
