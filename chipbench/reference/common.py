"""Pieces shared by the plain references: Glorot init, AdamW and the masked
cross-entropy, written from their published definitions in ``jax.numpy``.

Nothing here imports the program. ``dtype`` is float32 for the reference
and bfloat16 for the control. The reference computes its matrix products at
the precision the configuration states (``matmul_precision``, passed to
``jax.default_matmul_precision``): "default" is one bfloat16 pass with
float32 sums on a TPU and full float32 on a CPU, as the program's products
run. Sums of messages and the loss stay in float32. The control is the step
below float32: bfloat16 throughout.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def glorot(key, shape):
    """Glorot & Bengio (2010) uniform: U(-a, a), a = sqrt(6 / (fan_in + fan_out))."""
    lim = (6.0 / (shape[0] + shape[-1])) ** 0.5
    return jax.random.uniform(key, shape, jnp.float32, -lim, lim)


def xent(logits, y, mask):
    """Mean negative log-likelihood over the rows where ``mask`` is set."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, y[:, None], axis=1)[:, 0]
    m = mask.astype(logits.dtype)
    return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), jnp.asarray(1, m.dtype))


def adamw_init(params):
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    return {"t": 0, "m": zeros, "v": zeros}


def adamw_step(params, grads, state, *, lr, b1, b2, eps, weight_decay):
    """Loshchilov & Hutter (2019) AdamW with bias correction; the decay is
    scaled by the learning rate. ``state["t"]`` is a host integer."""
    t = state["t"] + 1
    c1, c2 = 1.0 - b1 ** t, 1.0 - b2 ** t

    def one(p, g, m, v):
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        step = (m / c1) / (jnp.sqrt(v / c2) + eps) + weight_decay * p
        return (p - lr * step).astype(p.dtype), m, v

    tree = jax.tree_util.tree_map(one, params, grads, state["m"], state["v"])
    pick = lambda i: jax.tree_util.tree_map(  # noqa: E731
        lambda leaf: leaf[i], tree, is_leaf=lambda x: isinstance(x, tuple))
    return pick(0), {"t": t, "m": pick(1), "v": pick(2)}


def cast(tree, dtype):
    return jax.tree_util.tree_map(
        lambda a: a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating)
        else a, tree)
