"""Plain reference of full-batch training of a two-layer GCN.

Kipf & Welling (arXiv:1609.02907): ``Z = Â relu(Â X W1 + b1) W2 + b2`` with
``Â = D^-1/2 (A + I) D^-1/2`` and ``D`` the row sums of ``A + I`` (rows are
edge destinations). Masked cross-entropy over the training nodes, AdamW.
Float32, with the matrix products at the configuration's precision
(``reference/common.py``); ``dtype`` lowers the whole computation for the
control. Nothing of the program is imported:
the initial weights are drawn from the seed with the documented Glorot
scheme, the adjacency is normalised here from the benchmark's edge list.

The aggregation scans over fixed blocks of edges, so the gathered messages
of one block (``CHUNK`` x K) are all that is live at a time.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.common import (adamw_init, adamw_step, cast,
                                        glorot, xent)

CHUNK = 1 << 20


def init_params(seed: int, f: int, hidden: int, classes: int) -> dict:
    """Two Glorot-uniform weights and zero biases; the key is split once per
    layer and once more inside the layer."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    out = {}
    for name, key, shape in (("l1", k1, (f, hidden)), ("l2", k2, (hidden, classes))):
        kw, = jax.random.split(key, 1)
        out[name] = {"w": glorot(kw, shape),
                     "b": jnp.zeros((shape[1],), jnp.float32)}
    return out


def normalized_adjacency(src: np.ndarray, dst: np.ndarray, n: int):
    """Â as (row, col, val) blocks of ``CHUNK`` entries, zero-padded."""
    loops = np.arange(n, dtype=np.int64)
    row = np.concatenate([dst.astype(np.int64), loops])
    col = np.concatenate([src.astype(np.int64), loops])
    deg = np.bincount(row, minlength=n).astype(np.float64)
    val = (1.0 / np.sqrt(deg[row])) * (1.0 / np.sqrt(deg[col]))
    nb = -(-row.shape[0] // CHUNK)
    pad = nb * CHUNK - row.shape[0]

    def blocks(a, dt):
        return jnp.asarray(np.concatenate([a, np.zeros(pad, a.dtype)])
                           .astype(dt).reshape(nb, CHUNK))
    return blocks(row, np.int32), blocks(col, np.int32), blocks(val, np.float32)


def spmm(adj, h):
    """Â h, one scatter-add per block of edges."""
    row, col, val = adj

    def body(acc, e):
        r, c, v = e
        return acc.at[r].add(v[:, None].astype(h.dtype) * h[c]), None

    out, _ = jax.lax.scan(body, jnp.zeros(h.shape, h.dtype), (row, col, val))
    return out


def loss_fn(params, adj, x, y, mask):
    h = jax.nn.relu(spmm(adj, x @ params["l1"]["w"]) + params["l1"]["b"])
    z = spmm(adj, h @ params["l2"]["w"]) + params["l2"]["b"]
    return xent(z, y, mask)


def train(params, adj, x, y, mask, hp: dict, precision: str,
          steps: int = 3, dtype=jnp.float32):
    """``steps`` AdamW steps from ``params``. Returns the loss of each step,
    the first gradient and the parameters after the last step."""
    params, x = cast(params, dtype), x.astype(dtype)
    state = adamw_init(params)
    losses, first_grad = [], None
    with jax.default_matmul_precision(precision):
        vg = jax.jit(jax.value_and_grad(loss_fn))
        for _ in range(steps):
            loss, grads = vg(params, adj, x, y, mask)
            losses.append(float(loss))
            first_grad = grads if first_grad is None else first_grad
            params, state = adamw_step(params, grads, state, lr=hp["lr"],
                                       b1=hp["b1"], b2=hp["b2"],
                                       eps=hp["eps"],
                                       weight_decay=hp["weight_decay"])
    return losses, first_grad, params
