"""Plain reference of full-batch training of the published three-layer GAT.

Veličković et al., *Graph Attention Networks* (arXiv:1710.10903), in its
inductive (PPI) configuration. Per layer with ``H`` heads of ``F``
features, over the entries of ``A + I`` (rows are edge destinations):

    z = h W                                   (H heads of F lanes)
    e_ij = LeakyReLU_0.2(a_dstᵀ z_i + a_srcᵀ z_j)        per head
    α_ij = exp(e_ij - max_j e_ij) / Σ_j exp(e_ij - max_j e_ij)
    h'_i = Σ_j α_ij z_j                        per head, then + b

Layers 1 and 2 concatenate their heads and apply ELU; layer 2 adds its
input (the identity skip) before its ELU; layer 3 averages its heads into
the logits. Masked cross-entropy over the training nodes, AdamW (the
paper's Adam: weight decay 0). Float32, products at the configuration's
precision (``reference/common.py``); ``dtype`` lowers the whole
computation for the control. Nothing of the program is imported: the
weights are drawn from the seed with the documented Glorot scheme and the
edge list is read from the benchmark's graph.

Each softmax takes three passes over fixed blocks of entries: the row
maxima, the row sums, then the weighted sum of rows of z. Every scan body
is under ``jax.checkpoint``, so one block's ``(CHUNK, H·F)`` messages are
all that is live at a time, forward and backward.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.common import adamw_init, adamw_step, cast, xent

CHUNK = 1 << 15
SLOPE = 0.2


def init_params(seed: int, f: int, heads, head_dims, concat) -> dict:
    """Per layer (one key each, split from the seed's key): ``w`` with each
    head's ``(in, F)`` block Glorot-uniform, ``a_src`` and ``a_dst``
    ``(H, F)`` each Glorot for ``(F, 1)`` (the layer's key split in three,
    in that order), and a zero bias over the concatenation or one head."""
    keys = jax.random.split(jax.random.PRNGKey(seed), len(heads))
    out, d_in = {}, f
    for i, (key, h, fd, cat) in enumerate(zip(keys, heads, head_dims,
                                              concat)):
        kw, ks, kd = jax.random.split(key, 3)
        lim_w = (6.0 / (d_in + fd)) ** 0.5
        lim_a = (6.0 / (fd + 1)) ** 0.5
        out[f"l{i + 1}"] = {
            "w": jax.random.uniform(kw, (d_in, h * fd), jnp.float32,
                                    -lim_w, lim_w),
            "a_src": jax.random.uniform(ks, (h, fd), jnp.float32,
                                        -lim_a, lim_a),
            "a_dst": jax.random.uniform(kd, (h, fd), jnp.float32,
                                        -lim_a, lim_a),
            "b": jnp.zeros(((h if cat else 1) * fd,), jnp.float32)}
        d_in = h * fd if cat else fd
    return out


def entries(src: np.ndarray, dst: np.ndarray, n: int):
    """The entries of ``A + I`` as (row, col, live) blocks of ``CHUNK``,
    padded with dead entries."""
    loops = np.arange(n, dtype=np.int64)
    row = np.concatenate([dst.astype(np.int64), loops])
    col = np.concatenate([src.astype(np.int64), loops])
    nb = -(-row.shape[0] // CHUNK)
    pad = nb * CHUNK - row.shape[0]

    def blocks(a, fill):
        return jnp.asarray(np.concatenate([a, np.full(pad, fill, a.dtype)])
                           .reshape(nb, CHUNK))
    live = np.ones(row.shape[0], bool)
    return (blocks(row.astype(np.int32), 0), blocks(col.astype(np.int32), 0),
            blocks(live, False))


def _scan(body, init, adj):
    out, _ = jax.lax.scan(jax.checkpoint(body), init, adj)
    return out


def attention(adj, z, s_dst, s_src, uniform: bool = False):
    """``h'_i = Σ_j α_ij z_j`` per head. ``uniform`` leaves the attention
    out (α = 1 / deg: a planted fault, for calibration)."""
    n, heads = s_dst.shape
    f = z.shape[1] // heads

    def logits(r, c, ok):
        e = s_dst[r] + s_src[c]
        e = jnp.where(e > 0, e, SLOPE * e)
        if uniform:
            e = jnp.zeros_like(e)
        return jnp.where(ok[:, None], e, -jnp.inf)

    def row_max(acc, blk):
        r, c, ok = blk
        return acc.at[r].max(logits(r, c, ok)), None

    m = _scan(row_max, jnp.full((n, heads), -jnp.inf, z.dtype), adj)
    m = jax.lax.stop_gradient(jnp.where(jnp.isfinite(m), m, 0))

    def row_sum(acc, blk):
        r, c, ok = blk
        return acc.at[r].add(jnp.exp(logits(r, c, ok) - m[r])), None

    den = _scan(row_sum, jnp.zeros((n, heads), z.dtype), adj)

    def weighted(acc, blk):
        r, c, ok = blk
        alpha = jnp.exp(logits(r, c, ok) - m[r]) / den[r]
        msg = alpha[:, :, None] * z[c].reshape(-1, heads, f)
        return acc.at[r].add(msg.reshape(-1, heads * f)), None

    return _scan(weighted, jnp.zeros(z.shape, z.dtype), adj)


def layer(p, adj, h, concat: bool, uniform: bool = False):
    heads, f = p["a_src"].shape
    z = h @ p["w"]
    zh = z.reshape(-1, heads, f)
    s_dst = jnp.einsum("nhf,hf->nh", zh, p["a_dst"])
    s_src = jnp.einsum("nhf,hf->nh", zh, p["a_src"])
    out = attention(adj, z, s_dst, s_src, uniform)
    if not concat:
        out = out.reshape(-1, heads, f).mean(axis=1)
    return out + p["b"]


def logits_fn(params, adj, x, uniform: bool = False):
    h1 = jax.nn.elu(layer(params["l1"], adj, x, True, uniform))
    h2 = jax.nn.elu(layer(params["l2"], adj, h1, True, uniform) + h1)
    return layer(params["l3"], adj, h2, False, uniform)


def loss_fn(params, adj, x, y, mask, uniform: bool = False):
    return xent(logits_fn(params, adj, x, uniform), y, mask)


def train(params, adj, x, y, mask, hp: dict, precision: str,
          steps: int = 3, dtype=jnp.float32, uniform: bool = False):
    """``steps`` AdamW steps from ``params``. Returns the loss of each step,
    the first gradient and the parameters after the last step."""
    params, x = cast(params, dtype), x.astype(dtype)
    state = adamw_init(params)
    losses, first_grad = [], None
    with jax.default_matmul_precision(precision):
        vg = jax.jit(jax.value_and_grad(loss_fn), static_argnums=(5,))
        for _ in range(steps):
            loss, grads = vg(params, adj, x, y, mask, uniform)
            losses.append(float(loss))
            first_grad = grads if first_grad is None else first_grad
            params, state = adamw_step(params, grads, state, lr=hp["lr"],
                                       b1=hp["b1"], b2=hp["b2"],
                                       eps=hp["eps"],
                                       weight_decay=hp["weight_decay"])
    return losses, first_grad, params
