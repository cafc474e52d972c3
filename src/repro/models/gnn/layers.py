"""GNN layers (GCN / GraphSAGE / GIN / GAT), patch-aware.

Every layer routes its aggregation through ``repro.core.patch.resolve`` so
the paper's patch()/unpatch() flips the whole model between the tuned iSpLib
path (CachedGraph + kernel plan + cached normalization) and the
PT-equivalent baseline (uncached, per-step normalization) — the same "two
lines of code" integration story, JAX-native.

All layers are functional: ``init_*(key, ...) -> params`` and
``*_conv(params, bundle, h, ...) -> h'``. Each runs its aggregation under
the ``aggregate`` stage and its products, biases and activations under
``dense`` (``repro.obs.stages``).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.core import baselines
from repro.core.patch import is_patched, resolve
from repro.models.gnn.bundle import GraphBundle
from repro.obs import stages

Array = Any

__all__ = ["init_gcn", "gcn_conv", "init_sage", "sage_conv", "init_gin",
           "gin_conv", "init_gat", "gat_conv", "sage_conv_block",
           "gin_conv_block"]


def _glorot(key, shape):
    fan_in, fan_out = shape[0], shape[-1]
    lim = (6.0 / (fan_in + fan_out)) ** 0.5
    return jax.random.uniform(key, shape, jnp.float32, -lim, lim)


# --------------------------------------------------------------------------
# GCN (Kipf & Welling): h' = Â (h W) + b     Â = D^-1/2 (A+I) D^-1/2
# --------------------------------------------------------------------------

def init_gcn(key, in_dim: int, out_dim: int) -> dict:
    kw, = jax.random.split(key, 1)
    return {"w": _glorot(kw, (in_dim, out_dim)),
            "b": jnp.zeros((out_dim,), jnp.float32)}


def gcn_conv(params: dict, bundle: GraphBundle, h: Array) -> Array:
    # project FIRST (the paper notes GCN's pre-projection is why tuned
    # kernels shine: SpMM runs at hidden width, not feature width)
    h = stages.dense(jnp.matmul, h, params["w"])
    spmm_fn = resolve("spmm")
    if is_patched():
        a_n = bundle.tuned_norm                           # cached Â — §3.3
    else:                                                 # per-step norm
        a_n = stages.normalize(baselines.gcn_norm_in_step, bundle.raw_sl)
    out = stages.aggregate(spmm_fn, a_n, h, "sum")
    return stages.dense(jnp.add, out, params["b"])


# --------------------------------------------------------------------------
# GraphSAGE: h' = W_s h + W_n agg_{j in N(i)} h_j,  agg in {sum, mean, max}
# --------------------------------------------------------------------------

def init_sage(key, in_dim: int, out_dim: int) -> dict:
    k1, k2 = jax.random.split(key)
    return {"w_self": _glorot(k1, (in_dim, out_dim)),
            "w_neigh": _glorot(k2, (in_dim, out_dim)),
            "b": jnp.zeros((out_dim,), jnp.float32)}


def _sage_combine(params: dict, h_self: Array, agg: Array) -> Array:
    return h_self @ params["w_self"] + agg @ params["w_neigh"] + params["b"]


def sage_conv(params: dict, bundle: GraphBundle, h: Array,
              aggr: str = "mean") -> Array:
    g = bundle.tuned if is_patched() else bundle.raw
    agg = stages.aggregate(resolve("spmm"), g, h, aggr)
    return stages.dense(_sage_combine, params, h, agg)


def _block_dst(pb, h: Array) -> Array:
    """Destination-row view of a block's source features: an explicit
    ``dst_pos`` gather rather than ``h[:n_dst]`` because bucket padding
    breaks the dst-prefix property past the real destination count
    (pad positions zero-fill)."""
    return jnp.take(h, pb.dst_pos, axis=0, mode="fill", fill_value=0)


def sage_conv_block(params: dict, pb, h: Array, aggr: str = "mean") -> Array:
    """GraphSAGE over one sampled bipartite block (MFG): ``h`` holds the
    block's *source* rows; output has the block's (padded) dst rows.
    Same params as :func:`sage_conv` — minibatch-trained weights drop into
    full-batch/layer-wise apply unchanged. The aggregation resolves
    through the patch registry ('block_spmm'): tuned = the bucket plan's
    packed ELL/SELL kernel, baseline = trusted segment ops."""
    agg = stages.aggregate(resolve("block_spmm"), pb, h, aggr)
    h_dst = stages.gather(_block_dst, pb, h)
    return stages.dense(_sage_combine, params, h_dst, agg)


# --------------------------------------------------------------------------
# GIN: h' = MLP((1 + eps) h + sum_{j in N(i)} h_j)
# --------------------------------------------------------------------------

def init_gin(key, in_dim: int, out_dim: int, hidden: int | None = None) -> dict:
    hidden = hidden or out_dim
    k1, k2 = jax.random.split(key)
    return {"eps": jnp.zeros((), jnp.float32),
            "w1": _glorot(k1, (in_dim, hidden)),
            "b1": jnp.zeros((hidden,), jnp.float32),
            "w2": _glorot(k2, (hidden, out_dim)),
            "b2": jnp.zeros((out_dim,), jnp.float32)}


def _gin_mlp(params: dict, h_self: Array, s: Array) -> Array:
    z = (1.0 + params["eps"]) * h_self + s
    z = jax.nn.relu(z @ params["w1"] + params["b1"])
    return z @ params["w2"] + params["b2"]


def gin_conv(params: dict, bundle: GraphBundle, h: Array) -> Array:
    g = bundle.tuned if is_patched() else bundle.raw
    s = stages.aggregate(resolve("spmm"), g, h, "sum")
    return stages.dense(_gin_mlp, params, h, s)


def gin_conv_block(params: dict, pb, h: Array) -> Array:
    """GIN over one sampled bipartite block; see :func:`sage_conv_block`
    for the operand convention."""
    s = stages.aggregate(resolve("block_spmm"), pb, h, "sum")
    h_dst = stages.gather(_block_dst, pb, h)
    return stages.dense(_gin_mlp, params, h_dst, s)


# --------------------------------------------------------------------------
# GAT (Veličković et al., arXiv:1710.10903): H heads of F features
#   z = h W;  e_ij = LeakyReLU(a_dstᵀ z_i + a_srcᵀ z_j) per head
#   h'_i = ‖_h Σ_{j ∈ N(i) ∪ {i}} softmax_j(e_ij) z_j   (or the heads' mean)
# --------------------------------------------------------------------------

def init_gat(key, in_dim: int, heads: int, head_dim: int, *,
             concat: bool = True) -> dict:
    """One GAT layer: the heads' projections side by side in ``w`` (each
    head's block Glorot-uniform for ``(in_dim, head_dim)``), attention
    vectors ``a_src`` and ``a_dst`` ``(heads, head_dim)`` (each Glorot for
    ``(head_dim, 1)``), and a zero bias after the aggregation (over the
    concatenation, or over one head's width when the heads are averaged).
    The key is split once into the three."""
    kw, ks, kd = jax.random.split(key, 3)
    lim_w = (6.0 / (in_dim + head_dim)) ** 0.5
    lim_a = (6.0 / (head_dim + 1)) ** 0.5
    uni = lambda k, shape, lim: jax.random.uniform(  # noqa: E731
        k, shape, jnp.float32, -lim, lim)
    return {"w": uni(kw, (in_dim, heads * head_dim), lim_w),
            "a_src": uni(ks, (heads, head_dim), lim_a),
            "a_dst": uni(kd, (heads, head_dim), lim_a),
            "b": jnp.zeros(((heads if concat else 1) * head_dim,),
                           jnp.float32)}


def _gat_scores(params: dict, z: Array) -> tuple:
    """Per head and node, ``a_dstᵀ z`` and ``a_srcᵀ z``: ``(H, n)`` each,
    head-major as the attention op takes them."""
    heads, f = params["a_src"].shape
    zh = z.reshape(z.shape[0], heads, f)
    return (jnp.einsum("nhf,hf->hn", zh, params["a_dst"]),
            jnp.einsum("nhf,hf->hn", zh, params["a_src"]))


def _gat_out(params: dict, out: Array, concat: bool) -> Array:
    if not concat:
        heads, f = params["a_src"].shape
        out = out.reshape(out.shape[0], heads, f).mean(axis=1)
    return out + params["b"]


def gat_conv(params: dict, bundle: GraphBundle, h: Array, *,
             concat: bool = True) -> Array:
    """One GAT layer over ``A + I`` (the pattern of the bundle's graph with
    self-loops): projection under ``dense``, scores and attention under
    ``attention``, the multi-head aggregation under ``aggregate`` (both
    staged by the op), bias and head mean under ``dense``. Tuned: the
    cached ``Â`` graph's plan and tables (values unused); baseline: the
    raw COO with self-loops."""
    z = stages.dense(jnp.matmul, h, params["w"])
    s_dst, s_src = stages.attention(_gat_scores, params, z)
    g = bundle.tuned_norm if is_patched() else bundle.raw_sl
    out = resolve("gat_attention")(g, z, s_dst, s_src)
    return stages.dense(_gat_out, params, out, concat)
