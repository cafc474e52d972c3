"""GNN layers (GCN / GraphSAGE / GIN / dot-GAT), patch-aware.

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
           "gin_conv", "init_gat", "dot_gat_conv", "sage_conv_block",
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
# Dot-product graph attention (exercises FusedMM/SDDMM — §3.4's
# "attention-style edge scoring"; scores never materialize on the tuned path)
# --------------------------------------------------------------------------

def init_gat(key, in_dim: int, out_dim: int) -> dict:
    kq, kk, kv = jax.random.split(key, 3)
    return {"wq": _glorot(kq, (in_dim, out_dim)),
            "wk": _glorot(kk, (in_dim, out_dim)),
            "wv": _glorot(kv, (in_dim, out_dim))}


def dot_gat_conv(params: dict, bundle: GraphBundle, h: Array) -> Array:
    g = bundle.tuned  # both paths take the same operand; impl differs
    q, k, v = stages.dense(_gat_qkv, params, h)
    return stages.aggregate(resolve("fusedmm"), g, q, k, v,
                            edge_op="softmax")


def _gat_qkv(params: dict, h: Array) -> tuple:
    q = h @ params["wq"]
    k = h @ params["wk"]
    v = h @ params["wv"]
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], q.dtype))
    return q * scale, k, v
