"""Two-layer GNN models — the paper's §4 benchmark set (+ dot-GAT extra).

``make_gnn(arch, ...)`` returns ``(init_fn, apply_fn)``; apply is
``apply(params, bundle, x) -> logits``. Architectures:

  gcn | sage-sum | sage-mean | sage-max | gin | gat
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.models.gnn import layers as L
from repro.models.gnn.bundle import GraphBundle
from repro.obs import stages

Array = Any

GNN_ARCHS = ("gcn", "sage-sum", "sage-mean", "sage-max", "gin", "gat")

__all__ = ["GNN_ARCHS", "make_gnn"]


def make_gnn(arch: str, in_dim: int, hidden: int, out_dim: int
             ) -> tuple[Callable, Callable]:
    if arch not in GNN_ARCHS:
        raise ValueError(f"unknown GNN arch {arch!r}; choose from {GNN_ARCHS}")

    if arch == "gcn":
        def init(key):
            k1, k2 = jax.random.split(key)
            return {"l1": L.init_gcn(k1, in_dim, hidden),
                    "l2": L.init_gcn(k2, hidden, out_dim)}

        def apply(params, bundle: GraphBundle, x: Array) -> Array:
            h = stages.dense(jax.nn.relu, L.gcn_conv(params["l1"], bundle, x))
            return L.gcn_conv(params["l2"], bundle, h)

    elif arch.startswith("sage"):
        aggr = arch.split("-")[1]

        def init(key):
            k1, k2 = jax.random.split(key)
            return {"l1": L.init_sage(k1, in_dim, hidden),
                    "l2": L.init_sage(k2, hidden, out_dim)}

        def apply(params, bundle: GraphBundle, x: Array) -> Array:
            h = stages.dense(jax.nn.relu,
                             L.sage_conv(params["l1"], bundle, x, aggr=aggr))
            return L.sage_conv(params["l2"], bundle, h, aggr=aggr)

    elif arch == "gin":
        def init(key):
            k1, k2 = jax.random.split(key)
            return {"l1": L.init_gin(k1, in_dim, hidden),
                    "l2": L.init_gin(k2, hidden, out_dim)}

        def apply(params, bundle: GraphBundle, x: Array) -> Array:
            h = stages.dense(jax.nn.relu, L.gin_conv(params["l1"], bundle, x))
            return L.gin_conv(params["l2"], bundle, h)

    else:  # gat
        def init(key):
            k1, k2, k3 = jax.random.split(key, 3)
            return {"proj": L._glorot(k1, (in_dim, hidden)),
                    "l1": L.init_gat(k2, hidden, hidden),
                    "l2": L.init_gat(k3, hidden, out_dim)}

        def apply(params, bundle: GraphBundle, x: Array) -> Array:
            h = stages.dense(jnp.matmul, x, params["proj"])
            h = stages.dense(jax.nn.relu,
                             L.dot_gat_conv(params["l1"], bundle, h))
            return L.dot_gat_conv(params["l2"], bundle, h)

    return init, apply
