"""GNN models — the paper's §4 benchmark set (two layers each) and the
published three-layer GAT.

``make_gnn(arch, ...)`` returns ``(init_fn, apply_fn)``; apply is
``apply(params, bundle, x) -> logits``. Architectures:

  gcn | sage-sum | sage-mean | sage-max | gin | gat
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax

from repro.models.gnn import layers as L
from repro.models.gnn.bundle import GraphBundle
from repro.obs import stages

Array = Any

GNN_ARCHS = ("gcn", "sage-sum", "sage-mean", "sage-max", "gin", "gat")

__all__ = ["GNN_ARCHS", "make_gnn"]


def _elu_skip(out: Array, h: Array) -> Array:
    return jax.nn.elu(out + h)


def make_gnn(arch: str, in_dim: int, hidden: int, out_dim: int, *,
             heads: tuple = (4, 4, 6)) -> tuple[Callable, Callable]:
    """``hidden`` is the hidden width; for ``gat`` it is each head's width.

    ``gat`` is the published inductive GAT (Veličković et al., PPI): three
    layers of ``heads`` heads; the first two concatenate ``hidden``-wide
    heads, then ELU, with an identity skip across the second (added before
    its ELU); the third averages ``out_dim``-wide heads into the logits."""
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
        assert len(heads) == 3 and heads[0] == heads[1], heads
        width = heads[0] * hidden

        def init(key):
            k1, k2, k3 = jax.random.split(key, 3)
            return {"l1": L.init_gat(k1, in_dim, heads[0], hidden),
                    "l2": L.init_gat(k2, width, heads[1], hidden),
                    "l3": L.init_gat(k3, width, heads[2], out_dim,
                                     concat=False)}

        def apply(params, bundle: GraphBundle, x: Array) -> Array:
            conv = functools.partial(L.gat_conv, bundle=bundle)
            h = stages.dense(jax.nn.elu, conv(params["l1"], h=x))
            h = stages.dense(_elu_skip, conv(params["l2"], h=h), h)
            return conv(params["l3"], h=h, concat=False)

    return init, apply
