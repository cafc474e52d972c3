"""Full-graph GNN trainer — the paper's §4 experimental loop.

Node classification, full-batch, AdamW; per-epoch wall-clock measured the
way the paper does (average over epochs, first/compile epoch excluded).
``use_isplib`` flips patch()/unpatch() — the two-lines-of-code story:

    from repro.core import patch
    patch()              # everything below now runs the tuned kernels
    train_gnn(...)

The step is jitted with the patch state folded in (patch_version is part of
the closure), so toggling retraces instead of reusing stale bindings. Its
loss and update run under the ``loss`` and ``optimizer`` stages of
``repro.obs.stages``; the model's layers name their own.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.patch import patched
from repro.models.gnn import build_bundle, make_gnn
from repro.obs import stages
from repro.optim import adamw, apply_updates

Array = Any

__all__ = ["train_gnn", "GNNTrainResult", "make_gnn_step"]


@dataclasses.dataclass
class GNNTrainResult:
    arch: str
    dataset: str
    use_isplib: bool
    losses: list
    train_acc: float
    test_acc: float
    epoch_time_s: float      # mean per-epoch wall-clock (post-compile)
    compile_time_s: float
    plan_kind: str
    epochs: int


def _xent(logits: Array, y: Array, mask: Array) -> Array:
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, y[:, None].astype(jnp.int32), axis=1)[:, 0]
    m = mask.astype(jnp.float32)
    return jnp.sum(nll * m) / jnp.maximum(m.sum(), 1.0)


def _acc(logits: Array, y: Array, mask: Array) -> Array:
    pred = jnp.argmax(logits, axis=-1).astype(y.dtype)
    m = mask.astype(jnp.float32)
    return jnp.sum((pred == y) * m) / jnp.maximum(m.sum(), 1.0)


def _apply_update(opt, p, s, grads):
    updates, s = opt.update(grads, s, p)
    return apply_updates(p, updates), s


def make_gnn_step(apply, opt):
    """The jitted full-batch update ``step(p, s, g, x, y, mask) -> (p, s,
    loss)`` of ``apply(p, g, x) -> logits`` under ``opt``.

    The graph operands are jit arguments, not closure constants: at full
    size a captured bundle would put A, A^T and their packed plans into the
    program as literals."""
    def loss_fn(p, g, x, y, mask):
        return stages.loss(_xent, apply(p, g, x), y, mask)

    def step(p, s, g, x, y, mask):
        loss, grads = jax.value_and_grad(loss_fn)(p, g, x, y, mask)
        p, s = stages.optimizer(_apply_update, opt, p, s, grads)
        return p, s, loss

    return jax.jit(step)


def train_gnn(arch: str, dataset, *, hidden: int = 128, epochs: int = 30,
              lr: float = 1e-2, weight_decay: float = 5e-4,
              use_isplib: bool = True, tune: bool = True,
              measure_tuning: bool = False, seed: int = 0,
              bundle=None, tuning_db=None, heads: tuple = (4, 4, 6),
              profile: bool = False) -> GNNTrainResult:
    """Train a ``make_gnn`` model full-batch on ``dataset`` (a
    data.graphs.GraphDataset): the two-layer GCN, GraphSAGE or GIN at
    width ``hidden``, or the three-layer GAT of ``heads`` heads, each
    ``hidden`` wide. ``tuning_db`` (a repro.core.TuningDB) skips
    re-measuring plans this machine has already tuned for this graph
    structure.

    ``profile=True`` enables the ``repro.obs`` tracer for the run (if not
    already on) and records ``train.build`` / ``train.step`` /
    ``train.eval`` spans with per-step device sync — attribution mode,
    not benchmarking (the sync serializes the epoch loop the timed
    ``epoch_time_s`` otherwise overlaps)."""
    with contextlib.ExitStack() as _ctx:
        if profile and not obs.enabled():
            _ctx.enter_context(obs.profiled(ops=True, fresh=False))
        _ctx.enter_context(patched(use_isplib))
        if bundle is None:
            with obs.span("train.build"):
                bundle = build_bundle(dataset, k_hint=hidden, tune=tune,
                                      measure=measure_tuning, db=tuning_db,
                                      slot_perm=arch == "gat")
        with obs.span("train.init"):
            init, apply = make_gnn(arch, dataset.num_features, hidden,
                                   dataset.num_classes, heads=heads)
            params = init(jax.random.PRNGKey(seed))
            opt = adamw(lr, weight_decay=weight_decay)
            opt_state = opt.init(params)
            jax.block_until_ready(jax.tree_util.tree_leaves(params)[0])

        step = make_gnn_step(apply, opt)

        @jax.jit
        def evaluate(p, g, x, y, mask):
            return _acc(apply(p, g, x), y, mask)

        x, y = dataset.x, dataset.y
        tm = dataset.train_mask

        t0 = time.perf_counter()
        with obs.span("train.step", step=0, compile=True):
            params, opt_state, loss = step(params, opt_state, bundle,
                                           x, y, tm)
            jax.block_until_ready(loss)
        compile_time = time.perf_counter() - t0

        losses = [float(loss)]
        t0 = time.perf_counter()
        for ep in range(max(epochs - 1, 1)):
            with obs.span("train.step", step=ep + 1):
                params, opt_state, loss = step(params, opt_state, bundle,
                                               x, y, tm)
                if profile:         # span times execution, not dispatch
                    jax.block_until_ready(loss)
            losses.append(float(loss))
        jax.block_until_ready(loss)
        epoch_time = (time.perf_counter() - t0) / max(epochs - 1, 1)

        with obs.span("train.eval"):
            train_acc = float(evaluate(params, bundle, x, y, tm))
            test_acc = float(evaluate(params, bundle, x, y,
                                      dataset.test_mask))

    return GNNTrainResult(
        arch=arch, dataset=dataset.name, use_isplib=use_isplib,
        losses=losses, train_acc=train_acc, test_acc=test_acc,
        epoch_time_s=epoch_time, compile_time_s=compile_time,
        plan_kind=bundle.tuned.plan.kind, epochs=epochs)
