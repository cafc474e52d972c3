"""Full-batch training, through the program's own entry ``repro.train.train_gnn``.

Set-up: the graph (cached host arrays, features made on the device), the
program's ``build_bundle`` (tuning and packing, timed as ``bundle_s``), and a
warm-up call of ``train_gnn`` that compiles the step and sizes the epochs.
Window: a second call that runs ``1 + ceil(seconds / epoch)`` epochs; its own
post-compile timer over the epochs after the first is ``epoch_s``. Its first
three losses, from the same compiled step, are compared with the plain
reference (``chipbench/reference/gcn.py``) once the window has closed.
"""
from __future__ import annotations

import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.lib import compare, counts, graphgen, program, trace
from chipbench.reference import gcn as ref

CHECK_STEPS = 3


class Setup:
    """The graph, the program's dataset and bundle, for one process."""

    def __init__(self, cell, root: str):
        cf, tr = cell.config, cell.traffic
        self.cell = cell
        self.g = graphgen.host_graph(tr["dataset"], tr["graph_seed"],
                                     tr.get("scale", 1.0), cache_root=root)
        self.n, self.f, self.classes = (int(v) for v in self.g["meta"])
        self.x = graphgen.device_features(self.n, self.f, self.g["y"],
                                          tr["graph_seed"])
        self.ds = program.graph_dataset(tr["dataset"], self.g, self.x)
        from repro.models.gnn import build_bundle
        jax.block_until_ready(self.x)
        t0 = self.t_graph = time.perf_counter()
        self.bundle = build_bundle(self.ds, k_hint=cf["hidden"])
        jax.block_until_ready([a for a in jax.tree_util.tree_leaves(self.bundle)
                               if isinstance(a, jax.Array)])
        self.bundle_s = time.perf_counter() - t0

    def train(self, seed: int, epochs: int):
        from repro.train import train_gnn
        cf = self.cell.config
        return train_gnn(cf["arch"], self.ds, hidden=cf["hidden"],
                         epochs=epochs, lr=cf["optimizer"]["lr"],
                         weight_decay=cf["optimizer"]["weight_decay"],
                         use_isplib=self.cell.traffic["use_isplib"],
                         seed=seed, bundle=self.bundle)

    def free_program(self) -> None:
        """Drop the program's graph state before the reference runs."""
        self.bundle = self.ds = None

    def reference(self, seed: int, dtype=jnp.float32, loss_share=1.0):
        """The reference's first ``CHECK_STEPS`` losses from ``seed``.
        ``loss_share`` < 1 keeps that share of the training nodes in the
        loss (a planted fault, for calibration)."""
        cf = self.cell.config
        adj = ref.normalized_adjacency(self.g["src"], self.g["dst"], self.n)
        params = ref.init_params(seed, self.f, cf["hidden"], self.classes)
        train = self.g["split"] == 0
        if loss_share < 1:
            idx = np.flatnonzero(train)
            train[idx[int(len(idx) * loss_share):]] = False
        losses, _, _ = ref.train(params, adj, self.x, jnp.asarray(self.g["y"]),
                                 jnp.asarray(train), cf["optimizer"],
                                 cf["matmul_precision"], steps=CHECK_STEPS,
                                 dtype=dtype)
        return losses


def numbers(prog_losses, ref_losses) -> dict:
    return {"loss_gap": max(compare.rel_gap(a, b) for a, b in
                            zip(prog_losses[:CHECK_STEPS], ref_losses))}


def run(spec) -> dict:
    s = Setup(spec.cell, spec.root)
    warm = s.train(spec.seed, epochs=2)
    t_warm = time.perf_counter()
    epochs = 1 + max(1, math.ceil(spec.seconds / warm.epoch_time_s))
    with trace.capture(spec.trace_dir):
        t_call = time.perf_counter()
        res = s.train(spec.seed, epochs=epochs)
    setup_s = t_call - spec.t_start + res.compile_time_s
    memory = trace.memory_peak(spec.devices)
    cf = spec.cell.config
    nnz_hat = len(s.g["src"]) + s.n
    work = {
        "steps": epochs - 1,
        "window_s": res.epoch_time_s * (epochs - 1),
        "step_module": "jit_step",
        "flops_per_step": counts.gcn_epoch_flops(s.n, nnz_hat, s.f,
                                                 cf["hidden"], s.classes),
        "spmm_calls_per_step": counts.gcn_epoch_spmm_calls(
            s.n, nnz_hat, cf["hidden"], s.classes),
        "bundle_s": s.bundle_s,
    }
    s.free_program()
    t_ref = time.perf_counter()
    ref_losses = s.reference(spec.seed)
    reference_s = time.perf_counter() - t_ref
    failed = sum(1 for v in res.losses if not np.isfinite(v))
    return {
        "end_to_end": {"setup_s": setup_s, "epoch_s": res.epoch_time_s},
        "numbers": numbers(res.losses, ref_losses),
        "setup_parts": {"graph": s.t_graph - spec.t_start,
                        "bundle": s.bundle_s,
                        "warm_up_call": t_warm - s.t_graph - s.bundle_s,
                        "first_timed_step": res.compile_time_s},
        "reference_s": reference_s,
        "attempted": epochs, "failed": failed,
        "memory_peak_bytes": memory, "work": work,
    }
