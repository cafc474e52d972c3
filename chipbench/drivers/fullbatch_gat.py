"""Full-batch training of the published GAT, through the program's own entry
``repro.train.train_gnn``.

As ``drivers/fullbatch.py`` does for GCN: set-up is the graph (cached host
arrays, features made on the device), the program's ``build_bundle``
(tuning and packing, timed as ``bundle_s``) and a warm-up call of
``train_gnn`` that compiles the step and sizes the epochs. The window is a
second call of ``1 + ceil(seconds / epoch)`` epochs, and at least
``CHECK_STEPS``, whose own post-compile timer over the epochs after the
first is ``epoch_s``. Its first ``CHECK_STEPS`` losses are compared with the
plain reference (``chipbench/reference/gat.py``) once the window has closed:
the same count the limits were calibrated at, however long an epoch is.

The cell exists for the attention on the gather kernels: the run stops,
before the timed call, unless the program's attention op exists and the
tuner's plan for ``A + I`` has a gather layout (ELL or SELL), so a silent
fall-back to the COO composition fails the run instead of timing it.
"""
from __future__ import annotations

import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.lib import compare, gat_counts, graphgen, program, trace
from chipbench.reference import gat as ref

CHECK_STEPS = 3
GATHER_PLANS = ("sell", "ell")


def require_attention() -> None:
    """Fail at once when the program has no GAT attention op."""
    try:
        from repro.core.fusedmm import gat_attention  # noqa: F401
    except ImportError as e:
        raise SystemExit(f"chipbench: the program has no multi-head GAT "
                         f"attention ({e}); the cell cannot run") from None


class Setup:
    """The graph, the program's dataset and bundle, for one process."""

    def __init__(self, cell, root: str):
        require_attention()
        cf, tr = cell.config, cell.traffic
        self.cell = cell
        self.g = graphgen.host_graph(tr["dataset"], tr["graph_seed"],
                                     tr.get("scale", 1.0), cache_root=root)
        self.n, self.f, self.classes = (int(v) for v in self.g["meta"])
        assert cf["head_dim"][-1] == self.classes, (cf["head_dim"],
                                                     self.classes)
        self.x = graphgen.device_features(self.n, self.f, self.g["y"],
                                          tr["graph_seed"])
        self.ds = program.graph_dataset(tr["dataset"], self.g, self.x)
        from repro.models.gnn import build_bundle
        jax.block_until_ready(self.x)
        t0 = self.t_graph = time.perf_counter()
        self.bundle = build_bundle(self.ds, k_hint=self.width,
                                   slot_perm=True)
        jax.block_until_ready([a for a in jax.tree_util.tree_leaves(self.bundle)
                               if isinstance(a, jax.Array)])
        self.bundle_s = time.perf_counter() - t0
        self.plan_kind = self.bundle.tuned_norm.plan.kind
        if tr["use_isplib"] and self.plan_kind not in GATHER_PLANS:
            raise SystemExit(f"chipbench: the tuner chose a {self.plan_kind!r}"
                             f" plan for A + I; the cell needs one of "
                             f"{GATHER_PLANS}")

    @property
    def width(self) -> int:
        """Width of the concatenated hidden layers, H * F."""
        cf = self.cell.config
        return cf["heads"][0] * cf["head_dim"][0]

    def train(self, seed: int, epochs: int):
        from repro.train import train_gnn
        cf = self.cell.config
        res = train_gnn(cf["arch"], self.ds, hidden=cf["head_dim"][0],
                        heads=tuple(cf["heads"]), epochs=epochs,
                        lr=cf["optimizer"]["lr"],
                        weight_decay=cf["optimizer"]["weight_decay"],
                        use_isplib=self.cell.traffic["use_isplib"],
                        seed=seed, bundle=self.bundle)
        assert res.plan_kind == self.plan_kind, res.plan_kind
        return res

    def free_program(self) -> None:
        """Drop the program's graph state before the reference runs."""
        self.bundle = self.ds = None

    def reference(self, seed: int, dtype=jnp.float32, loss_share=1.0,
                  uniform_attention: bool = False):
        """The reference's first ``CHECK_STEPS`` losses from ``seed``.
        ``loss_share`` < 1 keeps that share of the training nodes in the
        loss; ``uniform_attention`` weighs every entry of a row alike (both
        planted faults, for calibration)."""
        cf = self.cell.config
        adj = ref.entries(self.g["src"], self.g["dst"], self.n)
        params = ref.init_params(seed, self.f, cf["heads"], cf["head_dim"],
                                 cf["concat"])
        train = self.g["split"] == 0
        if loss_share < 1:
            idx = np.flatnonzero(train)
            train[idx[int(len(idx) * loss_share):]] = False
        losses, _, _ = ref.train(params, adj, self.x, jnp.asarray(self.g["y"]),
                                 jnp.asarray(train), cf["optimizer"],
                                 cf["matmul_precision"], steps=CHECK_STEPS,
                                 dtype=dtype, uniform=uniform_attention)
        return losses


def numbers(prog_losses, ref_losses) -> dict:
    if min(len(prog_losses), len(ref_losses)) < CHECK_STEPS:
        raise ValueError(f"the check compares {CHECK_STEPS} losses; got "
                         f"{len(prog_losses)} and {len(ref_losses)}")
    return {"loss_gap": max(compare.rel_gap(a, b) for a, b in
                            zip(prog_losses[:CHECK_STEPS], ref_losses))}


def window_epochs(seconds: float, epoch_s: float) -> int:
    """Epochs of the timed call: enough to fill ``seconds`` after the
    first, and never fewer than the check compares."""
    return max(CHECK_STEPS, 1 + max(1, math.ceil(seconds / epoch_s)))


def run(spec) -> dict:
    s = Setup(spec.cell, spec.root)
    warm = s.train(spec.seed, epochs=2)
    t_warm = time.perf_counter()
    epochs = window_epochs(spec.seconds, warm.epoch_time_s)
    with trace.capture(spec.trace_dir):
        t_call = time.perf_counter()
        res = s.train(spec.seed, epochs=epochs)
    setup_s = t_call - spec.t_start + res.compile_time_s
    memory = trace.memory_peak(spec.devices)
    cf = spec.cell.config
    nnz = len(s.g["src"]) + s.n
    dims = gat_counts.layer_dims(s.f, cf["heads"], cf["head_dim"],
                                 cf["concat"])
    work = {
        "steps": epochs - 1,
        "window_s": res.epoch_time_s * (epochs - 1),
        "step_module": "jit_step",
        "flops_per_step": gat_counts.gat_epoch_flops(s.n, nnz, dims),
        "spmm_calls_per_step": gat_counts.gat_epoch_spmm_calls(s.n, nnz,
                                                               dims),
        "sddmm_calls_per_step": gat_counts.gat_epoch_sddmm_calls(s.n, nnz,
                                                                 dims),
        "bundle_s": s.bundle_s,
        "plan_kind": s.plan_kind,
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
