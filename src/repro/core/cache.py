"""CachedGraph — the cache-enabled backpropagation artifact store (paper §3.3).

iSpLib's big end-to-end win comes from computing graph-static intermediates
ONCE and reusing them every step/epoch:

  * the transposed adjacency (backward pass operand)   — here: ``coo_t``/``bsr_t``/``sell_t``
  * the GCN-normalized adjacency                        — built via
    :func:`repro.core.sparse.gcn_normalize` before caching
  * row degrees / inverse degrees (mean semiring)       — ``degrees``/``inv_deg``
  * format conversion + kernel plan (autotuner output)  — ``bsr``/``sell``/``plan``
  * the tuner decision itself, across *processes*       — pass a
    :class:`repro.core.autotune.TuningDB` as ``db=`` and measured plans
    persist to disk (§3.2 one-time tuning)

The uncached baseline (what the paper compares against) recomputes the
normalization per forward and materializes message gradients per backward;
see ``benchmarks/bench_cached_backprop.py``.

A CachedGraph is a pytree and can be donated/closed-over by jitted steps.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import sparse as sp
from repro.core.autotune import (KernelPlan, TuningDB,  # noqa: F401 (re-export)
                                 autotune)

Array = Any

__all__ = ["CachedGraph", "build_cached_graph", "slot_rows",
           "transpose_slot_perm"]


@partial(jax.tree_util.register_dataclass,
         data_fields=["coo", "coo_t", "bsr", "bsr_t", "sell", "sell_t",
                      "ell", "ell_t", "slot_perm", "degrees", "degrees_t",
                      "inv_deg", "inv_deg_t"],
         meta_fields=["plan"])
@dataclasses.dataclass(frozen=True)
class CachedGraph:
    coo: sp.COO
    coo_t: sp.COO                 # cached transpose — §3.3
    bsr: Optional[sp.BSR]         # generated-kernel format (None if plan is trusted)
    bsr_t: Optional[sp.BSR]
    sell: Optional[sp.SELL]       # SELL-C-σ format (None unless plan wants it)
    sell_t: Optional[sp.SELL]
    ell: Optional[sp.ELL]         # ELLPACK (None unless plan wants it)
    ell_t: Optional[sp.ELL]
    slot_perm: Optional[Array]    # transpose slot -> forward slot (gather plans)
    degrees: Array                # out-degree per row of A
    degrees_t: Array              # per row of A^T
    inv_deg: Array                # 1/max(deg,1)  (mean semiring, cached)
    inv_deg_t: Array
    plan: KernelPlan              # static: autotuner decision

    @property
    def shape(self):
        return self.coo.shape

    @property
    def nrows(self):
        return self.coo.nrows

    @property
    def ncols(self):
        return self.coo.ncols


def slot_rows(a) -> np.ndarray:
    """Original row of every slot of an ELL or SELL table, flattened in
    table order (host arrays; SELL's pad rows are ids >= nrows)."""
    if isinstance(a, sp.ELL):
        return np.repeat(np.arange(a.nrows, dtype=np.int64), a.max_deg)
    sorted_row = (np.asarray(a.slice_of, np.int64)[:, None] * a.c
                  + np.arange(a.c))
    return np.asarray(a.perm, np.int64)[sorted_row].reshape(-1)


def transpose_slot_perm(fwd, tr) -> Array:
    """For every slot of ``tr`` (the packed transpose of ``fwd``), the slot
    of ``fwd`` that holds the same entry, or ``fwd``'s slot count for a
    pad slot. Both tables hold the same multiset of (row, col) entries,
    so sorting each side's entry keys pairs them; duplicate entries pair
    by rank."""
    n_f = int(np.prod(fwd.idx.shape))
    idx_f = np.asarray(fwd.idx).reshape(-1).astype(np.int64)
    idx_t = np.asarray(tr.idx).reshape(-1).astype(np.int64)
    live_f = np.flatnonzero(idx_f < fwd.ncols)
    live_t = np.flatnonzero(idx_t < tr.ncols)
    assert len(live_f) == len(live_t), (len(live_f), len(live_t))
    key_f = slot_rows(fwd)[live_f] * fwd.ncols + idx_f[live_f]
    key_t = idx_t[live_t] * fwd.ncols + slot_rows(tr)[live_t]
    perm = np.full(idx_t.shape[0], n_f, np.int32)
    perm[live_t[np.argsort(key_t)]] = live_f[np.argsort(key_f)]
    return jnp.asarray(perm)


def build_cached_graph(a: sp.COO, *, k_hint: int = 128,
                       plan: KernelPlan | None = None,
                       tune: bool = True,
                       measure: bool = False,
                       semiring_reduce: str = "sum",
                       db: Optional[TuningDB] = None,
                       slot_perm: bool = False) -> CachedGraph:
    """Host-side one-time preprocessing: transpose, degrees, BSR/SELL
    packing, kernel plan. ``k_hint`` is the embedding width the tuner
    optimizes for. A ``db`` (TuningDB) short-circuits the sweep with a
    previously persisted decision and records fresh ones — the paper's
    tune-once amortization across runs. ``semiring_reduce`` keys the DB row
    and, under ``measure=True``, makes the wall-clock pass time that
    semiring's own cost (mean's post-scale, max/min's segment reduce).

    Its parts are the set-up spans ``setup.transpose`` (with degrees),
    ``setup.tune`` (the sweep or a DB read) and ``setup.pack``, each also
    counted in ``setup.<part>_s`` (``obs.counted_span``).

    On a gather plan (ELL or SELL) with ``slot_perm``, the set-up span
    ``setup.slot_perm`` (counted in ``setup.slot_perm_s``) builds the
    forward-to-transpose slot permutation: for every slot of the cached
    transpose's table, the slot of the forward table that holds the same
    entry (the forward slot count for a pad slot). Per-slot values that
    change every step (attention weights) reach the transpose through
    it."""
    from repro import obs
    with obs.counted_span("setup.transpose"):
        a_t = sp.coo_transpose(a)
        deg = sp.row_degrees(a)
        deg_t = sp.row_degrees(a_t)
        inv_deg = 1.0 / jnp.maximum(deg, 1.0)
        inv_deg_t = 1.0 / jnp.maximum(deg_t, 1.0)

    source = "caller"
    with obs.counted_span("setup.tune"):
        if plan is None and db is not None:
            plan = db.get(a, k_hint, semiring=semiring_reduce)
            source = "db"
            obs.metrics().counter(
                "tuning.db.hit" if plan is not None
                else "tuning.db.miss").inc()
        if plan is None and tune:
            plan = autotune(a, k_hint, measure=measure,
                            semiring_reduce=semiring_reduce)
            source = "measure" if measure else "sweep"
            if db is not None:
                db.put(a, k_hint, plan, semiring=semiring_reduce)
                db.save()
        elif plan is None:
            plan = KernelPlan.trusted()
            source = "untuned"
    if obs.enabled():
        obs.instant("tuning.plan", site="build_cached_graph", source=source,
                    kind=plan.kind, k=k_hint, semiring=semiring_reduce,
                    graph=f"{a.nrows}x{a.ncols}nse{a.nse}")

    bsr = bsr_t = sell = sell_t = ell = ell_t = None
    with obs.counted_span("setup.pack", kind=plan.kind):
        if plan.wants_bsr:
            bsr = sp.bsr_from_coo(a, br=plan.br, bc=plan.bc)
            bsr_t = sp.bsr_from_coo(a_t, br=plan.br, bc=plan.bc)
        if plan.wants_sell:
            sell = sp.sell_from_coo(a, c=plan.sell_c, sigma=plan.sell_sigma)
            sell_t = sp.sell_from_coo(a_t, c=plan.sell_c,
                                      sigma=plan.sell_sigma)
        if plan.wants_ell:
            ell = sp.ell_from_coo(a)
            ell_t = sp.ell_from_coo(a_t)

    perm = None
    if slot_perm and (sell is not None or ell is not None):
        with obs.counted_span("setup.slot_perm"):
            perm = transpose_slot_perm(sell or ell, sell_t or ell_t)

    return CachedGraph(
        coo=a, coo_t=a_t, bsr=bsr, bsr_t=bsr_t, sell=sell, sell_t=sell_t,
        ell=ell, ell_t=ell_t, slot_perm=perm,
        degrees=deg, degrees_t=deg_t, inv_deg=inv_deg, inv_deg_t=inv_deg_t,
        plan=plan,
    )
