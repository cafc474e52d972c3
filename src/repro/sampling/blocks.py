"""Plan-aware packing of sampled MFG blocks + the block SpMM dispatch.

A :class:`repro.sampling.sampler.Block` is fresh numpy every batch; this
module turns it into a :class:`PackedBlock` — a pytree whose shapes come
from a *bucket* (see ``buckets.py``), so the jitted training step retraces
once per bucket signature instead of once per batch — and packs the
adjacency in the format the autotuner picked for that bucket:

* **ELL** — the natural fit for sampled blocks: fanout caps the row degree,
  so the neighbor table is a dense ``(n_dst, fanout)`` gather — rectangular
  ``kernels/ops.ell_spmm``.
* **SELL-C-σ** — degree-sorted slices for full-neighbor (inference) blocks
  whose degree skew survives sampling; the step count is padded up to the
  bucket's ``sell_steps`` with sentinel rows (inert: sentinel idx + zero
  val, assigned to the last slice).
* **trusted** — local COO triplets + a traced ``nnz_real`` mask; also the
  only path for max/min aggregation and the un-patched baseline.

Plans are chosen once per *shape bucket* by :class:`BlockPlanCache`
(consulting/persisting ``TuningDB`` rows under a ``block...`` string key —
per-batch structural fingerprints would never hit), which is how sampled
SpMM ends up on the same tuned kernels as full-batch training.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import sparse as sp
from repro.core.autotune import KernelPlan, TuningDB, autotune
from repro.core.semiring import Semiring, get_semiring
from repro.kernels import ops as kops
from repro.sampling.sampler import Block

Array = Any

__all__ = ["PackedBlock", "pack_block", "BlockPlanCache", "block_spmm",
           "block_spmm_baseline", "block_spmm_global", "gather_rows",
           "pad_sell_steps", "stack_blocks"]


@partial(jax.tree_util.register_dataclass,
         data_fields=["src_ids", "dst_pos", "row", "col", "val", "degrees",
                      "ell", "sell", "n_dst_real", "nnz_real"],
         meta_fields=["n_dst", "n_src", "plan_kind"])
@dataclasses.dataclass(frozen=True)
class PackedBlock:
    """Bucket-padded bipartite block, ready for a jitted step.

    Static meta (``n_dst``/``n_src``/``plan_kind``) is the bucket
    signature the step specializes on; everything per-batch (which rows
    are real, the edge lists, the sampled degrees) is traced data.
    Padding conventions: ``src_ids`` pads with ``num_nodes`` (out of
    range -> zero-fill on gather); ``col`` pads with ``n_src``; ``row``
    pads with ``n_dst - 1`` and ``val`` with 0 (inert under sum);
    ``dst_pos`` pads with ``n_src`` (zero-fill on the self-term gather).
    """

    src_ids: Array     # (n_src,) int32 global ids of source rows
    dst_pos: Array     # (n_dst,) int32 position of each dst among sources
    row: Array         # (nnz,) int32 local dst ids
    col: Array         # (nnz,) int32 local src ids
    val: Array         # (nnz,) float edge values
    degrees: Array     # (n_dst,) float32 sampled in-degrees
    ell: Optional[sp.ELL]
    sell: Optional[sp.SELL]
    n_dst_real: Array  # () int32 — real destination count
    nnz_real: Array    # () int32 — real edge count
    n_dst: int
    n_src: int
    plan_kind: str

    @property
    def nnz(self) -> int:
        return self.row.shape[0]

    @property
    def bucket_signature(self) -> tuple:
        """The (static) shape key this block retraces on."""
        sig = (self.n_dst, self.n_src, self.nnz, self.plan_kind)
        if self.sell is not None:
            sig += (self.sell.n_steps, self.sell.c, self.sell.sigma)
        if self.ell is not None:
            sig += (self.ell.max_deg,)
        return sig


def _pad_sell_steps(s: sp.SELL, n_steps: int) -> sp.SELL:
    """Pad a SELL's packed-step axis up to the bucket's static count.
    Sentinel steps carry idx == ncols (zero-gather) and val == 0 and are
    owned by the last slice — doubly inert in ``sell_packed_reduce`` and
    the Pallas kernel."""
    pad = n_steps - s.n_steps
    assert pad >= 0, (s.n_steps, n_steps)
    if pad == 0:
        return s
    idx = np.pad(np.asarray(s.idx), ((0, pad), (0, 0)),
                 constant_values=s.ncols)
    val = np.pad(np.asarray(s.val), ((0, pad), (0, 0)))
    slice_of = np.pad(np.asarray(s.slice_of), (0, pad),
                      constant_values=s.nslices - 1)
    slice_ptr = np.asarray(s.slice_ptr).copy()
    slice_ptr[-1] = n_steps
    return dataclasses.replace(
        s, idx=jnp.asarray(idx), val=jnp.asarray(val),
        slice_of=jnp.asarray(slice_of), slice_ptr=jnp.asarray(slice_ptr))


def pack_block(block: Block, *, n_dst: int, n_src: int, nnz: int,
               plan: KernelPlan, ell_width: int | None = None,
               sell_steps: int | None = None) -> PackedBlock:
    """Pad ``block`` to the bucket sizes and pack per ``plan``.

    ``ell_width`` (ELL plans) is the static neighbor-table width — the
    fanout for sampled blocks, the bucketed max degree for full-neighbor
    ones. ``sell_steps`` (SELL plans) is the *ladder base* for the packed
    step axis: the actual step count is rounded up the geometric ladder
    from it, so the traced step shape takes log-many values, not one per
    batch.
    """
    from repro.sampling.buckets import round_bucket
    assert block.n_dst <= n_dst and block.n_src <= n_src, \
        (block.n_dst, n_dst, block.n_src, n_src)
    assert block.nnz <= nnz, (block.nnz, nnz)
    nn = block.num_nodes

    src_ids = np.full(n_src, nn, np.int64)
    src_ids[: block.n_src] = block.src_ids
    dst_pos = np.full(n_dst, n_src, np.int64)      # sentinel -> zero-fill
    dst_pos[: block.n_dst] = np.arange(block.n_dst)

    row = np.full(nnz, max(n_dst - 1, 0), np.int64)
    col = np.full(nnz, n_src, np.int64)
    val = np.zeros(nnz, np.asarray(block.val).dtype
                   if block.val.size else np.float32)
    row[: block.nnz] = block.row
    col[: block.nnz] = block.col
    val[: block.nnz] = block.val

    degrees = np.zeros(n_dst, np.float32)
    degrees[: block.n_dst] = block.degrees()

    # local COO over the *padded* dst range — the host-side constructor
    # input for the packed formats (pads excluded via nse)
    local = sp.COO(row=np.asarray(block.row, np.int64),
                   col=np.asarray(block.col, np.int64),
                   val=np.asarray(block.val), nrows=n_dst, ncols=n_src,
                   nse=block.nnz)

    # NOTE: the packed containers' ``nse`` is pinned to the bucket's edge
    # capacity, not the batch's real count — ``nse`` is pytree *metadata*,
    # and a per-batch value would defeat the bucket ladder by retracing
    # the step on every distinct edge count. The kernels never read it
    # (pads are sentinel-inert); the real count lives in ``nnz_real``.
    ell = sell = None
    if plan.wants_ell:
        width = ell_width if ell_width is not None else \
            int(block.degrees().max()) if block.n_dst else 1
        ell = sp.ell_from_coo(local, max_deg=max(width, 1))
        ell = dataclasses.replace(ell, nse=nnz)
    elif plan.wants_sell:
        sell = sp.sell_from_coo(local, c=plan.sell_c, sigma=plan.sell_sigma)
        sell = _pad_sell_steps(
            sell, round_bucket(sell.n_steps, base=sell_steps or 64))
        sell = dataclasses.replace(sell, nse=nnz)

    return PackedBlock(
        src_ids=jnp.asarray(src_ids, jnp.int32),
        dst_pos=jnp.asarray(dst_pos, jnp.int32),
        row=jnp.asarray(row, jnp.int32), col=jnp.asarray(col, jnp.int32),
        val=jnp.asarray(val), degrees=jnp.asarray(degrees),
        ell=ell, sell=sell,
        n_dst_real=jnp.asarray(block.n_dst, jnp.int32),
        nnz_real=jnp.asarray(block.nnz, jnp.int32),
        n_dst=n_dst, n_src=n_src, plan_kind=plan.kind)


def pad_sell_steps(pb: PackedBlock, n_steps: int) -> PackedBlock:
    """``pb`` with its SELL packed-step axis padded up to ``n_steps``
    (inert sentinel steps — see ``_pad_sell_steps``). No-op for non-SELL
    plans or when already at ``n_steps``."""
    if pb.sell is None or pb.sell.n_steps >= n_steps:
        return pb
    return dataclasses.replace(pb, sell=_pad_sell_steps(pb.sell, n_steps))


def stack_blocks(pbs: list[PackedBlock]) -> PackedBlock:
    """Stack per-shard packed blocks of one layer along a new leading axis.

    The container the data-parallel trainer hands to ``shard_map``: leaf
    ``i`` of the result is ``stack([shard_0.leaf_i, ...])`` and the static
    meta is shared, so ``in_specs=P('data')`` splits the stack back into
    one real block per shard (the shard body squeezes the unit leading
    axis off). SELL step counts can legitimately differ across shards —
    they are padded to the shard max first (a ladder value, so the bucket
    bound on retraces survives); every other static must already agree,
    which the lockstep bucket merge (``buckets.merge_buckets``) plus the
    shared per-bucket plan guarantee. Asserted here."""
    sell_steps = [pb.sell.n_steps for pb in pbs if pb.sell is not None]
    if sell_steps:
        pbs = [pad_sell_steps(pb, max(sell_steps)) for pb in pbs]
    sigs = {pb.bucket_signature for pb in pbs}
    assert len(sigs) == 1, f"lockstep shards disagree on signature: {sigs}"
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *pbs)


# --------------------------------------------------------------------------
# Per-bucket plan selection (the autotuner applied to sampled workloads)
# --------------------------------------------------------------------------

class BlockPlanCache:
    """One :func:`repro.core.autotune` decision per (bucket shape, K,
    semiring) — the §3.2 sweep amortized over every batch that lands in
    the bucket, persisted across processes via ``TuningDB`` string keys.

    BSR is excluded from the sweep (``tile_candidates=()``): a sampled
    bipartite block has no dense tiles worth an MXU pass, and PackedBlock
    doesn't carry the format.
    """

    def __init__(self, *, semiring: str = "sum", tune: bool = True,
                 measure: bool = False, db: Optional[TuningDB] = None):
        self.semiring = semiring
        self.tune = tune
        self.measure = measure
        self.db = db
        self._plans: dict[tuple, KernelPlan] = {}

    @staticmethod
    def key(n_dst: int, n_src: int, nnz: int, k: int, semiring: str) -> str:
        return f"block{n_dst}x{n_src}nse{nnz}k{k}sr{semiring}"

    def plan_for(self, block: Block, *, n_dst: int, n_src: int, nnz: int,
                 k_hint: int, sell_ok: bool = True) -> KernelPlan:
        """``sell_ok=False`` restricts the candidate sweep (analytic and
        measured) to ELL/trusted — for consumers whose packing cannot
        build the degree-sorted SELL layout (the device-resident sampler),
        so they get the measured best of what they can actually run
        instead of a plan that silently degrades. Restricted plans cache
        and persist under their own key."""
        from repro import obs

        ck = (n_dst, n_src, nnz, k_hint, self.semiring, sell_ok)
        plan = self._plans.get(ck)
        if plan is not None:
            return plan
        skey = self.key(*ck[:5]) + ("" if sell_ok else "nosell")
        source = None
        if self.db is not None:
            plan = self.db.get_key(skey)
            source = "db" if plan is not None else None
        if plan is None:
            if self.tune and block.nnz:
                rep = sp.COO(row=np.asarray(block.row, np.int64),
                             col=np.asarray(block.col, np.int64),
                             val=np.asarray(block.val), nrows=n_dst,
                             ncols=n_src, nse=block.nnz)
                plan = autotune(rep, k_hint, measure=self.measure,
                                semiring_reduce=self.semiring,
                                tile_candidates=(),
                                sell_candidates=None if sell_ok else ())
                source = "measure" if self.measure else "sweep"
            else:
                plan = KernelPlan.trusted(k_hint)
                source = "untuned"
            if self.db is not None:
                self.db.put_key(skey, plan)
                self.db.save()
        self._plans[ck] = plan
        if obs.enabled():
            obs.instant("tuning.plan", site="block_plan_cache", key=skey,
                        source=source, kind=plan.kind)
        return plan

    def kinds(self) -> tuple:
        """Distinct kernel kinds chosen so far (sorted, for reporting)."""
        return tuple(sorted({p.kind for p in self._plans.values()}))


# --------------------------------------------------------------------------
# Block SpMM dispatch (registered as the 'block_spmm' op — patch-aware)
# --------------------------------------------------------------------------

def _trusted_reduce(pb: PackedBlock, h: Array, sr: Semiring) -> Array:
    """Segment-op path over the local COO triplets. Pads are masked by the
    *traced* ``nnz_real`` (bucket padding keeps static shapes, so the COO
    ``nse`` convention can't serve here)."""
    gathered = jnp.take(h, pb.col, axis=0, mode="fill", fill_value=0)
    msgs = sr.apply_combine(pb.val[:, None], gathered)
    valid = (jnp.arange(pb.nnz) < pb.nnz_real)[:, None]
    fill = jnp.asarray(sr.identity, msgs.dtype)
    msgs = jnp.where(valid, msgs, fill)
    out = sr.segment_reduce(msgs, pb.row, pb.n_dst)
    return sr.finalize(out, pb.degrees)


def block_spmm(pb: PackedBlock, h: Array, reduce: str = "mean",
               combine: str = "mul") -> Array:
    """out[i,:] = ⊕_{j in sampled N(i)} (A_ij ⊗ h[j,:]) over one block.

    The tuned path: the bucket's plan routes sum/mean through the packed
    ELL/SELL kernels (``kernels/ops``), mean dividing by the *sampled*
    degree; anything else takes the trusted segment path. Differentiable
    in ``h`` by plain AD — per-batch blocks have no reusable transpose to
    cache, so the custom-VJP machinery of the full-graph path would buy
    nothing here."""
    from repro.obs import op_record

    sr = get_semiring(reduce, combine)
    if pb.plan_kind == "ell" and pb.ell is not None and sr.mxu_eligible:
        out = kops.ell_spmm(pb.ell, h)
    elif pb.plan_kind == "sell" and pb.sell is not None and sr.mxu_eligible:
        out = kops.sell_spmm(pb.sell, h)
    else:
        out = _trusted_reduce(pb, h, sr).astype(h.dtype)
        op_record("block_spmm", h, plan="trusted", reduce=reduce)
        return out
    if sr.reduce == "mean":
        out = out * (1.0 / jnp.maximum(pb.degrees, 1.0))[:, None]
    out = out.astype(h.dtype)
    op_record("block_spmm", h, plan=pb.plan_kind, reduce=reduce)
    return out


def block_spmm_baseline(pb: PackedBlock, h: Array, reduce: str = "mean",
                        combine: str = "mul") -> Array:
    """The un-patched path: always the trusted segment ops, plan ignored —
    the PT-equivalent a sampled DGL/PyG loop would run."""
    sr = get_semiring(reduce, combine)
    return _trusted_reduce(pb, h, sr).astype(h.dtype)


def gather_rows(h_full: Array, ids: Array) -> Array:
    """Zero-filled row gather (out-of-range ids -> 0 rows)."""
    return jnp.take(h_full, ids, axis=0, mode="fill", fill_value=0)


def block_spmm_global(pb: PackedBlock, h_full: Array,
                      reduce: str = "mean", combine: str = "mul") -> Array:
    """Block SpMM whose dense operand is the *full* node-feature matrix
    (layer-wise inference): ELL plans fuse the src-feature gather into the
    neighbor gather (``kernels/ops.gathered_ell_spmm`` — the block's
    source rows are never materialized); other plans gather then
    dispatch."""
    from repro.core.patch import is_patched

    sr = get_semiring(reduce, combine)
    if (is_patched() and pb.plan_kind == "ell" and pb.ell is not None
            and sr.mxu_eligible):
        out = kops.gathered_ell_spmm(pb.ell, h_full, pb.src_ids)
        if sr.reduce == "mean":
            out = out * (1.0 / jnp.maximum(pb.degrees, 1.0))[:, None]
        return out.astype(h_full.dtype)
    h_src = gather_rows(h_full, pb.src_ids)
    fn = block_spmm if is_patched() else block_spmm_baseline
    return fn(pb, h_src, reduce, combine)
