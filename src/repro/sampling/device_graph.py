"""Device-resident graph + fused k-hop sampling (the GraphBolt pattern).

The host :class:`~repro.sampling.sampler.NeighborSampler` is per-batch
numpy: rank-select, relabel and block-pack all round-trip through host
memory every minibatch, and the ``loader.prefetch`` thread only hides part
of it. This module moves that stage on-device:

* :class:`DeviceGraph` — the CSR topology ``device_put`` **once** (a pytree,
  replicated over the mesh when given one), with one sentinel entry
  appended to ``indices``/``val`` so invalid sample slots route to an inert
  edge (id ``num_nodes``, value 0) instead of needing a host-side compact.
* :class:`DeviceSampler` — ``sample_blocks(seeds, rnd)`` is a *traced*
  function: every hop runs the ``kernels/sample`` primitives
  (``segment_sample`` → ``expand_indptr`` → ``flat_gather``), a sort/unique
  relabel, and emits a bucket-static :class:`~repro.sampling.blocks.
  PackedBlock` — so sample + pack + train-step jit-fuse into **one**
  program per bucket, and there is exactly one bucket: the per-hop
  capacities are fixed at construction from ``(batch_size, fanouts)``
  worst cases on *distinct* reachable ids (saturating at ``num_nodes``),
  rounded up to a multiple of the bucket base.

Determinism contract: draws are keyed on ``(seed, round, hop, node id,
slot)`` by a counter-based stateless hash, so a fixed ``(seeds, round)``
replays bit-for-bit — same property as the host sampler, but a *different
stream*: ``sampler="device"`` changes which edges a sampled run draws
(not their distribution). Full-neighbor hops (``fanout=None``) consume no
randomness and match the host sampler exactly (same edge multiset per
destination; column order differs — device relabel is sorted-unique, host
is first-appearance).

Capacity padding convention (vs host ``pack_block``): invalid edge slots
keep their true ``row``, carry ``col == n_src`` / ``val == 0`` (inert
under sum/mean), and ``nnz_real`` is pinned to the capacity so the trusted
path's prefix mask is a no-op — device blocks are therefore only valid for
sum/mean aggregation, which the trainer enforces.
"""
from __future__ import annotations

import copy
import dataclasses
from functools import partial
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import sparse as sp
from repro.core.autotune import KernelPlan
from repro.kernels import sample as ksample
from repro.sampling.blocks import PackedBlock
from repro.sampling.buckets import LayerBucket

Array = Any

__all__ = ["DeviceGraph", "DeviceSampler", "device_graph_from_csr"]


@partial(jax.tree_util.register_dataclass,
         data_fields=["indptr", "indices", "val"],
         meta_fields=["num_nodes", "nse", "max_deg"])
@dataclasses.dataclass(frozen=True)
class DeviceGraph:
    """CSR topology resident on device, sentinel-extended.

    ``indices``/``val`` carry ``nse + 1`` entries: the last is the inert
    sentinel edge (neighbor id ``num_nodes``, value 0) that
    ``expand_indptr`` routes invalid sample slots to.
    """

    indptr: Array      # (num_nodes + 1,) int32
    indices: Array     # (nse + 1,) int32, indices[nse] == num_nodes
    val: Array         # (nse + 1,) float32, val[nse] == 0
    num_nodes: int
    nse: int
    max_deg: int       # host-computed max in-degree (>= 1)


def device_graph_from_csr(csr: sp.CSR, *, mesh=None) -> DeviceGraph:
    """``device_put`` the adjacency once (replicated over ``mesh`` when
    given — each host shard samples from its own resident copy)."""
    assert csr.nrows == csr.ncols, "sampling expects a square adjacency"
    n = int(csr.nrows)
    indptr = np.asarray(csr.indptr, np.int64)
    indices = np.concatenate([np.asarray(csr.indices)[: csr.nse],
                              [n]]).astype(np.int32)
    val = np.concatenate([np.asarray(csr.val)[: csr.nse],
                          [0]]).astype(np.float32)
    max_deg = int(np.diff(indptr).max()) if n else 1
    from repro.dist.mesh import replicated_device_put
    place = partial(replicated_device_put, mesh=mesh)
    return DeviceGraph(
        indptr=place(jnp.asarray(indptr, jnp.int32)),
        indices=place(jnp.asarray(indices)),
        val=place(jnp.asarray(val)),
        num_nodes=n, nse=int(csr.nse), max_deg=max(max_deg, 1))


def _device_relabel(frontier: Array, nbr: Array, valid: Array, *,
                    n_src: int, num_nodes: int):
    """Traced analog of ``sampler._relabel``: the new source set is the
    sorted unique of (frontier ∪ sampled neighbors) — *deduplicating the
    frontier into the union* rather than keeping it as a positional prefix,
    so the per-hop capacity tracks the bound on **distinct** reachable ids
    (which saturates at ``num_nodes``) instead of compounding padded slot
    counts hop over hop. ``jnp.unique`` with static size: the ``num_nodes``
    sentinel sorts last, so truncation drops sentinels first and real ids
    only when the capacity was probed below the worst case.

    Overflow is *graceful*, never silent: every bisection is verified by
    gathering the id back — an edge whose endpoint was truncated out of
    ``src_ids`` is dropped (``ok`` False → inert slot), not mis-mapped to
    a neighboring id's features.

    Returns ``(src_ids (n_src,), col (F, width), ok (F, width))`` with
    ``col == n_src`` on invalid/dropped slots (the inert ELL/gather
    sentinel)."""
    cand = jnp.concatenate(
        [frontier, jnp.where(valid, nbr, num_nodes).ravel()])
    src_ids = jnp.unique(cand, size=n_src,
                         fill_value=num_nodes).astype(jnp.int32)
    pos = jnp.clip(jnp.searchsorted(src_ids, nbr), 0,
                   n_src - 1).astype(jnp.int32)
    ok = valid & (jnp.take(src_ids, pos) == nbr)
    col = jnp.where(ok, pos, jnp.int32(n_src))
    return src_ids, col, ok


class DeviceSampler:
    """Traced fused k-hop sampler over a :class:`DeviceGraph`.

    Mirrors the host ``NeighborSampler`` contract (``fanouts`` outermost-
    last, ``None`` = full neighborhood, ``replace`` with-replacement) but
    with *static* per-hop capacities: hop ``j`` (innermost first) expands
    ``r_j`` distinct reachable ids by width ``w_j`` (the fanout, or the
    graph max degree for full hops) into at most ``min(r_j * (1 + w_j),
    num_nodes)`` distinct sources (the relabel dedupes the frontier into
    the union, so the bound saturates at the node count instead of
    compounding), rounded up to a multiple of ``base`` — so the shapes,
    and therefore the jit trace, are fixed per ``(batch_size, fanouts)``.

    Call :meth:`set_plans` (outermost-first, one per layer — from the same
    ``BlockPlanCache``/TuningDB mechanism the host path uses) before
    :meth:`sample_blocks`.
    """

    def __init__(self, graph: DeviceGraph, fanouts: Sequence, *,
                 batch_size: int, seed: int = 0, replace: bool = False,
                 base: int = 128, src_caps: Optional[Sequence[int]] = None,
                 interpret: Optional[bool] = None):
        self.graph = graph
        self.fanouts = tuple(fanouts)
        self.batch_size = int(batch_size)
        self.seed = int(seed)
        self.replace = bool(replace)
        self.interpret = interpret
        self._plans: Optional[list[KernelPlan]] = None

        # innermost-first (hop 0 = seeds' direct neighbors) capacity chain.
        # ``bound`` is the exact worst case on *distinct* real ids a
        # frontier can hold (the relabel dedupes the frontier into the
        # union, so it saturates at num_nodes); ``src_caps`` (innermost-
        # first, e.g. probed from a few host-sampled batches) trades that
        # worst case for the observed scale — overflow then *drops* tail
        # edges gracefully (see ``_device_relabel``) instead of padding
        # every batch to a bound real batches never reach. Capacities
        # round up to a multiple of ``base`` only: there is exactly one
        # static shape per (batch_size, fanouts), so the geometric ladder
        # the host path needs to bound retracing would be pure padding.
        if src_caps is not None:
            assert len(src_caps) == len(self.fanouts), (src_caps, fanouts)
        self._hop_dims: list[tuple[int, int, int]] = []  # (n_dst,n_src,width)
        level = self.batch_size
        real = self.batch_size
        for j, fanout in enumerate(reversed(self.fanouts)):
            width = int(fanout) if fanout is not None else graph.max_deg
            width = max(width, 1)
            bound = min(real * (1 + width), graph.num_nodes)
            tgt = bound if src_caps is None else min(int(src_caps[j]), bound)
            n_src = -(-max(tgt, 1) // base) * base
            self._hop_dims.append((level, n_src, width))
            level = n_src
            real = min(n_src, bound)

    def with_graph(self, graph: DeviceGraph) -> "DeviceSampler":
        """This sampler (same capacities, plans and seed) reading
        ``graph`` — how a jitted step samples from the topology it took
        as an argument, so the edge arrays never become program
        constants."""
        twin = copy.copy(self)
        twin.graph = graph
        return twin

    # -- bucket/plan plumbing (reuses the host ladder machinery) ----------
    @property
    def buckets(self) -> list[LayerBucket]:
        """Outermost-first per-layer buckets — the keys ``BlockPlanCache``
        plans against (device capacities give their own bucket keys)."""
        out = [LayerBucket(n_dst=d, n_src=s, nnz=d * w, ell_width=w,
                           sell_steps=None)
               for d, s, w in self._hop_dims]
        return out[::-1]

    def set_plans(self, plans: Sequence[KernelPlan]) -> None:
        """Per-layer kernel plans, outermost first (same order as
        ``sample_blocks`` output). SELL/BSR plans are remapped to ELL:
        device packing never builds them (the degree-sorted permutation
        and the tile layout are host-side constructions), and fanout
        sampling *is* the fixed-width ELL layout — remapping keeps the
        layer on a generated kernel instead of silently degrading to the
        trusted dispatch."""
        assert len(plans) == len(self.fanouts), (len(plans),
                                                 len(self.fanouts))
        self._plans = [dataclasses.replace(p, kind="ell")
                       if p.kind in ("sell", "bsr") else p
                       for p in plans]

    @property
    def signature(self) -> tuple:
        """Static bucket signature of the emitted block tuple — one entry
        per layer, mirroring ``PackedBlock.bucket_signature``."""
        assert self._plans is not None, "call set_plans() first"
        sig = []
        for (d, s, w), plan in zip(self._hop_dims[::-1], self._plans):
            entry = (d, s, d * w, plan.kind)
            if plan.wants_ell:
                entry += (w,)
            sig.append(entry)
        return tuple(sig)

    # -- one traced hop ---------------------------------------------------
    def _hop(self, frontier: Array, hop: int, rnd):
        g = self.graph
        n_dst, n_src, width = self._hop_dims[hop]
        fanout = tuple(reversed(self.fanouts))[hop]
        plan = self._plans[len(self.fanouts) - 1 - hop]

        # degrees via clipped indptr lookups: sentinel frontier entries
        # (id == num_nodes) land on indptr[N] twice -> degree 0
        start = jnp.take(g.indptr, frontier, mode="clip")
        end = jnp.take(g.indptr, jnp.minimum(frontier + 1, g.num_nodes),
                       mode="clip")
        deg = end - start

        ranks = ksample.segment_sample(
            deg, frontier, rnd, width=width, fanout=fanout, seed=self.seed,
            hop=hop, replace=self.replace, interpret=self.interpret)
        valid = ksample.sample_valid_mask(deg, width=width, fanout=fanout,
                                          replace=self.replace)
        pos = ksample.expand_indptr(start, ranks, valid, sentinel=g.nse,
                                    interpret=self.interpret)
        nbr = ksample.flat_gather(g.indices, pos, interpret=self.interpret)
        evals = ksample.flat_gather(g.val, pos, interpret=self.interpret)

        src_ids, col2d, ok = _device_relabel(frontier, nbr, valid,
                                             n_src=n_src,
                                             num_nodes=g.num_nodes)

        nnz = n_dst * width
        row = jax.lax.broadcasted_iota(jnp.int32, (n_dst, width), 0)
        val2d = jnp.where(ok, evals, 0.0)
        ell = None
        if plan.wants_ell:
            ell = sp.ELL(idx=col2d, val=val2d, nrows=n_dst, ncols=n_src,
                         nse=nnz)
        # dst node i is frontier[i]; its self-term row in the (deduped,
        # sorted) source set is found by bisection with the same
        # gather-back overflow guard: a truncated dst id zero-fills its
        # self term rather than reading a neighboring id's features
        dpos = jnp.clip(jnp.searchsorted(src_ids, frontier), 0,
                        n_src - 1).astype(jnp.int32)
        dok = (frontier < g.num_nodes) & (jnp.take(src_ids, dpos)
                                          == frontier)
        dst_pos = jnp.where(dok, dpos, jnp.int32(n_src))
        # capacity-overflow count: sampled edges whose endpoint (or a dst
        # id's self term) was truncated out of src_ids by a probed capacity
        # below this batch's distinct-id reach. Dropped gracefully above
        # (inert slots) — this is the *surfacing* half of the contract.
        ovf = (jnp.sum((valid & ~ok).astype(jnp.int32))
               + jnp.sum(((frontier < g.num_nodes) & ~dok).astype(jnp.int32)))
        return ovf, PackedBlock(
            src_ids=src_ids,
            dst_pos=dst_pos,
            row=row.ravel(), col=col2d.ravel(), val=val2d.ravel(),
            degrees=jnp.sum(ok, axis=1).astype(jnp.float32),
            ell=ell, sell=None,
            n_dst_real=jnp.sum(frontier < g.num_nodes).astype(jnp.int32),
            # capacity, NOT the real count: invalid slots are scattered
            # through the table (not prefix-compacted), so the trusted
            # path's prefix mask must be a no-op — inertness comes from
            # val == 0 / col == n_src. Sum/mean only (trainer enforces).
            nnz_real=jnp.asarray(nnz, jnp.int32),
            n_dst=n_dst, n_src=n_src, plan_kind=plan.kind)

    # -- the fused k-hop pass (traced) ------------------------------------
    def sample_blocks(self, seeds: Array, rnd) -> tuple:
        """All hops for one seed batch, outermost first (host ``sample``
        order). ``seeds`` is the static ``(batch_size,)`` int32 vector with
        pad slots already set to the ``num_nodes`` sentinel; ``rnd`` is the
        (traced) round counter. Jit/shard_map-safe throughout."""
        return self.sample_blocks_stats(seeds, rnd)[0]

    def sample_blocks_stats(self, seeds: Array, rnd):
        """:meth:`sample_blocks` plus the batch's capacity-overflow count —
        ``(blocks, ovf)`` where ``ovf`` is the int32 number of sampled
        edges/self-terms dropped because a probed ``src_caps`` capacity was
        below this batch's distinct-id reach. The trainer accumulates it
        per epoch and escalates (re-probes capacities) when nonzero."""
        assert self._plans is not None, "call set_plans() first"
        frontier = seeds.astype(jnp.int32)
        blocks = []
        ovf = jnp.int32(0)
        for hop in range(len(self.fanouts)):
            hop_ovf, blk = self._hop(frontier, hop, rnd)
            ovf = ovf + hop_ovf
            blocks.append(blk)
            frontier = blk.src_ids
        return tuple(blocks[::-1]), ovf
