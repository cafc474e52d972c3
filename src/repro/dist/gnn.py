"""Distributed GNN message passing: 1-D row partition + halo'd banded SpMM.

The adjacency is split into ``num_parts`` contiguous row bands; the 2-D
vertex-cut generalization (tile grid, O(N/sqrt(P)) communication) lives
in :mod:`repro.dist.gnn2d` — this module remains the
simpler 1-D path, the right choice on small meshes where one fused
all-gather beats two grid collectives. Each band's layout follows the
*kernel plan* instead of hard-coding ELLPACK:

* ``kind == 'ell'`` (default / trusted plans): per-row padded neighbor
  lists, the original path — rectangular static gather tensor, halo = the
  columns the local ELL indexes.
* ``kind == 'sell'`` (plan selects SELL-C-σ): each band is degree-sorted
  and packed into slices of C rows padded to their own max degree
  (:func:`repro.core.sparse.sell_from_coo` per band, σ = band size), with
  the inverse row permutation applied band-locally after the reduce. On
  power-law graphs this shrinks the per-device gather tensor by the same
  factor as the single-device SELL kernel — the banding does not change
  the skew, so neither should the layout.

``distributed_spmm`` runs one step of A @ H under ``shard_map``: the
feature matrix H arrives row-sharded over the same axis, the halo exchange
is a tiled ``all_gather`` of H (every remote row a band could touch,
fetched in one fused collective — on TPU this beats per-neighbor sends by
a wide margin), then the band's gather/multiply/reduce runs locally.
Values and inverse degrees come pre-normalized from the
:class:`CachedGraph` machinery (core/spmm.py §3.3 caching), so nothing
graph-static is recomputed per step.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import sparse as sp
from repro.core.autotune import KernelPlan
from repro.core.cache import CachedGraph, build_cached_graph

Array = Any

__all__ = ["DistGraph", "build_dist_graph", "distributed_spmm",
           "comm_volume"]


@partial(jax.tree_util.register_dataclass,
         data_fields=["idx", "val", "inv_deg", "slice_of", "inv_perm"],
         meta_fields=["nrows", "ncols", "parts", "rows_per_part", "kind",
                      "sell_c"])
@dataclasses.dataclass(frozen=True)
class DistGraph:
    """Row-banded adjacency, stackable over the partition axis.

    ELL layout (``kind == 'ell'``): ``idx``/``val`` are
    (parts, rows_per_part, max_deg) with the pad sentinel ``idx == ncols``;
    ``slice_of``/``inv_perm`` are None.

    SELL layout (``kind == 'sell'``): ``idx``/``val`` are
    (parts, n_steps, C) packed degree-major per band (bands padded to a
    common step count with sentinel steps); ``slice_of`` is
    (parts, n_steps) and ``inv_perm`` (parts, rows_per_part) maps each
    band-local original row to its degree-sorted position.

    Column ids are GLOBAL in both layouts (they index the gathered H).
    ``inv_deg``: (parts, rows_per_part) cached 1/deg for the mean semiring.
    Rows past ``nrows`` (partition padding) are empty.
    """

    idx: Array
    val: Array
    inv_deg: Array
    slice_of: Optional[Array]
    inv_perm: Optional[Array]
    nrows: int
    ncols: int
    parts: int
    rows_per_part: int
    kind: str = "ell"
    sell_c: int = 8

    @property
    def max_deg(self) -> int:
        assert self.kind == "ell", "max_deg is an ELL-layout property"
        return self.idx.shape[-1]

    @property
    def n_steps(self) -> int:
        assert self.kind == "sell", "n_steps is a SELL-layout property"
        return self.idx.shape[1]

    @property
    def shape(self):
        return (self.nrows, self.ncols)


def _band_coo(row, col, val, lo: int, hi: int, nrows_band: int,
              ncols: int) -> sp.COO:
    m = (row >= lo) & (row < hi)
    return sp.coo_from_edges(col[m], row[m] - lo, val[m],
                             nrows=nrows_band, ncols=ncols)


def build_dist_graph(a: Union[sp.COO, sp.CSR, CachedGraph],
                     num_parts: int,
                     plan: Optional[KernelPlan] = None) -> DistGraph:
    """Host-side one-time partition (the cached-graph philosophy: all
    per-part structure is built once, never inside the training step).

    The band layout follows ``plan`` (explicit argument wins; else the
    CachedGraph's autotuned plan; else ELL): a SELL plan packs each band
    degree-sorted, anything else keeps the rectangular ELL band."""
    if isinstance(a, sp.CSR):
        a = a.to_coo()
    if isinstance(a, sp.COO):
        a = build_cached_graph(a, tune=False)
    if plan is None:
        plan = a.plan
    coo = a.coo
    nrows, ncols = coo.nrows, coo.ncols
    row = np.asarray(coo.row)[: coo.nse]
    col = np.asarray(coo.col)[: coo.nse]
    val = np.asarray(coo.val)[: coo.nse]
    deg = np.asarray(a.degrees)

    if plan.wants_sell:
        return _build_dist_sell(row, col, val, deg, nrows, ncols, num_parts,
                                c=plan.sell_c)

    rp = -(-nrows // num_parts)                   # rows per band, padded
    # common max_deg across bands so the per-part ELLs stack into one array
    counts = np.bincount(row, minlength=nrows)
    max_deg = max(int(counts.max()) if counts.size else 1, 1)

    idxs, vals, invs = [], [], []
    for p in range(num_parts):
        lo, hi = p * rp, min((p + 1) * rp, nrows)
        n_loc = max(hi - lo, 0)          # trailing bands can be empty
        if n_loc:
            part = _band_coo(row, col, val, lo, hi, n_loc, ncols)
            ell = sp.ell_from_coo(part, max_deg=max_deg)
            idx_p, val_p = np.asarray(ell.idx), np.asarray(ell.val)
        else:
            idx_p = np.empty((0, max_deg), np.int32)
            val_p = np.empty((0, max_deg), val.dtype)
        pad = rp - n_loc
        idxs.append(np.pad(idx_p, ((0, pad), (0, 0)),
                           constant_values=ncols))
        vals.append(np.pad(val_p, ((0, pad), (0, 0))))
        d = np.pad(deg[lo:lo + n_loc], (0, pad), constant_values=1.0)
        invs.append(1.0 / np.maximum(d, 1.0))

    return DistGraph(idx=jnp.asarray(np.stack(idxs), jnp.int32),
                     val=jnp.asarray(np.stack(vals)),
                     inv_deg=jnp.asarray(np.stack(invs), jnp.float32),
                     slice_of=None, inv_perm=None,
                     nrows=nrows, ncols=ncols, parts=num_parts,
                     rows_per_part=rp, kind="ell")


def _build_dist_sell(row, col, val, deg, nrows: int, ncols: int,
                     num_parts: int, c: int) -> DistGraph:
    """SELL-banded partition: each band is degree-sorted and sliced-packed
    (σ = band), then all bands are padded to a common packed step count
    with sentinel steps so they stack over the partition axis."""
    rp = -(-nrows // num_parts)
    rp = -(-rp // c) * c                 # multiple of C: slices never straddle
    bands = []
    for p in range(num_parts):
        lo, hi = p * rp, min((p + 1) * rp, nrows)
        # rp "virtual" rows per band; rows past hi have degree 0 and sort
        # to their slices' tails, exactly like sell_from_coo's row padding.
        part = _band_coo(row, col, val, lo, max(hi, lo), rp, ncols)
        bands.append(sp.sell_from_coo(part, c=c, sigma=0))
    n_steps = max(b.n_steps for b in bands)

    idxs, vals, sofs, invps, invs = [], [], [], [], []
    for p, b in enumerate(bands):
        pad = n_steps - b.n_steps
        # sentinel pad steps: no neighbors, attributed to slice 0 (adds 0)
        idxs.append(np.pad(np.asarray(b.idx), ((0, pad), (0, 0)),
                           constant_values=ncols))
        vals.append(np.pad(np.asarray(b.val), ((0, pad), (0, 0))))
        sofs.append(np.pad(np.asarray(b.slice_of), (0, pad)))
        invps.append(np.asarray(b.inv_perm))          # (rp,)
        lo = p * rp
        d = np.zeros(rp, np.float32)
        n_loc = max(min((p + 1) * rp, nrows) - lo, 0)
        d[:n_loc] = deg[lo: lo + n_loc]
        invs.append(1.0 / np.maximum(d, 1.0))

    return DistGraph(idx=jnp.asarray(np.stack(idxs), jnp.int32),
                     val=jnp.asarray(np.stack(vals)),
                     inv_deg=jnp.asarray(np.stack(invs), jnp.float32),
                     slice_of=jnp.asarray(np.stack(sofs), jnp.int32),
                     inv_perm=jnp.asarray(np.stack(invps), jnp.int32),
                     nrows=nrows, ncols=ncols, parts=num_parts,
                     rows_per_part=rp, kind="sell", sell_c=c)


def _partition_axis(mesh: Mesh) -> str:
    """The mesh axis the 1-D row bands shard over: 'data' when the mesh has
    one, else the mesh's first axis (the single-axis test meshes)."""
    return "data" if "data" in mesh.shape else next(iter(mesh.shape))


def comm_volume(g: DistGraph, k: int) -> dict:
    """Per-device collective traffic (feature rows / elements) of one
    ``distributed_spmm`` step: the 1-D halo exchange all-gathers the FULL
    padded feature matrix on every device — O(N * K) regardless of the
    device count, which is exactly what the 2-D partition
    (:func:`repro.dist.gnn2d.comm_volume_2d`) cuts to O(N/sqrt(P))."""
    n_pad = -(-g.ncols // g.parts) * g.parts
    return dict(gather_rows=n_pad, scatter_rows=0, elements=n_pad * k)


def distributed_spmm(g: DistGraph, h: Array, mesh: Mesh,
                     reduce: str = "sum") -> Array:
    """A @ H with A row-banded over the mesh's data axis. ``h``: (N, K)
    global features (sharded or not — shard_map partitions it); returns the
    (N, K) global result, row-sharded the same way. Dispatches on the
    band layout the kernel plan chose at partition time."""
    axis = _partition_axis(mesh)
    assert mesh.shape[axis] == g.parts, (mesh.shape, g.parts)
    assert reduce in ("sum", "mean"), reduce
    n, k = h.shape
    assert n == g.ncols, (n, g.ncols)
    # H lives in COLUMN space: pad its rows only so shard_map can split
    # them evenly over the axis (the tiled all_gather restores order, so
    # per-device chunk size is free to differ from rows_per_part)
    h_pad = -(-n // g.parts) * g.parts - n
    if h_pad:
        h = jnp.pad(h, ((0, h_pad), (0, 0)))


    if g.kind == "sell":
        from repro.kernels.ops import sell_packed_reduce
        nslices = g.rows_per_part // g.sell_c

        def body(idx, val, sof, invp, inv, h_loc):
            hg = jax.lax.all_gather(h_loc, axis, axis=0, tiled=True)
            out = sell_packed_reduce(idx[0], val[0], sof[0], nslices,
                                     invp[0], hg)
            if reduce == "mean":
                out = out * inv[0][:, None]
            return out.astype(h_loc.dtype)

        out = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(axis, None, None), P(axis, None, None),
                      P(axis, None), P(axis, None), P(axis, None),
                      P(axis, None)),
            out_specs=P(axis, None), check_vma=False,
        )(g.idx, g.val, g.slice_of, g.inv_perm, g.inv_deg, h)
        return out[: g.nrows]

    def body(idx, val, inv, h_loc):
        # halo exchange: one fused all-gather of the row-sharded features
        hg = jax.lax.all_gather(h_loc, axis, axis=0, tiled=True)   # (N_pad, K)
        gathered = jnp.take(hg, idx[0], axis=0, mode="fill",
                            fill_value=0)                          # (rp, md, K)
        msgs = val[0][..., None].astype(hg.dtype) * gathered
        out = jnp.where((idx[0] < g.ncols)[..., None], msgs, 0).sum(axis=1)
        if reduce == "mean":
            out = out * inv[0][:, None]
        return out.astype(h_loc.dtype)

    out = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis, None, None), P(axis, None, None),
                  P(axis, None), P(axis, None)),
        out_specs=P(axis, None), check_vma=False,
    )(g.idx, g.val, g.inv_deg, h)
    return out[: g.nrows]
