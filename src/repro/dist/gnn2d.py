"""2-D vertex-cut distributed SpMM over a (pr x pc) tile grid.

Why 2-D
-------
The 1-D row bands in :mod:`repro.dist.gnn` all-gather the FULL feature
matrix every layer — O(N * K) per device, independent of the device count.
Blocking the adjacency over a (sqrt(P) x sqrt(P)) sub-mesh instead (the
DGL / Qiu-et-al. vertex-cut design) makes device (i, j) own tile
A[i-th row block, j-th column block], and one SpMM step becomes

  1. **row-axis gather**: all-gather H's j-th column block over the 'row'
     axis — N/sqrt(P) rows, not N;
  2. **local tile SpMM**: the tile's packed (ELL or SELL-C-sigma) kernel,
     exactly the single-device algorithm on a (N/sqrt(P))^2 block;
  3. **column-axis reduce-scatter**: partial row sums summed over the 'col'
     axis, each device keeping its 1/pc slice — again N/sqrt(P) rows
     (optionally int8-quantized via
     :func:`repro.dist.collectives.compressed_psum_scatter`).

Per-device communication drops from O(N*K) to O(N*K/sqrt(P)) — the
difference between "runs on 4 devices" and scaling with the mesh.

Data layouts (all padding is structural, done once at partition time)
---------------------------------------------------------------------
* Rows pad to ``N_pad = pr * rows_per_tile`` with ``rows_per_tile`` a
  multiple of pc (so the reduce-scatter tiles evenly) and of the SELL
  slice height C when the plan picks SELL.
* Columns pad to ``M_pad = pc * cols_per_tile`` with ``cols_per_tile`` a
  multiple of pr (so column blocks gather evenly over the 'row' axis).
* Tile (i, j) stores LOCAL column ids (sentinel = ``cols_per_tile``); the
  gathered column block is all it ever indexes.
* **Row-major** results (``PartitionSpec((row, col))`` on dim 0): device
  (i, j) holds rows ``[i*rpt + j*rpt/pc, ...)`` — the SpMM output.
* **Column-major** operands (``PartitionSpec((col, row))`` on dim 0):
  device (i, j) holds rows ``[j*cpt + i*cpt/pr, ...)`` — the H input,
  laid out so the 'row'-axis all-gather reassembles column block j in
  order.

Plan-awareness: the autotuner's format choice applies per 2-D tile — a
SELL plan packs every tile degree-sorted tile-locally (sigma = tile) via
:func:`repro.core.sparse.sell_from_coo`, anything else keeps rectangular
ELL tiles, whose padding width is the per-TILE max degree (smaller than
the global max: the vertex cut also shrinks ELL pathology).

The SpMM is a plain shard_map composition of linear collectives and
differentiable locals, so ``jax.grad`` flows through it without a bespoke
VJP.
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
from repro.dist.sharding import grid_axes

Array = Any

__all__ = [
    "Graph2D",
    "partition_2d",
    "distributed_spmm_2d",
    "scores_to_dense",
    "comm_volume_2d",
]


@partial(jax.tree_util.register_dataclass,
         data_fields=["idx", "val", "inv_deg", "slice_of", "perm",
                      "inv_perm"],
         meta_fields=["nrows", "ncols", "pr", "pc", "rows_per_tile",
                      "cols_per_tile", "kind", "sell_c"])
@dataclasses.dataclass(frozen=True)
class Graph2D:
    """Vertex-cut adjacency: pr x pc tiles stacked row-major (p = i*pc + j).

    ELL layout (``kind == 'ell'``): ``idx``/``val`` are
    (pr*pc, rows_per_tile, max_deg) with LOCAL column ids and the pad
    sentinel ``idx == cols_per_tile``; ``slice_of``/``perm``/``inv_perm``
    are None.

    SELL layout (``kind == 'sell'``): ``idx``/``val`` are
    (pr*pc, n_steps, C) packed degree-major per tile (tiles padded to a
    common step count with sentinel steps); ``slice_of`` is
    (pr*pc, n_steps); ``perm``/``inv_perm`` are (pr*pc, rows_per_tile) —
    sorted position <-> original tile-local row (perm recovers each
    packed slot's row id, inv_perm un-sorts SpMM output).

    ``inv_deg``: (pr * rows_per_tile,) cached 1/deg of the FULL row (the
    mean semiring normalizes by the global degree, not the tile's), laid
    out row-major so it shards like the SpMM output.
    """

    idx: Array
    val: Array
    inv_deg: Array
    slice_of: Optional[Array]
    perm: Optional[Array]
    inv_perm: Optional[Array]
    nrows: int
    ncols: int
    pr: int
    pc: int
    rows_per_tile: int
    cols_per_tile: int
    kind: str = "ell"
    sell_c: int = 8

    @property
    def parts(self) -> int:
        return self.pr * self.pc

    @property
    def max_deg(self) -> int:
        assert self.kind == "ell", "max_deg is an ELL-layout property"
        return self.idx.shape[-1]

    @property
    def n_steps(self) -> int:
        assert self.kind == "sell", "n_steps is a SELL-layout property"
        return self.idx.shape[1]

    @property
    def nslices(self) -> int:
        assert self.kind == "sell", "nslices is a SELL-layout property"
        return self.rows_per_tile // self.sell_c

    @property
    def shape(self):
        return (self.nrows, self.ncols)


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def partition_2d(a: Union[sp.COO, sp.CSR, CachedGraph], pr: int,
                 pc: int | None = None,
                 plan: Optional[KernelPlan] = None) -> Graph2D:
    """Host-side one-time 2-D partition (cached-graph philosophy: all tile
    structure is built once, never inside the training step).

    Blocks the adjacency into a (pr x pc) grid — ``pc`` defaults to ``pr``
    (the square sub-mesh of :func:`repro.dist.mesh.make_grid_mesh`). The
    tile layout follows ``plan`` (explicit argument wins; else the
    CachedGraph's autotuned plan; else ELL): a SELL plan packs each tile
    degree-sorted tile-locally, anything else keeps ELL tiles padded to
    the per-tile max degree."""
    pc = pr if pc is None else pc
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

    kind = "sell" if plan.wants_sell else "ell"
    c = plan.sell_c
    r_align = int(np.lcm(pc, c)) if kind == "sell" else pc
    rpt = max(_round_up(-(-nrows // pr), r_align), r_align)
    cpt = max(_round_up(-(-ncols // pc), pr), pr)

    inv = np.ones(pr * rpt, np.float32)   # pad rows: deg 0 -> inv 1
    inv[:nrows] = 1.0 / np.maximum(deg, 1.0)

    tiles = []
    for i in range(pr):
        rm = (row >= i * rpt) & (row < (i + 1) * rpt)
        for j in range(pc):
            m = rm & (col >= j * cpt) & (col < (j + 1) * cpt)
            tiles.append(sp.coo_from_edges(col[m] - j * cpt, row[m] - i * rpt,
                                           val[m], nrows=rpt, ncols=cpt))

    if kind == "sell":
        sells = [sp.sell_from_coo(t, c=c, sigma=0) for t in tiles]
        n_steps = max(s.n_steps for s in sells)
        idxs, vals, sofs, perms, invps = [], [], [], [], []
        for s in sells:
            pad = n_steps - s.n_steps
            # sentinel pad steps: no neighbors, attributed to slice 0
            idxs.append(np.pad(np.asarray(s.idx), ((0, pad), (0, 0)),
                               constant_values=cpt))
            vals.append(np.pad(np.asarray(s.val), ((0, pad), (0, 0))))
            sofs.append(np.pad(np.asarray(s.slice_of), (0, pad)))
            perms.append(np.asarray(s.perm))
            invps.append(np.asarray(s.inv_perm))
        return Graph2D(idx=jnp.asarray(np.stack(idxs), jnp.int32),
                       val=jnp.asarray(np.stack(vals)),
                       inv_deg=jnp.asarray(inv),
                       slice_of=jnp.asarray(np.stack(sofs), jnp.int32),
                       perm=jnp.asarray(np.stack(perms), jnp.int32),
                       inv_perm=jnp.asarray(np.stack(invps), jnp.int32),
                       nrows=nrows, ncols=ncols, pr=pr, pc=pc,
                       rows_per_tile=rpt, cols_per_tile=cpt,
                       kind="sell", sell_c=c)

    md = 1   # common max_deg across tiles so they stack into one array
    for t in tiles:
        cnt = np.bincount(np.asarray(t.row)[: t.nse], minlength=rpt)
        md = max(md, int(cnt.max()) if cnt.size else 0)
    ells = [sp.ell_from_coo(t, max_deg=md) for t in tiles]
    return Graph2D(idx=jnp.asarray(np.stack([np.asarray(e.idx)
                                             for e in ells]), jnp.int32),
                   val=jnp.asarray(np.stack([np.asarray(e.val)
                                             for e in ells])),
                   inv_deg=jnp.asarray(inv),
                   slice_of=None, perm=None, inv_perm=None,
                   nrows=nrows, ncols=ncols, pr=pr, pc=pc,
                   rows_per_tile=rpt, cols_per_tile=cpt, kind="ell")


# --------------------------------------------------------------------------
# Layout helpers
# --------------------------------------------------------------------------

def _check_mesh(g: Graph2D, mesh: Mesh) -> tuple[str, str]:
    row_ax, col_ax = grid_axes(mesh)
    assert (mesh.shape[row_ax], mesh.shape[col_ax]) == (g.pr, g.pc), \
        (dict(mesh.shape), (g.pr, g.pc))
    return row_ax, col_ax


def _pad_rows(x: Array, to: int) -> Array:
    pad = to - x.shape[0]
    return jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)) if pad else x


def comm_volume_2d(g: Graph2D, k: int) -> dict:
    """Per-device collective traffic (feature rows / elements) of one
    ``distributed_spmm_2d`` step: the row-axis gather buffer plus the
    column-axis reduce-scatter operand. Compare with
    :func:`repro.dist.gnn.comm_volume` (1-D: the full N_pad-row gather)."""
    return dict(gather_rows=g.cols_per_tile, scatter_rows=g.rows_per_tile,
                elements=(g.cols_per_tile + g.rows_per_tile) * k)


# --------------------------------------------------------------------------
# SpMM
# --------------------------------------------------------------------------

def distributed_spmm_2d(g: Graph2D, h: Array, mesh: Mesh,
                        reduce: str = "sum", *,
                        compress: bool = False) -> Array:
    """A @ H with A vertex-cut over the mesh grid. ``h``: (M, K) global
    features; returns the (N, K) global result (row-major layout over the
    grid). ``compress=True`` routes the column-axis reduce through the int8
    :func:`repro.dist.collectives.compressed_psum_scatter` wire format.
    Dispatches on the tile layout the kernel plan chose at partition time.
    """
    row_ax, col_ax = _check_mesh(g, mesh)
    assert reduce in ("sum", "mean"), reduce
    m, k = h.shape
    assert m == g.ncols, (m, g.ncols)
    h = _pad_rows(h, g.pc * g.cols_per_tile)

    from repro.dist.collectives import compressed_psum_scatter
    cpt = g.cols_per_tile

    def reduce_cols(part, inv_loc, dtype):
        if compress:
            part = compressed_psum_scatter(part, col_ax)
        else:
            part = jax.lax.psum_scatter(part, col_ax, scatter_dimension=0,
                                        tiled=True)
        if reduce == "mean":
            part = part * inv_loc[:, None]
        return part.astype(dtype)

    if g.kind == "sell":
        from repro.kernels.ops import sell_packed_reduce
        nslices = g.nslices

        def body(idx, val, sof, invp, inv_loc, h_loc):
            hg = jax.lax.all_gather(h_loc, row_ax, axis=0, tiled=True)
            assert hg.shape[0] == cpt      # the O(N/sqrt(P)) halo buffer
            part = sell_packed_reduce(idx[0], val[0], sof[0], nslices,
                                      invp[0], hg)
            return reduce_cols(part, inv_loc, h_loc.dtype)

        out = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P((row_ax, col_ax), None, None),
                      P((row_ax, col_ax), None, None),
                      P((row_ax, col_ax), None), P((row_ax, col_ax), None),
                      P((row_ax, col_ax)), P((col_ax, row_ax), None)),
            out_specs=P((row_ax, col_ax), None), check_vma=False,
        )(g.idx, g.val, g.slice_of, g.inv_perm, g.inv_deg, h)
        return out[: g.nrows]

    def body(idx, val, inv_loc, h_loc):
        hg = jax.lax.all_gather(h_loc, row_ax, axis=0, tiled=True)
        assert hg.shape[0] == cpt          # the O(N/sqrt(P)) halo buffer
        gathered = jnp.take(hg, idx[0], axis=0, mode="fill",
                            fill_value=0)                  # (rpt, md, K)
        msgs = val[0][..., None].astype(hg.dtype) * gathered
        part = jnp.where((idx[0] < cpt)[..., None], msgs, 0).sum(axis=1)
        return reduce_cols(part, inv_loc, h_loc.dtype)

    out = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P((row_ax, col_ax), None, None),
                  P((row_ax, col_ax), None, None),
                  P((row_ax, col_ax)), P((col_ax, row_ax), None)),
        out_specs=P((row_ax, col_ax), None), check_vma=False,
    )(g.idx, g.val, g.inv_deg, h)
    return out[: g.nrows]


# --------------------------------------------------------------------------
# Inspection
# --------------------------------------------------------------------------

def scores_to_dense(g: Graph2D, s: Array, *, trim: bool = True) -> np.ndarray:
    """Host-side scatter of per-slot tile-layout values (``g.val`` itself
    for a structure round-trip) back to a dense matrix — for tests,
    debugging, and small-scale inspection only. ``trim=True`` returns the (N, M) logical
    matrix; ``trim=False`` keeps the padded (pr*rpt, pc*cpt) canvas so
    callers can assert the pad region stayed empty."""
    s = np.asarray(s)
    rpt, cpt = g.rows_per_tile, g.cols_per_tile
    out = np.zeros((g.pr * rpt, g.pc * cpt), s.dtype)
    idx = np.asarray(g.idx)
    for p in range(g.parts):
        i, j = divmod(p, g.pc)
        if g.kind == "sell":
            pos = (np.asarray(g.slice_of[p])[:, None] * g.sell_c
                   + np.arange(g.sell_c)[None, :])
            rows = np.asarray(g.perm[p])[pos]
        else:
            rows = np.broadcast_to(np.arange(rpt)[:, None], idx[p].shape)
        m = idx[p] < cpt
        np.add.at(out, (i * rpt + rows[m], j * cpt + idx[p][m]), s[p][m])
    return out[: g.nrows, : g.ncols] if trim else out
