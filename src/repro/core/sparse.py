"""Sparse matrix containers for TPU-friendly GNN message passing.

All containers are registered pytrees with *static* shapes so they can be
closed over by (or passed through) ``jax.jit``. Construction/conversion is
host-side numpy (graph preprocessing happens once per dataset — this is the
paper's "cache" philosophy applied to format conversion as well).

Formats
-------
COO   : canonical triplet form; the ``trusted`` (XLA segment-op) kernels and
        every ref oracle consume this.
CSR   : indptr/indices/val; kept for API parity with the paper (its matmul
        takes CSR) — internally we expand to COO row ids once and cache them.
BSR   : block-sparse rows — *the* TPU-generated-kernel format. The adjacency
        is tiled into dense Br x Bc tiles; only nonempty tiles are stored,
        sorted by (block_row, block_col), padded to a static tile count.
        This is the MXU analogue of iSpLib's register-blocked CSR kernels.
ELL   : ELLPACK (row-padded neighbor lists) — VPU/gather kernel format for
        very sparse rows, and the format used by the distributed halo path.
SELL  : SELL-C-σ (sliced ELLPACK) — rows sorted by degree within windows of
        σ, packed into slices of C rows, each slice padded only to its OWN
        max degree. Kills both ELL pathologies at once: global-max-degree
        padding and the (1, K) one-sublane output tiles. The SpMM wrapper
        inverts the row permutation on output.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

Array = Any

__all__ = [
    "COO",
    "CSR",
    "BSR",
    "ELL",
    "SELL",
    "coo_from_edges",
    "csr_from_coo",
    "bsr_from_coo",
    "ell_from_coo",
    "sell_from_coo",
    "sell_slice_degrees",
    "coo_transpose",
    "row_degrees",
    "gcn_normalize",
]


def _static(**kw):
    return dataclasses.field(metadata=dict(static=True), **kw)


@partial(jax.tree_util.register_dataclass,
         data_fields=["row", "col", "val"], meta_fields=["nrows", "ncols", "nse"])
@dataclasses.dataclass(frozen=True)
class COO:
    """Triplet sparse matrix. Entries past ``nse`` are zero-padding.

    Padding convention: ``row = nrows - 1, col = 0, val = 0`` — safe for the
    sum semiring; non-sum reductions mask with ``valid_mask()``.
    """

    row: Array  # (nnz_padded,) int32
    col: Array  # (nnz_padded,) int32
    val: Array  # (nnz_padded,) float
    nrows: int
    ncols: int
    nse: int    # number of real (non-pad) entries

    @property
    def nnz_padded(self) -> int:
        return self.row.shape[0]

    @property
    def shape(self):
        return (self.nrows, self.ncols)

    @property
    def dtype(self):
        return self.val.dtype

    def valid_mask(self) -> Array:
        return (jnp.arange(self.nnz_padded) < self.nse)

    def todense(self) -> Array:
        d = jnp.zeros(self.shape, self.val.dtype)
        v = jnp.where(self.valid_mask(), self.val, 0)
        return d.at[self.row, self.col].add(v)

    def with_values(self, val: Array) -> "COO":
        return dataclasses.replace(self, val=val)


@partial(jax.tree_util.register_dataclass,
         data_fields=["indptr", "indices", "val", "row_ids"],
         meta_fields=["nrows", "ncols", "nse"])
@dataclasses.dataclass(frozen=True)
class CSR:
    """Compressed sparse rows. ``row_ids`` is the expanded (cached!) COO row
    vector — iSpLib's cached-backprop idea applied to format bookkeeping: the
    expansion is done once at construction, never per training step."""

    indptr: Array   # (nrows+1,) int32
    indices: Array  # (nnz_padded,) int32
    val: Array      # (nnz_padded,)
    row_ids: Array  # (nnz_padded,) int32  — cached expansion
    nrows: int
    ncols: int
    nse: int

    @property
    def shape(self):
        return (self.nrows, self.ncols)

    def to_coo(self) -> COO:
        return COO(row=self.row_ids, col=self.indices, val=self.val,
                   nrows=self.nrows, ncols=self.ncols, nse=self.nse)


@partial(jax.tree_util.register_dataclass,
         data_fields=["blk_row", "blk_col", "blocks"],
         meta_fields=["nrows", "ncols", "br", "bc", "n_real_blocks"])
@dataclasses.dataclass(frozen=True)
class BSR:
    """Block-sparse rows, sorted by (block_row, block_col).

    Invariants required by the Pallas kernel (enforced by ``bsr_from_coo``):
      * blocks sorted by (blk_row, blk_col);
      * every block row owns at least one block (explicit zero block if
        empty) so each output tile is zero-initialised exactly once;
      * padding blocks replicate the final block row with zero data;
      * nrows % br == 0 and ncols % bc == 0 (matrix is padded up front).
    """

    blk_row: Array  # (nblocks,) int32
    blk_col: Array  # (nblocks,) int32
    blocks: Array   # (nblocks, br, bc)
    nrows: int      # padded row count (multiple of br)
    ncols: int      # padded col count (multiple of bc)
    br: int
    bc: int
    n_real_blocks: int

    @property
    def nblocks(self) -> int:
        return self.blk_row.shape[0]

    @property
    def shape(self):
        return (self.nrows, self.ncols)

    @property
    def n_block_rows(self) -> int:
        return self.nrows // self.br

    @property
    def density(self) -> float:
        total = self.n_block_rows * (self.ncols // self.bc)
        return self.n_real_blocks / max(total, 1)

    def todense(self) -> Array:
        d = jnp.zeros(self.shape, self.blocks.dtype)

        def put(d, i):
            r, c = self.blk_row[i] * self.br, self.blk_col[i] * self.bc
            return jax.lax.dynamic_update_slice(
                d, jax.lax.dynamic_slice(d, (r, c), (self.br, self.bc))
                + self.blocks[i], (r, c))

        return jax.lax.fori_loop(0, self.nblocks, lambda i, d: put(d, i), d)


@partial(jax.tree_util.register_dataclass,
         data_fields=["idx", "val"],
         meta_fields=["nrows", "ncols", "nse"])
@dataclasses.dataclass(frozen=True)
class ELL:
    """ELLPACK: per-row padded neighbor lists. Pad slots have ``idx == ncols``
    (one-past-the-end sentinel) and ``val == 0``."""

    idx: Array  # (nrows, max_deg) int32
    val: Array  # (nrows, max_deg)
    nrows: int
    ncols: int
    nse: int

    @property
    def max_deg(self) -> int:
        return self.idx.shape[1]

    @property
    def shape(self):
        return (self.nrows, self.ncols)

    def pad_mask(self) -> Array:
        return self.idx < self.ncols


@partial(jax.tree_util.register_dataclass,
         data_fields=["idx", "val", "slice_of", "slice_ptr", "perm",
                      "inv_perm"],
         meta_fields=["nrows", "ncols", "nse", "c", "sigma", "nslices"])
@dataclasses.dataclass(frozen=True)
class SELL:
    """SELL-C-σ: degree-sorted sliced ELLPACK (Kreutzer et al. layout).

    Rows are sorted by descending degree within windows of ``sigma`` rows
    (``sigma == 0`` means one global window), then grouped into slices of
    ``c`` consecutive sorted rows; each slice is padded only to its own max
    degree (min 1, so every output tile sees at least one zero-init step).

    Storage is *degree-major packed*: packed step ``t`` holds the d-th
    neighbor of all ``c`` rows of one slice, so ``idx``/``val`` have shape
    ``(n_steps, c)`` with ``n_steps = Σ_s max_deg_s`` — the per-slice
    padding savings are structural, not just skipped work. Pad slots carry
    the ``idx == ncols`` sentinel and ``val == 0``.

    ``slice_of[t]`` is the owning slice per step (monotonic); slice ``s``
    owns steps ``[slice_ptr[s], slice_ptr[s + 1])`` — the per-slice table
    the Pallas kernel's grid walks (one word per C rows, so it fits SMEM
    where a per-step table would not).
    ``perm`` maps sorted position -> original row over the padded row range
    (a permutation of ``arange(nslices * c)``; positions >= nrows are
    degree-0 pad rows); ``inv_perm`` maps original row -> sorted position
    and is what the SpMM wrapper applies to un-sort the output.
    """

    idx: Array         # (n_steps, c) int32; pad slots == ncols sentinel
    val: Array         # (n_steps, c)
    slice_of: Array    # (n_steps,) int32
    slice_ptr: Array   # (nslices + 1,) int32
    perm: Array        # (nslices * c,) int32
    inv_perm: Array    # (nrows,) int32
    nrows: int
    ncols: int
    nse: int
    c: int
    sigma: int
    nslices: int

    @property
    def n_steps(self) -> int:
        return self.idx.shape[0]

    @property
    def nrows_padded(self) -> int:
        return self.nslices * self.c

    @property
    def shape(self):
        return (self.nrows, self.ncols)

    def pad_mask(self) -> Array:
        return self.idx < self.ncols

    @property
    def packing_efficiency(self) -> float:
        """nse / stored slots — 1.0 means zero padding waste."""
        return self.nse / max(self.n_steps * self.c, 1)


# --------------------------------------------------------------------------
# Host-side constructors (numpy; run once per graph — never inside jit)
# --------------------------------------------------------------------------

def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def coo_from_edges(src: np.ndarray, dst: np.ndarray, val: np.ndarray | None,
                   nrows: int, ncols: int, pad_to: int | None = None,
                   dtype=np.float32) -> COO:
    """Build a row-major-sorted COO from edge lists. ``dst -> row`` so that
    ``spmm(A, H)[i]`` aggregates over in-neighbors of i (message passing)."""
    src = np.asarray(src, np.int32)
    dst = np.asarray(dst, np.int32)
    if val is None:
        val = np.ones(src.shape[0], dtype)
    order = np.lexsort((src, dst))
    row, col, val = dst[order], src[order], np.asarray(val, dtype)[order]
    nse = row.shape[0]
    tot = pad_to if pad_to is not None else nse
    assert tot >= nse
    row = np.concatenate([row, np.full(tot - nse, max(nrows - 1, 0), np.int32)])
    col = np.concatenate([col, np.zeros(tot - nse, np.int32)])
    val = np.concatenate([val, np.zeros(tot - nse, dtype)])
    return COO(row=jnp.asarray(row), col=jnp.asarray(col), val=jnp.asarray(val),
               nrows=nrows, ncols=ncols, nse=nse)


def csr_from_coo(a: COO) -> CSR:
    row = np.asarray(a.row)[: a.nse]
    col = np.asarray(a.col)[: a.nse]
    val = np.asarray(a.val)[: a.nse]
    order = np.lexsort((col, row))
    row, col, val = row[order], col[order], val[order]
    indptr = np.zeros(a.nrows + 1, np.int64)
    np.add.at(indptr, row + 1, 1)
    indptr = np.cumsum(indptr).astype(np.int32)
    pad = a.nnz_padded - a.nse
    col = np.concatenate([col, np.zeros(pad, np.int32)])
    val = np.concatenate([val, np.zeros(pad, val.dtype)])
    row_ids = np.concatenate([row, np.full(pad, max(a.nrows - 1, 0), np.int32)])
    return CSR(indptr=jnp.asarray(indptr), indices=jnp.asarray(col),
               val=jnp.asarray(val), row_ids=jnp.asarray(row_ids),
               nrows=a.nrows, ncols=a.ncols, nse=a.nse)


def bsr_from_coo(a: COO, br: int = 128, bc: int = 128,
                 pad_blocks_to: int | None = None) -> BSR:
    """Tile a COO matrix into dense Br x Bc blocks (host-side).

    Every block row is guaranteed >= 1 block (explicit zeros) — see BSR
    invariants. Rows/cols are padded up to multiples of (br, bc)."""
    nrows_p, ncols_p = _round_up(a.nrows, br), _round_up(a.ncols, bc)
    n_brows = nrows_p // br
    row = np.asarray(a.row)[: a.nse].astype(np.int64)
    col = np.asarray(a.col)[: a.nse].astype(np.int64)
    val = np.asarray(a.val)[: a.nse]

    brow, bcol = row // br, col // bc
    key = brow * (ncols_p // bc) + bcol
    uniq, inv = np.unique(key, return_inverse=True)
    ub_row, ub_col = (uniq // (ncols_p // bc)), (uniq % (ncols_p // bc))

    # ensure every block row non-empty
    missing = np.setdiff1d(np.arange(n_brows), ub_row)
    all_rows = np.concatenate([ub_row, missing])
    all_cols = np.concatenate([ub_col, np.zeros(len(missing), np.int64)])
    order = np.lexsort((all_cols, all_rows))
    all_rows, all_cols = all_rows[order], all_cols[order]
    n_real = len(all_rows)

    # map original unique-block index -> slot after sort/merge
    slot_of_uniq = np.empty(len(uniq) + len(missing), np.int64)
    slot_of_uniq[order] = np.arange(n_real)

    blocks = np.zeros((n_real, br, bc), val.dtype)
    slot = slot_of_uniq[inv]
    np.add.at(blocks, (slot, row % br, col % bc), val)  # duplicates accumulate

    nb = pad_blocks_to if pad_blocks_to is not None else n_real
    assert nb >= n_real, (nb, n_real)
    pad = nb - n_real
    blk_row = np.concatenate([all_rows, np.full(pad, all_rows[-1] if n_real else 0)])
    blk_col = np.concatenate([all_cols, np.zeros(pad, np.int64)])
    blocks = np.concatenate([blocks, np.zeros((pad, br, bc), val.dtype)])
    return BSR(blk_row=jnp.asarray(blk_row, jnp.int32),
               blk_col=jnp.asarray(blk_col, jnp.int32),
               blocks=jnp.asarray(blocks),
               nrows=nrows_p, ncols=ncols_p, br=br, bc=bc, n_real_blocks=n_real)


def ell_from_coo(a: COO, max_deg: int | None = None) -> ELL:
    """Degenerate cases are explicit: an empty graph (``nse == 0`` and/or
    ``nrows == 0``) and a requested ``max_deg == 0`` both yield a single
    all-sentinel column, so downstream kernels always see ``max_deg >= 1``
    and zero-degree rows reduce to 0 via the sentinel zero-row trick."""
    row = np.asarray(a.row)[: a.nse]
    col = np.asarray(a.col)[: a.nse]
    val = np.asarray(a.val)[: a.nse]
    counts = np.bincount(row, minlength=a.nrows)
    if max_deg is None:
        md = int(counts.max()) if counts.size else 0
    else:
        md = max_deg
    md = max(md, 1)
    idx = np.full((a.nrows, md), a.ncols, np.int32)   # sentinel
    v = np.zeros((a.nrows, md), val.dtype)
    order = np.lexsort((col, row))
    row, col, val = row[order], col[order], val[order]
    # slot within row
    slot = np.arange(len(row)) - np.repeat(np.cumsum(counts) - counts, counts)
    keep = slot < md
    idx[row[keep], slot[keep]] = col[keep]
    v[row[keep], slot[keep]] = val[keep]
    return ELL(idx=jnp.asarray(idx), val=jnp.asarray(v),
               nrows=a.nrows, ncols=a.ncols, nse=a.nse)


def sell_slice_degrees(degrees: np.ndarray, c: int, sigma: int = 0
                       ) -> tuple[np.ndarray, np.ndarray]:
    """Window-sort rows by degree and compute per-slice max degrees.

    Shared by :func:`sell_from_coo` and the autotuner's cost model so the
    packed-step count both see is identical. ``sigma == 0`` sorts globally;
    otherwise sigma is rounded up to a multiple of ``c`` so slices never
    straddle a sort window. Returns ``(slice_deg, perm)`` where ``perm`` is
    a permutation of ``arange(nrows_padded)`` (sorted position -> original
    row; padded virtual rows have degree 0) and ``slice_deg`` (>= 1
    elementwise) is the per-slice padded width.
    """
    assert c >= 1, c
    n = int(degrees.shape[0])
    nrows_p = max(_round_up(n, c), c)
    d = np.zeros(nrows_p, np.int64)
    d[:n] = degrees
    sig = nrows_p if sigma == 0 else min(_round_up(max(int(sigma), 1), c),
                                         nrows_p)
    perm = np.concatenate([
        lo + np.argsort(-d[lo: lo + sig], kind="stable")
        for lo in range(0, nrows_p, sig)
    ])
    slice_deg = d[perm].reshape(-1, c).max(axis=1)
    return np.maximum(slice_deg, 1), perm


def sell_from_coo(a: COO, c: int = 8, sigma: int = 0) -> SELL:
    """Pack a COO matrix into SELL-C-σ (host-side, once per graph).

    ``c`` is the slice height (kernel sublane tile); ``sigma`` the sort
    window (0 = global sort, best packing; smaller windows trade padding
    for locality of the row permutation)."""
    row = np.asarray(a.row)[: a.nse]
    col = np.asarray(a.col)[: a.nse]
    val = np.asarray(a.val)[: a.nse]
    counts = np.bincount(row, minlength=a.nrows) if a.nrows else \
        np.zeros(0, np.int64)
    slice_deg, perm = sell_slice_degrees(counts, c, sigma)
    nslices = slice_deg.shape[0]
    nrows_p = nslices * c
    inv = np.empty(nrows_p, np.int64)
    inv[perm] = np.arange(nrows_p)

    sptr = np.concatenate([[0], np.cumsum(slice_deg)])
    n_steps = int(sptr[-1])
    idx = np.full((n_steps, c), a.ncols, np.int32)
    v = np.zeros((n_steps, c), val.dtype if val.size else np.float32)
    if row.size:
        order = np.lexsort((col, row))
        row, col, val = row[order], col[order], val[order]
        # slot within row (edges are row-sorted)
        slot = np.arange(len(row)) - np.repeat(np.cumsum(counts) - counts,
                                               counts)
        spos = inv[row]                      # sorted position of each edge's row
        step = sptr[spos // c] + slot        # packed step; slot < slice_deg
        idx[step, spos % c] = col
        v[step, spos % c] = val
    return SELL(idx=jnp.asarray(idx), val=jnp.asarray(v),
                slice_of=jnp.asarray(np.repeat(np.arange(nslices), slice_deg),
                                     jnp.int32),
                slice_ptr=jnp.asarray(sptr, jnp.int32),
                perm=jnp.asarray(perm, jnp.int32),
                inv_perm=jnp.asarray(inv[: a.nrows], jnp.int32),
                nrows=a.nrows, ncols=a.ncols, nse=a.nse,
                c=c, sigma=sigma, nslices=nslices)


# --------------------------------------------------------------------------
# Graph-static precomputations (the things iSpLib caches)
# --------------------------------------------------------------------------

def coo_transpose(a: COO) -> COO:
    """Host-side transpose with re-sort — built ONCE and cached (iSpLib §3.3);
    the uncached baseline pays an argsort per backward step instead."""
    row = np.asarray(a.row)[: a.nse]
    col = np.asarray(a.col)[: a.nse]
    val = np.asarray(a.val)[: a.nse]
    order = np.lexsort((row, col))
    return coo_from_edges(row[order], col[order], val[order],
                          nrows=a.ncols, ncols=a.nrows,
                          pad_to=a.nnz_padded, dtype=np.asarray(val).dtype)


def row_degrees(a: COO) -> Array:
    ones = jnp.where(a.valid_mask(), 1.0, 0.0)
    return jax.ops.segment_sum(ones, a.row, num_segments=a.nrows)


def gcn_normalize(a: COO, add_self_loops: bool = True) -> COO:
    """D^-1/2 (A + I) D^-1/2 — host-side, cached once per graph."""
    row = np.asarray(a.row)[: a.nse]
    col = np.asarray(a.col)[: a.nse]
    val = np.asarray(a.val)[: a.nse].astype(np.float64)
    if add_self_loops:
        eye = np.arange(min(a.nrows, a.ncols))
        row = np.concatenate([row, eye])
        col = np.concatenate([col, eye])
        val = np.concatenate([val, np.ones(len(eye))])
    deg = np.zeros(a.nrows)
    np.add.at(deg, row, val)
    dinv = 1.0 / np.sqrt(np.maximum(deg, 1e-12))
    val = dinv[row] * val * dinv[col]
    pad_to = max(a.nnz_padded + (min(a.nrows, a.ncols) if add_self_loops else 0),
                 len(row))
    return coo_from_edges(col, row, val.astype(np.float32), a.nrows, a.ncols,
                          pad_to=pad_to)
