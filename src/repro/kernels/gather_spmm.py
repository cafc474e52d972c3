"""Row-gather SpMM Pallas TPU kernel shared by the ELL and SELL plans.

Both layouts store a flat table of ``(neighbor id, value)`` elements in
which every output tile of ``R`` rows owns one contiguous element range:

* ELL: tile ``t`` is rows ``[8t, 8t + 8)``; element ``e`` of the range
  belongs to row ``e // max_deg`` of the tile;
* SELL-C-σ: tile ``t`` is ``R / C`` consecutive slices (``R = max(C,
  8)``, so a tile always fills the 8 sublanes); element ``e`` of a
  slice's range belongs to row ``e % C`` of that slice (degree-major
  packing).

Work unit: a *chunk* is at most 128 consecutive elements of one tile;
every tile has at least one (an empty tile's chunk has no elements). The
per-segment element offsets (``ptr``: one segment per ELL tile or SELL
slice, plus one) are the only scalar-prefetched table, so SMEM use grows
with the number of rows / 8, not with the number of edges. The element
table stays in HBM as ``(rows, 128)`` ``idx`` and ``val`` arrays; a
chunk's two table rows (a chunk need not start on a row) are copied into
SMEM (``idx``) and VMEM (``val``), and every neighbor row of H is fetched
with its own DMA: a ``(1, 128)`` slice of a ``(128, 128)`` buffer when K
pads to 128 lanes, else a whole ``(1, K)`` tile of a ``(128, 1, K)``
buffer (H then carries the same unit middle axis).

Pipeline: one grid step covers ``rows_per_step`` output rows, several
tiles chosen from the static shapes (about 32k elements per step, at most
512 rows), and walks all their chunks as one stream, two chunks deep:

* ``idx`` chunk table, two SMEM slots, one DMA semaphore each: chunk
  ``j + 2``'s copy starts before chunk ``j + 1``'s rows are issued;
* row buffer and ``val`` chunk, two VMEM slots, a row and a value
  semaphore per slot: chunk ``j + 1``'s rows are issued (a scalar-only
  loop, unrolled by 8) before chunk ``j``'s are waited for and reduced.

The pipeline starts once per grid step. A chunk's element at table lane
``l`` lands in buffer row ``l``, so the chunk's weight row is the two
``val`` rows merged at the chunk's offset, with no per-element vector
work. What stays exact: every stored slot is multiplied by its value
and summed by one ``(R, 128) @ (128, K)`` dot per chunk at
``Precision.HIGHEST`` with f32 accumulation; buffer rows a partial chunk
leaves unfilled are selected to 0 before the dot, whatever they hold.

Sentinel convention: pad elements carry ``idx == ncols`` and ``val == 0``;
the wrapper appends one zero row to H at position ``ncols``. Sum semiring
only, as for every generated kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["gather_spmm_pallas", "gather_plan", "chunk_counts", "LANES"]

LANES = 128          # elements per chunk == lanes of one table row
_LOG_LANES = 7
DEPTH = 2            # chunks in flight (the one reduced, the one issued):
                     # the kernel's slots alternate between two
_LOG_UNROLL = 3      # 8 row DMAs issued or waited per loop iteration
_STEP_ELEMS = 1 << 15  # elements a grid step aims to cover
_STEP_ROWS = 512     # most output rows a grid step holds


def gather_plan(nseg: int, n_elements: int, *, row_div: int = 0,
                seg_rows: int = 0) -> dict:
    """The kernel's static pipeline parameters for ``nseg`` segments over
    ``n_elements`` table elements: rows per grid step (whole tiles, about
    ``_STEP_ELEMS`` elements, at most ``_STEP_ROWS`` rows), grid steps,
    pipeline depth in chunks."""
    assert bool(row_div) != bool(seg_rows), (row_div, seg_rows)
    seg = seg_rows or 8
    tile_rows = max(seg, 8)
    ntiles = max(-(-nseg // (tile_rows // seg)), 1)
    per_tile = max(n_elements // ntiles, 1)
    tiles = min(-(-_STEP_ELEMS // per_tile), _STEP_ROWS // tile_rows, ntiles)
    return {"rows_per_step": tiles * tile_rows, "tiles_per_step": tiles,
            "steps": -(-ntiles // tiles), "tile_rows": tile_rows,
            "depth": DEPTH, "elements": n_elements}


def chunk_counts(ptr, *, row_div: int = 0, seg_rows: int = 0
                 ) -> tuple[int, int]:
    """(pipeline starts, chunks) of one kernel call over a packed ELL or
    SELL table with segment offsets ``ptr`` (host array). Every chunk but
    the first of a grid step has its DMAs issued while an earlier chunk
    is still in flight, so ``1 - starts / chunks`` is the share that
    overlaps."""
    ptr = np.asarray(ptr, np.int64)
    plan = gather_plan(len(ptr) - 1, int(ptr[-1]), row_div=row_div,
                       seg_rows=seg_rows)
    segs = plan["tile_rows"] // (seg_rows or 8)
    ntiles = plan["steps"] * plan["tiles_per_step"]
    bounds = ptr[np.minimum(np.arange(ntiles + 1) * segs, len(ptr) - 1)]
    chunks = np.maximum(-(-np.diff(bounds) // LANES), 1).sum()
    return plan["steps"], int(chunks)


def _kernel(ptr_ref, idx_hbm, val_hbm, h_hbm, out_ref,
            idx_s, val_v, rows, tsem, vsem, rsem, *, tile_rows: int,
            tiles: int, row_div: int, seg_rows: int):
    segs = tile_rows // seg_rows if seg_rows else 1    # segments per tile
    t_lo = pl.program_id(0) * tiles
    t_end = t_lo + tiles
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    sub = jax.lax.broadcasted_iota(jnp.int32, (tile_rows, LANES), 0)
    kp = rows.shape[-1]
    slot_row = jax.lax.broadcasted_iota(jnp.int32, (LANES, kp), 0)

    def first(t):                       # first element of tile t
        return ptr_ref[t * segs]

    def advance(t, b):                  # the chunk after chunk (t, b)
        e = first(jnp.minimum(t, t_end - 1) + 1)
        more = (b + LANES < e) & (t < t_end)
        return jnp.where(more, t, t + 1), jnp.where(more, b + LANES, e)

    def size(t, b):                     # elements of chunk (t, b)
        return jnp.clip(first(t + 1) - b, 0, LANES)

    def idx_copy(b, slot):
        return pltpu.make_async_copy(idx_hbm.at[pl.ds(b >> _LOG_LANES, 2)],
                                     idx_s.at[pl.ds(2 * slot, 2)],
                                     tsem.at[slot])

    def val_copy(b, slot):
        return pltpu.make_async_copy(val_hbm.at[pl.ds(b >> _LOG_LANES, 2)],
                                     val_v.at[slot], vsem.at[slot])

    def row_copy(r, slot, i):
        if rows.ndim == 3:              # one 128-lane row per buffer row
            return pltpu.make_async_copy(h_hbm.at[pl.ds(r, 1)],
                                         rows.at[slot, pl.ds(i, 1)],
                                         rsem.at[slot])
        return pltpu.make_async_copy(h_hbm.at[r], rows.at[slot, i],
                                     rsem.at[slot])

    def unrolled(n, body):              # body(i) for i in [0, n)
        def group(q, c):
            for u in range(1 << _LOG_UNROLL):
                body((q << _LOG_UNROLL) + u)
            return c

        full = n >> _LOG_UNROLL
        jax.lax.fori_loop(0, full, group, 0)
        jax.lax.fori_loop(full << _LOG_UNROLL, n,
                          lambda i, c: (body(i), c)[1], 0)

    def issue(t, b, slot):              # idx(t, b) is in SMEM slot `slot`
        val_copy(b, slot).start()
        off = b & (LANES - 1)

        def one(i):
            p = off + i                 # table lane p % 128 -> buffer row
            lp = p & (LANES - 1)
            row_copy(idx_s[2 * slot + (p >> _LOG_LANES), lp], slot,
                     lp).start()

        unrolled(size(t, b), one)

    def reduce(t, b, slot):
        n = size(t, b)
        unrolled(n, lambda i: row_copy(0, slot, 0).wait())
        val_copy(b, slot).wait()
        off = b & (LANES - 1)
        rel = (lane - off) & (LANES - 1)  # element of the chunk at each lane
        v = val_v[slot]
        vrow = jnp.where(lane >= off, v[0:1], v[1:2])
        e = b - first(t) + rel          # element of the tile
        if row_div:            # ELL: row = element // max_deg (exact in f32)
            owner = jnp.floor((e.astype(jnp.float32) + 0.5)
                              * (1.0 / row_div)).astype(jnp.int32)
        else:                  # SELL: row = element % C within its slice
            owner = jnp.bitwise_and(e, seg_rows - 1)
            for q in range(1, segs):           # later slices of the tile
                start = ptr_ref[t * segs + q] - first(t)
                owner = jnp.where(
                    e >= start,
                    jnp.bitwise_and(e - start, seg_rows - 1) + q * seg_rows,
                    owner)
        # both sides of the dot are masked to the chunk: lanes past it hold
        # the next tile's values, buffer rows past it stale or unset rows
        w = jnp.where((sub == owner) & (rel < n),
                      jnp.broadcast_to(vrow, sub.shape), 0.0)
        g = rows[slot].reshape(LANES, kp).astype(jnp.float32)
        g = jnp.where(((slot_row - off) & (LANES - 1)) < n, g, 0.0)
        r0 = pl.multiple_of((t - t_lo) * tile_rows, 8)
        out_ref[pl.ds(r0, tile_rows), :] += jnp.dot(
            w, g, precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)

    out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)
    t0, b0 = t_lo, first(t_lo)
    idx_copy(b0, 0).start()
    idx_copy(b0, 0).wait()
    t1, b1 = advance(t0, b0)

    @pl.when(t1 < t_end)
    def _():
        idx_copy(b1, 1).start()

    issue(t0, b0, 0)

    def step(carry):
        j, t0, b0, t1, b1 = carry
        slot = j & 1
        t2, b2 = advance(t1, b1)

        @pl.when(t1 < t_end)
        def _():
            idx_copy(b1, 1 - slot).wait()

            @pl.when(t2 < t_end)
            def _():
                idx_copy(b2, slot).start()

            issue(t1, b1, 1 - slot)

        reduce(t0, b0, slot)
        return j + 1, t1, b1, t2, b2

    jax.lax.while_loop(lambda c: c[1] < t_end, step,
                       (jnp.int32(0), t0, b0, t1, b1))


def _flat_table(x, fill) -> jnp.ndarray:
    """Flatten a per-element table into ``(rows, 128)`` with spare rows, so
    the two-row chunk copy never reads past the end, even for a chunk
    that starts at the last element."""
    x = x.reshape(-1)
    rows = x.shape[0] // LANES + 2
    return jnp.pad(x, (0, rows * LANES - x.shape[0]),
                   constant_values=fill).reshape(rows, LANES)


def gather_spmm_pallas(ptr: jnp.ndarray, idx: jnp.ndarray, val: jnp.ndarray,
                       h: jnp.ndarray, *, ncols: int, row_div: int = 0,
                       seg_rows: int = 0, interpret: bool = False
                       ) -> jnp.ndarray:
    """Float32 sums of ``val * h[idx]`` per output row, ``(nseg * R, K)``
    rows for ``nseg = len(ptr) - 1`` segments of ``R`` rows each.

    ``ptr`` holds each segment's element range in the flattened
    ``idx``/``val`` tables. ELL (``row_div = max_deg``): 8-row segments,
    element ``e`` of a range goes to row ``e // row_div``. SELL
    (``seg_rows = C``, a power of two): C-row segments (slices), element
    ``e`` goes to row ``e % C``; slices are grouped into 8-row tiles when
    C < 8."""
    assert h.shape[0] == ncols, (h.shape, ncols)
    assert row_div or seg_rows & (seg_rows - 1) == 0, seg_rows
    nseg = ptr.shape[0] - 1
    plan = gather_plan(nseg, idx.size, row_div=row_div, seg_rows=seg_rows)
    tile_rows, tiles = plan["tile_rows"], plan["tiles_per_step"]
    segs = tile_rows // (seg_rows or 8)
    # empty trailing segments complete the last grid step
    ptr = jnp.pad(ptr.astype(jnp.int32),
                  (0, plan["steps"] * tiles * segs - nseg), mode="edge")
    k = h.shape[1]
    kp = -(-k // LANES) * LANES
    # sentinel zero row at ncols. One row is one DMA: a (1, 128) slice at
    # K <= 128, else its own (1, K) tile of an (N, 1, K) array.
    hp = jnp.pad(h, ((0, 1), (0, kp - k)))
    if kp > LANES:
        hp = hp.reshape(ncols + 1, 1, kp)
    kernel = functools.partial(_kernel, tile_rows=tile_rows, tiles=tiles,
                               row_div=row_div, seg_rows=seg_rows)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    rows_per_step = plan["rows_per_step"]
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,          # per-segment element offsets
            grid=(plan["steps"],),
            in_specs=[hbm, hbm, hbm],
            out_specs=pl.BlockSpec((rows_per_step, kp),
                                   lambda s, ptr: (s, 0)),
            scratch_shapes=[
                pltpu.SMEM((2 * DEPTH, LANES), jnp.int32),
                pltpu.VMEM((DEPTH, 2, LANES), jnp.float32),
                pltpu.VMEM((DEPTH, LANES) + hp.shape[1:], h.dtype),
                pltpu.SemaphoreType.DMA((DEPTH,)),
                pltpu.SemaphoreType.DMA((DEPTH,)),
                pltpu.SemaphoreType.DMA((DEPTH,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(
            (plan["steps"] * rows_per_step, kp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(ptr, _flat_table(idx.astype(jnp.int32), ncols),
      _flat_table(val.astype(jnp.float32), 0), hp)
    seg = seg_rows or 8
    return out[: nseg * seg, :k]
