"""Row-gather SpMM Pallas TPU kernel shared by the ELL and SELL plans.

Both layouts store a flat table of ``(neighbor id, value)`` elements in
which every output tile of ``R`` rows owns one contiguous element range:

* ELL: tile ``t`` is rows ``[8t, 8t + 8)``; element ``e`` of the range
  belongs to row ``e // max_deg`` of the tile;
* SELL-C-σ: tile ``t`` is ``R / C`` consecutive slices (``R = max(C,
  8)``, so a tile always fills the 8 sublanes); element ``e`` of a
  slice's range belongs to row ``e % C`` of that slice (degree-major
  packing).

Grid: one step per output tile. The per-segment element offsets
(``ptr``: one segment per ELL tile or SELL slice, plus one) are the only
scalar-prefetched table, so SMEM use grows with the number of rows / 8,
not with the number of edges. The
element table itself stays in HBM: each chunk of 128 elements is copied
into SMEM (two 128-lane rows, since a chunk need not start on a row), and
every neighbor row of H is fetched with its own DMA into a ``(128, 1, K)``
VMEM buffer (H and the buffer carry a unit middle axis so that one row is
one DMA tile at any K). The chunk's values and row owners become a ``(R, 128)``
weight matrix, so one MXU matmul applies the edge values and reduces the
chunk into the ``(R, K)`` accumulator.

Sentinel convention: pad elements carry ``idx == ncols`` and ``val == 0``;
the wrapper appends one zero row to H at position ``ncols``. Sum semiring
only, as for every generated kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["gather_spmm_pallas", "LANES"]

LANES = 128          # elements per chunk == lanes of one table row


def _kernel(ptr_ref, idx_hbm, val_hbm, h_hbm, out_ref,
            idx_s, val_s, rows, tsem, rsem, *, tile_rows: int, row_div: int,
            seg_rows: int):
    segs = tile_rows // seg_rows if seg_rows else 1    # segments per tile
    t = pl.program_id(0)
    lo, hi = ptr_ref[t * segs], ptr_ref[(t + 1) * segs]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    sub = jax.lax.broadcasted_iota(jnp.int32, (tile_rows, LANES), 0)

    def chunk(j, acc):
        base = lo + j * LANES
        n = jnp.minimum(hi - base, LANES)
        r0, off = base // LANES, base % LANES
        cp_i = pltpu.make_async_copy(idx_hbm.at[pl.ds(r0, 2)], idx_s,
                                     tsem.at[0])
        cp_v = pltpu.make_async_copy(val_hbm.at[pl.ds(r0, 2)], val_s,
                                     tsem.at[1])
        cp_i.start()
        cp_v.start()
        cp_i.wait()
        cp_v.wait()

        @pl.when(n < LANES)
        def _clear():          # unfetched rows meet zero weights: keep 0*x=0
            rows[...] = jnp.zeros_like(rows)

        def issue(i, vrow):
            p = off + i
            r = idx_s[p // LANES, p % LANES]
            pltpu.make_async_copy(h_hbm.at[r], rows.at[i],
                                  rsem.at[0]).start()
            return jnp.where(lane == i, val_s[p // LANES, p % LANES], vrow)

        vrow = jax.lax.fori_loop(0, n, issue,
                                 jnp.zeros((1, LANES), jnp.float32))

        def drain(i, carry):
            pltpu.make_async_copy(h_hbm.at[0], rows.at[0],
                                  rsem.at[0]).wait()
            return carry

        jax.lax.fori_loop(0, n, drain, 0)
        rel = j * LANES + lane
        if row_div:            # ELL: row = element // max_deg (exact in f32)
            owner = jnp.floor((rel.astype(jnp.float32) + 0.5)
                              * (1.0 / row_div)).astype(jnp.int32)
        else:                  # SELL: row = element % C within its slice
            owner = jnp.bitwise_and(rel, seg_rows - 1)
            for q in range(1, segs):           # later slices of the tile
                start = ptr_ref[t * segs + q] - lo
                owner = owner + jnp.where(rel >= start, seg_rows, 0)
        w = jnp.where(sub == owner, jnp.broadcast_to(vrow, sub.shape), 0.0)
        g = rows[...].reshape(LANES, rows.shape[2]).astype(jnp.float32)
        return acc + jnp.dot(w, g,
                             precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)

    n_chunks = (hi - lo + LANES - 1) // LANES
    out_ref[...] = jax.lax.fori_loop(
        0, n_chunks, chunk, jnp.zeros(out_ref.shape, jnp.float32))


def _flat_table(x, fill) -> jnp.ndarray:
    """Flatten a per-element table into ``(rows, 128)`` with one spare row,
    so the two-row chunk copy never reads past the end."""
    x = x.reshape(-1)
    rows = -(-x.shape[0] // LANES) + 1
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
    assert bool(row_div) != bool(seg_rows), (row_div, seg_rows)
    assert row_div or seg_rows & (seg_rows - 1) == 0, seg_rows
    seg = seg_rows or 8
    tile_rows = max(seg, 8)
    segs = tile_rows // seg
    nseg = ptr.shape[0] - 1
    ntiles = -(-nseg // segs)
    # empty trailing segments complete the last tile
    ptr = jnp.pad(ptr.astype(jnp.int32), (0, ntiles * segs - nseg),
                  mode="edge")
    k = h.shape[1]
    kp = -(-k // LANES) * LANES
    # sentinel zero row at ncols; (N, 1, K) so each row is its own DMA tile
    h3 = jnp.pad(h, ((0, 1), (0, kp - k))).reshape(ncols + 1, 1, kp)
    kernel = functools.partial(_kernel, tile_rows=tile_rows, row_div=row_div,
                               seg_rows=seg_rows)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,          # per-tile element offsets
            grid=(ntiles,),
            in_specs=[hbm, hbm, hbm],
            out_specs=pl.BlockSpec((tile_rows, kp), lambda t, ptr: (t, 0)),
            scratch_shapes=[
                pltpu.SMEM((2, LANES), jnp.int32),
                pltpu.SMEM((2, LANES), jnp.float32),
                pltpu.VMEM((LANES, 1, kp), h.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((1,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((ntiles * tile_rows, kp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(ptr, _flat_table(idx.astype(jnp.int32), ncols),
      _flat_table(val.astype(jnp.float32), 0), h3)
    return out[: nseg * seg, :k]
