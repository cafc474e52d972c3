"""Row-gather SpMM (and SDDMM) Pallas TPU kernel shared by the ELL and SELL
plans.

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

Two more uses of the same stream, for attention (a static ``heads = H``):

* multi-head SpMM: ``val`` holds ``H`` values per element, one flat table
  per head (an ``H``-row chunk copy each); H's ``K = H * F`` lanes are the
  heads side by side. An element's row still arrives by one DMA, and the
  chunk's reduction is one dot per head (on a lane-aligned slice of the
  rows when ``F`` is a multiple of 128, else on the rows with the other
  heads' lanes selected to 0). ``H = 1`` is the kernel above, unchanged;
* gather-SDDMM (``_sddmm_kernel``): per element and head, the dot of the
  gathered row with its output row of ``dout``, whose block is resident
  per grid step: the ``(R, K) x (128, K)ᵀ`` product per head is masked to
  each lane's row and summed over sublanes, so results come out one per
  table lane. They are written to an ``(H, rows, 128)`` table by row
  DMAs once a table row is complete; a step's stream covers consecutive
  elements, so only the row it starts in is shared with the step before
  (steps run in order), and that row is read back first.

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

__all__ = ["gather_spmm_pallas", "gather_sddmm_pallas", "gather_plan",
           "LANES"]

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
            "depth": DEPTH, "elements": n_elements, "nseg": nseg}


class _Stream:
    """The chunk stream of one grid step: the tables, row DMAs and row
    rule both kernel modes share. ``run`` drives the two-deep pipeline;
    a mode supplies what is issued beside a chunk's rows and how a
    landed chunk is reduced."""

    def __init__(self, ptr_ref, idx_hbm, h_hbm, idx_s, rows, tsem, rsem, *,
                 tile_rows: int, tiles: int, row_div: int, seg_rows: int):
        self.ptr_ref, self.idx_hbm, self.h_hbm = ptr_ref, idx_hbm, h_hbm
        self.idx_s, self.rows, self.tsem, self.rsem = idx_s, rows, tsem, rsem
        self.tile_rows, self.row_div, self.seg_rows = tile_rows, row_div, \
            seg_rows
        self.segs = tile_rows // seg_rows if seg_rows else 1  # per tile
        self.t_lo = pl.program_id(0) * tiles
        self.t_end = self.t_lo + tiles
        self.lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
        self.sub = jax.lax.broadcasted_iota(jnp.int32, (tile_rows, LANES), 0)
        self.kp = rows.shape[-1]
        self.slot_row = jax.lax.broadcasted_iota(jnp.int32,
                                                 (LANES, self.kp), 0)

    def first(self, t):                 # first element of tile t
        return self.ptr_ref[t * self.segs]

    def advance(self, t, b):            # the chunk after chunk (t, b)
        e = self.first(jnp.minimum(t, self.t_end - 1) + 1)
        more = (b + LANES < e) & (t < self.t_end)
        return jnp.where(more, t, t + 1), jnp.where(more, b + LANES, e)

    def size(self, t, b):               # elements of chunk (t, b)
        return jnp.clip(self.first(t + 1) - b, 0, LANES)

    def idx_copy(self, b, slot):
        return pltpu.make_async_copy(
            self.idx_hbm.at[pl.ds(b >> _LOG_LANES, 2)],
            self.idx_s.at[pl.ds(2 * slot, 2)], self.tsem.at[slot])

    def row_copy(self, r, slot, i):
        if self.rows.ndim == 3:         # one 128-lane row per buffer row
            return pltpu.make_async_copy(self.h_hbm.at[pl.ds(r, 1)],
                                         self.rows.at[slot, pl.ds(i, 1)],
                                         self.rsem.at[slot])
        return pltpu.make_async_copy(self.h_hbm.at[r], self.rows.at[slot, i],
                                     self.rsem.at[slot])

    @staticmethod
    def unrolled(n, body):              # body(i) for i in [0, n)
        def group(q, c):
            for u in range(1 << _LOG_UNROLL):
                body((q << _LOG_UNROLL) + u)
            return c

        full = n >> _LOG_UNROLL
        jax.lax.fori_loop(0, full, group, 0)
        jax.lax.fori_loop(full << _LOG_UNROLL, n,
                          lambda i, c: (body(i), c)[1], 0)

    def issue_rows(self, t, b, slot):   # idx(t, b) is in SMEM slot `slot`
        off = b & (LANES - 1)

        def one(i):
            p = off + i                 # table lane p % 128 -> buffer row
            lp = p & (LANES - 1)
            self.row_copy(self.idx_s[2 * slot + (p >> _LOG_LANES), lp],
                          slot, lp).start()

        self.unrolled(self.size(t, b), one)

    def wait_rows(self, n, slot):
        self.unrolled(n, lambda i: self.row_copy(0, slot, 0).wait())

    def owner(self, t, b, rel):
        """Row of the tile that the element at each lane belongs to."""
        e = b - self.first(t) + rel     # element of the tile
        if self.row_div:       # ELL: row = element // max_deg (exact in f32)
            return jnp.floor((e.astype(jnp.float32) + 0.5)
                             * (1.0 / self.row_div)).astype(jnp.int32)
        seg_rows = self.seg_rows  # SELL: row = element % C within its slice
        owner = jnp.bitwise_and(e, seg_rows - 1)
        for q in range(1, self.segs):           # later slices of the tile
            start = self.ptr_ref[t * self.segs + q] - self.first(t)
            owner = jnp.where(
                e >= start,
                jnp.bitwise_and(e - start, seg_rows - 1) + q * seg_rows,
                owner)
        return owner

    def gathered(self, slot, off, n):
        """The chunk's landed rows as ``(128, kp)`` f32, rows past the
        chunk selected to 0 whatever they hold."""
        g = self.rows[slot].reshape(LANES, self.kp).astype(jnp.float32)
        return jnp.where(((self.slot_row - off) & (LANES - 1)) < n, g, 0.0)

    def tile_row0(self, t):
        return pl.multiple_of((t - self.t_lo) * self.tile_rows, 8)

    def run(self, issue, reduce, carry=()):
        """Walk the step's chunks two deep: ``issue(t, b, slot)`` starts a
        chunk's copies once its ``idx`` table is in SMEM, ``reduce(t, b,
        slot, carry) -> carry`` consumes a landed chunk. Returns the
        final carry."""
        t0, b0 = self.t_lo, self.first(self.t_lo)
        self.idx_copy(b0, 0).start()
        self.idx_copy(b0, 0).wait()
        t1, b1 = self.advance(t0, b0)

        @pl.when(t1 < self.t_end)
        def _():
            self.idx_copy(b1, 1).start()

        issue(t0, b0, 0)

        def step(c):
            j, t0, b0, t1, b1, carry = c
            slot = j & 1
            t2, b2 = self.advance(t1, b1)

            @pl.when(t1 < self.t_end)
            def _():
                self.idx_copy(b1, 1 - slot).wait()

                @pl.when(t2 < self.t_end)
                def _():
                    self.idx_copy(b2, slot).start()

                issue(t1, b1, 1 - slot)

            carry = reduce(t0, b0, slot, carry)
            return j + 1, t1, b1, t2, b2, carry

        out = jax.lax.while_loop(lambda c: c[1] < self.t_end, step,
                                 (jnp.int32(0), t0, b0, t1, b1, carry))
        return out[-1]


def _head_dots(w_of, g, dot, *, heads: int, head_dim: int):
    """``dot(w_of(h), g_h)`` per head, where ``g_h`` holds head ``h``'s
    lanes of ``g``: a lane-aligned slice when the head width is a
    multiple of 128, else ``g`` with the other heads' lanes selected to
    0. Yields (head, lane slice or None, product)."""
    if head_dim % LANES == 0:
        for hd in range(heads):
            cols = slice(hd * head_dim, (hd + 1) * head_dim)
            yield hd, cols, dot(w_of(hd), g[:, cols])
        return
    col = jax.lax.broadcasted_iota(jnp.int32, g.shape, 1)
    for hd in range(heads):
        mine = (col >= hd * head_dim) & (col < (hd + 1) * head_dim)
        yield hd, None, dot(w_of(hd), jnp.where(mine, g, 0.0))


def _kernel(ptr_ref, idx_hbm, val_hbm, h_hbm, out_ref,
            idx_s, val_v, rows, tsem, vsem, rsem, *, tile_rows: int,
            tiles: int, row_div: int, seg_rows: int, heads: int,
            head_dim: int):
    s = _Stream(ptr_ref, idx_hbm, h_hbm, idx_s, rows, tsem, rsem,
                tile_rows=tile_rows, tiles=tiles, row_div=row_div,
                seg_rows=seg_rows)
    lane, sub = s.lane, s.sub

    def val_copy(b, slot, hd=None):
        if hd is None:
            return pltpu.make_async_copy(
                val_hbm.at[pl.ds(b >> _LOG_LANES, 2)], val_v.at[slot],
                vsem.at[slot])
        return pltpu.make_async_copy(
            val_hbm.at[hd, pl.ds(b >> _LOG_LANES, 2)], val_v.at[slot, hd],
            vsem.at[slot])

    def issue(t, b, slot):
        if heads == 1:
            val_copy(b, slot).start()
        else:
            for hd in range(heads):
                val_copy(b, slot, hd).start()
        s.issue_rows(t, b, slot)

    def dot(w, g):
        return jnp.dot(w, g, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)

    def reduce(t, b, slot, carry):
        n = s.size(t, b)
        s.wait_rows(n, slot)
        if heads == 1:
            val_copy(b, slot).wait()
        else:
            for hd in range(heads):
                val_copy(b, slot, hd).wait()
        off = b & (LANES - 1)
        rel = (lane - off) & (LANES - 1)  # element of the chunk at each lane
        v = val_v[slot]
        if heads == 1:
            vrows = [jnp.where(lane >= off, v[0:1], v[1:2])]
        else:
            vrows = [jnp.where(lane >= off, v[hd, 0:1], v[hd, 1:2])
                     for hd in range(heads)]
        owner = s.owner(t, b, rel)
        # both sides of the dot are masked to the chunk: lanes past it hold
        # the next tile's values, buffer rows past it stale or unset rows
        mine = (sub == owner) & (rel < n)

        def w_of(hd):
            return jnp.where(mine, jnp.broadcast_to(vrows[hd], sub.shape),
                             0.0)

        if heads == 1:
            w = w_of(0)
            g = s.gathered(slot, off, n)
            r0 = s.tile_row0(t)
            out_ref[pl.ds(r0, tile_rows), :] += dot(w, g)
            return carry
        g = s.gathered(slot, off, n)
        r0 = s.tile_row0(t)
        acc = None
        for _, cols, prod in _head_dots(w_of, g, dot, heads=heads,
                                        head_dim=head_dim):
            if cols is not None:
                out_ref[pl.ds(r0, tile_rows), cols] += prod
            else:
                acc = prod if acc is None else acc + prod
        if acc is not None:
            out_ref[pl.ds(r0, tile_rows), :] += acc
        return carry

    out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)
    s.run(issue, reduce)


def _sddmm_kernel(ptr_ref, idx_hbm, dout_ref, h_hbm, out_hbm,
                  idx_s, rows, wbuf, rbuf, tsem, rsem, wsem, bsem, *,
                  tile_rows: int, tiles: int, row_div: int, seg_rows: int,
                  heads: int, head_dim: int):
    """Per element and head, the dot of its gathered row of H with its
    output row of ``dout`` (resident: the grid step's block). Results
    land in the ``(heads, rows, 128)`` element table ``out_hbm`` at the
    element's own position: a table row is written once all its lanes
    are known. The stream of a step covers consecutive elements, so only
    the row a step starts in is shared with the step before; it is read
    back first (steps run in order)."""
    s = _Stream(ptr_ref, idx_hbm, h_hbm, idx_s, rows, tsem, rsem,
                tile_rows=tile_rows, tiles=tiles, row_div=row_div,
                seg_rows=seg_rows)
    lane, sub = s.lane, s.sub

    def write(q, slot):
        return [pltpu.make_async_copy(wbuf.at[slot, pl.ds(hd, 1)],
                                      out_hbm.at[hd, pl.ds(q, 1)],
                                      wsem.at[slot]) for hd in range(heads)]

    def dot_nt(d, g):       # (R, k) x (128, k) -> (R, 128)
        return jax.lax.dot_general(
            d, g, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)

    def reduce(t, b, slot, carry):
        st, p0, p1 = carry
        n = s.size(t, b)
        s.wait_rows(n, slot)
        off = b & (LANES - 1)
        rel = (lane - off) & (LANES - 1)
        mine = (sub == s.owner(t, b, rel)) & (rel < n)
        g = s.gathered(slot, off, n)
        d = dout_ref[pl.ds(s.tile_row0(t), tile_rows), :].astype(jnp.float32)
        cols_of = {}

        def d_of(hd):
            return d if cols_of.get(hd) is None else d[:, cols_of[hd]]

        res = []
        if head_dim % LANES == 0:
            for hd in range(heads):
                cols_of[hd] = slice(hd * head_dim, (hd + 1) * head_dim)
        for _, _, prod in _head_dots(d_of, g, dot_nt, heads=heads,
                                     head_dim=head_dim):
            res.append(jnp.sum(jnp.where(mine, prod, 0.0), axis=0,
                               keepdims=True))
        r = res[0] if heads == 1 else jnp.concatenate(res, axis=0)
        end = off + n
        merged = jnp.where((lane >= off) & (lane < end), r, st)
        done = end >= LANES             # the chunk completes table row b/128
        pend = jnp.where(slot == 0, p0, p1)

        @pl.when(done & (pend > 0))
        def _():
            for c in write(0, slot):
                c.wait()

        @pl.when(done)
        def _():
            wbuf[slot] = merged
            for c in write(b >> _LOG_LANES, slot):
                c.start()

        st = jnp.where(done, jnp.where(lane < end - LANES, r, 0.0), merged)
        pend = jnp.maximum(pend, done.astype(jnp.int32))
        p0 = jnp.where(slot == 0, pend, p0)
        p1 = jnp.where(slot == 1, pend, p1)
        return st, p0, p1

    def reads(q):
        return [pltpu.make_async_copy(out_hbm.at[hd, pl.ds(q, 1)],
                                      rbuf.at[pl.ds(hd, 1)], bsem.at[0])
                for hd in range(heads)]

    pos0 = s.first(s.t_lo)
    rbuf[...] = jnp.zeros(rbuf.shape, rbuf.dtype)

    @pl.when((pos0 & (LANES - 1)) != 0)
    def _():                            # the row the step before began
        for c in reads(pos0 >> _LOG_LANES):
            c.start()
        for c in reads(pos0 >> _LOG_LANES):
            c.wait()

    st, p0, p1 = s.run(lambda t, b, slot: s.issue_rows(t, b, slot),
                       reduce, (rbuf[...], jnp.int32(0), jnp.int32(0)))
    for slot, p in ((0, p0), (1, p1)):
        @pl.when(p > 0)
        def _():
            for c in write(0, slot):
                c.wait()

    pos = s.first(s.t_end)

    @pl.when((pos & (LANES - 1)) != 0)
    def _():                            # the row the next step completes
        wbuf[0] = st
        for c in write(pos >> _LOG_LANES, 0):
            c.start()
        for c in write(pos >> _LOG_LANES, 0):
            c.wait()


def _flat_table(x, fill) -> jnp.ndarray:
    """Flatten a per-element table into ``(rows, 128)`` with spare rows, so
    the two-row chunk copy never reads past the end, even for a chunk
    that starts at the last element."""
    x = x.reshape(-1)
    rows = x.shape[0] // LANES + 2
    return jnp.pad(x, (0, rows * LANES - x.shape[0]),
                   constant_values=fill).reshape(rows, LANES)


def _head_table(val) -> jnp.ndarray:
    """Per-head values ``(H, elements)`` as ``H`` flat tables, ``(H, rows,
    128)``, each laid out as :func:`_flat_table` lays one."""
    val = val.reshape(val.shape[0], -1).astype(jnp.float32)
    rows = val.shape[1] // LANES + 2
    return jnp.pad(val, ((0, 0), (0, rows * LANES - val.shape[1]))
                   ).reshape(val.shape[0], rows, LANES)


def _prepared(ptr, idx, h, *, ncols, row_div, seg_rows):
    """Shared set-up of both modes: the static plan, the padded segment
    offsets, the flat ``idx`` table and H with its zero sentinel row (one
    row is one DMA: a (1, 128) slice at K <= 128, else its own (1, K)
    tile of an (N, 1, K) array)."""
    assert h.shape[0] == ncols, (h.shape, ncols)
    assert row_div or seg_rows & (seg_rows - 1) == 0, seg_rows
    nseg = ptr.shape[0] - 1
    plan = gather_plan(nseg, idx.size, row_div=row_div, seg_rows=seg_rows)
    segs = plan["tile_rows"] // (seg_rows or 8)
    # empty trailing segments complete the last grid step
    ptr = jnp.pad(ptr.astype(jnp.int32),
                  (0, plan["steps"] * plan["tiles_per_step"] * segs - nseg),
                  mode="edge")
    k = h.shape[1]
    kp = -(-k // LANES) * LANES
    hp = jnp.pad(h, ((0, 1), (0, kp - k)))
    if kp > LANES:
        hp = hp.reshape(ncols + 1, 1, kp)
    return plan, ptr, _flat_table(idx.astype(jnp.int32), ncols), hp, kp


def _kernel_params(plan, row_div, seg_rows, heads, head_dim) -> dict:
    return dict(tile_rows=plan["tile_rows"], tiles=plan["tiles_per_step"],
                row_div=row_div, seg_rows=seg_rows, heads=heads,
                head_dim=head_dim)


def gather_spmm_pallas(ptr: jnp.ndarray, idx: jnp.ndarray, val: jnp.ndarray,
                       h: jnp.ndarray, *, ncols: int, row_div: int = 0,
                       seg_rows: int = 0, heads: int = 1,
                       interpret: bool = False) -> jnp.ndarray:
    """Float32 sums of ``val * h[idx]`` per output row, ``(nseg * R, K)``
    rows for ``nseg = len(ptr) - 1`` segments of ``R`` rows each.

    ``ptr`` holds each segment's element range in the flattened
    ``idx``/``val`` tables. ELL (``row_div = max_deg``): 8-row segments,
    element ``e`` of a range goes to row ``e // row_div``. SELL
    (``seg_rows = C``, a power of two): C-row segments (slices), element
    ``e`` goes to row ``e % C``; slices are grouped into 8-row tiles when
    C < 8.

    Multi-head (``heads = H > 1``): ``val`` holds ``H`` values per element,
    head-major (``(H,) + idx.shape``, so each head's values stay dense in
    lanes), and H's ``K = H * F`` lanes are ``H`` heads of ``F``; head
    ``h`` of a row sums ``val[h] * h[idx, hF:(h+1)F]``.
    Each element's row still arrives by one DMA; the chunk's reduction is
    one dot per head."""
    plan, ptr, idx_t, hp, kp = _prepared(ptr, idx, h, ncols=ncols,
                                         row_div=row_div, seg_rows=seg_rows)
    k = h.shape[1]
    assert k % heads == 0, (k, heads)
    if heads == 1:
        val_t = _flat_table(val.astype(jnp.float32), 0)
        val_scratch = pltpu.VMEM((DEPTH, 2, LANES), jnp.float32)
    else:
        val_t = _head_table(val)
        val_scratch = pltpu.VMEM((DEPTH, heads, 2, LANES), jnp.float32)
    kernel = functools.partial(
        _kernel, **_kernel_params(plan, row_div, seg_rows, heads,
                                  k // heads))
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
                val_scratch,
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
    )(ptr, idx_t, val_t, hp)
    return out[: plan["nseg"] * (seg_rows or 8), :k]


def gather_sddmm_pallas(ptr: jnp.ndarray, idx: jnp.ndarray, dout: jnp.ndarray,
                        h: jnp.ndarray, *, ncols: int, row_div: int = 0,
                        seg_rows: int = 0, heads: int = 1,
                        interpret: bool = False) -> jnp.ndarray:
    """Per element ``e`` of the flattened table and head ``h``, the dot of
    head ``h``'s lanes of ``dout[row(e)]`` and of ``h[idx[e]]``: the
    gradient of :func:`gather_spmm_pallas` in its values, over the same
    table and pipeline. ``dout`` has the kernel's output rows
    (``nseg * R``, ELL rows or SELL's sorted rows). Returns
    ``(heads,) + idx.shape`` f32 (``idx.shape`` when ``heads == 1``); pad
    elements read 0 (they gather the zero row)."""
    plan, ptr, idx_t, hp, kp = _prepared(ptr, idx, h, ncols=ncols,
                                         row_div=row_div, seg_rows=seg_rows)
    k = h.shape[1]
    assert k % heads == 0 and dout.shape[1] == k, (k, heads, dout.shape)
    rows_per_step = plan["rows_per_step"]
    dp = jnp.pad(dout, ((0, plan["steps"] * rows_per_step - dout.shape[0]),
                        (0, kp - k)))
    kernel = functools.partial(
        _sddmm_kernel, **_kernel_params(plan, row_div, seg_rows, heads,
                                        k // heads))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(plan["steps"],),
            in_specs=[hbm, pl.BlockSpec((rows_per_step, kp),
                                        lambda s, ptr: (s, 0)), hbm],
            out_specs=hbm,
            scratch_shapes=[
                pltpu.SMEM((2 * DEPTH, LANES), jnp.int32),
                pltpu.VMEM((DEPTH, LANES) + hp.shape[1:], h.dtype),
                pltpu.VMEM((DEPTH, heads, LANES), jnp.float32),
                pltpu.VMEM((heads, LANES), jnp.float32),
                pltpu.SemaphoreType.DMA((DEPTH,)),
                pltpu.SemaphoreType.DMA((DEPTH,)),
                pltpu.SemaphoreType.DMA((DEPTH,)),
                pltpu.SemaphoreType.DMA((1,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((heads,) + idx_t.shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(ptr, idx_t, dp, hp)
    out = out.reshape(heads, -1)[:, : idx.size]
    return out.reshape(((heads,) if heads > 1 else ()) + idx.shape)
