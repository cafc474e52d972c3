"""Jit'd dispatch wrappers over the Pallas kernels.

Backend policy (recorded in DESIGN.md §2): the Pallas TPU kernels run when a
TPU backend is attached; on CPU (this container) the same mathematical
operation dispatches to an XLA path that preserves the *algorithmic* choice
(block-sparse matmuls for BSR, gathers for ELL) so CPU wall-clock benches
remain an honest proxy for the kernel-selection logic. ``interpret=True``
forces the Pallas body through the interpreter for correctness tests.

Profile-ops mode (``repro.obs``): every dispatcher below records one
``op.<name>.trace`` instant per call — operand shapes and backend, no
time (under ``jit`` it fires at trace time; device time per kernel comes
from the device trace, by stage). Disabled (the default), the cost is one
module-flag check per dispatch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.sparse import BSR, COO, ELL, SELL
from repro.obs import op_record

__all__ = [
    "on_tpu",
    "bsr_spmm",
    "bsr_spmm_xla",
    "ell_spmm",
    "gathered_ell_spmm",
    "slot_gather",
    "table_insert",
    "sell_spmm",
    "sell_spmm_xla",
    "sell_packed_reduce",
    "gather_overlap_share",
    "gather_spmm_heads",
    "gather_sddmm",
    "sddmm_bsr",
    "fusedmm_bsr",
    "ragged_gemm",
    "flash_attention",
]


def on_tpu() -> bool:
    """True when the default jax backend is a TPU — the dispatchers below
    use this to choose Pallas kernels over their XLA proxies."""
    return jax.default_backend() == "tpu"


# --------------------------------------------------------------------------
# BSR SpMM — the "generated" MXU kernel (sum semiring)
# --------------------------------------------------------------------------

def bsr_spmm_xla(a: BSR, h: jnp.ndarray) -> jnp.ndarray:
    """Vectorized XLA path with the same block algorithm as the Pallas
    kernel: gather H block-rows, batched tile matmul, segment-sum scatter."""
    k = h.shape[1]
    pad = a.ncols - h.shape[0]
    if pad:
        h = jnp.pad(h, ((0, pad), (0, 0)))
    hb = h.reshape(a.ncols // a.bc, a.bc, k)[a.blk_col]       # (nb, bc, k)
    contrib = jnp.einsum("nij,njk->nik", a.blocks, hb,
                         preferred_element_type=jnp.float32)   # (nb, br, k)
    out = jax.ops.segment_sum(contrib, a.blk_row,
                              num_segments=a.n_block_rows)     # (nbr, br, k)
    return out.reshape(a.nrows, k).astype(h.dtype)


def bsr_spmm(a: BSR, h: jnp.ndarray, *, fk: int = 256,
             interpret: bool | None = None) -> jnp.ndarray:
    """(a.nrows, K) = a @ h with the generated kernel.

    ``h`` may have fewer rows than ``a.ncols`` (pre-padding); zero-padded.
    """
    if h.shape[0] != a.ncols:
        h = jnp.pad(h, ((0, a.ncols - h.shape[0]), (0, 0)))
    use_pallas = on_tpu() if interpret is None else True
    if use_pallas:
        from repro.kernels.bsr_spmm import bsr_spmm_pallas
        out = bsr_spmm_pallas(a, h, fk=fk, interpret=bool(interpret))
    else:
        out = bsr_spmm_xla(a, h)
    op_record("bsr_spmm", a.blocks, h,
              backend="pallas" if use_pallas else "xla")
    return out


# --------------------------------------------------------------------------
# ELL SpMM — VPU gather kernel for very sparse / regular-degree graphs
# --------------------------------------------------------------------------

def ell_spmm(a: ELL, h: jnp.ndarray, *, interpret: bool | None = None
             ) -> jnp.ndarray:
    """(a.nrows, K) = a @ h over the row-padded ELLPACK neighbor lists
    (sum semiring). Rectangular operands are first-class: ``h`` has
    ``a.ncols`` rows, which sampled bipartite blocks set to their source
    count (≠ nrows). Pallas gather kernel on TPU, the jnp oracle
    elsewhere; ``interpret=True`` forces the Pallas body through the
    interpreter. Differentiable in ``h`` (see :func:`_pallas_gather`)."""
    use_pallas = on_tpu() if interpret is None else True
    if use_pallas:
        out = _pallas_gather(a, h, bool(interpret))
    else:
        from repro.kernels.ref import spmm_ell_ref
        from repro.core.semiring import get_semiring
        out = spmm_ell_ref(a, h, get_semiring("sum"))
    op_record("ell_spmm", a.idx, h,
              backend="pallas" if use_pallas else "xla",
              **(_gather_pipeline(a) if use_pallas else {}))
    return out


def _gather_rows_of(a) -> tuple:
    """Output row of every (idx, val) slot of an ELL or SELL operand, and
    the row count they range over (SELL's degree-0 pad rows included)."""
    if isinstance(a, ELL):
        return jax.lax.broadcasted_iota(jnp.int32, a.idx.shape, 0), a.nrows
    sorted_row = a.slice_of[:, None] * a.c + jnp.arange(a.c)[None, :]
    return a.perm[sorted_row], a.nrows_padded


def _gather_pipeline(a) -> dict:
    """Static pipeline parameters of the row-gather kernel on an ELL or
    SELL operand, for its op record: rows per grid step, depth in chunks
    and elements in the table."""
    from repro.kernels.gather_spmm import gather_plan
    if isinstance(a, ELL):
        from repro.kernels.ell_spmm import ell_ptr
        ptr = ell_ptr(a)
        plan = gather_plan(len(ptr) - 1, int(ptr[-1]), row_div=a.max_deg)
    else:
        plan = gather_plan(a.nslices, a.n_steps * a.c, seg_rows=a.c)
    return {k: plan[k] for k in ("rows_per_step", "depth", "elements")}


def gather_overlap_share(tables) -> float:
    """Share of the row-gather kernel's chunks, over calls on each packed
    ELL or SELL table of ``tables``, whose DMAs are issued while an
    earlier chunk is still in flight: ``1 - pipeline starts / chunks``."""
    from repro.kernels.ell_spmm import ell_ptr
    from repro.kernels.gather_spmm import chunk_counts
    counts = [chunk_counts(ell_ptr(a), row_div=a.max_deg)
              if isinstance(a, ELL) else
              chunk_counts(np.asarray(a.slice_ptr) * a.c, seg_rows=a.c)
              for a in tables]
    starts, chunks = (sum(c) for c in zip(*counts))
    return 1.0 - starts / chunks


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _pallas_gather(a, h, interpret: bool):
    """The ELL / SELL Pallas forward with a gradient in ``h``: Pallas
    calls have no autodiff rule, and a sampled block (the one caller that
    differentiates through here — the full graph's custom VJP in
    ``core/spmm`` runs the cached transpose instead) has no transposed
    layout to run the kernel backwards on, so dH = Aᵀ·dY is the
    scatter-add of ``val * dY[row]`` onto ``idx`` over the same table."""
    if isinstance(a, ELL):
        from repro.kernels.ell_spmm import ell_spmm_pallas
        return ell_spmm_pallas(a, h, interpret=interpret)
    from repro.kernels.sell_spmm import sell_spmm_pallas
    return sell_spmm_pallas(a, h, interpret=interpret)


def _pallas_gather_fwd(a, h, interpret):
    return _pallas_gather(a, h, interpret), a


def _pallas_gather_bwd(interpret, a, dy):
    rows, nrows = _gather_rows_of(a)
    dy = jnp.pad(dy, ((0, nrows - dy.shape[0]), (0, 0)))  # pad rows: zero
    msgs = a.val[..., None].astype(dy.dtype) * jnp.take(dy, rows, axis=0)
    dh = jax.ops.segment_sum(msgs.reshape(-1, dy.shape[1]), a.idx.ravel(),
                             num_segments=a.ncols + 1)[: a.ncols]
    return jax.tree_util.tree_map(jnp.zeros_like, a), dh


_pallas_gather.defvjp(_pallas_gather_fwd, _pallas_gather_bwd)


def gathered_ell_spmm(a: ELL, h_full: jnp.ndarray, src_ids: jnp.ndarray
                      ) -> jnp.ndarray:
    """``ell_spmm(a, h_full[src_ids])`` without materializing the gathered
    source block: the block-local neighbor ids are composed with the
    global ``src_ids`` relabeling so XLA fuses both gathers into one
    (nrows, max_deg, K) fetch from the full feature matrix.

    This is the layer-wise-inference hot path — there the dense operand is
    the whole node-embedding table, and the (n_src, K) staging copy this
    skips is the dominant memory cost per block. Sentinel slots compose to
    out-of-range twice (local pad -> ``src_ids`` fill past ``h_full`` ->
    zero row) and carry ``val == 0``, so they stay doubly inert. Sum
    semiring, like :func:`ell_spmm`.
    """
    gid = jnp.take(src_ids, a.idx, mode="fill",
                   fill_value=h_full.shape[0])
    gathered = jnp.take(h_full, gid, axis=0, mode="fill",
                        fill_value=0)                      # (N, D, K)
    out = (a.val[:, :, None].astype(gathered.dtype) * gathered).sum(axis=1)
    op_record("gathered_ell_spmm", a.idx, h_full, src_ids)
    return out


# --------------------------------------------------------------------------
# Slot-map gather/insert — the serving feature-cache device primitives
# --------------------------------------------------------------------------

@jax.jit
def _slot_gather_jit(table: jnp.ndarray, slots: jnp.ndarray,
                     rows: jnp.ndarray) -> jnp.ndarray:
    safe = jnp.clip(slots, 0, table.shape[0] - 1)
    hit = jnp.take(table, safe, axis=0)
    return jnp.where((slots >= 0)[:, None], hit, rows)


def slot_gather(table: jnp.ndarray, slots: jnp.ndarray,
                rows: jnp.ndarray) -> jnp.ndarray:
    """Row-wise select between a device-resident cache table and staged
    fallback rows: ``out[i] = table[slots[i]]`` when ``slots[i] >= 0``
    (a cache hit — the slot map resolved the id), else ``rows[i]`` (the
    pinned-host fallback gather, already staged to device by the caller).

    The hit path never touches host memory and the select is exact
    (rows are copied bit-for-bit, never recomputed), which is what lets
    the serving parity suite demand cache-hit == cache-miss bitwise.
    ``slots`` out-of-range on the miss lanes is clamped before the gather
    so the table fetch stays in-bounds (the lane's value is discarded by
    the select)."""
    out = _slot_gather_jit(table, slots, rows)
    op_record("slot_gather", table, slots, rows)
    return out


@functools.partial(jax.jit, donate_argnums=(0,))
def _table_insert_jit(table: jnp.ndarray, slots: jnp.ndarray,
                      rows: jnp.ndarray) -> jnp.ndarray:
    return table.at[jnp.where(slots >= 0, slots, table.shape[0])].set(rows,
                                                                      mode="drop")


def table_insert(table: jnp.ndarray, slots: jnp.ndarray,
                 rows: jnp.ndarray) -> jnp.ndarray:
    """Scatter miss rows into their assigned cache slots:
    ``table[slots] = rows`` with the old buffer donated, so steady-state
    insertion is an in-place device scatter, not a table-sized copy.
    Out-of-range slots (< 0, the "no insert" lane) drop silently via
    scatter's OOB semantics."""
    out = _table_insert_jit(table, slots, rows)
    op_record("table_insert", slots, rows)
    return out


# --------------------------------------------------------------------------
# SELL SpMM — sliced degree-sorted gather kernel (sum semiring)
# --------------------------------------------------------------------------

def _weighted(val: jnp.ndarray, gathered: jnp.ndarray) -> jnp.ndarray:
    """``val * gathered`` per slot: one value per slot (``val.shape ==
    gathered.shape[:-1]``), or one per slot and head (a trailing head axis
    of H; the K lanes of a row are H heads of K / H)."""
    if val.ndim == gathered.ndim - 1:
        return val[..., None].astype(gathered.dtype) * gathered
    heads, k = val.shape[-1], gathered.shape[-1]
    g = gathered.reshape(gathered.shape[:-1] + (heads, k // heads))
    return (val[..., None].astype(g.dtype) * g).reshape(gathered.shape)


def sell_packed_reduce(idx: jnp.ndarray, val: jnp.ndarray,
                       slice_of: jnp.ndarray, nslices: int,
                       inv_perm: jnp.ndarray, h: jnp.ndarray) -> jnp.ndarray:
    """The packed-slice SELL reduction on raw arrays: gather the
    (n_steps, C) neighbor table, one fused segment-sum over slices,
    inverse-permute rows. Shared by :func:`sell_spmm_xla` and the
    distributed per-band body (dist/gnn.py) so the algorithm lives once.
    The gather tensor is O(n_steps · C · K) — the per-slice padding savings
    that make SELL beat the ELL path carry over to the CPU proxy unchanged.
    Sentinel slots (idx out of range) gather 0 via mode='fill' and carry
    val == 0, so they are doubly inert. ``val`` may carry a trailing head
    axis (:func:`_weighted`)."""
    c = idx.shape[1]
    gathered = jnp.take(h, idx, axis=0, mode="fill",
                        fill_value=0)                       # (S, C, K)
    msgs = _weighted(val, gathered)
    acc = jax.ops.segment_sum(msgs, slice_of,
                              num_segments=nslices)         # (nslices, C, K)
    return acc.reshape(nslices * c, h.shape[1])[inv_perm]


def sell_spmm_xla(a: SELL, h: jnp.ndarray) -> jnp.ndarray:
    """Vectorized XLA path with the same packed-slice algorithm as the
    Pallas kernel (see :func:`sell_packed_reduce`)."""
    out = sell_packed_reduce(a.idx, a.val, a.slice_of, a.nslices,
                             a.inv_perm, h)
    return out.astype(h.dtype)


def sell_spmm(a: SELL, h: jnp.ndarray, *, interpret: bool | None = None
              ) -> jnp.ndarray:
    """(a.nrows, K) = a @ h over SELL-C-σ packed slices (sum semiring),
    output already un-sorted back to original row order via ``inv_perm``.
    Pallas kernel on TPU, :func:`sell_spmm_xla` elsewhere. Differentiable
    in ``h`` (see :func:`_pallas_gather`)."""
    use_pallas = on_tpu() if interpret is None else True
    if use_pallas:
        out = _pallas_gather(a, h, bool(interpret))
    else:
        out = sell_spmm_xla(a, h)
    op_record("sell_spmm", a.idx, h,
              backend="pallas" if use_pallas else "xla",
              **(_gather_pipeline(a) if use_pallas else {}))
    return out


# --------------------------------------------------------------------------
# Multi-head SpMM and gather-SDDMM over an ELL or SELL table (attention)
# --------------------------------------------------------------------------

def _pallas_table(a, vals=None):
    """The row-gather kernel's operands for ``a``: segment offsets, the
    ``idx`` table (ELL rows padded to whole 8-row tiles), head-major
    per-slot values ``(H, slots)`` padded alike, and the layout keyword."""
    if isinstance(a, ELL):
        from repro.kernels.ell_spmm import ell_ptr
        ptr = ell_ptr(a)
        pad = (len(ptr) - 1) * 8 - a.nrows
        idx = jnp.pad(a.idx, ((0, pad), (0, 0)), constant_values=a.ncols)
        if vals is not None:
            vals = jnp.pad(vals, ((0, 0), (0, pad * a.max_deg)))
        return jnp.asarray(ptr), idx, vals, {"row_div": a.max_deg}
    return a.slice_ptr * a.c, a.idx, vals, {"seg_rows": a.c}


def gather_spmm_heads(a, vals: jnp.ndarray, z: jnp.ndarray, *,
                      interpret: bool | None = None) -> jnp.ndarray:
    """Multi-head SpMM over ELL or SELL ``a``'s slots: head ``h`` of output
    row i is ``sum_slots vals[h, s] * z[idx[s], h-th K/H lanes]`` over row
    i's slots, ``vals`` ``(H, slots)`` in table order (ELL ``(nrows,
    max_deg)`` or SELL ``(n_steps, C)``, flattened) and head-major, so
    every head's values are dense in lanes. Output rows in original
    order. The Pallas row-gather kernel in its multi-head mode on TPU (one
    DMA per slot for all heads), the XLA gather elsewhere. Not
    differentiable: the attention op in ``core/fusedmm`` owns the VJP."""
    heads = vals.shape[0]
    use_pallas = on_tpu() if interpret is None else True
    if use_pallas:
        from repro.kernels.gather_spmm import gather_spmm_pallas
        ptr, idx, vp, kw = _pallas_table(a, vals)
        out = gather_spmm_pallas(ptr, idx, vp if heads > 1 else vp[0], z,
                                 ncols=a.ncols, heads=heads,
                                 interpret=bool(interpret), **kw)
        out = out[: a.nrows] if isinstance(a, ELL) else out[a.inv_perm]
    else:
        v = vals.T.reshape(a.idx.shape + (heads,))
        if isinstance(a, ELL):
            g = jnp.take(z, a.idx, axis=0, mode="fill", fill_value=0)
            out = _weighted(v, g).sum(axis=1)
        else:
            out = sell_packed_reduce(a.idx, v, a.slice_of, a.nslices,
                                     a.inv_perm, z)
    op_record("gather_spmm_heads", a.idx, vals, z, heads=heads,
              backend="pallas" if use_pallas else "xla",
              **(_gather_pipeline(a) if use_pallas else {}))
    return out.astype(z.dtype)


def _kernel_rows(a, x: jnp.ndarray) -> jnp.ndarray:
    """Rows of ``x`` (original order) in the kernel's output row order:
    SELL's degree-sorted rows with its pad rows zero; ELL's own."""
    if isinstance(a, ELL):
        return x
    x = jnp.pad(x, ((0, a.nrows_padded - x.shape[0]), (0, 0)))
    return jnp.take(x, a.perm, axis=0)


def gather_sddmm(a, dout: jnp.ndarray, z: jnp.ndarray, *, heads: int,
                 interpret: bool | None = None) -> jnp.ndarray:
    """Per slot of ELL or SELL ``a`` and head h, the dot of head h's lanes
    of ``dout[row]`` and ``z[idx]``: ``(heads, slots)`` in table order,
    0 on pad slots. The gradient of :func:`gather_spmm_heads` in its
    values (an SDDMM over the packed layout). The Pallas row-gather kernel
    in its SDDMM mode on TPU (each slot's row of ``z`` by one DMA, its
    output row resident), the XLA gather elsewhere."""
    use_pallas = on_tpu() if interpret is None else True
    d = _kernel_rows(a, dout)
    if use_pallas:
        from repro.kernels.gather_spmm import gather_sddmm_pallas
        ptr, idx, _, kw = _pallas_table(a)
        if isinstance(a, ELL):
            d = jnp.pad(d, ((0, idx.shape[0] - d.shape[0]), (0, 0)))
        out = gather_sddmm_pallas(ptr, idx, d, z, ncols=a.ncols,
                                  heads=heads, interpret=bool(interpret),
                                  **kw)
        out = out.reshape(heads, -1)[:, : a.idx.size]
    else:
        g = jnp.take(z, a.idx, axis=0, mode="fill", fill_value=0)
        if isinstance(a, ELL):
            drow = d[:, None, :]
        else:
            drow = d.reshape(a.nslices, a.c, -1)[a.slice_of]
        k = z.shape[1]
        prod = (g * drow).reshape(g.shape[:-1] + (heads, k // heads))
        out = prod.sum(axis=-1).reshape(-1, heads).T
    op_record("gather_sddmm", a.idx, dout, z, heads=heads,
              backend="pallas" if use_pallas else "xla",
              **(_gather_pipeline(a) if use_pallas else {}))
    return out


# --------------------------------------------------------------------------
# SDDMM / FusedMM on BSR tiles
# --------------------------------------------------------------------------

def sddmm_bsr(a: BSR, x: jnp.ndarray, y: jnp.ndarray, *,
              scale_by_a: bool = True,
              interpret: bool | None = None) -> jnp.ndarray:
    """Sampled dense-dense matmul over A's block pattern: returns
    (nblocks, br, bc) per-block scores x_i . y_j, optionally scaled by A's
    stored values. MXU-tiled Pallas kernel on TPU, vmapped XLA otherwise."""
    use_pallas = on_tpu() if interpret is None else True
    if use_pallas:
        from repro.kernels.sddmm import sddmm_bsr_pallas
        out = sddmm_bsr_pallas(a, x, y, scale_by_a=scale_by_a,
                               interpret=bool(interpret))
    else:
        from repro.kernels.ref import sddmm_bsr_ref
        out = sddmm_bsr_ref(a, x, y, scale_by_a=scale_by_a)
    op_record("sddmm", a.blocks, x, y,
              backend="pallas" if use_pallas else "xla")
    return out


def fusedmm_bsr(a: BSR, x: jnp.ndarray, y: jnp.ndarray, h: jnp.ndarray, *,
                edge_op: str = "softmax",
                interpret: bool | None = None) -> jnp.ndarray:
    """Fused SDDMM -> edge op -> SpMM over BSR tiles: out[i] = sum_j
    f(x_i . y_j) h_j without materializing the edge tensor in HBM
    (paper §3.4 / FusedMM). ``edge_op``: softmax | sigmoid | none."""
    use_pallas = on_tpu() if interpret is None else True
    if use_pallas:
        from repro.kernels.fusedmm import fusedmm_bsr_pallas
        out = fusedmm_bsr_pallas(a, x, y, h, edge_op=edge_op,
                                 interpret=bool(interpret))
    else:
        out = _fusedmm_bsr_xla(a, x, y, h, edge_op=edge_op)
    op_record("fusedmm", a.blocks, x, y, h,
              edge_op=edge_op, backend="pallas" if use_pallas else "xla")
    return out


def _fusedmm_bsr_xla(a: BSR, x, y, h, *, edge_op: str) -> jnp.ndarray:
    from repro.kernels.ref import fusedmm_softmax_ref, sddmm_bsr_ref
    if edge_op == "softmax":
        return fusedmm_softmax_ref(a, x, y, h)
    s = sddmm_bsr_ref(a, x, y, scale_by_a=False)
    mask = a.blocks != 0
    w = jnp.where(mask, jax.nn.sigmoid(s) if edge_op == "sigmoid" else s, 0.0)
    hb = h.reshape(a.ncols // a.bc, a.bc, h.shape[1])[a.blk_col]
    contrib = jnp.einsum("nij,njk->nik", w, hb)
    out = jax.ops.segment_sum(contrib, a.blk_row, num_segments=a.n_block_rows)
    return out.reshape(a.nrows, h.shape[1])


# --------------------------------------------------------------------------
# Ragged (grouped) GEMM — MoE expert matmul over tile-aligned groups
# --------------------------------------------------------------------------

def ragged_gemm(x: jnp.ndarray, w: jnp.ndarray, tile_expert: jnp.ndarray, *,
                tm: int = 128, interpret: bool | None = None) -> jnp.ndarray:
    """x: (T, D) tokens sorted by expert, T % tm == 0; w: (E, D, F);
    tile_expert: (T//tm,) expert id per token tile. Returns (T, F)."""
    use_pallas = on_tpu() if interpret is None else True
    if use_pallas:
        from repro.kernels.ragged_gemm import ragged_gemm_pallas
        return ragged_gemm_pallas(x, w, tile_expert, tm=tm,
                                  interpret=bool(interpret))
    xt = x.reshape(-1, tm, x.shape[1])
    wt = w[tile_expert]                       # (T//tm, D, F)
    return jnp.einsum("tmd,tdf->tmf", xt, wt).reshape(x.shape[0], w.shape[2])


# --------------------------------------------------------------------------
# Flash attention (LM prefill)
# --------------------------------------------------------------------------

def flash_attention(q, k, v, *, causal: bool = True, window: int | None = None,
                    interpret: bool | None = None) -> jnp.ndarray:
    """Tiled online-softmax attention for LM prefill; ``window`` enables
    sliding-window masking. Pallas on TPU, chunked XLA attention
    elsewhere."""
    use_pallas = on_tpu() if interpret is None else True
    if use_pallas:
        from repro.kernels.flash_attention import flash_attention_pallas
        return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                      interpret=bool(interpret))
    from repro.models.lm.attention import chunked_attention
    return chunked_attention(q, k, v, causal=causal, window=window)
