"""Backend dispatch over the sparse, sampling-serving and LM kernels.

Each op here runs its Pallas TPU kernel when the default backend is a TPU
and an XLA formulation of the same result elsewhere; ``interpret=True``
forces the Pallas body through the interpreter (how the CPU tests run
the kernels), ``interpret=False`` forces the compiled kernel. The ELL and
SELL ops share one row-gather kernel (``kernels/gather_spmm.py``), whose
operands and output row order are built in one place here
(:func:`_gather_operands`, :func:`_original_rows`). Speed is measured on
the chip (``chipbench/``); the XLA paths are for correctness off it.

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
    "gather_spmm_heads",
    "gather_sddmm",
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
# The row-gather kernel's operands and row order, for ELL and SELL tables
# --------------------------------------------------------------------------

_ELL_TILE_ROWS = 8


def _gather_operands(a, vals=None) -> tuple:
    """The row-gather kernel's operands for ELL or SELL table ``a``:
    per-segment element offsets, the ``idx`` table, the per-slot values
    and the layout keyword. An ELL segment is a tile of 8 rows (``row_div
    = max_deg``), its rows padded to whole tiles with sentinel slots; a
    SELL segment is one C-row slice (``seg_rows = C``). ``vals``:
    head-major ``(H, slots)`` in table order; by default the table's own
    ``val``, in its own shape."""
    if isinstance(a, ELL):
        ntiles = -(-max(a.nrows, 1) // _ELL_TILE_ROWS)
        tile = _ELL_TILE_ROWS * a.max_deg
        ptr = np.arange(ntiles + 1, dtype=np.int32) * tile
        pad = ntiles * _ELL_TILE_ROWS - a.nrows
        idx = jnp.pad(a.idx, ((0, pad), (0, 0)), constant_values=a.ncols)
        vals = (jnp.pad(a.val, ((0, pad), (0, 0))) if vals is None
                else jnp.pad(vals, ((0, 0), (0, pad * a.max_deg))))
        return jnp.asarray(ptr), idx, vals, {"row_div": a.max_deg}
    return (a.slice_ptr * a.c, a.idx, a.val if vals is None else vals,
            {"seg_rows": a.c})


def _gather_pipeline(a) -> dict:
    """Static pipeline parameters of the row-gather kernel on an ELL or
    SELL operand, for its op record: rows per grid step, depth in chunks
    and elements in the table."""
    from repro.kernels.gather_spmm import gather_plan
    ptr, idx, _, layout = _gather_operands(a)
    plan = gather_plan(ptr.shape[0] - 1, idx.size, **layout)
    return {k: plan[k] for k in ("rows_per_step", "depth", "elements")}


def _original_rows(a, out: jnp.ndarray) -> jnp.ndarray:
    """The kernel's output rows (ELL's padded to whole tiles, SELL's
    degree-sorted) in original row order."""
    return out[: a.nrows] if isinstance(a, ELL) else out[a.inv_perm]


def _kernel_rows(a, x: jnp.ndarray) -> jnp.ndarray:
    """Rows of ``x`` (original order) in the kernel's output row order:
    SELL's degree-sorted rows with its pad rows zero; ELL's own."""
    if isinstance(a, ELL):
        return x
    x = jnp.pad(x, ((0, a.nrows_padded - x.shape[0]), (0, 0)))
    return jnp.take(x, a.perm, axis=0)


def _slot_rows(a) -> jnp.ndarray:
    """The kernel's output row of every slot, in the table's shape."""
    if isinstance(a, ELL):
        return jax.lax.broadcasted_iota(jnp.int32, a.idx.shape, 0)
    return a.slice_of[:, None] * a.c + jnp.arange(a.c)[None, :]


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


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _pallas_gather(a, h, interpret: bool):
    """The ELL / SELL Pallas forward with a gradient in ``h``: Pallas
    calls have no autodiff rule, and a sampled block (the one caller that
    differentiates through here — the full graph's custom VJP in
    ``core/spmm`` runs the cached transpose instead) has no transposed
    layout to run the kernel backwards on, so dH = Aᵀ·dY is the
    scatter-add of ``val * dY[row]`` onto ``idx`` over the same table."""
    from repro.kernels.gather_spmm import gather_spmm_pallas
    ptr, idx, val, layout = _gather_operands(a)
    return _original_rows(a, gather_spmm_pallas(
        ptr, idx, val, h, ncols=a.ncols, interpret=interpret, **layout))


def _pallas_gather_fwd(a, h, interpret):
    return _pallas_gather(a, h, interpret), a


def _pallas_gather_bwd(interpret, a, dy):
    rows = jnp.take(_kernel_rows(a, dy), _slot_rows(a), axis=0)
    msgs = a.val[..., None].astype(dy.dtype) * rows
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
        ptr, idx, vp, layout = _gather_operands(a, vals)
        out = _original_rows(a, gather_spmm_pallas(
            ptr, idx, vp if heads > 1 else vp[0], z, ncols=a.ncols,
            heads=heads, interpret=bool(interpret), **layout))
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
        ptr, idx, _, layout = _gather_operands(a)
        out = gather_sddmm_pallas(ptr, idx, d, z, ncols=a.ncols,
                                  heads=heads, interpret=bool(interpret),
                                  **layout)
        out = out.reshape(heads, -1)[:, : a.idx.size]
    else:
        g = jnp.take(z, a.idx, axis=0, mode="fill", fill_value=0)
        drow = jnp.take(d, _slot_rows(a), axis=0)
        k = z.shape[1]
        prod = (g * drow).reshape(g.shape[:-1] + (heads, k // heads))
        out = prod.sum(axis=-1).reshape(-1, heads).T
    op_record("gather_sddmm", a.idx, dout, z, heads=heads,
              backend="pallas" if use_pallas else "xla",
              **(_gather_pipeline(a) if use_pallas else {}))
    return out


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
