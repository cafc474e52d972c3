"""Multi-head GAT attention (Veličković et al., arXiv:1710.10903) over a
cached graph:

    e_ijh = LeakyReLU(s_dst[h, i] + s_src[h, j])   over the stored (i, j)
    α_ijh = softmax_j(e_ijh) over row i's entries
    out[i, h] = Σ_j α_ijh z[j, h]                   (z's K lanes: H heads)

On a gather plan (ELL or SELL) the weights live in the packed table's
slot order and never leave it: the multi-head SpMM runs the row-gather
kernel over them (``kernels/ops.gather_spmm_heads``), the cached
transpose gets them through the graph's slot permutation, and their
gradient is the kernel's gather-SDDMM (``kernels/ops.gather_sddmm``). No
``(nnz, K)`` message tensor exists. Other plans, and the unpatched
baseline, run the COO composition (:func:`gat_attention_coo`) under plain
AD.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.core import sparse as sp
from repro.core.cache import CachedGraph
from repro.kernels import ops as kops
from repro.kernels import sell_rows
from repro import obs
from repro.obs import stages

Array = Any

__all__ = ["gat_attention", "gat_attention_coo"]


NEGATIVE_SLOPE = 0.2      # LeakyReLU's slope in the scores (the paper's)


def _leaky(x):
    return jnp.where(x > 0, x, NEGATIVE_SLOPE * x)


def _gather_tables(g: CachedGraph):
    """(table, transposed table) of a gather plan, or None."""
    if g.plan.wants_sell and g.sell is not None:
        tables = g.sell, g.sell_t
    elif g.plan.wants_ell and g.ell is not None:
        tables = g.ell, g.ell_t
    else:
        return None
    if g.slot_perm is None:
        raise ValueError("GAT attention on a gather plan needs the graph's "
                         "slot permutation: build it with slot_perm=True")
    return tables


# Per-slot values cross the kernel boundary head-major and flat, ``(H,
# slots)`` in table order: a trailing axis of H (which XLA's batched gathers
# and scatters produce) would pad each slot to 128 lanes on a TPU. Inside
# the softmax they take the table's own shape, so that every row broadcast
# and row reduction follows the table and holds no per-slot index:
#
# * ELL: ``(H, rows, max_deg)``; a row op runs along the last axis.
# * SELL: ``(H, C, n_steps)``, steps on lanes. Step t's slots belong to the
#   rows ``slice_of[t] * C + j`` and ``slice_of`` is sorted, so per-row
#   values, ``(H, C, nslices)``, reach the slots by one index per step,
#   shared by every head and lane, and come back by a sorted segment
#   reduction over the steps. On a TPU the row ops and the moves between
#   the two layouts are the Pallas kernels of ``kernels/sell_rows.py``
#   (XLA's gathers and scatters along the steps would pad C or H·C to 128
#   lanes); elsewhere they are XLA's.
#
# The per-slot gather of ``s_src`` and the scatter of its gradient read the
# table's columns in that same order; the weights change layout only where
# they enter or leave the kernels.

def _per_head(fn, *xs):
    return jnp.stack([fn(*(x[h] for x in xs)) for h in range(xs[0].shape[0])])


def _take(x, idx, fill: bool = False):
    """``x[:, idx]`` per head; with ``fill`` indices past the end read 0."""
    kw = {"mode": "fill", "fill_value": 0} if fill else {}
    return _per_head(lambda v: jnp.take(v, idx, **kw), x)


def _pallas_rows(a, heads: int) -> bool:
    """Whether SELL table ``a``'s row ops and layout moves for ``heads``
    heads run the Pallas kernels: on a TPU, for a slice height and a
    per-row array they take."""
    return kops.on_tpu() and sell_rows.fits(a.c, heads * a.c, a.nslices)


def _slots(a, x):
    """``(H, slots)`` in table order to the table's shape."""
    h = x.shape[0]
    if isinstance(a, sp.ELL):
        return x.reshape(h, *a.idx.shape)
    if _pallas_rows(a, h):
        return sell_rows.to_steps(x, a.c, a.n_steps)
    return x.reshape(h, a.n_steps, a.c).transpose(0, 2, 1)


def _flat(a, x):
    """The table's shape back to ``(H, slots)`` in table order."""
    if isinstance(a, sp.ELL):
        return x.reshape(x.shape[0], -1)
    if _pallas_rows(a, x.shape[0]):
        return sell_rows.to_flat(x)
    return x.transpose(0, 2, 1).reshape(x.shape[0], -1)


def _cols(a):
    """Each slot's column (``ncols`` on pad slots), in the table's shape
    without the head axis."""
    return a.idx if isinstance(a, sp.ELL) else a.idx.T


def _rows(a, y):
    """``(H, rows)`` in the kernel's order (ELL rows, SELL's sorted rows)
    to the row ops' shape: ELL ``(H, rows, 1)``, SELL ``(H, C, nslices)``."""
    if isinstance(a, sp.ELL):
        return y[..., None]
    return y.reshape(y.shape[0], a.nslices, a.c).transpose(0, 2, 1)


def _unrows(a, y):
    """The row ops' shape back to ``(H, rows)`` in the kernel's order."""
    if isinstance(a, sp.ELL):
        return y[..., 0]
    return y.transpose(0, 2, 1).reshape(y.shape[0], -1)


def _count_row_indices(a):
    obs.metrics().counter("kernels.attention_row_indices").inc(
        0 if isinstance(a, sp.ELL) else a.n_steps)


def _row_bcast(a, y):
    """Per-row values, in :func:`_rows`'s shape, to each of the row's
    slots, in the table's shape."""
    _count_row_indices(a)
    if isinstance(a, sp.ELL):
        return jnp.broadcast_to(y, y.shape[:-1] + (a.max_deg,))
    h = y.shape[0]
    if _pallas_rows(a, h):
        out = sell_rows.row_bcast(y.reshape(h * a.c, a.nslices), a.slice_of,
                                  a.n_steps)
        return out.reshape(h, a.c, a.n_steps)
    return jnp.take(y, a.slice_of, axis=2, mode="clip",
                    indices_are_sorted=True)


def _row_reduce(a, x, kind: str):
    """The max or the sum of each row's slots, in :func:`_rows`'s shape."""
    _count_row_indices(a)
    if isinstance(a, sp.ELL):
        red = jnp.max if kind == "max" else jnp.sum
        return red(x, axis=-1, keepdims=True)
    h = x.shape[0]
    if _pallas_rows(a, h):
        out = sell_rows.row_reduce(x.reshape(h * a.c, a.n_steps), a.slice_of,
                                   a.nslices, kind)
        return out.reshape(h, a.c, a.nslices)
    seg = jax.ops.segment_max if kind == "max" else jax.ops.segment_sum
    out = seg(jnp.moveaxis(x, 2, 0), a.slice_of, num_segments=a.nslices,
              indices_are_sorted=True)
    return jnp.moveaxis(out, 0, 2)


def _node_rows(a, v):
    """``(H, n)`` in node order to the row ops' shape (SELL pad rows 0)."""
    return _rows(a, v if isinstance(a, sp.ELL) else _take(v, a.perm,
                                                           fill=True))


def _row_nodes(a, y):
    """The row ops' shape back to ``(H, n)`` in node order."""
    y = _unrows(a, y)
    return y if isinstance(a, sp.ELL) else _take(y, a.inv_perm)


def _slot_logits(a, s_dst, s_src):
    """LeakyReLU's input per slot: ``s_dst[row] + s_src[idx]``."""
    cols = _cols(a)
    src = _take(s_src, cols.reshape(-1), fill=True)
    return (_row_bcast(a, _node_rows(a, s_dst))
            + src.reshape(src.shape[:1] + cols.shape))


@jax.custom_vjp
def _gat_softmax(g, s_dst, s_src, z):
    """(α, carrier): the attention weights per slot of the plan's table,
    and a zero stand-in of z's shape. :func:`_gat_spmm` hands its output
    gradient back as the carrier's, so this rule's backward holds the
    gather-SDDMM of the weights' gradient with the softmax backward."""
    return _gat_softmax_fwd(g, s_dst, s_src, z)[0]


def _gat_softmax_fwd(g, s_dst, s_src, z):
    a, _ = _gather_tables(g)
    pre = _slot_logits(a, s_dst, s_src)
    e = jnp.where(_cols(a) < a.ncols, _leaky(pre), -jnp.inf)
    m = _row_reduce(a, e, "max")
    m = jnp.where(jnp.isfinite(m), m, 0.0)           # rows with no entries
    p = jnp.exp(e - _row_bcast(a, m))                # pad slots: 0
    den = _row_reduce(a, p, "sum")
    alpha = _flat(a, p / _row_bcast(a, jnp.where(den > 0, den, 1.0)))
    return (alpha, jnp.zeros_like(z)), (g, z, alpha, pre > 0)


def _gat_softmax_bwd(res, cts):
    g, z, alpha, pos = res
    d_alpha, dout = cts
    a, _ = _gather_tables(g)
    d_alpha = _slots(a, d_alpha + kops.gather_sddmm(a, dout, z,
                                                    heads=alpha.shape[0]))
    alpha = _slots(a, alpha)
    # softmax backward: dE = α (dα - Σ_row α dα); then LeakyReLU's slope
    de = alpha * (d_alpha - _row_bcast(a, _row_reduce(a, alpha * d_alpha,
                                                       "sum")))
    dpre = jnp.where(pos, de, NEGATIVE_SLOPE * de)
    ds_dst = _row_nodes(a, _row_reduce(a, dpre, "sum"))
    cols = _cols(a).reshape(-1)         # pad slots (== ncols) drop
    ds_src = _per_head(lambda v: jax.ops.segment_sum(
        v, cols, num_segments=a.ncols), dpre.reshape(dpre.shape[0], -1))
    return (jax.tree_util.tree_map(jnp.zeros_like, g), ds_dst, ds_src,
            jnp.zeros_like(z))


_gat_softmax.defvjp(_gat_softmax_fwd, _gat_softmax_bwd)


@jax.custom_vjp
def _gat_spmm(g, alpha, z, carrier):
    """The multi-head SpMM of ``z`` under the slot weights ``alpha``. Its
    backward runs the cached transpose (weights moved by the slot
    permutation) for dz, and passes the output gradient on as
    ``carrier``'s: :func:`_gat_softmax` turns it into the weights'."""
    a, _ = _gather_tables(g)
    return kops.gather_spmm_heads(a, alpha, z)


def _gat_spmm_fwd(g, alpha, z, carrier):
    return _gat_spmm(g, alpha, z, carrier), (g, alpha)


def _gat_spmm_bwd(res, dout):
    g, alpha = res
    _, a_t = _gather_tables(g)
    alpha_t = _take(alpha, g.slot_perm, fill=True)
    dz = kops.gather_spmm_heads(a_t, alpha_t, dout)
    return (jax.tree_util.tree_map(jnp.zeros_like, g),
            jnp.zeros_like(alpha), dz, dout)


_gat_spmm.defvjp(_gat_spmm_fwd, _gat_spmm_bwd)


def _coo_softmax(a: sp.COO, s_dst, s_src):
    valid = a.valid_mask()[None, :]
    e = jnp.where(valid, _leaky(s_dst[:, a.row] + s_src[:, a.col]), -jnp.inf)
    seg = lambda f, v: jax.vmap(  # noqa: E731
        lambda x: f(x, a.row, num_segments=a.nrows))(v)
    m = seg(jax.ops.segment_max, jax.lax.stop_gradient(e))
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.where(valid, jnp.exp(e - m[:, a.row]), 0.0)
    den = seg(jax.ops.segment_sum, p)
    return p / jnp.where(den > 0, den, 1.0)[:, a.row]


def _coo_aggregate(a: sp.COO, alpha, z):
    heads = alpha.shape[0]
    zc = z[a.col].reshape(a.nnz_padded, heads, -1)
    msgs = (alpha.T[..., None] * zc).reshape(a.nnz_padded, z.shape[1])
    return jax.ops.segment_sum(msgs, a.row, num_segments=a.nrows)


def gat_attention_coo(a: sp.COO, z: Array, s_dst: Array,
                      s_src: Array) -> Array:
    """The COO composition of :func:`gat_attention` under plain AD: the
    weights per stored entry, then the ``(nnz, K)`` messages and a
    segment sum."""
    alpha = stages.attention(_coo_softmax, a, s_dst, s_src)
    return stages.aggregate(_coo_aggregate, a, alpha, z)


def gat_attention(g: CachedGraph, z: Array, s_dst: Array,
                  s_src: Array) -> Array:
    """Multi-head GAT attention over the stored entries of ``g``:
    ``out[i, h] = Σ_j softmax_j(LeakyReLU(s_dst[h, i] + s_src[h, j]))
    z[j, h]``, with ``z`` ``(ncols, H·F)`` (head h's F lanes) and the
    scores head-major, ``(H, n)``. Differentiable in ``z``, ``s_dst`` and
    ``s_src``.

    The weights and their backward, with the SDDMM, run under the
    ``attention`` stage; the multi-head SpMM and its transpose under
    ``aggregate``. On an ELL or SELL plan both run the row-gather kernel
    over the packed tables, and each call (each trace, under ``jit``) adds
    its slots times heads to the counter ``kernels.attention_slots`` and
    the indices its row ops issue to ``kernels.attention_row_indices``;
    other plans take :func:`gat_attention_coo`."""
    tables = _gather_tables(g)
    if tables is None:
        return gat_attention_coo(g.coo, z, s_dst, s_src)
    obs.metrics().counter("kernels.attention_slots").inc(
        tables[0].idx.size * s_dst.shape[0])
    alpha, carrier = stages.attention(_gat_softmax, g, s_dst, s_src, z)
    return stages.aggregate(_gat_spmm, g, alpha, z, carrier)
