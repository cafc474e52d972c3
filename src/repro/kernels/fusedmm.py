"""FusedMM Pallas TPU kernel: SDDMM -> edge nonlinearity -> SpMM, fused.

iSpLib inherits FusedMM (Rahman et al., IPDPS'21): compute the per-edge score
and immediately consume it in the aggregation so the E-sized edge tensor is
never materialized. TPU translation: one grid step per adjacency tile,
sequential within a block row; the score tile lives only in VREGs, and the
row-softmax is computed *online* (flash-attention style running max /
denominator in VMEM scratch) because a block row's tiles arrive one by one.

Grid: ``(nblocks,)`` sorted by (block_row, block_col) — the same layout the
BSR SpMM kernel uses, so one CachedGraph serves both.

edge_op: 'softmax' (graph attention), 'sigmoid', 'none' (raw scores).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.sparse import BSR

__all__ = ["fusedmm_bsr_pallas"]

_NEG_INF = -1e30


def _kernel(blk_row_ref, blk_col_ref, x_ref, y_ref, a_ref, h_ref, out_ref,
            m_ref, z_ref, acc_ref, *, edge_op: str, nblocks: int):
    b = pl.program_id(0)
    row = blk_row_ref[b]
    is_first = jnp.logical_or(b == 0, blk_row_ref[jnp.maximum(b - 1, 0)] != row)
    is_last = jnp.logical_or(b == nblocks - 1,
                             blk_row_ref[jnp.minimum(b + 1, nblocks - 1)] != row)

    @pl.when(is_first)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        z_ref[...] = jnp.zeros_like(z_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    s = jax.lax.dot_general(
        x_ref[...], y_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)              # (br, bc)
    mask = a_ref[0] != 0

    if edge_op == "softmax":
        s = jnp.where(mask, s, _NEG_INF)
        m_prev = m_ref[:, :1]                              # (br, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)                    # exp(-1e30-(-1e30))=1 ok
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        z_ref[...] = z_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, h_ref[...], preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

        @pl.when(is_last)
        def _flush():
            out_ref[...] = acc_ref[...] / jnp.maximum(z_ref[:, :1], 1e-30)
    else:
        if edge_op == "sigmoid":
            w = jnp.where(mask, jax.nn.sigmoid(s), 0.0)
        else:  # 'none'
            w = jnp.where(mask, s, 0.0)
        acc_ref[...] += jnp.dot(w, h_ref[...],
                                preferred_element_type=jnp.float32,
                                precision=jax.lax.Precision.HIGHEST)

        @pl.when(is_last)
        def _flush2():
            out_ref[...] = acc_ref[...]


def fusedmm_bsr_pallas(a: BSR, x: jnp.ndarray, y: jnp.ndarray,
                       h: jnp.ndarray, *, edge_op: str = "softmax",
                       interpret: bool = False) -> jnp.ndarray:
    """out[i] = ⊕_j f(x_i·y_j) h_j over sparsity(a). Returns (nrows, K)."""
    assert edge_op in ("softmax", "sigmoid", "none"), edge_op
    d, k = x.shape[1], h.shape[1]
    d_pad, k_pad = (-d) % 128, (-k) % 128
    if d_pad:
        x = jnp.pad(x, ((0, 0), (0, d_pad)))
        y = jnp.pad(y, ((0, 0), (0, d_pad)))
    if k_pad:
        h = jnp.pad(h, ((0, 0), (0, k_pad)))
    if x.shape[0] != a.nrows:
        x = jnp.pad(x, ((0, a.nrows - x.shape[0]), (0, 0)))
    if y.shape[0] != a.ncols:
        y = jnp.pad(y, ((0, a.ncols - y.shape[0]), (0, 0)))
    if h.shape[0] != a.ncols:
        h = jnp.pad(h, ((0, a.ncols - h.shape[0]), (0, 0)))
    dp, kp = x.shape[1], h.shape[1]

    kernel = functools.partial(_kernel, edge_op=edge_op, nblocks=a.nblocks)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(a.nblocks,),
            in_specs=[
                pl.BlockSpec((a.br, dp), lambda b, br_, bc_: (br_[b], 0)),  # x
                pl.BlockSpec((a.bc, dp), lambda b, br_, bc_: (bc_[b], 0)),  # y
                pl.BlockSpec((1, a.br, a.bc), lambda b, br_, bc_: (b, 0, 0)),
                pl.BlockSpec((a.bc, kp), lambda b, br_, bc_: (bc_[b], 0)),  # h
            ],
            out_specs=pl.BlockSpec((a.br, kp), lambda b, br_, bc_: (br_[b], 0)),
            scratch_shapes=[
                pltpu.VMEM((a.br, 128), jnp.float32),   # running max
                pltpu.VMEM((a.br, 128), jnp.float32),   # running denom
                pltpu.VMEM((a.br, kp), jnp.float32),    # accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((a.nrows, kp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(a.blk_row, a.blk_col, x, y, a.blocks, h)

    return out[:, :k] if k_pad else out
