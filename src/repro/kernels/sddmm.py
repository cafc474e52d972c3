"""Block SDDMM Pallas TPU kernel:  S_b = X[row_b] @ Y[col_b]^T (* A_b).

Each grid step computes one (Br x Bc) score tile with a single MXU matmul;
scalar-prefetched block coordinates route the X / Y operand tiles. The edge
scores never exist outside their tile — the downstream consumer is either
the caller (explicit SDDMM, returns block scores) or the fused kernel in
``fusedmm.py`` (scores never reach HBM at all).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.sparse import BSR

__all__ = ["sddmm_bsr_pallas"]


def _kernel(blk_row_ref, blk_col_ref, x_ref, y_ref, a_ref, out_ref, *,
            scale_by_a: bool):
    del blk_row_ref, blk_col_ref
    s = jax.lax.dot_general(
        x_ref[...], y_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)     # f32-exact, as bsr_spmm
    if scale_by_a:
        s = s * a_ref[0]
    out_ref[0, ...] = s


def sddmm_bsr_pallas(a: BSR, x: jnp.ndarray, y: jnp.ndarray, *,
                     scale_by_a: bool = True,
                     interpret: bool = False) -> jnp.ndarray:
    """x: (a.nrows, D), y: (a.ncols, D) -> (nblocks, br, bc) scores."""
    d = x.shape[1]
    d_pad = (-d) % 128
    if d_pad:
        x = jnp.pad(x, ((0, 0), (0, d_pad)))
        y = jnp.pad(y, ((0, 0), (0, d_pad)))
    if x.shape[0] != a.nrows:
        x = jnp.pad(x, ((0, a.nrows - x.shape[0]), (0, 0)))
    if y.shape[0] != a.ncols:
        y = jnp.pad(y, ((0, a.ncols - y.shape[0]), (0, 0)))
    dp = x.shape[1]

    kernel = functools.partial(_kernel, scale_by_a=scale_by_a)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(a.nblocks,),
            in_specs=[
                pl.BlockSpec((a.br, dp), lambda b, br_, bc_: (br_[b], 0)),
                pl.BlockSpec((a.bc, dp), lambda b, br_, bc_: (bc_[b], 0)),
                pl.BlockSpec((1, a.br, a.bc), lambda b, br_, bc_: (b, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, a.br, a.bc),
                                   lambda b, br_, bc_: (b, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((a.nblocks, a.br, a.bc), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(a.blk_row, a.blk_col, x, y, a.blocks)
