"""Block-sparse-row SpMM Pallas TPU kernel — the "generated kernel" of this
repo (iSpLib §3.2 adapted to TPU).

Design
------
The adjacency is stored as dense Br x Bc tiles sorted by (block_row,
block_col). The grid is ``(k_tiles, nblocks)`` with the block dimension
innermost and sequential ("arbitrary") so consecutive grid steps that target
the same output row-tile keep the accumulator resident in VMEM (Pallas'
revisiting rule); the K dimension is "parallel". Tile indices are delivered
through scalar prefetch (SMEM) so the BlockSpec index maps can route HBM->VMEM
copies of exactly the A-tile and H-tile needed per step — the TPU equivalent
of iSpLib's register blocking: the MXU consumes (Br x Bc) @ (Bc x Fk) tiles
while the next tiles stream in.

Zero-initialisation happens on the first block of each block row (BSR
construction guarantees every block row owns >= 1 block). Padding blocks
replicate the last row with zero data, so they accumulate nothing.

Only the sum semiring is implemented here — faithful to the paper ("only the
sum reduction operation has the generated kernel support"); mean is a cached
inverse-degree post-scale in ops.py, min/max take the trusted XLA path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.sparse import BSR

__all__ = ["bsr_spmm_pallas"]


def _kernel(blk_row_ref, blk_col_ref, blocks_ref, h_ref, out_ref, *, acc_dtype):
    del blk_col_ref  # consumed by the index maps only
    b = pl.program_id(1)
    prev = blk_row_ref[jnp.maximum(b - 1, 0)]
    is_first = jnp.logical_or(b == 0, blk_row_ref[b] != prev)

    @pl.when(is_first)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # HIGHEST: an f32 dot at default precision rounds its inputs to bf16
    # on the MXU — measured ~1e4x over the f32 summation bound on a v5e
    out_ref[...] += jnp.dot(
        blocks_ref[0], h_ref[...], preferred_element_type=acc_dtype,
        precision=jax.lax.Precision.HIGHEST)


def bsr_spmm_pallas(a: BSR, h: jnp.ndarray, *, fk: int = 256,
                    acc_dtype=jnp.float32, interpret: bool = False) -> jnp.ndarray:
    """Sum-semiring SpMM: (a.nrows, K) = a @ h.

    ``h`` must have a.ncols rows; K is padded to a multiple of ``fk`` here and
    cropped on return.
    """
    assert h.shape[0] == a.ncols, (h.shape, a.shape)
    k = h.shape[1]
    assert fk % 128 == 0, "K tile must be a lane multiple"
    fk = min(fk, ((k + 127) // 128) * 128)  # never exceed K rounded to lanes
    k_pad = (-k) % fk
    if k_pad:
        h = jnp.pad(h, ((0, 0), (0, k_pad)))
    kp = h.shape[1]
    k_tiles = kp // fk

    grid = (k_tiles, a.nblocks)
    kernel = functools.partial(_kernel, acc_dtype=acc_dtype)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, a.br, a.bc), lambda kt, b, br_, bc_: (b, 0, 0)),
                pl.BlockSpec((a.bc, fk), lambda kt, b, br_, bc_: (bc_[b], kt)),
            ],
            out_specs=pl.BlockSpec((a.br, fk),
                                   lambda kt, b, br_, bc_: (br_[b], kt)),
        ),
        out_shape=jax.ShapeDtypeStruct((a.nrows, kp), acc_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(a.blk_row, a.blk_col, a.blocks, h)

    if k_pad:
        out = out[:, :k]
    return out
