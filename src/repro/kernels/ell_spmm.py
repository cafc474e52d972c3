"""ELLPACK SpMM Pallas TPU kernel — the gather-path "generated" kernel.

For very sparse, near-regular-degree graphs (and fanout-capped sampled
blocks) the BSR tiles are mostly empty and the MXU wastes its cycles on
zeros; the winning layout is per-row padded neighbor lists (ELL). The
kernel is the shared row-gather kernel (``gather_spmm.py``) over tiles of
8 consecutive rows: tile ``t`` owns the ``8 * max_deg`` elements of rows
``[8t, 8t + 8)``, so its output is one full-sublane ``(8, K)`` tile.

Sentinel convention: pad slots have ``idx == ncols`` and ``val == 0``;
they gather the zero row the shared wrapper appends to H (sum semiring
only — faithful to the paper's "only sum has generated-kernel support").
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.sparse import ELL
from repro.kernels.gather_spmm import gather_spmm_pallas

__all__ = ["ell_spmm_pallas", "ell_ptr"]

_TILE_ROWS = 8


def ell_ptr(a: ELL) -> np.ndarray:
    """Element offsets of ``a``'s 8-row tiles in its row-padded table."""
    ntiles = -(-max(a.nrows, 1) // _TILE_ROWS)
    return np.arange(ntiles + 1, dtype=np.int32) * (_TILE_ROWS * a.max_deg)


def ell_spmm_pallas(a: ELL, h: jnp.ndarray, *, interpret: bool = False
                    ) -> jnp.ndarray:
    """Sum-semiring SpMM: (a.nrows, K) = a @ h via row gathers."""
    assert h.shape[0] == a.ncols, (h.shape, a.shape)
    ptr = ell_ptr(a)
    pad = ((0, (len(ptr) - 1) * _TILE_ROWS - a.nrows), (0, 0))
    idx = jnp.pad(a.idx, pad, constant_values=a.ncols)
    val = jnp.pad(a.val, pad)
    out = gather_spmm_pallas(jnp.asarray(ptr), idx, val, h, ncols=a.ncols,
                             row_div=a.max_deg, interpret=interpret)
    return out[: a.nrows]
