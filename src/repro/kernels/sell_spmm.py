"""SELL-C-σ SpMM Pallas TPU kernel — the degree-sorted sliced gather path.

Why ELL loses on skewed graphs: it pays the GLOBAL max degree for every
row. SELL-C-σ fixes that structurally: rows are degree-sorted within σ
windows, grouped into slices of C rows, and each slice is padded only to
its own max degree. The packed layout (see :class:`repro.core.sparse.SELL`)
stores one (C,) lane-bundle per (slice, degree-position), so the total
step count is ``n_steps = Σ_s max_deg_s`` — for power-law graphs orders of
magnitude below ``nrows · max_deg``.

The kernel is the shared row-gather kernel (``gather_spmm.py``) with one
grid step per slice: slice ``s`` owns the elements of steps
``[slice_ptr[s], slice_ptr[s + 1])`` and element ``e`` of the slice lands
in sorted row ``e % C`` of its ``(C, K)`` output tile. Only ``slice_ptr``
is scalar-prefetched (``nslices + 1`` int32), so SMEM holds one word per
C rows however many edges the graph has.

Sentinel convention: pad slots have ``idx == ncols`` and ``val == 0``
(sum semiring only, faithful to the paper's "only sum has
generated-kernel support"). The wrapper applies ``inv_perm`` on the way
out to undo the degree sort.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.sparse import SELL
from repro.kernels.gather_spmm import gather_spmm_pallas

__all__ = ["sell_spmm_pallas"]


def sell_spmm_pallas(a: SELL, h: jnp.ndarray, *, interpret: bool = False
                     ) -> jnp.ndarray:
    """Sum-semiring SpMM: (a.nrows, K) = a @ h via packed sliced gathers."""
    assert h.shape[0] == a.ncols, (h.shape, a.shape)
    out = gather_spmm_pallas(a.slice_ptr * a.c, a.idx, a.val, h,
                             ncols=a.ncols, seg_rows=a.c,
                             interpret=interpret)
    return out[a.inv_perm]                  # undo the degree sort
