"""Baseline (PyTorch-2.1-equivalent) sparse paths the paper compares against.

The paper's Fig. 3 baselines are PyTorch/PyG sparse CPU training: per-step
normalization, per-backward transpose (csr2csc), no kernel specialization.
Re-created here in JAX so speedups are measured against a *fair, same-
framework* opponent (DESIGN.md §7):

* ``spmm_uncached``            — trusted kernel + plain JAX AD. No CachedGraph
  reuse, but JAX's scatter-add backward is already transpose-free; this is a
  *stronger* baseline than PyTorch's.
* ``spmm_uncached_transpose``  — additionally pays the per-backward explicit
  transpose build (argsort + reindex on device), which is what
  pytorch_sparse's csr2csc does when the cache is cold. This is the
  PT-equivalent cost model.
* ``gcn_norm_in_step``         — D^-1/2 (A+I) D^-1/2 recomputed per forward
  (the uncached normalization the paper's §3.3 removes).

Both baselines take the same COO the tuned path's CachedGraph wraps, so
accuracy is bit-comparable.
"""
from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.semiring import Semiring, get_semiring
from repro.core import sparse as sp
from repro.kernels.ref import spmm_coo_ref

Array = Any

__all__ = ["spmm_uncached", "spmm_uncached_transpose", "gcn_norm_in_step",
           "gat_attention_uncached"]


def _as_coo(a) -> sp.COO:
    from repro.core.cache import CachedGraph
    if isinstance(a, CachedGraph):
        return a.coo
    if isinstance(a, sp.CSR):
        return a.to_coo()
    assert isinstance(a, sp.COO), type(a)
    return a


def spmm_uncached(a, h: Array, reduce: str = "sum", combine: str = "mul"
                  ) -> Array:
    """Trusted path, plain JAX AD, degrees recomputed per call."""
    coo = _as_coo(a)
    sr = get_semiring(reduce, combine)
    deg = None
    if reduce == "mean":
        deg = jax.ops.segment_sum(
            jnp.where(coo.valid_mask(), 1.0, 0.0), coo.row,
            num_segments=coo.nrows)          # recomputed EVERY call (uncached)
    return spmm_coo_ref(coo, h, sr, degrees=deg)


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def _spmm_t(a: sp.COO, h: Array, reduce: str) -> Array:
    sr = get_semiring(reduce)
    deg = None
    if reduce == "mean":
        deg = jax.ops.segment_sum(
            jnp.where(a.valid_mask(), 1.0, 0.0), a.row, num_segments=a.nrows)
    return spmm_coo_ref(a, h, sr, degrees=deg)


def _spmm_t_fwd(a, h, reduce):
    return _spmm_t(a, h, reduce), (a,)


def _spmm_t_bwd(reduce, res, dy):
    (a,) = res
    # EXPLICIT per-backward transpose: sort edges by (col, row) — the
    # csr2csc cost pytorch_sparse pays when nothing is cached.
    order = jnp.lexsort((a.row, a.col))
    row_t, col_t, val_t = a.col[order], a.row[order], a.val[order]
    if reduce == "mean":
        deg = jax.ops.segment_sum(
            jnp.where(a.valid_mask(), 1.0, 0.0), a.row, num_segments=a.nrows)
        dy = dy * (1.0 / jnp.maximum(deg, 1.0))[:, None]
    msgs = val_t[:, None] * dy[col_t]
    dh = jax.ops.segment_sum(msgs, row_t, num_segments=a.ncols)
    da = jax.tree_util.tree_map(jnp.zeros_like, a)
    return da, dh


_spmm_t.defvjp(_spmm_t_fwd, _spmm_t_bwd)


def spmm_uncached_transpose(a, h: Array, reduce: str = "sum") -> Array:
    """PT-equivalent: backward rebuilds A^T (argsort) every step."""
    assert reduce in ("sum", "mean"), "transpose baseline: linear reductions"
    return _spmm_t(_as_coo(a), h, reduce)


def gcn_norm_in_step(a, add_self_loops: bool = True) -> sp.COO:
    """Symmetric GCN normalization executed INSIDE the step (uncached
    baseline). Self-loops must be pre-added structurally (static nse); when
    ``add_self_loops`` the input is expected to already contain them and this
    recomputes only the degree scaling — matching PyG's gcn_norm cost."""
    coo = _as_coo(a)
    val = jnp.where(coo.valid_mask(), coo.val, 0.0)
    deg = jax.ops.segment_sum(val, coo.row, num_segments=coo.nrows)
    dinv = jax.lax.rsqrt(jnp.maximum(deg, 1e-12))
    new_val = dinv[coo.row] * val * dinv[jnp.minimum(coo.col, coo.nrows - 1)]
    return coo.with_values(new_val)


def gat_attention_uncached(a, z: Array, s_dst: Array, s_src: Array
                           ) -> Array:
    """Multi-head GAT attention as the unfused COO composition (the
    ``(nnz, K)`` message tensor materialized), plain JAX AD."""
    from repro.core.fusedmm import gat_attention_coo
    return gat_attention_coo(_as_coo(a), z, s_dst, s_src)
