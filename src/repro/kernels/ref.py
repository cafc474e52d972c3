"""Pure-jnp oracles for every kernel in this package.

These are the ground truth for correctness tests (Pallas kernels are swept
against them in interpret mode) AND the production "trusted" path — the
paper's terminology for the generic kernel that handles any (K, semiring,
sparsity) point the generated kernels don't cover.
"""
from __future__ import annotations

from typing import TYPE_CHECKING

import jax
import jax.numpy as jnp

if TYPE_CHECKING:  # annotation-only: avoids core<->kernels circular import
    from repro.core.semiring import Semiring
    from repro.core.sparse import BSR, COO, ELL

__all__ = [
    "spmm_coo_ref",
    "spmm_dense_ref",
    "spmm_ell_ref",
    "bsr_spmm_ref",
    "flash_attention_ref",
]


# --------------------------------------------------------------------------
# SpMM
# --------------------------------------------------------------------------

def spmm_coo_ref(a: COO, h: jnp.ndarray, sr: Semiring, degrees=None) -> jnp.ndarray:
    """out[i] = ⊕_{(i,j) in A} A_ij ⊗ h[j]  — XLA segment-op path."""
    msgs = sr.apply_combine(a.val[:, None], h[a.col])  # (nnz, K)
    if sr.reduce in ("max", "min"):
        fill = jnp.asarray(sr.identity, msgs.dtype)
        msgs = jnp.where(a.valid_mask()[:, None], msgs, fill)
    out = sr.segment_reduce(msgs, a.row, a.nrows)
    return sr.finalize(out, degrees)


def spmm_dense_ref(a_dense: jnp.ndarray, h: jnp.ndarray, sr: Semiring,
                   degrees=None) -> jnp.ndarray:
    """Densified oracle (small shapes only)."""
    mask = a_dense != 0
    msg = sr.apply_combine(a_dense[:, :, None], h[None, :, :])  # (N, M, K)
    if sr.reduce in ("sum", "mean"):
        out = jnp.where(mask[:, :, None], msg, 0).sum(axis=1)
    elif sr.reduce == "max":
        out = jnp.where(mask[:, :, None], msg, -jnp.inf).max(axis=1)
    else:
        out = jnp.where(mask[:, :, None], msg, jnp.inf).min(axis=1)
    if degrees is None and sr.reduce == "mean":
        degrees = mask.sum(axis=1).astype(h.dtype)
    return sr.finalize(out, degrees)


def spmm_ell_ref(a: ELL, h: jnp.ndarray, sr: Semiring, degrees=None) -> jnp.ndarray:
    gathered = jnp.take(h, a.idx, axis=0, mode="fill", fill_value=0)  # (N, D, K)
    msg = sr.apply_combine(a.val[:, :, None], gathered)
    valid = a.pad_mask()[:, :, None]
    if sr.reduce in ("sum", "mean"):
        out = jnp.where(valid, msg, 0).sum(axis=1)
    elif sr.reduce == "max":
        out = jnp.where(valid, msg, -jnp.inf).max(axis=1)
    else:
        out = jnp.where(valid, msg, jnp.inf).min(axis=1)
    return sr.finalize(out, degrees)


def bsr_spmm_ref(a: BSR, h: jnp.ndarray, scale=None) -> jnp.ndarray:
    """Sum-semiring block-sparse oracle: loops blocks with dense matmuls.
    ``scale``: optional per-row post-scale (mean semiring / GCN norm)."""
    n_bk = h.shape[1]
    out = jnp.zeros((a.nrows, n_bk), jnp.promote_types(a.blocks.dtype, h.dtype))

    def step(i, out):
        hblk = jax.lax.dynamic_slice(h, (a.blk_col[i] * a.bc, 0), (a.bc, n_bk))
        contrib = a.blocks[i] @ hblk
        r = a.blk_row[i] * a.br
        cur = jax.lax.dynamic_slice(out, (r, 0), (a.br, n_bk))
        return jax.lax.dynamic_update_slice(out, cur + contrib, (r, 0))

    out = jax.lax.fori_loop(0, a.nblocks, step, out)
    if scale is not None:
        out = out * scale[:, None]
    return out


# --------------------------------------------------------------------------
# Dense flash-attention oracle (LM side; causal / sliding-window)
# --------------------------------------------------------------------------

def flash_attention_ref(q, k, v, *, causal: bool = True, window: int | None = None,
                        scale: float | None = None) -> jnp.ndarray:
    """q: (B, Hq, S, D), k/v: (B, Hkv, T, D). GQA by head repetition."""
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    if hq != hkv:
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    scale = scale if scale is not None else 1.0 / jnp.sqrt(d).astype(q.dtype)
    logits = jnp.einsum("bhsd,bhtd->bhst", q, k) * scale
    t = k.shape[2]
    qpos = jnp.arange(s)[:, None] + (t - s)   # align ends (decode-friendly)
    kpos = jnp.arange(t)[None, :]
    mask = jnp.ones((s, t), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    logits = jnp.where(mask[None, None], logits, -jnp.inf)
    w = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhst,bhtd->bhsd", w, v)
