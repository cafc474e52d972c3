"""Pallas kernel sweeps (interpret mode) against the pure-jnp oracles:
shapes x dtypes per kernel, per the deliverable."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core as C
from repro.kernels import ops as kops
from repro.kernels.ref import (bsr_spmm_ref, fusedmm_softmax_ref,
                               sddmm_bsr_ref, spmm_ell_ref,
                               flash_attention_ref)
from conftest import random_coo


@pytest.mark.parametrize("br,bc,fk", [(8, 128, 128), (16, 128, 256),
                                      (32, 256, 128)])
@pytest.mark.parametrize("k", [64, 128, 200])
def test_bsr_spmm_sweep(rng, br, bc, fk, k):
    coo, dense = random_coo(rng, 150, 140, 1200)
    bsr = C.bsr_from_coo(coo, br=br, bc=bc)
    h = jnp.asarray(rng.standard_normal((bsr.ncols, k)).astype(np.float32))
    out = kops.bsr_spmm(bsr, h, fk=fk, interpret=True)
    ref = bsr_spmm_ref(bsr, h)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_bsr_spmm_dtypes(rng, dtype):
    coo, dense = random_coo(rng, 80, 80, 600)
    bsr = C.bsr_from_coo(coo, br=8, bc=128)
    h = jnp.asarray(rng.standard_normal((bsr.ncols, 128))).astype(dtype)
    out = kops.bsr_spmm(bsr, h, fk=128, interpret=True)
    ref = bsr_spmm_ref(bsr, h.astype(jnp.float32))
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out, dtype=np.float32),
                               np.asarray(ref), rtol=tol, atol=tol)


@pytest.mark.parametrize("k", [32, 128])
@pytest.mark.parametrize("max_deg_cap", [None, 4])
def test_ell_spmm_sweep(rng, k, max_deg_cap):
    coo, dense = random_coo(rng, 60, 50, 300)
    ell = C.ell_from_coo(coo, max_deg=max_deg_cap)
    h = jnp.asarray(rng.standard_normal((50, k)).astype(np.float32))
    out = kops.ell_spmm(ell, h, interpret=True)
    ref = spmm_ell_ref(ell, h, C.get_semiring("sum"))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("c,sigma", [(8, 0), (8, 16), (16, 0), (32, 0)])
@pytest.mark.parametrize("k", [32, 128])
def test_sell_spmm_sweep(rng, c, sigma, k):
    """Interpret-mode Pallas body vs the COO oracle — exercises the packed
    layout, the per-slice zero-init, and the inverse row permutation."""
    coo, dense = random_coo(rng, 60, 50, 300)
    sell = C.sell_from_coo(coo, c=c, sigma=sigma)
    h = jnp.asarray(rng.standard_normal((50, k)).astype(np.float32))
    out = kops.sell_spmm(sell, h, interpret=True)
    ref = np.asarray(dense) @ np.asarray(h)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-4)
    # and the XLA dispatch path (what CPU serves)
    out_xla = kops.sell_spmm(sell, h, interpret=None)
    np.testing.assert_allclose(np.asarray(out_xla), ref, rtol=1e-4,
                               atol=1e-4)


def test_sell_spmm_skewed_degrees(rng):
    """Power-law-ish rows (one hub row + sparse tail): the exact regime
    where ELL max-degree padding explodes; SELL numerics must be exact."""
    n, m = 64, 64
    src = np.concatenate([rng.integers(0, m, 60),          # hub row 0
                          rng.integers(0, m, 40)])
    dst = np.concatenate([np.zeros(60, np.int64),
                          rng.integers(1, n, 40)])
    uniq = np.unique(np.stack([dst, src], 1), axis=0)
    dst, src = uniq[:, 0], uniq[:, 1]
    val = rng.standard_normal(len(dst)).astype(np.float32)
    coo = C.coo_from_edges(src, dst, val, n, m)
    dense = np.zeros((n, m), np.float32)
    dense[dst, src] = val
    sell = C.sell_from_coo(coo, c=8)
    # packed slots must be far below the ELL footprint nrows * max_deg
    max_deg = int((dense != 0).sum(1).max())
    assert sell.n_steps * sell.c < n * max_deg / 4
    h = jnp.asarray(rng.standard_normal((m, 128)).astype(np.float32))
    out = kops.sell_spmm(sell, h, interpret=True)
    np.testing.assert_allclose(np.asarray(out), dense @ np.asarray(h),
                               rtol=1e-4, atol=1e-4)


def test_sell_spmm_zero_degree_rows_and_empty(rng):
    # zero-degree rows must come back exactly 0 after the inverse perm
    coo = C.coo_from_edges(np.array([1, 2]), np.array([3, 3]),
                           np.array([2.0, 3.0], np.float32), 6, 6)
    sell = C.sell_from_coo(coo, c=4)
    h = jnp.asarray(np.eye(6, dtype=np.float32))
    out = np.asarray(kops.sell_spmm(sell, h, interpret=True))
    assert (out[[0, 1, 2, 4, 5]] == 0).all()
    assert out[3, 1] == 2.0 and out[3, 2] == 3.0
    # empty graph: every slice still has its >= 1 zero-init step
    empty = C.coo_from_edges(np.zeros(0, np.int64), np.zeros(0, np.int64),
                             None, 5, 5, pad_to=0)
    sell_e = C.sell_from_coo(empty, c=8)
    out_e = kops.sell_spmm(sell_e, jnp.ones((5, 8), jnp.float32),
                           interpret=True)
    assert np.asarray(out_e).shape == (5, 8)
    assert (np.asarray(out_e) == 0).all()


@pytest.mark.parametrize("fmt", ["ell", "sell8", "sell4"])
def test_pallas_gather_spmm_grad(rng, fmt):
    """Pallas calls have no autodiff rule: the ELL/SELL dispatch carries
    its own VJP, and dH must be A^T @ dOut (sampled blocks rely on it)."""
    coo, dense = random_coo(rng, 37, 29, 300)
    if fmt == "ell":
        a, op = C.ell_from_coo(coo), kops.ell_spmm
    else:
        a, op = C.sell_from_coo(coo, c=int(fmt[4:])), kops.sell_spmm
    h = jnp.asarray(rng.standard_normal((29, 40)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((37, 40)).astype(np.float32))
    g = jax.jit(jax.grad(
        lambda hh: jnp.sum(op(a, hh, interpret=True) * w)))(h)
    np.testing.assert_allclose(np.asarray(g), dense.T @ np.asarray(w),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("d", [16, 64, 130])
@pytest.mark.parametrize("scale_by_a", [True, False])
def test_sddmm_sweep(rng, d, scale_by_a):
    coo, dense = random_coo(rng, 100, 90, 700)
    bsr = C.bsr_from_coo(coo, br=16, bc=128)
    x = jnp.asarray(rng.standard_normal((bsr.nrows, d)).astype(np.float32))
    y = jnp.asarray(rng.standard_normal((bsr.ncols, d)).astype(np.float32))
    out = kops.sddmm_bsr(bsr, x, y, scale_by_a=scale_by_a, interpret=True)
    ref = sddmm_bsr_ref(bsr, x, y, scale_by_a=scale_by_a)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-3,
                               atol=1e-3)


@pytest.mark.parametrize("edge_op", ["softmax", "sigmoid", "none"])
def test_fusedmm_kernel(rng, edge_op):
    coo, dense = random_coo(rng, 90, 80, 600)
    bsr = C.bsr_from_coo(coo, br=16, bc=128)
    x = jnp.asarray(rng.standard_normal((bsr.nrows, 32)).astype(np.float32))
    y = jnp.asarray(rng.standard_normal((bsr.ncols, 32)).astype(np.float32))
    h = jnp.asarray(rng.standard_normal((bsr.ncols, 64)).astype(np.float32))
    out = kops.fusedmm_bsr(bsr, x, y, h, edge_op=edge_op, interpret=True)
    if edge_op == "softmax":
        ref = fusedmm_softmax_ref(bsr, x, y, h)[: bsr.nrows]
    else:
        ref = kops.fusedmm_bsr(bsr, x, y, h, edge_op=edge_op, interpret=None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-3,
                               atol=1e-3)


@pytest.mark.parametrize("e,t,dm,f", [(4, 512, 128, 256), (2, 256, 256, 128)])
def test_ragged_gemm_sweep(rng, e, t, dm, f):
    from repro.kernels.ragged_gemm import ragged_gemm_pallas
    tm = 128
    x = jnp.asarray(rng.standard_normal((t, dm)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((e, dm, f)).astype(np.float32))
    te = jnp.asarray(rng.integers(0, e, t // tm).astype(np.int32))
    out = ragged_gemm_pallas(x, w, te, tm=tm, interpret=True)
    ref = jnp.concatenate(
        [x.reshape(-1, tm, dm)[i] @ w[te[i]] for i in range(t // tm)])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (8, 1)])
@pytest.mark.parametrize("window", [None, 96])
def test_flash_attention_sweep(rng, hq, hkv, window):
    from repro.kernels.flash_attention import flash_attention_pallas
    B, S, D = 2, 256, 64
    q = jnp.asarray(rng.standard_normal((B, hq, S, D)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((B, hkv, S, D)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((B, hkv, S, D)).astype(np.float32))
    out = flash_attention_pallas(q, k, v, causal=True, window=window,
                                 bq=128, bk=128, interpret=True)
    ref = flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4,
                               atol=1e-4)


def test_flash_attention_decode_tail(rng):
    from repro.kernels.flash_attention import flash_attention_pallas
    B, H, S, T, D = 1, 2, 128, 384, 64
    q = jnp.asarray(rng.standard_normal((B, H, S, D)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((B, H, T, D)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((B, H, T, D)).astype(np.float32))
    out = flash_attention_pallas(q, k, v, causal=True, bq=128, bk=128,
                                 interpret=True)
    ref = flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4,
                               atol=1e-4)
