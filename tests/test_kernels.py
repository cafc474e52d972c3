"""Pallas kernel sweeps (interpret mode) against the pure-jnp oracles:
shapes x dtypes per kernel, per the deliverable."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core as C
from repro.kernels import ops as kops
from repro.kernels.ref import bsr_spmm_ref, spmm_ell_ref, flash_attention_ref
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


@pytest.mark.parametrize("fmt", ["ell", "sell8", "sell4", "ell-700",
                                 "sell8-700"])
def test_pallas_gather_spmm_grad(rng, fmt):
    """Pallas calls have no autodiff rule: the ELL/SELL dispatch carries
    its own VJP, and dH must be A^T @ dOut (sampled blocks rely on it).
    At 700 rows the kernel groups 64 tiles per grid step over 2 steps."""
    fmt, _, rows = fmt.partition("-")
    n = int(rows or 37)
    coo, dense = random_coo(rng, n, 29, 300 if n == 37 else 8 * n)
    if fmt == "ell":
        a, op = C.ell_from_coo(coo), kops.ell_spmm
    else:
        a, op = C.sell_from_coo(coo, c=int(fmt[4:])), kops.sell_spmm
    h = jnp.asarray(rng.standard_normal((29, 40)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((n, 40)).astype(np.float32))
    g = jax.jit(jax.grad(
        lambda hh: jnp.sum(op(a, hh, interpret=True) * w)))(h)
    np.testing.assert_allclose(np.asarray(g), dense.T @ np.asarray(w),
                               rtol=1e-4, atol=1e-4)


def _element_rows(ptr, *, row_div=0, seg_rows=0):
    """The gather kernel's contract: element ``e`` of segment ``s`` goes
    to row ``s * R + (e - ptr[s]) // row_div`` (ELL, R = 8) or ``s * C +
    (e - ptr[s]) % C`` (SELL)."""
    seg = np.repeat(np.arange(len(ptr) - 1), np.diff(ptr))
    off = np.arange(ptr[-1]) - ptr[seg]
    return seg * (seg_rows or 8) + (off // row_div if row_div
                                    else off % seg_rows)


def _zero_row(h):
    return np.concatenate([np.asarray(h, np.float64),
                           np.zeros((1, h.shape[1]))])


def _segment_oracle(ptr, idx, val, h, *, row_div=0, seg_rows=0):
    """Dense sums of ``val * h[idx]`` per output row."""
    out = np.zeros(((len(ptr) - 1) * (seg_rows or 8), h.shape[1]))
    np.add.at(out, _element_rows(ptr, row_div=row_div, seg_rows=seg_rows),
              np.asarray(val, np.float64)[:, None] * _zero_row(h)[idx])
    return out


def _assert_sddmm_mode(out, rows, idx, dout, h, heads):
    """The SDDMM mode's ``(heads, elements)`` against ``dot(dout[row(e)],
    h[idx[e]])`` per element and head over the head's lanes, exactly 0
    on pad elements (``idx == ncols``)."""
    f = h.shape[1] // heads
    d = np.asarray(dout, np.float64)[rows].reshape(-1, heads, f)
    want = (d * _zero_row(h)[idx].reshape(-1, heads, f)).sum(-1).T
    out = np.asarray(out).reshape(heads, -1)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-4)
    assert (out[:, idx == h.shape[0]] == 0).all()


def _segment_table(rng, sizes, ncols):
    ptr = np.concatenate([[0], np.cumsum(sizes)]).astype(np.int32)
    idx = rng.integers(0, ncols + 1, ptr[-1]).astype(np.int32)  # + pads
    val = rng.standard_normal(ptr[-1]).astype(np.float32)
    val[idx == ncols] = 0.0
    return ptr, idx, val


# tiles of exactly 0, 1, 127, 128, 129 and 256 elements; a hub slice over
# many chunks between empty slices, ending in a partial tile; 150 slices
# grouped 64 tiles per grid step (3 steps); C=4 slices paired into tiles
# with an odd slice count
_SELL_CASES = {
    "edges": (8, [0, 1, 127, 128, 129, 256]),
    "hub": (8, [0, 0, 1500, 0, 0, 3]),
    "grouped": (8, list(np.random.default_rng(1).integers(0, 60, 150))),
    "c4": (4, [5, 0, 130, 127, 1]),
}


@pytest.mark.parametrize("mode", ["spmm", "sddmm"])
@pytest.mark.parametrize("k", [41, 128, 256])
@pytest.mark.parametrize("case", sorted(_SELL_CASES))
def test_gather_spmm_sell_boundaries(rng, case, k, mode):
    """The pipelined row-gather kernel, in its SpMM and its SDDMM mode
    (1 head at K=41, else 4), against dense oracles on SELL segment sizes
    at the chunk and grid-step boundaries."""
    from repro.kernels.gather_spmm import (gather_sddmm_pallas,
                                           gather_spmm_pallas)
    c, sizes = _SELL_CASES[case]
    ncols = 90
    ptr, idx, val = _segment_table(rng, sizes, ncols)
    h = rng.standard_normal((ncols, k)).astype(np.float32)
    if mode == "spmm":
        out = gather_spmm_pallas(jnp.asarray(ptr), jnp.asarray(idx),
                                 jnp.asarray(val), jnp.asarray(h),
                                 ncols=ncols, seg_rows=c, interpret=True)
        np.testing.assert_allclose(
            np.asarray(out), _segment_oracle(ptr, idx, val, h, seg_rows=c),
            rtol=1e-5, atol=1e-4)
        return
    heads = 1 if k == 41 else 4
    dout = rng.standard_normal(((len(ptr) - 1) * c, k)).astype(np.float32)
    out = gather_sddmm_pallas(jnp.asarray(ptr), jnp.asarray(idx),
                              jnp.asarray(dout), jnp.asarray(h), ncols=ncols,
                              seg_rows=c, heads=heads, interpret=True)
    _assert_sddmm_mode(out, _element_rows(ptr, seg_rows=c), idx, dout, h, heads)


@pytest.mark.parametrize("mode", ["spmm", "sddmm"])
@pytest.mark.parametrize("k", [41, 128, 256])
@pytest.mark.parametrize("nrows,max_deg", [(13, 1), (16, 16), (9, 32),
                                           (600, 2)])
def test_gather_spmm_ell_boundaries(rng, nrows, max_deg, k, mode):
    """ELL tiles of 8, 128 and 256 elements (a partial last tile at 13
    and 9 rows), and 75 tiles over 2 grid steps at 600 rows, through the
    SpMM and the SDDMM entry (1 head at K=41, else 4)."""
    ncols = 70
    idx = rng.integers(0, ncols + 1, (nrows, max_deg)).astype(np.int32)
    val = rng.standard_normal((nrows, max_deg)).astype(np.float32)
    val[idx == ncols] = 0.0
    ell = C.ELL(idx=jnp.asarray(idx), val=jnp.asarray(val), nrows=nrows,
                ncols=ncols, nse=int((idx < ncols).sum()))
    h = rng.standard_normal((ncols, k)).astype(np.float32)
    if mode == "spmm":
        out = kops.ell_spmm(ell, jnp.asarray(h), interpret=True)
        ref = np.einsum("rd,rdk->rk", val, _zero_row(h)[idx])
        np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5,
                                   atol=1e-4)
        return
    heads = 1 if k == 41 else 4
    dout = rng.standard_normal((nrows, k)).astype(np.float32)
    out = kops.gather_sddmm(ell, jnp.asarray(dout), jnp.asarray(h),
                            heads=heads, interpret=True)
    _assert_sddmm_mode(out, np.repeat(np.arange(nrows), max_deg), idx.ravel(),
                  dout, h, heads)


@pytest.mark.parametrize("fmt", ["ell", "sell"])
def test_gather_spmm_zero_weight_slots_add_exact_zero(rng, fmt):
    """A row of H that only zero-weight slots point at holds 1e30: those
    slots still fetch it, and every output stays what the other slots
    give, exactly 0 where they are all padding."""
    n, ncols, big = 40, 30, 7
    idx = rng.integers(0, ncols, (n, 6)).astype(np.int32)
    idx[idx == big] = big + 1
    val = rng.standard_normal((n, 6)).astype(np.float32)
    idx[::3, :] = big                   # every third row: only the big row
    val[::3, :] = 0.0
    idx[1::3, 0] = big
    val[1::3, 0] = 0.0
    h = rng.standard_normal((ncols, 128)).astype(np.float32)
    h[big] = 1e30
    ell = C.ELL(idx=jnp.asarray(idx), val=jnp.asarray(val), nrows=n,
                ncols=ncols, nse=int((val != 0).sum()))
    if fmt == "ell":
        out = kops.ell_spmm(ell, jnp.asarray(h), interpret=True)
    else:
        rows = np.repeat(np.arange(n), 6)
        coo = C.coo_from_edges(idx.ravel(), rows, val.ravel(), n, ncols)
        out = kops.sell_spmm(C.sell_from_coo(coo, c=8), jnp.asarray(h),
                             interpret=True)
    out = np.asarray(out)
    hz = h.astype(np.float64)
    hz[big] = 0.0
    ref = np.einsum("rd,rdk->rk", val, hz[idx])
    assert np.isfinite(out).all()
    assert (out[::3] == 0).all()
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-4)


def test_gather_spmm_partial_chunk_reads_only_its_elements(rng):
    """Chunk 0 fetches a NaN row into buffer row 50; chunk 2 reuses that
    buffer slot with 5 elements, so row 50 still holds the NaN, and its
    two table rows also hold tile 3's values, one of them inf. Only the
    tiles that store the NaN row and the inf value may read non-finite
    numbers (a tile's dot spreads them over its 8 rows)."""
    from repro.kernels.gather_spmm import gather_spmm_pallas
    ncols = 40
    ptr, idx, val = _segment_table(rng, [128, 128, 5, 20], ncols)
    idx[idx == 9] = 10
    idx[50], val[50] = 9, 1.0
    idx[264], val[264] = 3, np.inf
    h = rng.standard_normal((ncols, 128)).astype(np.float32)
    h[9] = np.nan
    out = np.asarray(gather_spmm_pallas(
        jnp.asarray(ptr), jnp.asarray(idx), jnp.asarray(val),
        jnp.asarray(h), ncols=ncols, seg_rows=8, interpret=True))
    assert np.isnan(out[50 % 8]).all()
    assert not np.isfinite(out[24 + (264 - 261) % 8]).any()
    np.testing.assert_allclose(
        out[8:24], _segment_oracle(ptr, idx, val, h, seg_rows=8)[8:24],
        rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("fmt", ["ell", "sell"])
def test_gather_spmm_op_record_carries_pipeline(rng, fmt):
    from repro import obs
    coo, _ = random_coo(rng, 37, 29, 300)
    if fmt == "ell":
        a, op = C.ell_from_coo(coo), kops.ell_spmm
    else:
        a, op = C.sell_from_coo(coo, c=8), kops.sell_spmm
    with obs.profiled(ops=True) as tracer:
        op(a, jnp.ones((29, 16), jnp.float32), interpret=True)
    rec, = [s for s in tracer.snapshot()
            if s.name == f"op.{fmt}_spmm.trace"]
    elements = a.idx.size if fmt == "sell" else 40 * a.max_deg
    assert rec.attrs["backend"] == "pallas"
    assert rec.attrs["depth"] == 2
    assert rec.attrs["elements"] == elements
    assert rec.attrs["rows_per_step"] == 40


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
