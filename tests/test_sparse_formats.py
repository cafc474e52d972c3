"""Sparse container correctness: every format's todense == the COO dense,
transpose/normalize identities, padding invariants. Includes hypothesis
property tests over random graphs."""
import numpy as np
import jax.numpy as jnp
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:     # fall back to the deterministic sampling stub
    from _hypothesis_stub import given, settings, strategies as st

from repro.core import (bsr_from_coo, coo_from_edges, coo_transpose,
                        csr_from_coo, ell_from_coo, gcn_normalize,
                        row_degrees, sell_from_coo, sell_slice_degrees)
from conftest import random_coo


def test_coo_todense(small_graph):
    coo, dense = small_graph
    np.testing.assert_allclose(np.asarray(coo.todense()), dense, rtol=1e-6)


def test_csr_roundtrip(small_graph):
    coo, dense = small_graph
    csr = csr_from_coo(coo)
    np.testing.assert_allclose(np.asarray(csr.to_coo().todense()), dense,
                               rtol=1e-6)
    # cached row expansion is consistent with indptr
    indptr = np.asarray(csr.indptr)
    assert indptr[-1] == coo.nse


@pytest.mark.parametrize("br,bc", [(16, 16), (8, 32), (32, 8)])
def test_bsr_todense(small_graph, br, bc):
    coo, dense = small_graph
    bsr = bsr_from_coo(coo, br=br, bc=bc)
    d = np.asarray(bsr.todense())[: coo.nrows, : coo.ncols]
    np.testing.assert_allclose(d, dense, rtol=1e-6)
    # invariants: sorted blocks, every block row non-empty
    blk = np.asarray(bsr.blk_row)[: bsr.n_real_blocks]
    assert (np.diff(blk) >= 0).all()
    assert set(range(bsr.n_block_rows)) <= set(blk.tolist())


def test_ell_roundtrip(small_graph):
    coo, dense = small_graph
    ell = ell_from_coo(coo)
    # reconstruct dense from ELL
    d = np.zeros(coo.shape, np.float32)
    idx, val = np.asarray(ell.idx), np.asarray(ell.val)
    for i in range(coo.nrows):
        for j in range(ell.max_deg):
            if idx[i, j] < coo.ncols:
                d[i, idx[i, j]] += val[i, j]
    np.testing.assert_allclose(d, dense, rtol=1e-6)


@pytest.mark.parametrize("c,sigma", [(4, 0), (8, 0), (8, 16)])
def test_sell_roundtrip(small_graph, c, sigma):
    """Unpacking the SELL slices through perm must reproduce the dense
    matrix; perm/inv_perm must be mutually inverse; slices sorted."""
    coo, dense = small_graph
    s = sell_from_coo(coo, c=c, sigma=sigma)
    idx, val = np.asarray(s.idx), np.asarray(s.val)
    sof, perm = np.asarray(s.slice_of), np.asarray(s.perm)
    d_sorted = np.zeros((s.nrows_padded, coo.ncols), np.float32)
    for t in range(s.n_steps):
        for lane in range(c):
            if idx[t, lane] < coo.ncols:
                d_sorted[sof[t] * c + lane, idx[t, lane]] += val[t, lane]
    d = np.zeros_like(d_sorted)
    d[perm] = d_sorted
    np.testing.assert_allclose(d[: coo.nrows], dense, rtol=1e-6)
    # perm is a permutation of the padded row range, inverse-consistent
    assert sorted(perm.tolist()) == list(range(s.nrows_padded))
    inv = np.asarray(s.inv_perm)
    assert (perm[inv] == np.arange(coo.nrows)).all()
    # steps are slice-monotonic and slice_ptr brackets each slice's steps
    assert (np.diff(sof) >= 0).all()
    ptr = np.asarray(s.slice_ptr)
    assert ptr[0] == 0 and ptr[-1] == s.n_steps
    np.testing.assert_array_equal(
        ptr, np.searchsorted(sof, np.arange(s.nslices + 1)))


def test_sell_packing_beats_ell_on_skew(rng):
    """One hub row must not inflate every slice (the ELL pathology)."""
    n = 64
    src = rng.integers(0, n, 50)
    coo = coo_from_edges(np.unique(src), np.zeros(len(np.unique(src)),
                                                  np.int64), None, n, n)
    s = sell_from_coo(coo, c=8, sigma=0)
    ell = ell_from_coo(coo)
    assert s.n_steps * s.c < ell.nrows * ell.max_deg / 4


def test_sell_slice_degrees_windows():
    deg = np.array([9, 0, 0, 0, 5, 0, 0, 0])
    # global sort: both high-degree rows land in the same slice
    sd, perm = sell_slice_degrees(deg, c=4, sigma=0)
    assert sd.tolist() == [9, 1]
    assert perm[0] == 0 and perm[1] == 4
    # sigma=4 restricts sorting to each window: one hub per slice
    sd_w, _ = sell_slice_degrees(deg, c=4, sigma=4)
    assert sd_w.tolist() == [9, 5]


def test_ell_degenerate_zero_degree_rows(rng):
    # rows 0/2/4 have no neighbors: sentinel-only rows, spmm yields zeros
    coo = coo_from_edges(np.array([1, 1]), np.array([1, 3]),
                         np.array([1.5, -2.0], np.float32), 5, 5)
    ell = ell_from_coo(coo)
    idx = np.asarray(ell.idx)
    assert (idx[[0, 2, 4]] == coo.ncols).all()
    from repro.core.semiring import get_semiring
    from repro.kernels.ref import spmm_ell_ref
    h = jnp.asarray(np.eye(5, dtype=np.float32))
    out = np.asarray(spmm_ell_ref(ell, h, get_semiring("sum")))
    assert (out[[0, 2, 4]] == 0).all()
    assert out[1, 1] == 1.5 and out[3, 1] == -2.0


def test_ell_degenerate_empty_graph_and_zero_max_deg():
    empty = coo_from_edges(np.zeros(0, np.int64), np.zeros(0, np.int64),
                           None, 4, 4, pad_to=0)
    ell = ell_from_coo(empty)
    assert ell.max_deg == 1                 # guarded: never a 0-width table
    assert (np.asarray(ell.idx) == empty.ncols).all()
    # explicit max_deg=0 request is clamped the same way
    ell0 = ell_from_coo(empty, max_deg=0)
    assert ell0.max_deg == 1
    # zero-row matrix must not crash the constructor
    norows = coo_from_edges(np.zeros(0, np.int64), np.zeros(0, np.int64),
                            None, 0, 4, pad_to=0)
    ell_nr = ell_from_coo(norows)
    assert np.asarray(ell_nr.idx).shape == (0, 1)


def test_transpose(small_graph):
    coo, dense = small_graph
    coo_t = coo_transpose(coo)
    np.testing.assert_allclose(np.asarray(coo_t.todense()), dense.T,
                               rtol=1e-6)


def test_degrees(small_graph):
    coo, dense = small_graph
    deg = np.asarray(row_degrees(coo))
    np.testing.assert_allclose(deg, (dense != 0).sum(1), rtol=1e-6)


def test_gcn_normalize_square(rng):
    # square graph so D^-1/2 (A+I) D^-1/2 is fully defined
    from conftest import random_coo as rc
    coo, dense = rc(rng, 40, 40, 300)
    a_n = gcn_normalize(coo, add_self_loops=True)
    dn = np.asarray(a_n.todense())
    a_sl = dense + np.eye(40, dtype=np.float32)
    deg = a_sl.sum(1)
    dinv = 1.0 / np.sqrt(np.maximum(deg, 1e-12))
    exp = dinv[:, None] * a_sl * dinv[None, :]
    np.testing.assert_allclose(dn, exp, rtol=1e-4, atol=1e-5)


@settings(max_examples=25, deadline=None)
@given(n=st.integers(4, 40), m=st.integers(4, 40),
       density=st.floats(0.02, 0.5), seed=st.integers(0, 1000))
def test_formats_agree_property(n, m, density, seed):
    rng = np.random.default_rng(seed)
    nnz = max(1, int(n * m * density))
    coo, dense = random_coo(rng, n, m, nnz, pad_to=nnz + 7)
    bsr = bsr_from_coo(coo, br=8, bc=8)
    ell = ell_from_coo(coo)
    d_bsr = np.asarray(bsr.todense())[:n, :m]
    np.testing.assert_allclose(d_bsr, dense, rtol=1e-5, atol=1e-6)
    # spmm against ones must agree across formats (sum semiring)
    from repro.core.semiring import get_semiring
    from repro.kernels.ref import spmm_coo_ref, spmm_ell_ref
    h = jnp.asarray(rng.standard_normal((m, 8)).astype(np.float32))
    sr = get_semiring("sum")
    out_coo = np.asarray(spmm_coo_ref(coo, h, sr))
    out_ell = np.asarray(spmm_ell_ref(ell, h, sr))
    np.testing.assert_allclose(out_coo, dense @ np.asarray(h), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(out_ell, out_coo, rtol=1e-4, atol=1e-5)
