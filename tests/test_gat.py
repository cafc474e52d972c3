"""The published multi-head GAT on the gather kernels.

* The row-gather kernel's multi-head SpMM and gather-SDDMM modes (Pallas
  in interpret mode) against dense oracles at H in {1, 4, 6}, on SELL and
  ELL tables whose hub row spans more than 256 slots (chunk and grid-step
  boundaries) and whose isolated rows hold nothing; at H = 1 the
  multi-head mode is the SpMM kernel bit for bit.
* The transpose slot permutation against the entries it pairs.
* ``make_gnn("gat")`` against the benchmark's plain float32 reference
  (``chipbench/reference/gat.py``): logits, loss and every parameter's
  gradient from the same seeded weights, on the patched path over SELL
  and ELL (XLA and the Pallas modes) and on the unpatched path.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from repro.core import sparse as sp
from repro.core.autotune import KernelPlan
from repro.core.cache import build_cached_graph, slot_rows
from repro.core.patch import patched
from repro.kernels import ops as kops

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

N = 300
HUB = 5                 # in-neighbours: every node (300 slots)
ISOLATED = (7, 11)      # no edges at all: only a self-loop in A + I


def _edges(rng, n=N, m=2500):
    src = np.concatenate([rng.integers(0, n, m), np.arange(n)])
    dst = np.concatenate([rng.integers(0, n, m), np.full(n, HUB)])
    keep = ~np.isin(src, ISOLATED) & ~np.isin(dst, ISOLATED)
    key = np.unique(dst[keep].astype(np.int64) * n + src[keep])
    return (key % n).astype(np.int32), (key // n).astype(np.int32)


@pytest.fixture(scope="module")
def graph():
    src, dst = _edges(np.random.default_rng(0))
    val = np.random.default_rng(1).random(len(src)).astype(np.float32)
    return sp.coo_from_edges(src, dst, val, N, N), src, dst


def _table(a, kind):
    return sp.sell_from_coo(a, c=8) if kind == "sell" else sp.ell_from_coo(a)


@pytest.mark.parametrize("kind", ["sell", "ell"])
@pytest.mark.parametrize("heads,f", [(1, 128), (4, 64), (6, 41)])
def test_kernel_modes_against_dense(graph, kind, heads, f):
    a = _table(graph[0], kind)
    rng = np.random.default_rng(heads)
    k = heads * f
    z = rng.standard_normal((N, k)).astype(np.float32)
    dout = rng.standard_normal((N, k)).astype(np.float32)
    rows = slot_rows(a)
    idx = np.asarray(a.idx).reshape(-1)
    live = idx < N
    vals = rng.random((heads, idx.size)).astype(np.float32) * live
    assert np.bincount(rows[live], minlength=N)[HUB] > 256

    out = np.asarray(kops.gather_spmm_heads(a, jnp.asarray(vals),
                                            jnp.asarray(z), interpret=True))
    zh = z.reshape(N, heads, f).astype(np.float64)
    want = np.zeros((N, heads, f))
    np.add.at(want, rows[live], vals.T[live][:, :, None] * zh[idx[live]])
    np.testing.assert_allclose(out, want.reshape(N, k), rtol=1e-5, atol=1e-4)
    assert not out[list(ISOLATED)].any()

    got = np.asarray(kops.gather_sddmm(a, jnp.asarray(dout), jnp.asarray(z),
                                       heads=heads, interpret=True))
    dh = dout.reshape(N, heads, f).astype(np.float64)
    want = np.zeros((heads, idx.size))
    want[:, live] = (dh[rows[live]] * zh[idx[live]]).sum(-1).T
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)
    xla = np.asarray(kops.gather_sddmm(a, jnp.asarray(dout), jnp.asarray(z),
                                       heads=heads))
    np.testing.assert_allclose(xla, want, rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("kind", ["sell", "ell"])
def test_one_head_is_the_spmm_kernel_bitwise(graph, kind):
    """At H = 1 the multi-head mode lowers to the SpMM kernel: the same
    numbers to the bit as ``sell_spmm`` / ``ell_spmm`` over the same
    values."""
    a = _table(graph[0], kind)
    z = jnp.asarray(np.random.default_rng(3).standard_normal((N, 96)),
                    jnp.float32)
    one = kops.gather_spmm_heads(a, a.val.reshape(1, -1), z, interpret=True)
    spmm = kops.sell_spmm if kind == "sell" else kops.ell_spmm
    np.testing.assert_array_equal(np.asarray(one),
                                  np.asarray(spmm(a, z, interpret=True)))


def test_attention_counts_slots_and_set_up(graph):
    """Building a gather plan counts ``setup.slot_perm_s``; each traced
    call counts its slots times heads in ``kernels.attention_slots``."""
    from repro import obs
    from repro.core.fusedmm import gat_attention
    before = obs.metrics().snapshot()
    g = build_cached_graph(graph[0], plan=KernelPlan(kind="sell"), tune=False,
                           slot_perm=True)
    z = jnp.ones((N, 8), jnp.float32)
    s = jnp.zeros((4, N), jnp.float32)
    gat_attention(g, z, s, s)
    after = obs.metrics().snapshot()
    assert after["setup.slot_perm_s"] > before.get("setup.slot_perm_s", 0)
    assert (after["kernels.attention_slots"]
            - before.get("kernels.attention_slots", 0)) == 4 * g.sell.idx.size


@pytest.mark.parametrize("kind", ["sell", "ell"])
def test_transpose_slot_perm_pairs_entries(graph, kind):
    g = build_cached_graph(graph[0], plan=KernelPlan(kind=kind), tune=False,
                           slot_perm=True)
    fwd, tr = (g.sell, g.sell_t) if kind == "sell" else (g.ell, g.ell_t)
    perm = np.asarray(g.slot_perm)
    idx_f, idx_t = (np.asarray(t.idx).reshape(-1) for t in (fwd, tr))
    rows_f, rows_t = slot_rows(fwd), slot_rows(tr)
    live = idx_t < N
    assert (perm[~live] == idx_f.size).all()
    np.testing.assert_array_equal(rows_f[perm[live]], idx_t[live])
    np.testing.assert_array_equal(idx_f[perm[live]], rows_t[live])
    assert len(np.unique(perm[live])) == live.sum()


def test_slot_perm_only_on_request(graph):
    """Only a graph built for attention pays for the permutation; the
    attention op refuses a gather plan without one instead of falling
    back to the ``(nnz, K)`` composition."""
    from repro.core.fusedmm import gat_attention
    g = build_cached_graph(graph[0], plan=KernelPlan(kind="sell"), tune=False)
    assert g.slot_perm is None
    s = jnp.zeros((4, N), jnp.float32)
    with pytest.raises(ValueError, match="slot_perm=True"):
        gat_attention(g, jnp.ones((N, 8), jnp.float32), s, s)


def _dataset(src, dst):
    from repro.data.graphs import GraphDataset
    rng = np.random.default_rng(4)
    loops = np.arange(N, dtype=np.int32)
    coo = sp.coo_from_edges(src, dst, None, N, N, pad_to=len(src) + 24)
    coo_sl = sp.coo_from_edges(np.concatenate([src, loops]),
                               np.concatenate([dst, loops]), None, N, N,
                               pad_to=len(src) + N + 40)
    y = rng.integers(0, 5, N).astype(np.int32)
    train = rng.random(N) < 0.6
    return GraphDataset(
        name="hub", coo=coo, coo_sl=coo_sl,
        x=jnp.asarray(rng.standard_normal((N, 24)), jnp.float32),
        y=jnp.asarray(y), train_mask=jnp.asarray(train),
        val_mask=jnp.asarray(~train), test_mask=jnp.asarray(~train),
        num_classes=5)


HEADS, HIDDEN = (4, 4, 6), 32


@pytest.fixture(scope="module")
def reference_run(graph):
    """The reference's logits, loss and gradient from seed 5."""
    from chipbench.reference import gat as ref
    _, src, dst = graph
    ds = _dataset(src, dst)
    params = ref.init_params(5, 24, HEADS, [HIDDEN, HIDDEN, 5],
                             [True, True, False])
    adj = ref.entries(src, dst, N)
    args = (adj, ds.x, ds.y, ds.train_mask)
    with jax.default_matmul_precision("highest"):
        logits = ref.logits_fn(params, adj, ds.x)
        loss, grad = jax.value_and_grad(ref.loss_fn)(params, *args)
    return ds, params, np.asarray(logits), float(loss), grad


def _interpret_pallas(monkeypatch):
    """Every dispatcher takes its Pallas kernel, run by the interpreter."""
    call = pl.pallas_call
    monkeypatch.setattr(kops, "on_tpu", lambda: True)
    monkeypatch.setattr(pl, "pallas_call",
                        lambda *a, **kw: call(*a, **{**kw, "interpret": True}))


@pytest.mark.parametrize("path", ["sell", "ell", "sell-pallas",
                                  "ell-pallas", "trusted", "unpatched"])
def test_gat_matches_reference(reference_run, monkeypatch, path):
    from repro.models.gnn import build_bundle, make_gnn
    from repro.train.gnn import _xent
    ds, ref_params, ref_logits, ref_loss, ref_grad = reference_run
    kind = path.split("-")[0]
    plan = KernelPlan(kind="trusted" if kind == "unpatched" else kind)
    bundle = build_bundle(ds, plan=plan, slot_perm=True)
    if path.endswith("pallas"):
        _interpret_pallas(monkeypatch)
    init, apply = make_gnn("gat", 24, HIDDEN, 5, heads=HEADS)
    params = init(jax.random.PRNGKey(5))
    jax.tree_util.tree_map(np.testing.assert_array_equal, params, ref_params)

    def loss_fn(p):
        logits = apply(p, bundle, ds.x)
        return _xent(logits, ds.y, ds.train_mask), logits

    with patched(path != "unpatched"), \
            jax.default_matmul_precision("highest"):
        (loss, logits), grad = jax.value_and_grad(loss_fn, has_aux=True)(
            params)
    np.testing.assert_allclose(np.asarray(logits), ref_logits, rtol=1e-4,
                               atol=1e-5)
    assert abs(float(loss) - ref_loss) <= 1e-5 * abs(ref_loss)
    for layer, leaves in ref_grad.items():
        for name, want in leaves.items():
            want = np.asarray(want)
            np.testing.assert_allclose(
                np.asarray(grad[layer][name]), want, rtol=1e-3,
                atol=1e-5 * np.abs(want).max(), err_msg=f"{layer}.{name}")
