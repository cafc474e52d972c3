"""The published multi-head GAT on the gather kernels.

* The row-gather kernel's multi-head SpMM and gather-SDDMM modes (Pallas
  in interpret mode) against dense oracles at H in {1, 4, 6}, on SELL and
  ELL tables whose hub row spans more than 256 slots (chunk and grid-step
  boundaries) and whose isolated rows hold nothing; at H = 1 the
  multi-head mode is the SpMM kernel bit for bit.
* The transpose slot permutation against the entries it pairs.
* The softmax's row broadcast and row reductions, which follow the table
  (one index per SELL step for all heads, none on ELL), against the
  per-slot segment formulation; and the jaxpr of the softmax, forward and
  backward, which holds no slot-length gather or scatter but those of
  ``s_src[idx]`` and its gradient.
* ``make_gnn("gat")`` against the benchmark's plain float32 reference
  (``chipbench/reference/gat.py``): logits, loss and every parameter's
  gradient from the same seeded weights, on the patched path over SELL
  and ELL (XLA and the Pallas modes) and on the unpatched path.
"""
import importlib
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
    call counts its slots times heads in ``kernels.attention_slots``, and
    in ``kernels.attention_row_indices`` the indices its row ops issue:
    one per SELL step for each of the forward's five row ops and the
    backward's three, none on ELL."""
    from repro import obs
    from repro.core.fusedmm import gat_attention

    def counted(fn):
        before = obs.metrics().snapshot()
        out = fn()
        after = obs.metrics().snapshot()
        return out, {k: after.get(k, 0) - before.get(k, 0) for k in after}

    z = jnp.ones((N, 8), jnp.float32)
    s = jnp.zeros((4, N), jnp.float32)
    for kind in ("sell", "ell"):
        g, built = counted(lambda: build_cached_graph(
            graph[0], plan=KernelPlan(kind=kind), tune=False,
            slot_perm=True))
        assert built["setup.slot_perm_s"] > 0
        table = g.sell if kind == "sell" else g.ell
        per_op = table.n_steps if kind == "sell" else 0
        _, fwd = counted(lambda: gat_attention(g, z, s, s))
        assert fwd["kernels.attention_slots"] == 4 * table.idx.size
        assert fwd["kernels.attention_row_indices"] == 5 * per_op
        _, both = counted(lambda: jax.grad(
            lambda s: gat_attention(g, z, s, s).sum())(s))
        assert both["kernels.attention_row_indices"] == 8 * per_op


def _row_graph(c):
    """A + I-like pattern for the row ops: a hub of more than 256 slots,
    rows of one entry (so SELL slices one step wide), rows with none, and
    a row count that leaves SELL pad rows at every C."""
    rng = np.random.default_rng(c)
    n = 300
    src = [np.arange(n), rng.integers(0, n, 900), rng.integers(0, n, 60)]
    dst = [np.full(n, 3), rng.integers(0, 150, 900), np.arange(150, 210)]
    key = np.unique(np.concatenate(dst).astype(np.int64) * n
                    + np.concatenate(src))
    return sp.coo_from_edges((key % n).astype(np.int32),
                             (key // n).astype(np.int32), None, n, n)


def _per_slot_rows(a):
    """Today's per-slot formulation: each slot's row in the kernel's order
    and the row count, the oracle of the row ops."""
    e = np.arange(a.idx.size)
    if isinstance(a, sp.ELL):
        return e // a.max_deg, a.nrows
    return (np.asarray(a.slice_of)[e // a.c] * a.c + e % a.c,
            a.nrows_padded)


ROW_OP_CASES = [(table, heads, backend)
                for backend, tables in (("xla", ("sell8", "sell16", "sell32",
                                                 "ell")),
                                        ("pallas", ("sell8", "sell16",
                                                    "sell32")))
                for table in tables for heads in (1, 4, 6)]


@pytest.mark.parametrize("table,heads,backend", ROW_OP_CASES)
def test_row_ops_match_per_slot_segments(table, heads, backend,
                                         monkeypatch):
    """Row broadcast, max and sum (and, for SELL, the moves between flat
    slot order and steps on lanes) against the per-slot segment
    formulation: XLA's, and the ``sell_rows`` kernels in interpret mode."""
    fm = importlib.import_module("repro.core.fusedmm")
    if backend == "pallas":
        _interpret_pallas(monkeypatch)
    coo = _row_graph(heads)
    a = (sp.ell_from_coo(coo) if table == "ell"
         else sp.sell_from_coo(coo, c=int(table[4:])))
    rows, nrows = _per_slot_rows(a)
    deg = np.bincount(rows[np.asarray(a.idx).reshape(-1) < a.ncols],
                      minlength=nrows)
    assert deg.max() > 256 and (deg == 0).any()
    if table != "ell":
        assert (np.bincount(np.asarray(a.slice_of)) == 1).any()
        assert a.nrows_padded > a.nrows
    rng = np.random.default_rng(7)
    live = np.asarray(a.idx).reshape(1, -1) < a.ncols
    x = rng.standard_normal((heads, a.idx.size)).astype(np.float32)
    y = jnp.asarray(rng.standard_normal((heads, nrows)).astype(np.float32))

    got = fm._flat(a, fm._row_bcast(a, fm._rows(a, y)))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(y)[:, rows])
    for kind, fill in (("max", -np.inf), ("sum", 0.0)):
        v = jnp.asarray(np.where(live, x, fill))
        seg = jax.ops.segment_max if kind == "max" else jax.ops.segment_sum
        want = np.stack([seg(v[h], rows, num_segments=nrows)
                         for h in range(heads)])
        got = fm._unrows(a, fm._row_reduce(a, fm._slots(a, v), kind))
        if kind == "max":
            np.testing.assert_array_equal(np.asarray(got), want)
        else:
            np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6,
                                       atol=1e-5)


def test_sell_row_kernels_span_grid_steps():
    """The ``sell_rows`` kernels (interpret mode) over more steps than two
    grid steps hold, with a slice across a grid-step boundary, slices one
    step wide and a step count off the lane tiles."""
    from repro.kernels import sell_rows
    rng = np.random.default_rng(11)
    c, heads, nslices = 8, 2, 700
    n_steps = 2 * sell_rows.BLOCK + 300
    width = np.ones(nslices, np.int64)
    width[[3, 300]] = (sell_rows.BLOCK + 77, 600)
    width[400:] += rng.integers(0, 3, nslices - 400)
    width[-1] += n_steps - width.sum()
    slice_of = np.repeat(np.arange(nslices, dtype=np.int32), width)
    x = rng.standard_normal((heads, n_steps * c)).astype(np.float32)
    steps = np.asarray(sell_rows.to_steps(jnp.asarray(x), c, n_steps,
                                          interpret=True))
    want = x.reshape(heads, n_steps, c).transpose(0, 2, 1)
    np.testing.assert_array_equal(steps, want)
    np.testing.assert_array_equal(
        np.asarray(sell_rows.to_flat(jnp.asarray(want), interpret=True)), x)
    y = rng.standard_normal((heads * c, nslices)).astype(np.float32)
    np.testing.assert_array_equal(np.asarray(sell_rows.row_bcast(
        jnp.asarray(y), jnp.asarray(slice_of), n_steps, interpret=True)),
        y[:, slice_of])
    v = want.reshape(heads * c, n_steps)
    for kind, red in (("max", np.maximum), ("sum", np.add)):
        oracle = np.full((heads * c, nslices),
                         -np.inf if kind == "max" else 0.0)
        for col, s in enumerate(slice_of):
            oracle[:, s] = red(oracle[:, s], v[:, col])
        got = np.asarray(sell_rows.row_reduce(
            jnp.asarray(v), jnp.asarray(slice_of), nslices, kind,
            interpret=True))
        if kind == "max":
            np.testing.assert_array_equal(got, oracle)
        else:
            np.testing.assert_allclose(got, oracle, rtol=1e-5, atol=1e-4)


def _index_counts(jaxpr):
    """(primitive, indices issued) of every gather and scatter, nested
    jaxprs included."""
    out = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name.startswith(("gather", "scatter")):
            out.append((name, int(np.prod(eqn.invars[1].aval.shape[:-1]))))
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    out += _index_counts(sub)
    return out


def test_softmax_holds_no_per_slot_row_index(graph, monkeypatch):
    """The SELL softmax, forward and backward: the only gathers and
    scatters as long as the table's slots are the per-head ``s_src[idx]``
    gather and the ``ds_src`` scatter; every row op issues one index per
    step. (The SDDMM is the kernel's and is stubbed.)"""
    fm = importlib.import_module("repro.core.fusedmm")
    heads = 4
    g = build_cached_graph(graph[0], plan=KernelPlan(kind="sell"),
                           tune=False, slot_perm=True)
    slots, steps = g.sell.idx.size, g.sell.n_steps
    monkeypatch.setattr(kops, "gather_sddmm", lambda a, dout, z, heads: (
        jnp.zeros((heads, a.idx.size), dout.dtype)))

    def fwd_bwd(s_dst, s_src, z):
        out, vjp = jax.vjp(lambda d, s: fm._gat_softmax(g, d, s, z),
                           s_dst, s_src)
        return vjp((out[0], z))

    s = jnp.zeros((heads, N), jnp.float32)
    jaxpr = jax.make_jaxpr(fwd_bwd)(s, s, jnp.ones((N, 8), jnp.float32))
    counts = _index_counts(jaxpr.jaxpr)
    per_slot = sorted(name for name, n in counts if n == slots)
    assert per_slot == ["gather"] * heads + ["scatter-add"] * heads, counts
    assert sum(n == steps for _, n in counts) == 8, counts


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
