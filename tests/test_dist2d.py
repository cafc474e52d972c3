"""2-D vertex-cut partition (repro.dist.gnn2d) on 1 device: tile structure
round-trips vs COO, edge cases (empty tiles, rectangular adjacency),
1-device execution of the distributed SpMM, plan-awareness, and the
communication-volume model. Multi-device execution lives in
test_multidevice.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding

from repro.core import build_cached_graph, coo_from_edges
from repro.core.autotune import KernelPlan
from repro.dist import (build_dist_graph, comm_volume, comm_volume_2d,
                        distributed_spmm_2d, partition_2d, scores_to_dense)
from repro.dist.partition import graph2d_shardings


def _random_coo(rng, nr, nc, nnz):
    lin = rng.choice(nr * nc, size=nnz, replace=False)
    dst, src = lin // nc, lin % nc
    val = rng.standard_normal(nnz).astype(np.float32)
    a = coo_from_edges(src, dst, val, nr, nc)
    dense = np.zeros((nr, nc), np.float32)
    dense[dst, src] = val
    return a, dense


def _tiles_to_dense(g):
    """Scatter every tile's VALUES back to the padded dense canvas — the
    structure round-trip is the library's own slot-to-row mapping applied
    to ``g.val`` (so tests and scores_to_dense can't drift apart)."""
    return scores_to_dense(g, g.val, trim=False)


# --------------------------------------------------------------------------
# Structure round-trips
# --------------------------------------------------------------------------

def test_partition_2d_ell_roundtrip(rng):
    n, nnz = 50, 300                      # 50 % 2 != 0: exercises padding
    a, dense = _random_coo(rng, n, n, nnz)
    g = partition_2d(a, 2, 2)
    assert g.kind == "ell" and g.parts == 4
    assert g.rows_per_tile % g.pc == 0
    assert g.cols_per_tile % g.pr == 0
    assert g.idx.shape == (4, g.rows_per_tile, g.max_deg)
    rebuilt = _tiles_to_dense(g)
    np.testing.assert_allclose(rebuilt[:n, :n], dense, rtol=1e-6)
    assert (rebuilt[n:] == 0).all() and (rebuilt[:, n:] == 0).all()


def test_partition_2d_sell_roundtrip(rng):
    n, nnz = 50, 300
    a, dense = _random_coo(rng, n, n, nnz)
    g = partition_2d(a, 2, 2, plan=KernelPlan(kind="sell", sell_c=8))
    assert g.kind == "sell" and g.sell_c == 8
    assert g.rows_per_tile % (g.sell_c * g.pc) == 0 or \
        g.rows_per_tile % np.lcm(g.sell_c, g.pc) == 0
    assert g.idx.shape == (4, g.n_steps, 8)
    assert g.perm.shape == g.inv_perm.shape == (4, g.rows_per_tile)
    rebuilt = _tiles_to_dense(g)
    np.testing.assert_allclose(rebuilt[:n, :n], dense, rtol=1e-6)


def test_partition_2d_tile_max_deg_beats_global(rng):
    """The ELL pad width is the per-TILE max degree — on a graph with one
    hub row whose neighbors are spread over column blocks, the tiles are
    narrower than a 1-D band's global max_deg."""
    n = 32
    src = np.arange(n)                    # row 0 neighbors everyone
    dst = np.zeros(n, np.int64)
    a = coo_from_edges(src, dst, np.ones(n, np.float32), n, n)
    g2 = partition_2d(a, 2, 2)
    g1 = build_dist_graph(a, 4)
    assert g2.max_deg == n // 2           # hub row split over 2 col blocks
    assert g1.max_deg == n


def test_partition_2d_empty_tiles(rng):
    # all edges in the top-left quadrant: three tiles are empty
    a = coo_from_edges(np.array([0, 1, 2]), np.array([1, 0, 2]),
                       np.ones(3, np.float32), 40, 40)
    g = partition_2d(a, 2, 2)
    idx = np.asarray(g.idx)
    for p in (1, 2, 3):
        assert (idx[p] == g.cols_per_tile).all()   # all-sentinel tiles
    rebuilt = _tiles_to_dense(g)
    assert rebuilt.sum() == 3.0


def test_partition_2d_plan_awareness(rng):
    """The CachedGraph's autotuned plan flows into the tile layout."""
    a, _ = _random_coo(rng, 40, 40, 200)
    cg = build_cached_graph(a, tune=False)          # trusted plan
    assert partition_2d(cg, 2).kind == "ell"
    cg_sell = build_cached_graph(a, plan=KernelPlan(kind="sell", sell_c=8))
    assert partition_2d(cg_sell, 2).kind == "sell"


def test_partition_2d_rectangular(rng):
    nr, nc, nnz = 12, 100, 80
    a, dense = _random_coo(rng, nr, nc, nnz)
    g = partition_2d(a, 2, 2)
    rebuilt = _tiles_to_dense(g)
    np.testing.assert_allclose(rebuilt[:nr, :nc], dense, rtol=1e-6)


# --------------------------------------------------------------------------
# 1-device execution (the (1, 1) grid degenerates to the local kernels)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("plan", [None, KernelPlan(kind="sell", sell_c=8)])
def test_distributed_spmm_2d_one_device(rng, plan):
    nr, nc, nnz, k = 24, 40, 120, 8
    a, dense = _random_coo(rng, nr, nc, nnz)
    g = partition_2d(a, 1, 1, plan=plan)
    h = jnp.asarray(rng.standard_normal((nc, k)), jnp.float32)
    mesh = jax.make_mesh((1, 1), ("row", "col"))
    with mesh:
        for red in ("sum", "mean"):
            out = jax.jit(lambda hh: distributed_spmm_2d(g, hh, mesh,
                                                         reduce=red))(h)
            ref = dense @ np.asarray(h)
            if red == "mean":
                ref = ref / np.maximum((dense != 0).sum(1), 1)[:, None]
            np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4,
                                       atol=1e-4)


# --------------------------------------------------------------------------
# Communication model + sharding helper
# --------------------------------------------------------------------------

def test_comm_volume_2d_is_sublinear(rng):
    """The 2-D gather buffer is the column block (~N/pc rows), vs the full
    matrix for the 1-D band path — the O(N/sqrt(P)) claim, checked on the
    static tile geometry the shard_map bodies assert at trace time."""
    n, k, parts = 256, 64, 4
    a, _ = _random_coo(rng, n, n, 2000)
    g1 = build_dist_graph(a, parts)
    g2 = partition_2d(a, 2, 2)
    v1, v2 = comm_volume(g1, k), comm_volume_2d(g2, k)
    assert v1["gather_rows"] >= n                        # 1-D: everything
    assert v2["gather_rows"] == g2.cols_per_tile == n // 2
    assert v2["gather_rows"] * 2 <= v1["gather_rows"] + 2 * g2.pr
    # total elements: 2N/sqrt(P) vs N — ties at P=4, wins beyond
    g4 = partition_2d(a, 4, 4)
    v4 = comm_volume_2d(g4, k)
    assert v4["elements"] <= v1["elements"] // 2
    assert v4["gather_rows"] == n // 4


def test_graph2d_shardings_match_tree(rng):
    a, _ = _random_coo(rng, 32, 32, 100)
    g = partition_2d(a, 1, 1, plan=KernelPlan(kind="sell", sell_c=8))
    mesh = jax.make_mesh((1, 1), ("row", "col"))
    sh = graph2d_shardings(mesh, g)
    assert (jax.tree_util.tree_structure(sh)
            == jax.tree_util.tree_structure(g))
    leaves = jax.tree_util.tree_leaves(sh)
    assert leaves and all(isinstance(s, NamedSharding) for s in leaves)
    placed = jax.device_put(g, sh)                       # placeable
    assert placed.idx.shape == g.idx.shape
