"""Compile-only checks of every Pallas kernel a TPU dispatcher can reach.

Each test lowers and compiles one kernel for a v5e chip that is described
(``get_topology_desc``) but not attached, at the shapes the main paths run
on the chip: full-size synthetic reddit (232,965 nodes, ~10.3 M edges,
602 features, hidden 128) and device-sampled blocks at batch 512 x fanout
(10, 10). Nothing runs, so these say nothing about results or time; they
catch what interpret mode cannot — block shapes off the (8, 128) tiling,
scalar-prefetch tables past SMEM, VMEM overuse — at no chip time.

The topology is described inside a module fixture (never at import), and
JAX's persistent compilation cache is off around these compiles: a TPU
executable cannot be read back on a machine without the chip.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

from repro.core import sparse as sp

N_REDDIT = 232_965          # nodes of full-size reddit
NSE_REDDIT = 10_310_000     # edges after dedup (R-MAT, scale 1)
SELL_STEPS = 1_340_151      # SELL-C=8 packed steps of its normalized A
BATCH, FANOUT = 512, 10
HOP1_DST = 5_632            # second-hop frontier: 512 * 11 rounded to 128
HOP1_SRC = 61_952           # its source set: 5632 * 11


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:          # no TPU compiler in this install
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


def _spec(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), "no Pallas kernel"
    return compiled


@pytest.mark.parametrize("k", [128, 41, 602])
def test_sell_spmm_full_reddit(one_chip, k):
    """Hidden width, class count and feature width of full reddit; the
    only scalar-prefetched table is slice_ptr (nslices + 1 words)."""
    from repro.kernels import ops as kops
    s = lambda *a: _spec(one_chip, *a)      # noqa: E731
    _compile(lambda a, h: kops.sell_spmm(a, h, interpret=False),
             _reddit_sell(s), s((N_REDDIT, k), jnp.float32))


def _reddit_sell(s):
    c = 8
    nsl = -(-N_REDDIT // c)
    return sp.SELL(idx=s((SELL_STEPS, c)), val=s((SELL_STEPS, c), jnp.float32),
                   slice_of=s((SELL_STEPS,)), slice_ptr=s((nsl + 1,)),
                   perm=s((nsl * c,)), inv_perm=s((N_REDDIT,)),
                   nrows=N_REDDIT, ncols=N_REDDIT, nse=NSE_REDDIT, c=c,
                   sigma=0, nslices=nsl)


# the published GAT's layers: 4 heads of 256 (K=1024), 6 heads of 41 (K=246)
GAT_HEADS = [(4, 1024), (6, 246)]


@pytest.mark.parametrize("heads,k", GAT_HEADS)
def test_gather_spmm_heads_full_reddit(one_chip, heads, k):
    """The row-gather kernel's multi-head mode over the SELL table of full
    reddit's A + I: one DMA per slot for all heads, a dot per head. The
    values are head-major, ``(H, slots)``, as ``ops.gather_spmm_heads``
    passes them."""
    from repro.kernels.gather_spmm import gather_spmm_pallas
    s = lambda *a: _spec(one_chip, *a)      # noqa: E731
    a = _reddit_sell(s)
    _compile(lambda a, v, h: gather_spmm_pallas(
        a.slice_ptr * a.c, a.idx, v, h, ncols=N_REDDIT, seg_rows=a.c,
        heads=heads), a, s((heads, SELL_STEPS * 8), jnp.float32),
        s((N_REDDIT, k), jnp.float32))


@pytest.mark.parametrize("heads,k", GAT_HEADS)
def test_gather_sddmm_full_reddit(one_chip, heads, k):
    """The row-gather kernel's SDDMM mode over the same table: the output
    gradient's rows resident per grid step, results written to the slot
    table by row DMAs."""
    from repro.kernels.gather_spmm import gather_sddmm_pallas
    s = lambda *a: _spec(one_chip, *a)      # noqa: E731
    a = _reddit_sell(s)
    _compile(lambda a, d, h: gather_sddmm_pallas(
        a.slice_ptr * a.c, a.idx, d, h, ncols=N_REDDIT, seg_rows=a.c,
        heads=heads), a, s((a.nslices * a.c, k), jnp.float32),
        s((N_REDDIT, k), jnp.float32))


@pytest.mark.parametrize("heads,k", GAT_HEADS)
def test_gat_attention_full_reddit(one_chip, monkeypatch, heads, k):
    """The GAT attention's softmax, forward and backward, over the SELL
    table of full reddit's A + I: the ``sell_rows`` row ops and layout
    moves, the per-head ``s_src`` gather and ``ds_src`` scatter, and the
    gather-SDDMM kernel, dispatched as on the chip."""
    import importlib
    from repro.core.autotune import KernelPlan
    from repro.core.cache import CachedGraph
    from repro.kernels import ops as kops
    fm = importlib.import_module("repro.core.fusedmm")
    monkeypatch.setattr(kops, "on_tpu", lambda: True)
    s = lambda *a: _spec(one_chip, *a)      # noqa: E731
    a = _reddit_sell(s)
    g = CachedGraph(coo=None, coo_t=None, bsr=None, bsr_t=None, sell=a,
                    sell_t=a, ell=None, ell_t=None,
                    slot_perm=s((SELL_STEPS * a.c,)), degrees=None,
                    degrees_t=None, inv_deg=None, inv_deg_t=None,
                    plan=KernelPlan(kind="sell"))

    def fwd_bwd(g, s_dst, s_src, z, dout):
        out, vjp = jax.vjp(lambda d, s_: fm._gat_softmax(g, d, s_, z),
                           s_dst, s_src)
        return out[0], vjp((jnp.zeros_like(out[0]), dout))

    _compile(fwd_bwd, g, s((heads, N_REDDIT), jnp.float32),
             s((heads, N_REDDIT), jnp.float32),
             s((N_REDDIT, k), jnp.float32), s((N_REDDIT, k), jnp.float32))


@pytest.mark.parametrize("n_dst,n_src,k", [(HOP1_DST, HOP1_SRC, 602),
                                           (BATCH, HOP1_DST, 128),
                                           (BATCH, HOP1_DST, 256)])
def test_ell_spmm_sampled_blocks(one_chip, n_dst, n_src, k):
    """The two device-sampled layers: features into the hop-1 frontier,
    hidden into the seeds (at 128 and at sage2-mean-h256's 256)."""
    from repro.kernels import ops as kops
    s = lambda *a: _spec(one_chip, *a)      # noqa: E731
    a = sp.ELL(idx=s((n_dst, FANOUT)), val=s((n_dst, FANOUT), jnp.float32),
               nrows=n_dst, ncols=n_src, nse=n_dst * FANOUT)
    _compile(lambda a, h: kops.ell_spmm(a, h, interpret=False), a,
             s((n_src, k), jnp.float32))


def _bsr(s, nrows=16_384, nblocks=8_192, b=128):
    return sp.BSR(blk_row=s((nblocks,)), blk_col=s((nblocks,)),
                  blocks=s((nblocks, b, b), jnp.float32), nrows=nrows,
                  ncols=nrows, br=b, bc=b, n_real_blocks=nblocks)


def test_bsr_spmm_fitting_shape(one_chip):
    """BSR at a block count whose index tables fit SMEM (the tuner keeps
    larger ones off the TPU candidate set)."""
    from repro.kernels.bsr_spmm import bsr_spmm_pallas
    s = lambda *a: _spec(one_chip, *a)      # noqa: E731
    a = _bsr(s)
    _compile(lambda a, h: bsr_spmm_pallas(a, h), a,
             s((a.ncols, 128), jnp.float32))


@pytest.mark.parametrize("f", [BATCH, HOP1_DST])
@pytest.mark.parametrize("replace", [False, True])
def test_segment_sample(one_chip, f, replace):
    from repro.kernels.sample import _segment_sample_pallas
    s = lambda *a: _spec(one_chip, *a)      # noqa: E731
    _compile(lambda d, g, r: _segment_sample_pallas(
        d, g, r, width=FANOUT, fanout=FANOUT, seed=0, hop=1,
        replace=replace, interpret=False), s((f,)), s((f,)), s(()))


@pytest.mark.parametrize("f", [BATCH, HOP1_DST])
def test_expand_indptr(one_chip, f):
    from repro.kernels.sample import _expand_indptr_pallas
    s = lambda *a: _spec(one_chip, *a)      # noqa: E731
    _compile(lambda st, r, m: _expand_indptr_pallas(
        st, r, m, sentinel=NSE_REDDIT, interpret=False),
        s((f,)), s((f, FANOUT)), s((f, FANOUT), jnp.bool_))


@pytest.mark.parametrize("dtype", [jnp.int32, jnp.float32])
@pytest.mark.parametrize("f", [BATCH, HOP1_DST])
def test_flat_gather(one_chip, f, dtype):
    """Gathers sampled neighbor ids and edge values from the full edge
    arrays (nse + 1 entries); no per-position table in SMEM."""
    from repro.kernels.sample import _flat_gather_pallas
    s = lambda *a: _spec(one_chip, *a)      # noqa: E731
    _compile(lambda arr, pos: _flat_gather_pallas(arr, pos, interpret=False),
             s((NSE_REDDIT + 1,), dtype), s((f, FANOUT)))


def test_ragged_gemm(one_chip):
    """The MoE expert matmul (``core/dispatch``) at a mixtral-like width."""
    from repro.kernels.ragged_gemm import ragged_gemm_pallas
    s = lambda *a: _spec(one_chip, *a)      # noqa: E731
    t, d, f, e = 2048, 1024, 3584, 8
    _compile(lambda x, w, te: ragged_gemm_pallas(x, w, te),
             s((t, d), jnp.bfloat16), s((e, d, f), jnp.bfloat16),
             s((t // 128,)))


def test_flash_attention(one_chip):
    from repro.kernels.flash_attention import flash_attention_pallas
    s = lambda *a: _spec(one_chip, *a)      # noqa: E731
    q = s((1, 8, 1024, 128), jnp.bfloat16)
    kv = s((1, 2, 1024, 128), jnp.bfloat16)
    _compile(lambda q, k, v: flash_attention_pallas(q, k, v), q, kv, kv)
