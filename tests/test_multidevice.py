"""Multi-device behaviour, each case in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count (the main test process must
stay single-device per the assignment)."""
import os
import subprocess
import sys
import textwrap

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(body: str, devices: int = 8, timeout: int = 560) -> str:
    code = ("import os\n"
            f"os.environ['XLA_FLAGS'] = "
            f"'--xla_force_host_platform_device_count={devices}'\n"
            + textwrap.dedent(body))
    env = dict(os.environ, PYTHONPATH=os.path.join(_ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout, env=env)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_moe_manual_matches_einsum():
    _run("""
    import dataclasses, jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_smoke_config
    from repro.models.lm.moe import moe_layer, init_moe, _moe_einsum
    cfg = dataclasses.replace(get_smoke_config('phi3.5-moe-42b-a6.6b'),
                              capacity_factor=8.0)
    mesh = jax.make_mesh((2, 4), ('data', 'model'),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    p = init_moe(jax.random.PRNGKey(0), cfg)
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (4, 32, cfg.d_model)), jnp.float32)
    out_e, _ = jax.jit(lambda p, x: _moe_einsum(cfg, p, x))(p, x)
    with mesh:
        out_m, _ = jax.jit(lambda p, x: moe_layer(cfg, p, x))(p, x)
    err = float(jnp.abs(out_e - out_m).max()) / float(jnp.abs(out_e).max())
    assert err < 1e-5, err
    """)


def test_moe_manual_grads_flow():
    _run("""
    import dataclasses, jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_smoke_config
    from repro.models.lm.moe import moe_layer, init_moe, _moe_einsum
    cfg = dataclasses.replace(get_smoke_config('phi3.5-moe-42b-a6.6b'),
                              capacity_factor=8.0)
    mesh = jax.make_mesh((2, 4), ('data', 'model'),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    p = init_moe(jax.random.PRNGKey(0), cfg)
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (4, 32, cfg.d_model)), jnp.float32)
    def loss_m(p, x):
        out, aux = moe_layer(cfg, p, x)
        return jnp.sum(out ** 2) + aux
    def loss_e(p, x):
        out, aux = _moe_einsum(cfg, p, x)
        return jnp.sum(out ** 2) + aux
    with mesh:
        gm = jax.jit(jax.grad(loss_m))(p, x)
    ge = jax.jit(jax.grad(loss_e))(p, x)
    for k in ('wg', 'wu', 'wd', 'router'):
        a, b = np.asarray(gm[k]), np.asarray(ge[k])
        rel = np.abs(a - b).max() / max(np.abs(b).max(), 1e-9)
        assert rel < 1e-4, (k, rel)
    """)


def test_pipeline_parallel_matches_sequential():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.dist.pipeline import pipeline_apply
    mesh = jax.make_mesh((4,), ('pipe',),
                         axis_types=(jax.sharding.AxisType.Auto,) * 1)
    S, B, D = 4, 8, 16
    rng = np.random.default_rng(0)
    params = jnp.asarray(rng.standard_normal((S, D, D)), jnp.float32) * 0.3
    x = jnp.asarray(rng.standard_normal((B, D)), jnp.float32)
    def fn(w, a):
        return jnp.tanh(a @ w)
    with mesh:
        y = jax.jit(lambda p, x: pipeline_apply(
            fn, mesh, p, x, microbatches=4))(params, x)
    ref = x
    for s in range(S):
        ref = jnp.tanh(ref @ params[s])
    err = float(jnp.abs(y - ref).max())
    assert err < 1e-5, err
    """)


def test_compressed_psum_close_to_exact():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.dist.collectives import compressed_psum
    mesh = jax.make_mesh((8,), ('pod',),
                         axis_types=(jax.sharding.AxisType.Auto,) * 1)
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.standard_normal((8, 64)), jnp.float32)
    def body(gl):
        return compressed_psum({'g': gl}, 'pod')['g']
    with mesh:
        out = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P('pod'),
                                    out_specs=P('pod')))(g)
    exact = jnp.broadcast_to(g.mean(0, keepdims=True), g.shape)
    err = float(jnp.abs(out - exact).max())
    # int8 with shared scale: error bounded by quantum = amax/127
    bound = float(jnp.abs(g).max()) / 127.0 + 1e-6
    assert err <= bound, (err, bound)
    """)


def test_dryrun_cell_single_and_multipod():
    """One full production-mesh cell end-to-end in a subprocess (512 devs)."""
    _run("""
    from repro.launch.dryrun import run_cell
    row = run_cell('qwen2-1.5b', 'decode_32k', multi_pod=False, verbose=False)
    assert row['bottleneck'] in ('compute', 'memory', 'collective')
    assert row['chips'] == 256
    row2 = run_cell('qwen2-1.5b', 'decode_32k', multi_pod=True, verbose=False)
    assert row2['chips'] == 512
    """, devices=512)


def test_lm_train_step_sharded_small_mesh():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_smoke_config
    from repro.train import lm as TL
    cfg = get_smoke_config('llama3-8b')
    mesh = jax.make_mesh((2, 2), ('data', 'model'),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    step, opt = TL.make_train_step(cfg, lr=1e-3)
    with mesh:
        state = TL.make_train_state(cfg, jax.random.PRNGKey(0), opt)
        rng = np.random.default_rng(0)
        batch = {'tokens': jnp.asarray(rng.integers(0, cfg.vocab, (4, 64)),
                                       jnp.int32),
                 'targets': jnp.asarray(rng.integers(0, cfg.vocab, (4, 64)),
                                        jnp.int32)}
        jstep = jax.jit(step, donate_argnums=0)
        losses = []
        for _ in range(5):
            state, m = jstep(state, batch)
            losses.append(float(m['loss']))
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses
    """)


def test_distributed_spmm_matches_local():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import coo_from_edges
    from repro.dist.gnn import build_dist_graph, distributed_spmm
    mesh = jax.make_mesh((4,), ('data',),
                         axis_types=(jax.sharding.AxisType.Auto,) * 1)
    rng = np.random.default_rng(0)
    N, K, NNZ = 64, 16, 500
    lin = rng.choice(N * N, size=NNZ, replace=False)
    dst, src = lin // N, lin % N
    val = rng.standard_normal(NNZ).astype(np.float32)
    a = coo_from_edges(src, dst, val, N, N)
    g = build_dist_graph(a, 4)
    h = jnp.asarray(rng.standard_normal((N, K)), jnp.float32)
    with mesh:
        out = jax.jit(lambda hh: distributed_spmm(g, hh, mesh))(h)
    dense = np.zeros((N, N), np.float32); dense[dst, src] = val
    err = float(jnp.abs(out - dense @ np.asarray(h)).max())
    assert err < 1e-4, err
    """)


def test_distributed_spmm_sell_matches_local():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import coo_from_edges
    from repro.core.autotune import KernelPlan
    from repro.dist.gnn import build_dist_graph, distributed_spmm
    mesh = jax.make_mesh((4,), ('data',),
                         axis_types=(jax.sharding.AxisType.Auto,) * 1)
    rng = np.random.default_rng(0)
    N, K, NNZ = 64, 16, 500
    lin = rng.choice(N * N, size=NNZ, replace=False)
    dst, src = lin // N, lin % N
    val = rng.standard_normal(NNZ).astype(np.float32)
    a = coo_from_edges(src, dst, val, N, N)
    g = build_dist_graph(a, 4, plan=KernelPlan(kind='sell', sell_c=8))
    assert g.kind == 'sell'
    h = jnp.asarray(rng.standard_normal((N, K)), jnp.float32)
    with mesh:
        out = jax.jit(lambda hh: distributed_spmm(g, hh, mesh))(h)
    dense = np.zeros((N, N), np.float32); dense[dst, src] = val
    err = float(jnp.abs(out - dense @ np.asarray(h)).max())
    assert err < 1e-4, err
    """)


def test_distributed_spmm_2d_matches_local():
    """2x2 vertex-cut grid vs the dense reference, ELL + SELL tiles, sum +
    mean, with the O(N/sqrt(P)) gather-buffer shape asserted: the shard_map
    body trace-asserts ``hg.shape[0] == cols_per_tile`` and the test checks
    that is half the (padded) feature matrix on the 2x2 grid."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import coo_from_edges
    from repro.core.autotune import KernelPlan
    from repro.dist import comm_volume, comm_volume_2d, build_dist_graph
    from repro.dist.gnn2d import partition_2d, distributed_spmm_2d
    mesh = jax.make_mesh((2, 2), ('row', 'col'),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    rng = np.random.default_rng(0)
    N, K, NNZ = 64, 16, 500
    lin = rng.choice(N * N, size=NNZ, replace=False)
    dst, src = lin // N, lin % N
    val = rng.standard_normal(NNZ).astype(np.float32)
    a = coo_from_edges(src, dst, val, N, N)
    h = jnp.asarray(rng.standard_normal((N, K)), jnp.float32)
    dense = np.zeros((N, N), np.float32); dense[dst, src] = val
    deg = np.maximum((dense != 0).sum(1), 1)[:, None]
    for plan in (None, KernelPlan(kind='sell', sell_c=8)):
        g = partition_2d(a, 2, 2, plan=plan)
        # the halo each device gathers is one column block, not the matrix
        assert g.cols_per_tile == N // 2, g.cols_per_tile
        v1 = comm_volume(build_dist_graph(a, 4), K)
        v2 = comm_volume_2d(g, K)
        assert v2['gather_rows'] * 2 == v1['gather_rows'], (v1, v2)
        with mesh:
            out = jax.jit(lambda hh: distributed_spmm_2d(g, hh, mesh))(h)
            outm = jax.jit(lambda hh: distributed_spmm_2d(
                g, hh, mesh, reduce='mean'))(h)
        ref = dense @ np.asarray(h)
        assert float(np.abs(np.asarray(out) - ref).max()) < 1e-4
        assert float(np.abs(np.asarray(outm) - ref / deg).max()) < 1e-4
    """, devices=4)


def test_distributed_spmm_2d_compressed_reduce():
    """int8 column-axis reduce-scatter stays within the shared-scale
    quantization bound (pc quantization errors sum per output element)."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import coo_from_edges
    from repro.dist.gnn2d import partition_2d, distributed_spmm_2d
    mesh = jax.make_mesh((2, 2), ('row', 'col'),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    rng = np.random.default_rng(0)
    N, K, NNZ = 64, 16, 500
    lin = rng.choice(N * N, size=NNZ, replace=False)
    dst, src = lin // N, lin % N
    val = rng.standard_normal(NNZ).astype(np.float32)
    a = coo_from_edges(src, dst, val, N, N)
    g = partition_2d(a, 2, 2)
    h = jnp.asarray(rng.standard_normal((N, K)), jnp.float32)
    with mesh:
        out = jax.jit(lambda hh: distributed_spmm_2d(
            g, hh, mesh, compress=True))(h)
    dense = np.zeros((N, N), np.float32); dense[dst, src] = val
    ref = dense @ np.asarray(h)
    # per-column-block partials bound the shared quantization grid
    cpt = g.cols_per_tile
    parts = [dense[:, j*cpt:(j+1)*cpt] @ np.asarray(h)[j*cpt:(j+1)*cpt]
             for j in range(2)]
    bound = 2 * max(np.abs(p).max() for p in parts) / 127.0 + 1e-6
    err = float(np.abs(np.asarray(out) - ref).max())
    assert err <= bound, (err, bound)
    """, devices=4)


def test_minibatch_data_parallel_grad_sync_bitwise():
    """The lockstep minibatch step under shard_map: feeding both 'data'
    shards the IDENTICAL packed batch, the fp32 psum-mean gradient (and
    the updated params) must match the 1-shard step bitwise, and the int8
    wire must land within the shared-quantum bound (amax/127)."""
    _run("""
    import numpy as np, jax, jax.numpy as jnp
    from repro.core import sparse as sp
    from repro.data import make_dataset
    from repro.optim import adamw
    from repro.sampling import (BlockPlanCache, NeighborSampler, pack_block,
                                plan_buckets, stack_blocks)
    from repro.train.gnn_minibatch import (make_minibatch_step,
                                           make_block_model, init_step_stats)
    ds = make_dataset('reddit', scale=1/512, seed=1)
    csr = sp.csr_from_coo(ds.coo)
    B = 32
    sampler = NeighborSampler(csr, (4, 4), seed=0)
    seeds = np.arange(B)
    blocks = sampler.sample(seeds, round=1)
    buckets = plan_buckets(blocks, batch_size=B, fanouts=(4, 4))
    cache = BlockPlanCache(semiring='mean')
    dims = [ds.num_features, 32, ds.num_classes]
    pbs = []
    for blk, bk, k in zip(blocks, buckets, dims):
        plan = cache.plan_for(blk, n_dst=bk.n_dst, n_src=bk.n_src,
                              nnz=bk.nnz, k_hint=k)
        pbs.append(pack_block(blk, n_dst=bk.n_dst, n_src=bk.n_src,
                              nnz=bk.nnz, plan=plan, ell_width=bk.ell_width,
                              sell_steps=bk.sell_steps))
    pbs = tuple(pbs)
    init, conv, apply_blocks, _ = make_block_model(
        'sage-mean', ds.num_features, 32, ds.num_classes, 2)
    params = init(jax.random.PRNGKey(0))
    opt = adamw(1e-2)
    s0 = opt.init(params)
    x, y = jnp.asarray(ds.x), jnp.asarray(ds.y)
    sids, nr = jnp.asarray(seeds), jnp.asarray(B)
    gi = jnp.int32(0)
    step1 = make_minibatch_step(apply_blocks, opt, batch_size=B)
    p1, s1, l1, g1, st1 = step1(params, s0, pbs, sids, nr, x, y, gi,
                                init_step_stats())
    assert int(st1['skipped']) == 0 and int(st1['overflow']) == 0
    mesh = jax.make_mesh((2,), ('data',),
                         axis_types=(jax.sharding.AxisType.Auto,) * 1)
    step2 = make_minibatch_step(apply_blocks, opt, batch_size=B, mesh=mesh,
                                num_shards=2)
    spbs = tuple(stack_blocks([pb, pb]) for pb in pbs)
    p2, s2, l2, g2, st2 = step2(params, s0, spbs, jnp.stack([sids, sids]),
                                jnp.stack([nr, nr]), x, y, gi,
                                init_step_stats())
    leaves = jax.tree_util.tree_leaves
    for a, b in zip(leaves(g1), leaves(g2)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(leaves(p1), leaves(p2)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert float(l1) == float(l2)
    step3 = make_minibatch_step(apply_blocks, opt, batch_size=B, mesh=mesh,
                                num_shards=2, grad_sync='int8')
    p3, s3, l3, g3, st3 = step3(params, s0, spbs, jnp.stack([sids, sids]),
                                jnp.stack([nr, nr]), x, y, gi,
                                init_step_stats())
    for a, b in zip(leaves(g1), leaves(g3)):
        a, b = np.asarray(a), np.asarray(b)
        bound = np.abs(a).max() / 127.0 + 1e-7
        assert np.abs(a - b).max() <= bound, (np.abs(a - b).max(), bound)
    """, devices=2)


def test_minibatch_trainer_data_parallel_lockstep_no_deadlock():
    """train_gnn_minibatch(mesh=) end to end on a data=2 mesh with an
    adversarial seed count (129 seeds, batch 64: pre-fix shard batch
    counts were 2 vs 1 — the psum deadlock). Must finish (a hang trips
    the subprocess timeout), keep the trace <= bucket bound, and land
    near the 1-shard run's accuracy; the int8 wire must also train."""
    _run("""
    import dataclasses
    import numpy as np, jax
    from repro.data import make_dataset
    from repro.train import train_gnn_minibatch
    ds = make_dataset('reddit', scale=1/512, seed=1)
    mask = np.zeros(ds.num_nodes, bool); mask[:129] = True
    ds = dataclasses.replace(ds, train_mask=mask)
    mesh = jax.make_mesh((2, 2), ('data', 'model'),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    r2 = train_gnn_minibatch('sage-mean', ds, fanouts=(4, 4), batch_size=64,
                             hidden=64, epochs=3, seed=0, mesh=mesh)
    assert r2.num_shards == 2 and r2.sync_bytes_per_step > 0
    assert r2.n_traces <= r2.n_buckets, (r2.n_traces, r2.n_buckets)
    assert all(np.isfinite(r2.losses)), r2.losses
    r1 = train_gnn_minibatch('sage-mean', ds, fanouts=(4, 4), batch_size=64,
                             hidden=64, epochs=3, seed=0)
    # sampled training on a ~450-node graph is noisy; the tight 2-point
    # parity criterion lives in benchmarks/bench_sampling.py at 1/32 scale
    assert abs(r1.test_acc - r2.test_acc) < 0.25, (r1.test_acc, r2.test_acc)
    ri = train_gnn_minibatch('sage-mean', ds, fanouts=(4, 4), batch_size=64,
                             hidden=64, epochs=2, seed=0, mesh=mesh,
                             grad_sync='int8')
    assert ri.grad_sync == 'int8' and np.isfinite(ri.losses[-1])
    """, devices=4)


def test_lm_train_step_data_parallel_shard_map():
    """make_data_parallel_step: the LM step under shard_map over 'data'
    with the hand-written gradient collective — fp32 trains (loss
    decreases, state donated), and the int8 compressed_psum wire takes a
    finite step."""
    _run("""
    import numpy as np, jax, jax.numpy as jnp
    from repro.configs import get_smoke_config
    from repro.train import lm as TL
    cfg = get_smoke_config('llama3-8b')
    mesh = jax.make_mesh((2, 2), ('data', 'model'),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    step, opt = TL.make_data_parallel_step(cfg, mesh, lr=1e-3)
    with mesh:
        state = TL.make_train_state(cfg, jax.random.PRNGKey(0), opt)
        rng = np.random.default_rng(0)
        batch = {'tokens': jnp.asarray(rng.integers(0, cfg.vocab, (4, 64)),
                                       jnp.int32),
                 'targets': jnp.asarray(rng.integers(0, cfg.vocab, (4, 64)),
                                        jnp.int32)}
        jstep = jax.jit(step, donate_argnums=0)
        losses = []
        for _ in range(5):
            state, m = jstep(state, batch)
            losses.append(float(m['loss']))
        assert all(np.isfinite(losses)), losses
        assert losses[-1] < losses[0], losses
        step8, opt8 = TL.make_data_parallel_step(cfg, mesh, lr=1e-3,
                                                 compression=True)
        st = TL.make_train_state(cfg, jax.random.PRNGKey(0), opt8,
                                 compression=True)
        st, m8 = jax.jit(step8)(st, batch)
        assert np.isfinite(float(m8['loss'])), m8
    """, devices=4)


def test_ring_allgather_matmul():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.dist.collectives import ring_allgather_matmul
    mesh = jax.make_mesh((4,), ('data',),
                         axis_types=(jax.sharding.AxisType.Auto,) * 1)
    rng = np.random.default_rng(0)
    N, K = 32, 16   # global rows; 4 shards of 8
    A = jnp.asarray(rng.standard_normal((N, N)), jnp.float32)
    H = jnp.asarray(rng.standard_normal((N, K)), jnp.float32)
    def body(a_band, h_loc):
        # a_band: (8, N) local row band; chunks of 8 columns x ring position
        def blocks(src):
            return jax.lax.dynamic_slice(a_band, (0, src * 8), (8, 8))
        return ring_allgather_matmul(blocks, h_loc, 'data')
    with mesh:
        out = jax.jit(jax.shard_map(body, mesh=mesh,
                                    in_specs=(P('data', None), P('data', None)),
                                    out_specs=P('data', None)))(A, H)
    err = float(jnp.abs(out - A @ H).max())
    assert err < 1e-4, err
    """)
