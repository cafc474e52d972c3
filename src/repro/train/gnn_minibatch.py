"""Minibatch neighbor-sampled GNN training + layer-wise inference.

The production-scale counterpart of ``train/gnn.py``: instead of one
full-graph SpMM per layer per step, each step trains on a seed minibatch
expanded by the fused k-hop sampler (``repro.sampling``), with the
bipartite blocks packed in the autotuner's per-bucket format. An epoch is

    shuffled seed loader -> sample -> bucket -> plan-aware pack -> jitted step

and the step retraces at most once per bucket signature (geometric shape
ladder), not once per batch. Evaluation is exact: layer-wise
*full-neighbor* inference sweeps every node through each layer in batches,
so reported accuracy has no sampling noise — only training does.

Data parallelism (``mesh=``) is *lockstep*: the seed stream splits over
the mesh's 'data' axis under the loader's lockstep contract (equal batch
counts per shard — see ``sampling/loader.py``), each shard samples and
packs its own batch on the host (one batch ahead of the device via
``prefetch`` — the double buffer), and the jitted step runs under
``shard_map`` with the gradients psum'd over 'data' between
``value_and_grad`` and ``opt.update`` (``grad_sync='fp32'`` exact, or
``'int8'`` via ``dist.collectives.compressed_psum`` — the shared-scale
quantized wire). Parameters and optimizer state stay replicated, so every
shard applies the identical update and weights never diverge.

``sampler="device"`` replaces the host half of the pipeline entirely: the
adjacency is ``device_put`` once (``sampling.device_graph``), sampling +
relabel + bucket-static packing are traced (``kernels/sample``), and the
whole sample+pack+step chain compiles into **one** jitted program per
bucket — there is exactly one bucket, since the device capacities are
fixed from ``(batch_size, fanouts)``. The host double-buffer thread has
nothing left to hide on this path and is not used. Lockstep data
parallelism is preserved by sampling from on-device seed shards with a
per-shard round counter (``rnd + axis_index('data')``). Restrictions:
finite fanouts and sum/mean aggregation only (device capacity padding is
inert under sum — see ``sampling/device_graph.py``); draws come from a
different (counter-based) RNG stream than the host sampler, so sampled
edges differ batch-for-batch while the distribution is unchanged.

Both paths honor the paper's two knobs: ``use_isplib`` flips the
patch()/unpatch() registry (tuned packed kernels vs trusted segment ops),
and a ``TuningDB`` persists the per-bucket plan decisions across runs.
Weights are interchangeable with the full-batch trainer (same param
pytree), which is what the accuracy-parity acceptance bench relies on.

**Fault tolerance** (``ckpt_dir=``, ``skip_nonfinite=``, ``faults=``):
long sampled runs survive failures without breaking either determinism or
the lockstep contract. ``ckpt_dir`` checkpoints ``(params, opt_state)``
plus the loader position (the global step) through
``repro.ckpt.Checkpointer``; because every random stream here is
*stateless* — the epoch permutation is keyed ``(seed, epoch)``, host
sampler draws by the round counter, device draws by ``(seed, round, hop,
node, slot)`` — resume is a pure fast-forward: skip the first
``start_batch`` indices of the restart epoch and the replayed tail is
bit-for-bit the schedule the killed run would have executed, so a killed
+ resumed run ends with *bitwise-identical* params. The non-finite guard
skips a poisoned update by a decision that is itself a collective
(``dist.collectives.all_agree``), so one shard's NaN can never strand the
others in the gradient psum; the prefetch worker restarts a bounded
number of times from the delivered-batch count
(``sampling.loader.resilient_prefetch``); device-sampler capacity
overflow is counted on device and escalates to doubled capacities at
epoch end. ``repro.testing.faults`` injects each failure mode for the
``tests/test_fault_injection.py`` suite.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
import warnings
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import sparse as sp
from repro.core.autotune import TuningDB
from repro.core.patch import patched
from repro.models.gnn import layers as L
from repro.obs import stages
from repro.optim import adamw, apply_updates
from repro.sampling import (BlockPlanCache, NeighborSampler, block_spmm_global,
                            gather_rows, merge_buckets, num_seed_batches,
                            pack_block, pad_sell_steps, plan_buckets,
                            resilient_prefetch, round_bucket, seed_batches,
                            stack_blocks)
from repro.train.gnn import _acc, _xent

Array = Any

__all__ = ["train_gnn_minibatch", "MinibatchTrainResult", "make_minibatch_step",
           "make_device_minibatch_step", "make_block_model",
           "layerwise_inference", "MB_ARCHS",
           "GRAD_SYNC_WIRES", "SAMPLERS", "init_step_stats"]

MB_ARCHS = ("sage-sum", "sage-mean", "sage-max", "gin")
GRAD_SYNC_WIRES = ("fp32", "int8")
SAMPLERS = ("host", "device")


@dataclasses.dataclass
class MinibatchTrainResult:
    arch: str
    dataset: str
    use_isplib: bool
    fanouts: tuple
    batch_size: int
    losses: list
    train_acc: float
    test_acc: float
    epoch_time_s: float      # mean sampled-training wall-clock per epoch
    compile_time_s: float    # first (warmup) epoch, includes all retraces
    infer_time_s: float      # one layer-wise full-neighbor inference pass
    n_traces: int            # jitted-step compilations after warmup
    n_buckets: int           # distinct bucket signatures seen
    plan_kinds: tuple        # kernel kinds the bucket plans picked
    epochs: int
    num_shards: int = 1      # 'data'-axis data-parallel degree
    grad_sync: str = "fp32"  # gradient-sync wire format ('fp32' | 'int8')
    sync_bytes_per_step: int = 0   # per-shard gradient bytes on the wire
    sampler: str = "host"    # 'host' numpy pipeline | 'device' traced path
    sample_time_s: float = 0.0     # sample(+pack) stage, one shard-0 epoch
    # -- fault-tolerance accounting --------------------------------------
    skipped_steps: int = 0         # updates skipped by the non-finite guard
    overflow_edges: int = 0        # device-sampler capacity-dropped edges
    capacity_escalations: int = 0  # device capacity re-probes (doublings)
    prefetch_restarts: int = 0     # prefetch-worker recoveries
    resumed_step: int = -1         # global step restored from (-1 = fresh)
    ckpt_saves: int = 0            # checkpoints written this run
    final_params: Any = dataclasses.field(default=None, repr=False)


def _block_arch(arch: str):
    """(aggr-or-None, semiring) for a minibatch-capable arch."""
    if arch not in MB_ARCHS:
        raise ValueError(f"minibatch arch must be one of {MB_ARCHS}, "
                         f"got {arch!r}")
    if arch == "gin":
        return None, "sum"
    aggr = arch.split("-")[1]
    return aggr, aggr


def make_block_model(arch: str, in_dim: int, hidden: int, out_dim: int,
                     n_layers: int):
    """init/apply over a block stack — the step factory shared by the
    minibatch trainer AND the online serving path (``repro.serving``),
    so a served prediction runs the exact computation a training-step
    forward (and therefore the parity suite's offline reference) runs.
    Params are layer-keyed ('l0', 'l1', ...) with the exact per-layer
    structure of the full-batch zoo, so minibatch-trained weights serve
    full-batch apply and vice versa.

    Returns ``(init, conv, apply_blocks, dims)``: ``conv(p_l, pb, h)``
    applies one layer over one packed block, ``apply_blocks(params, pbs,
    h)`` folds a whole block stack with inter-layer relu (none after the
    last layer)."""
    aggr, _ = _block_arch(arch)
    dims = [in_dim] + [hidden] * (n_layers - 1) + [out_dim]
    init_one = L.init_gin if arch == "gin" else L.init_sage

    def init(key):
        keys = jax.random.split(key, n_layers)
        return {f"l{i}": init_one(keys[i], dims[i], dims[i + 1])
                for i in range(n_layers)}

    def conv(p_l, pb, h):
        if arch == "gin":
            return L.gin_conv_block(p_l, pb, h)
        return L.sage_conv_block(p_l, pb, h, aggr=aggr)

    def apply_blocks(params, pbs, h):
        for i, pb in enumerate(pbs):
            h = conv(params[f"l{i}"], pb, h)
            if i < len(pbs) - 1:
                h = stages.dense(jax.nn.relu, h)
        return h

    return init, conv, apply_blocks, dims


def init_step_stats() -> obs.DeviceCounters:
    """Device-resident fault counters the step threads through itself:
    ``skipped`` (updates vetoed by the non-finite guard) and ``overflow``
    (device-sampler capacity-dropped edges). Carried as a jit argument so
    counting costs no per-step host sync — the trainer reads them back
    once per epoch / checkpoint.

    Backed by :class:`repro.obs.DeviceCounters` (the generalized form of
    this pattern): dict-style reads (``int(stats["skipped"])``) keep
    working, updates inside the traced step are functional
    (``stats.add("skipped", 1)``), and ``stats.drain()`` is the one
    deliberate host sync."""
    return obs.device_counters("skipped", "overflow")


def _seed_mask(batch_size: int, n_real):
    return jnp.arange(batch_size) < n_real


def _shard_draw_args(seeds, n_real, rnd):
    """This shard's seeds, real count and round (offset by its index)."""
    return seeds[0], n_real[0], rnd + jax.lax.axis_index("data")


def _seed_xent(logits, y, seed_ids, mask):
    return _xent(logits, jnp.take(y, seed_ids), mask)


def _inject_nan(grads, nan_inject, num_shards: int, step_idx):
    t_step, t_shard = nan_inject
    hit = step_idx == jnp.int32(t_step)
    if num_shards > 1:
        hit = hit & (jax.lax.axis_index("data") == t_shard)
    bad = jnp.where(hit, jnp.float32(jnp.nan), jnp.float32(0.0))
    return jax.tree_util.tree_map(lambda g: g + bad.astype(g.dtype), grads)


def _finite(loss, grads):
    ok = jnp.isfinite(loss)
    for leaf in jax.tree_util.tree_leaves(grads):
        ok = ok & jnp.all(jnp.isfinite(leaf))
    return ok


def _zero_if_skipped(ok, loss, grads):
    grads = jax.tree_util.tree_map(
        lambda g: jnp.where(ok, g, jnp.zeros_like(g)), grads)
    loss = jnp.where(jnp.isfinite(loss), loss, jnp.zeros_like(loss))
    return loss, grads


def _sync(grads, loss, grad_sync: str):
    from repro.dist.collectives import sync_grads
    return (sync_grads(grads, "data", wire=grad_sync),
            jax.lax.pmean(loss, "data"))


def _update(opt, p, s, grads, stats, ok, ovf):
    updates, s_new = opt.update(grads, s, p)
    p_new = apply_updates(p, updates)
    if ok is not None:
        p_new = jax.tree_util.tree_map(
            lambda a, b: jnp.where(ok, a, b), p_new, p)
        s_new = jax.tree_util.tree_map(
            lambda a, b: jnp.where(ok, a, b), s_new, s)
        stats = stats.add("skipped", jnp.where(ok, 0, 1))
    return p_new, s_new, stats.add("overflow", ovf)


def _step_tail(opt, p, s, loss, grads, stats, ovf, *, num_shards: int,
               grad_sync: str, skip_nonfinite: bool, nan_inject, step_idx):
    """Everything between ``value_and_grad`` and the applied update, shared
    by the host- and device-sampled steps: optional NaN injection (test
    harness), the lockstep-safe non-finite guard, the gradient sync, and
    the guarded parameter/optimizer-state select. The collectives run under
    the ``grad_sync`` stage, the rest under ``optimizer``; ``ovf`` (the
    sampler's overflow count) rides into ``stats``.

    The guard's order matters: (1) each shard checks its *local*
    loss+grads for non-finites; (2) the verdict is made global with
    :func:`~repro.dist.collectives.all_agree` — a collective every shard
    issues unconditionally, so all shards agree to keep or skip and no
    later psum can strand a disagreeing shard; (3) poisoned grads are
    zeroed *before* the sync (the int8 wire's shared scale is a pmax over
    ``|g|`` — syncing a NaN first would poison every shard); (4) the
    update is computed unconditionally (same trace either way) and
    discarded with a ``jnp.where`` select on skip, for params *and*
    optimizer state (Adam moments must not ingest a skipped step)."""
    if nan_inject is not None:
        grads = stages.optimizer(_inject_nan, grads, nan_inject, num_shards,
                                 step_idx)
    ok = None
    if skip_nonfinite:
        ok = stages.optimizer(_finite, loss, grads)
        if num_shards > 1:
            from repro.dist.collectives import all_agree
            ok = stages.grad_sync(all_agree, ok, "data")
        loss, grads = stages.optimizer(_zero_if_skipped, ok, loss, grads)
    if num_shards > 1:
        grads, loss = stages.grad_sync(_sync, grads, loss, grad_sync)
    p_new, s_new, stats = stages.optimizer(_update, opt, p, s, grads, stats,
                                           ok, ovf)
    return p_new, s_new, loss, grads, stats


def make_minibatch_step(apply_blocks, opt, *, batch_size: int, mesh=None,
                        num_shards: int = 1, grad_sync: str = "fp32",
                        skip_nonfinite: bool = True, nan_inject=None):
    """Build the jitted minibatch update:
    ``step(params, opt_state, pbs, seed_ids, n_real, x, y, step_idx,
    stats) -> (params, opt_state, loss, grads, stats)``.

    ``x``/``y`` are jit *arguments* (``device_put`` once by the caller),
    not closure constants — a captured feature matrix would be baked into
    every bucket trace as a separate copy. ``step_idx`` is the (traced)
    global step counter and ``stats`` the :func:`init_step_stats` carry.

    With ``num_shards > 1`` the step runs under ``shard_map`` over the
    mesh's 'data' axis: ``pbs``/``seed_ids``/``n_real`` arrive host-stacked
    with a leading shard axis (``in_specs=P('data')`` deals each shard its
    own batch; the body squeezes the unit axis off), params/opt state/
    features are replicated, and the per-shard gradients are reduced with
    :func:`repro.dist.collectives.sync_grads` — exact fp32 psum by
    default, the int8 shared-scale wire with ``grad_sync='int8'``. The
    sync sits between ``value_and_grad`` and ``opt.update`` and
    differentiates nothing; because the reduced tree is identical on every
    shard, the replicated params stay bitwise in lockstep. The returned
    loss is the shard mean; the returned grads are the *synced* tree
    (handy for tests — the device buffers are lazy either way).

    ``skip_nonfinite`` compiles in the lockstep-safe non-finite guard
    (see :func:`_step_tail`); ``nan_inject=(step, shard)`` is the test
    harness's gradient-poisoning hook."""
    if grad_sync not in GRAD_SYNC_WIRES:
        raise ValueError(f"grad_sync must be one of {GRAD_SYNC_WIRES}, "
                         f"got {grad_sync!r}")

    def update(p, s, pbs, seed_ids, n_real, x, y, step_idx, stats):
        def loss_fn(p):
            h = stages.gather(gather_rows, x, pbs[0].src_ids)
            logits = apply_blocks(p, pbs, h)
            return stages.loss(lambda: _seed_xent(
                logits, y, seed_ids, _seed_mask(batch_size, n_real)))

        loss, grads = jax.value_and_grad(loss_fn)(p)
        return _step_tail(opt, p, s, loss, grads, stats, 0,
                          num_shards=num_shards, grad_sync=grad_sync,
                          skip_nonfinite=skip_nonfinite,
                          nan_inject=nan_inject, step_idx=step_idx)

    if num_shards <= 1:
        return jax.jit(update)

    assert mesh is not None, "num_shards > 1 needs the mesh"
    from jax.sharding import PartitionSpec as P

    def body(p, s, pbs, seed_ids, n_real, x, y, step_idx, stats):
        pbs, seed_ids, n_real = stages.gather(
            jax.tree_util.tree_map, lambda a: a[0], (pbs, seed_ids, n_real))
        return update(p, s, pbs, seed_ids, n_real, x, y, step_idx, stats)

    # check_vma=False: the block kernels are Pallas calls, whose outputs
    # carry no varying-axes annotation for the checker to propagate
    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(), P("data"), P("data"), P("data"), P(), P(),
                  P(), P()),
        out_specs=(P(), P(), P(), P(), P()), check_vma=False))


def make_device_minibatch_step(apply_blocks, opt, dev_sampler, *,
                               batch_size: int, mesh=None,
                               num_shards: int = 1,
                               grad_sync: str = "fp32",
                               skip_nonfinite: bool = True, nan_inject=None):
    """Build the fully-fused device-sampled update:
    ``step(params, opt_state, seeds, n_real, rnd, x, y, step_idx, stats)
    -> (params, opt_state, loss, grads, stats)``.

    The blocks never exist outside the trace: ``dev_sampler.sample_blocks``
    runs *inside* the jitted program (sampling is integer-only, so taking
    it outside ``value_and_grad`` just keeps AD away from it — there is
    nothing to differentiate), and the step's static shapes come from the
    sampler's fixed capacities, so the whole chain compiles exactly once.
    Pad seed slots are routed to the ``num_nodes`` sentinel before
    sampling (degree-0 frontier rows -> inert blocks) and masked out of
    the loss as on the host path.

    With ``num_shards > 1`` the step runs under ``shard_map`` over 'data'
    like the host-sampled step, except the per-shard *sampling* also moves
    inside: every shard offsets the replicated round counter by its
    ``axis_index('data')``, so the lockstep round formula
    ``(epoch * 100003 + batch) * num_shards + shard`` from the host path
    carries over unchanged — shards draw from disjoint counter streams and
    the gradient psum contract (PR 5) is untouched.

    The capacity-overflow count from
    :meth:`~repro.sampling.device_graph.DeviceSampler.sample_blocks_stats`
    rides the ``stats`` carry (psum'd over 'data' when sharded, so the
    replicated stats stay identical on every shard)."""
    if grad_sync not in GRAD_SYNC_WIRES:
        raise ValueError(f"grad_sync must be one of {GRAD_SYNC_WIRES}, "
                         f"got {grad_sync!r}")
    num_nodes = dev_sampler.graph.num_nodes

    # The sampled topology is a jit argument (bound below), not a closure
    # constant: at full size its edge arrays would otherwise be baked into
    # the program.
    def draw(g, seeds, n_real, rnd):
        mask = _seed_mask(batch_size, n_real)
        seeds_m = jnp.where(mask, seeds, jnp.int32(num_nodes))
        return mask, dev_sampler.with_graph(g).sample_blocks_stats(seeds_m,
                                                                   rnd)

    def update(g, p, s, seeds, n_real, rnd, x, y, step_idx, stats):
        mask, (pbs, ovf) = stages.sample(draw, g, seeds, n_real, rnd)
        if num_shards > 1:
            ovf = stages.grad_sync(jax.lax.psum, ovf, "data")

        def loss_fn(p):
            h = stages.gather(gather_rows, x, pbs[0].src_ids)
            logits = apply_blocks(p, pbs, h)
            return stages.loss(_seed_xent, logits, y, seeds, mask)

        loss, grads = jax.value_and_grad(loss_fn)(p)
        return _step_tail(opt, p, s, loss, grads, stats, ovf,
                          num_shards=num_shards, grad_sync=grad_sync,
                          skip_nonfinite=skip_nonfinite,
                          nan_inject=nan_inject, step_idx=step_idx)

    if num_shards <= 1:
        return partial(jax.jit(update), dev_sampler.graph)

    assert mesh is not None, "num_shards > 1 needs the mesh"
    from jax.sharding import PartitionSpec as P

    def body(g, p, s, seeds, n_real, rnd, x, y, step_idx, stats):
        seeds, n_real, rnd = stages.sample(_shard_draw_args, seeds, n_real,
                                           rnd)
        return update(g, p, s, seeds, n_real, rnd, x, y, step_idx, stats)

    return partial(jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(), P(), P("data"), P("data"), P(), P(), P(), P(),
                  P()),
        out_specs=(P(), P(), P(), P(), P()), check_vma=False)),
        dev_sampler.graph)


def _compiles(step) -> int:
    """Compiled-program count of a step from the factories above (the
    device step is the jitted function with its graph bound)."""
    return getattr(step, "func", step)._cache_size()


def layerwise_inference(params, sampler: NeighborSampler, x: Array, *,
                        arch: str, dims: list[int],
                        plan_cache: BlockPlanCache,
                        batch_size: int = 1024,
                        bucket_base: int = 128,
                        upto: Optional[int] = None) -> Array:
    """Exact logits for every node, one layer at a time (the DGL
    inference pattern): layer l is computed for *all* nodes over their
    *full* neighborhoods before layer l+1 starts, so each node's
    representation is sampled-noise-free while peak memory stays
    O(batch x max_deg x K) instead of O(edges x K).

    Blocks ride the same bucket ladder and plan cache as training; the
    dense operand is the full current-layer matrix, so the ELL plans take
    the fused-gather path (``kernels/ops.gathered_ell_spmm``).

    ``upto`` stops after that many layers and returns the hidden matrix
    instead of logits (relu applied after every computed layer, since all
    of them are non-final) — the serving path's historical-embedding
    refresh: the layer-(L-1) matrix this produces is, bit-for-bit, the
    penultimate state the full pass would have used, which is what makes
    historical serving exactly parity-checkable against offline logits."""
    aggr, _ = _block_arch(arch)
    n = sampler.num_nodes
    n_layers = len(dims) - 1
    n_run = n_layers if upto is None else int(upto)
    assert 0 <= n_run <= n_layers, (upto, n_layers)

    @partial(jax.jit, static_argnames=("relu_after",))
    def infer_layer(p_l, pb, h, relu_after):
        agg = block_spmm_global(pb, h, aggr or "sum")
        dst_gids = jnp.take(pb.src_ids, pb.dst_pos, mode="fill",
                            fill_value=h.shape[0])
        h_dst = gather_rows(h, dst_gids)
        if arch == "gin":
            z = (1.0 + p_l["eps"]) * h_dst + agg
            z = jax.nn.relu(z @ p_l["w1"] + p_l["b1"])
            out = z @ p_l["w2"] + p_l["b2"]
        else:
            out = (h_dst @ p_l["w_self"] + agg @ p_l["w_neigh"] + p_l["b"])
        return jax.nn.relu(out) if relu_after else out

    # Full-neighbor blocks depend only on the dst batch, not the layer —
    # sample/relabel once per batch and reuse across layers. Packing
    # depends only on the *plan* (never on K), so packed blocks are
    # memoized per (batch, plan signature): when the per-layer K values
    # tune to the same plan (the common case) the pack cost is paid once.
    batches = []
    for lo in range(0, n, batch_size):
        dst = np.arange(lo, min(lo + batch_size, n))
        blk = sampler.full_block(dst)
        sizes = dict(n_dst=batch_size,
                     n_src=round_bucket(blk.n_src, base=bucket_base),
                     nnz=round_bucket(blk.nnz, base=bucket_base))
        width = round_bucket(int(blk.degrees().max()) if blk.nnz else 1,
                             base=8)
        batches.append((dst, blk, sizes, width, {}))

    h = x
    for li in range(n_run):
        rows = []
        for dst, blk, sizes, width, packed in batches:
            plan = plan_cache.plan_for(blk, k_hint=h.shape[1], **sizes)
            psig = (plan.kind, plan.sell_c, plan.sell_sigma)
            pb = packed.get(psig)
            if pb is None:
                pb = packed[psig] = pack_block(blk, plan=plan,
                                               ell_width=width, **sizes)
            out = infer_layer(params[f"l{li}"], pb, h,
                              relu_after=li < n_layers - 1)
            rows.append(out[: len(dst)])
        h = jnp.concatenate(rows, axis=0)
    return h


def train_gnn_minibatch(arch: str, dataset, *, fanouts=(10, 10),
                        batch_size: int = 256, hidden: int = 128,
                        epochs: int = 5, lr: float = 1e-2,
                        weight_decay: float = 5e-4, use_isplib: bool = True,
                        tune: bool = True, measure_tuning: bool = False,
                        seed: int = 0, tuning_db: Optional[TuningDB] = None,
                        mesh=None, grad_sync: str = "fp32",
                        double_buffer: bool = True, bucket_base: int = 128,
                        infer_batch: int = 1024,
                        sampler: str = "host",
                        skip_nonfinite: bool = True,
                        ckpt_dir: Optional[str] = None,
                        ckpt_every: int = 50, ckpt_keep: int = 3,
                        resume: bool = True,
                        faults=None, prefetch_restarts: int = 2,
                        device_caps=None, max_escalations: int = 2,
                        watchdog=None,
                        profile: bool = False) -> MinibatchTrainResult:
    """Neighbor-sampled minibatch training on ``dataset`` (a
    ``data.graphs.GraphDataset``), one layer per fanout entry.

    ``mesh`` engages lockstep data parallelism over the mesh's 'data'
    axis: the seed stream splits into ``mesh.shape['data']`` shards with
    equal per-shard batch counts (the loader's lockstep contract — short
    shards pad with ``n_real == 0`` tail batches so the gradient
    collective never strands a shard), each step samples and packs one
    batch per shard, and the jitted step runs under ``shard_map`` with
    gradients psum'd over 'data' before ``opt.update`` (``grad_sync``:
    ``'fp32'`` exact, ``'int8'`` = the compressed shared-scale wire).
    Params/optimizer state are replicated and receive the identical
    update on every shard. This is the single-controller view — the host
    feeds all shards; a multi-process launch would hand each process its
    ``jax.process_index()``-th slice of shard indices. Without a mesh (or
    with ``data == 1``) the path is the plain single-shard jit.

    The host sampler is double-buffered one batch ahead of the device
    step (``sampling.loader.prefetch``); ``double_buffer=False`` restores
    the serial alternation (determinism is unaffected either way).
    ``tuning_db`` persists the per-bucket kernel plans (§3.2 amortization
    applied to the sampled workload).

    ``sampler="device"`` moves the whole sampling stage on-device (see
    module docstring): the step samples, relabels, packs and trains in one
    jitted program, ``double_buffer`` is ignored (nothing host-side left
    to overlap), and the per-bucket plans are still chosen by the same
    ``BlockPlanCache``/TuningDB sweep, run once on a representative
    host-sampled batch. Requires finite fanouts and sum/mean aggregation;
    evaluation (layer-wise inference) stays on the host path.

    Fault tolerance (see module docstring for the contract):

    * ``ckpt_dir`` enables checkpoint/resume: every ``ckpt_every`` steps
      (and at the end) the replicated ``(params, opt_state)`` plus the
      run's resume metadata — loss history, device capacities, fault
      counters — are saved atomically/asynchronously; ``resume=True``
      restores the latest committed step and fast-forwards the
      deterministic loader to its ``(epoch, batch)`` position, replaying
      the interrupted run bit-for-bit. ``ckpt_keep`` bounds retained steps.
    * ``skip_nonfinite`` (default on) compiles the lockstep-safe
      non-finite guard into the step: a NaN/Inf loss or gradient on *any*
      shard skips that update on *every* shard (decision psum'd via
      ``all_agree``) and counts it in ``result.skipped_steps``.
    * host-path prefetch-worker deaths restart the pipeline from the
      delivered batch count, at most ``prefetch_restarts`` times per
      epoch stream (``result.prefetch_restarts`` counts them).
    * device-path capacity overflow (edges dropped because the probed
      ``src_caps`` were undersized) is counted on device; a nonzero
      epoch delta escalates — capacities double (clamped to the exact
      worst case) and the sampler+step rebuild — at most
      ``max_escalations`` times. ``device_caps`` pins the initial
      capacities (innermost-first), overriding the probe.
    * ``faults`` (a ``repro.testing.FaultPlan``) injects failures at the
      production injection points; ``watchdog`` (a
      ``train.fault_tolerance.StragglerWatchdog``) observes per-step
      wall-clock (forces a per-step device sync — benchmarking off).

    ``profile=True`` turns the run into a profiled session: the
    ``repro.obs`` tracer is enabled for the duration (with op records) if
    it isn't already, the per-stage spans — ``loader.sample`` /
    ``loader.pack`` / ``loader.h2d`` on the prefetch thread,
    ``train.step`` / ``train.epoch`` / ``train.ckpt`` / ``train.infer``
    on the main thread — carry real durations, and every step is
    ``block_until_ready``-synced so ``train.step`` measures device
    execution rather than dispatch (profile-mode semantics: this sync
    defeats the async pipeline, so profiled epoch times are for
    attribution, not benchmarking). Export afterwards with
    ``obs.write_chrome_trace(path)``. Default off: the spans compile down
    to one flag check each."""
    from repro.dist.mesh import (axis_shard_count, leading_axis_sharding,
                                 replicated_sharding)

    aggr, semiring = _block_arch(arch)
    n_layers = len(fanouts)
    if sampler not in SAMPLERS:
        raise ValueError(f"sampler must be one of {SAMPLERS}, "
                         f"got {sampler!r}")
    if sampler == "device":
        if semiring not in ("sum", "mean"):
            raise ValueError("sampler='device' supports sum/mean "
                             "aggregation only (capacity padding is inert "
                             f"under sum); arch {arch!r} needs {semiring}")
        if any(f is None for f in fanouts):
            raise ValueError("sampler='device' needs finite fanouts")
    with contextlib.ExitStack() as _ctx:
        if profile and not obs.enabled():
            # spans stay in the tracer after return, ready for export
            _ctx.enter_context(obs.profiled(ops=True, fresh=False))
        _ctx.enter_context(patched(use_isplib))
        csr = sp.csr_from_coo(dataset.coo)
        host_sampler = NeighborSampler(csr, fanouts, seed=seed)
        init, conv, apply_blocks, dims = make_block_model(
            arch, dataset.num_features, hidden, dataset.num_classes,
            n_layers)
        params = init(jax.random.PRNGKey(seed))
        opt = adamw(lr, weight_decay=weight_decay)
        opt_state = opt.init(params)
        plan_cache = BlockPlanCache(semiring=semiring, tune=tune,
                                    measure=measure_tuning, db=tuning_db)

        train_ids = np.nonzero(np.asarray(dataset.train_mask))[0]
        num_shards = axis_shard_count(mesh, "data") if mesh is not None else 1

        # device_put the epoch-invariant operands ONCE and thread them as
        # jit arguments — as closure captures they were numpy constants,
        # baking a full feature-matrix copy into every bucket trace.
        if num_shards > 1:
            rep = replicated_sharding(mesh)
            x = jax.device_put(jnp.asarray(dataset.x), rep)
            y = jax.device_put(jnp.asarray(dataset.y), rep)
            # commit the train state to the replicated placement up front:
            # the step returns committed-P() outputs, and a first call on
            # uncommitted arrays would recompile its bucket once
            params = jax.device_put(params, rep)
            opt_state = jax.device_put(opt_state, rep)
            stacked = leading_axis_sharding(mesh, "data")
        else:
            x = jax.device_put(jnp.asarray(dataset.x))
            y = jax.device_put(jnp.asarray(dataset.y))
            stacked = None

        # -- checkpoint/resume state ----------------------------------
        # global step = epoch * steps_per_epoch + batch_index; a committed
        # checkpoint at step N means "N lockstep steps completed". All
        # randomness is stateless (permutation keyed (seed, epoch), draws
        # keyed by round counters), so resuming = restoring the train
        # state and skipping the first divmod(N, steps_per_epoch)[1]
        # batch indices of epoch N // steps_per_epoch — the replayed tail
        # is bitwise the schedule the killed run would have executed.
        steps_per_epoch = num_seed_batches(len(train_ids), batch_size,
                                           num_shards=num_shards)
        ckpt = None
        resumed_step = -1
        start_step = 0
        prior_losses: list = []
        restored_caps = None
        skipped_base = 0          # counters carried over from the killed run
        overflow_base = 0
        escalations = 0
        ckpt_saves = 0
        n_prefetch_restarts = 0
        if ckpt_dir is not None:
            from repro.ckpt import (Checkpointer, checkpoint_extra,
                                    latest_step)
            ckpt = Checkpointer(ckpt_dir, keep=ckpt_keep)
            if resume and latest_step(ckpt_dir) is not None:
                like = {"params": params, "opt_state": opt_state}
                shardings = (jax.tree_util.tree_map(lambda _: rep, like)
                             if num_shards > 1 else None)
                restored, start_step = ckpt.restore(like,
                                                    shardings=shardings)
                params, opt_state = restored["params"], restored["opt_state"]
                resumed_step = start_step
                extra = checkpoint_extra(ckpt_dir, start_step)
                prior_losses = list(extra.get("losses", []))
                restored_caps = extra.get("src_caps")
                skipped_base = int(extra.get("skipped", 0))
                overflow_base = int(extra.get("overflow", 0))
                escalations = int(extra.get("escalations", 0))

        dev = None
        src_caps = None
        nan_inject = faults.nan_grad_at if faults is not None else None
        if sampler == "device":
            from repro.sampling import DeviceSampler, device_graph_from_csr
            dgraph = device_graph_from_csr(csr, mesh=mesh)
            # probe a few host-sampled batches for the per-hop frontier
            # scale: the exact worst case (batch * prod(fanouts+1)) pads
            # every dense layer-0 operand to a size real batches never
            # reach once neighbor sets overlap. 1.5x the observed max,
            # clamped to the worst case inside the sampler, keeps the
            # overflow edge-drop a tail event while the matmuls run at
            # the observed scale.
            probe = [host_sampler.sample(
                train_ids[: min(batch_size, len(train_ids))], round=r)
                for r in range(3)]
            n_hops = len(fanouts)
            # capacity precedence: checkpointed caps (sampling depends on
            # them — a resumed run must truncate exactly like the killed
            # one to replay bitwise) > caller-pinned > probed
            if restored_caps is not None:
                src_caps = [int(c) for c in restored_caps]
            elif device_caps is not None:
                src_caps = [int(c) for c in device_caps]
            else:
                src_caps = [int(1.5 * max(p[n_hops - 1 - j].n_src
                                          for p in probe))
                            for j in range(n_hops)]

            def build_device(caps):
                """(re)build sampler + fused step for ``caps`` — the
                overflow-escalation path calls this again with doubled
                capacities (a fresh trace; the old step's compile count
                is folded into ``extra_traces``)."""
                d = DeviceSampler(dgraph, fanouts, batch_size=batch_size,
                                  seed=seed, base=bucket_base,
                                  src_caps=caps)
                # plans come from the same per-bucket sweep the host path
                # runs (BlockPlanCache -> TuningDB), keyed on the device
                # capacities, fed one representative host-sampled batch;
                # sell_ok=False because device packing cannot build the
                # degree-sorted SELL layout — the sweep measures the best
                # of ELL vs trusted
                d.set_plans([
                    plan_cache.plan_for(blk, n_dst=bk.n_dst, n_src=bk.n_src,
                                        nnz=bk.nnz, k_hint=k, sell_ok=False)
                    for blk, bk, k in zip(probe[0], d.buckets, dims)])
                st = make_device_minibatch_step(
                    apply_blocks, opt, d, batch_size=batch_size, mesh=mesh,
                    num_shards=num_shards, grad_sync=grad_sync,
                    skip_nonfinite=skip_nonfinite, nan_inject=nan_inject)
                return d, st

            dev, step = build_device(src_caps)
        else:
            step = make_minibatch_step(apply_blocks, opt,
                                       batch_size=batch_size, mesh=mesh,
                                       num_shards=num_shards,
                                       grad_sync=grad_sync,
                                       skip_nonfinite=skip_nonfinite,
                                       nan_inject=nan_inject)

        signatures: set[tuple] = set()
        extra_traces = 0            # compiles folded in from rebuilt steps
        losses: list = [float(v) for v in prior_losses]
        stats = init_step_stats()
        if num_shards > 1:
            # commit the carry to the replicated placement like params —
            # an uncommitted scalar on the first call would retrace once
            stats = jax.device_put(stats, rep)

        def save_state(nsteps: int, last, *, blocking: bool = False):
            """Checkpoint ``(params, opt_state)`` + resume metadata at the
            ``nsteps``-completed-steps point. Reading the stats carry here
            forces a device sync — paid only at ckpt cadence."""
            nonlocal ckpt_saves
            ep_losses = list(losses)
            if steps_per_epoch and nsteps % steps_per_epoch == 0 and \
                    last is not None and \
                    len(ep_losses) < nsteps // steps_per_epoch:
                # the save landed exactly on an epoch boundary, before the
                # epoch loop appends this epoch's loss — include it so the
                # restored history matches the resumed epoch count
                ep_losses.append(float(last))
            with obs.span("train.ckpt", step=nsteps):
                drained = stats.drain()   # the deliberate ckpt-cadence sync
                extra = {"losses": ep_losses,
                         "src_caps": src_caps,
                         "skipped": skipped_base + drained["skipped"],
                         "overflow": overflow_base + drained["overflow"],
                         "escalations": escalations}
                ckpt.save(nsteps, {"params": params, "opt_state": opt_state},
                          blocking=blocking, extra=extra)
            ckpt_saves += 1

        def maybe_ckpt(gstep: int, last) -> None:
            if ckpt is not None and ckpt_every > 0 and \
                    (gstep + 1) % ckpt_every == 0:
                save_state(gstep + 1, last)

        def seed_groups(epoch: int):
            """Lockstep per-shard seed batches, zipped (equal lengths by
            the loader contract)."""
            shard_iters = [seed_batches(train_ids, batch_size, shuffle=True,
                                        seed=seed, epoch=epoch,
                                        num_shards=num_shards,
                                        shard_index=si)
                           for si in range(num_shards)]
            return enumerate(zip(*shard_iters))

        def pack_shard(blocks, buckets):
            pbs = []
            for blk, bk, k in zip(blocks, buckets, dims):
                plan = plan_cache.plan_for(blk, n_dst=bk.n_dst,
                                           n_src=bk.n_src, nnz=bk.nnz,
                                           k_hint=k)
                pbs.append(pack_block(
                    blk, n_dst=bk.n_dst, n_src=bk.n_src, nnz=bk.nnz,
                    plan=plan, ell_width=bk.ell_width,
                    sell_steps=bk.sell_steps))
            return pbs

        def batch_stream(epoch: int, start: int = 0):
            """Host half of the pipeline: sample + bucket + pack one
            lockstep batch group per step; runs in the prefetch thread.
            Yields (pbs, seed_ids, n_real, signature). ``start`` skips the
            first batch indices without sampling them — the resume
            fast-forward (and the resilient-prefetch rebuild): every
            stream here is stateless per (seed, epoch, batch index), so
            skipping consumes no randomness and the tail replays
            bit-for-bit."""
            # Shard 0 owns the longest slice, so whenever any shard has
            # real seeds, shard 0 does too — it is packed first and
            # therefore the one that tunes a fresh bucket's plan.
            for bi, group in seed_groups(epoch):
                if bi < start:
                    continue
                with obs.span("loader.sample", batch=bi):
                    shard_blocks = [
                        host_sampler.sample(seed_ids[:n_real],
                                       round=(epoch * 100003 + bi)
                                       * num_shards + si)
                        for si, (seed_ids, n_real) in enumerate(group)]
                with obs.span("loader.pack", batch=bi):
                    buckets = merge_buckets(
                        [plan_buckets(blocks, batch_size=batch_size,
                                      fanouts=fanouts, base=bucket_base)
                         for blocks in shard_blocks])
                    shard_pbs = [pack_shard(blocks, buckets)
                                 for blocks in shard_blocks]
                if num_shards == 1:
                    sig = tuple(pb.bucket_signature for pb in shard_pbs[0])
                    (seed_ids, n_real), = group
                    with obs.span("loader.h2d", batch=bi):
                        item = (tuple(shard_pbs[0]), jnp.asarray(seed_ids),
                                jnp.asarray(n_real), sig)
                    yield item
                else:
                    # unify SELL step counts across shards BEFORE reading
                    # the signature — the padded count is part of the
                    # traced shape, so the recorded bucket must match what
                    # the step actually compiles on
                    layers = []
                    for i in range(n_layers):
                        per = [sp[i] for sp in shard_pbs]
                        if any(pb.sell is not None for pb in per):
                            steps = max(pb.sell.n_steps for pb in per)
                            per = [pad_sell_steps(pb, steps) for pb in per]
                        layers.append(per)
                    sig = tuple(per[0].bucket_signature for per in layers)
                    pbs = tuple(stack_blocks(per) for per in layers)
                    with obs.span("loader.h2d", batch=bi):
                        pbs = jax.device_put(pbs, stacked)
                        sids = jax.device_put(
                            jnp.asarray(np.stack([g[0] for g in group])),
                            stacked)
                        nrs = jax.device_put(
                            jnp.asarray([g[1] for g in group]), stacked)
                    yield pbs, sids, nrs, sig

        # the watchdog starts observing after the first executed epoch:
        # warmup steps' wall-clock is dominated by compiles, which would
        # inflate the EMA baseline stragglers are judged against
        watch_on = False

        def before_step(gstep: int) -> float:
            t0 = time.perf_counter() if watchdog is not None else 0.0
            if faults is not None:      # after t0: an injected straggler
                faults.before_step(gstep)   # delay lands in the window
            return t0

        def after_step(gstep: int, t0: float, last) -> None:
            if watchdog is not None and watch_on:
                jax.block_until_ready(last)
                watchdog.observe(gstep, time.perf_counter() - t0)
            maybe_ckpt(gstep, last)

        def run_epoch(epoch: int, start: int = 0):
            nonlocal params, opt_state, stats, n_prefetch_restarts
            last = None

            def on_restart(n, delivered, exc):
                nonlocal n_prefetch_restarts
                n_prefetch_restarts += 1
                warnings.warn(
                    f"prefetch worker died ({exc!r}); restarted from "
                    f"batch {start + delivered} (restart {n})")

            def mk(delivered: int):
                s = batch_stream(epoch, start=start + delivered)
                return faults.wrap_stream(s) if faults is not None else s

            if double_buffer:
                stream = resilient_prefetch(
                    mk, max_restarts=prefetch_restarts,
                    on_restart=on_restart)
            else:
                stream = mk(0)
            bi = start
            for pbs, sids, nrs, sig in stream:
                gstep = epoch * steps_per_epoch + bi
                t0 = before_step(gstep)
                signatures.add(sig)
                with obs.span("train.step", step=gstep,
                              grad_sync=grad_sync if num_shards > 1
                              else None):
                    params, opt_state, last, _, stats = step(
                        params, opt_state, pbs, sids, nrs, x, y,
                        jnp.int32(gstep), stats)
                    if profile:   # profile-mode semantics: the span times
                        jax.block_until_ready(last)   # execution, not dispatch
                after_step(gstep, t0, last)
                bi += 1
            return last

        def run_epoch_device(epoch: int, start: int = 0):
            """The sampler='device' epoch: the host only feeds seed ids
            and the round counter — sampling, packing and the update are
            one jitted call (no prefetch thread: there is no host stage
            left to overlap with)."""
            nonlocal params, opt_state, stats
            last = None
            for bi, group in seed_groups(epoch):
                if bi < start:
                    continue
                rnd = jnp.int32((epoch * 100003 + bi) * num_shards)
                if num_shards == 1:
                    (seed_ids, n_real), = group
                    sids = jnp.asarray(seed_ids)
                    nrs = jnp.asarray(n_real)
                else:
                    sids = jax.device_put(
                        jnp.asarray(np.stack([g[0] for g in group])),
                        stacked)
                    nrs = jax.device_put(
                        jnp.asarray([g[1] for g in group]), stacked)
                gstep = epoch * steps_per_epoch + bi
                t0 = before_step(gstep)
                signatures.add(dev.signature)
                with obs.span("train.step", step=gstep, sampler="device",
                              grad_sync=grad_sync if num_shards > 1
                              else None):
                    params, opt_state, last, _, stats = step(
                        params, opt_state, sids, nrs, rnd, x, y,
                        jnp.int32(gstep), stats)
                    if profile:
                        jax.block_until_ready(last)
                after_step(gstep, t0, last)
            return last

        epoch_fn = run_epoch_device if sampler == "device" else run_epoch

        start_epoch, start_batch = (divmod(start_step, steps_per_epoch)
                                    if steps_per_epoch else (0, 0))
        executed = 0
        compile_time = 0.0
        post_time = 0.0
        ovf_seen = 0
        loss = None
        try:
            for ep in range(start_epoch, epochs):
                t0 = time.perf_counter()
                with obs.span("train.epoch", epoch=ep):
                    loss = epoch_fn(ep,
                                    start_batch if ep == start_epoch else 0)
                    jax.block_until_ready(loss)
                dt = time.perf_counter() - t0
                if executed == 0:   # first executed epoch compiles buckets
                    compile_time = dt
                else:
                    post_time += dt
                executed += 1
                watch_on = True
                losses.append(float(loss))
                if dev is not None:
                    # capacity-overflow escalation, at the epoch boundary
                    # (never mid-epoch: the rebuild changes the trace and
                    # the sampled stream, so it must land on a schedule
                    # point checkpoints can name)
                    ovf_now = int(stats["overflow"])
                    if ovf_now > ovf_seen and escalations < max_escalations:
                        escalations += 1
                        extra_traces += _compiles(step)
                        src_caps = [2 * c for c in src_caps]
                        warnings.warn(
                            f"device sampler dropped {ovf_now - ovf_seen} "
                            f"edges to capacity overflow in epoch {ep}; "
                            f"escalating capacities to {src_caps} "
                            f"({escalations}/{max_escalations})")
                        dev, step = build_device(src_caps)
                    ovf_seen = ovf_now
        except BaseException:
            # drain any in-flight async save so the directory a restart
            # reads is quiescent, then let the failure propagate
            if ckpt is not None:
                try:
                    ckpt.wait()
                except Exception:
                    pass
            raise
        epoch_time = (post_time / (executed - 1) if executed > 1
                      else compile_time)

        if ckpt is not None:
            if epochs * steps_per_epoch > start_step:
                save_state(epochs * steps_per_epoch, loss, blocking=True)
            ckpt.wait()

        def measure_sample_stage() -> float:
            """Wall-clock of the sample(+pack) stage alone for one shard-0
            epoch — host: the numpy sample/bucket/pack loop; device: the
            jitted ``sample_blocks`` program (compile excluded). The bench
            compares these to show what moving the stage on-device buys."""
            batches = list(seed_batches(train_ids, batch_size, shuffle=True,
                                        seed=seed, epoch=0,
                                        num_shards=num_shards,
                                        shard_index=0))
            if sampler == "device":
                samp = jax.jit(lambda s, nr, r: dev.sample_blocks(
                    jnp.where(jnp.arange(batch_size) < nr, s,
                              jnp.int32(dev.graph.num_nodes)), r))
                out = samp(jnp.asarray(batches[0][0]),
                           jnp.asarray(batches[0][1]), jnp.int32(0))
                jax.block_until_ready(out)
                t0 = time.perf_counter()
                for bi, (sids, nr) in enumerate(batches):
                    out = samp(jnp.asarray(sids), jnp.asarray(nr),
                               jnp.int32(bi))
                jax.block_until_ready(out)
                return time.perf_counter() - t0
            pbs = None
            t0 = time.perf_counter()
            for bi, (sids, nr) in enumerate(batches):
                blocks = host_sampler.sample(sids[:nr], round=bi)
                buckets = plan_buckets(blocks, batch_size=batch_size,
                                       fanouts=fanouts, base=bucket_base)
                pbs = pack_shard(blocks, buckets)
            jax.block_until_ready(pbs)
            return time.perf_counter() - t0

        sample_time = measure_sample_stage()

        t0 = time.perf_counter()
        with obs.span("train.infer"):
            logits = layerwise_inference(params, host_sampler, x, arch=arch,
                                         dims=dims, plan_cache=plan_cache,
                                         batch_size=infer_batch,
                                         bucket_base=bucket_base)
            jax.block_until_ready(logits)
        infer_time = time.perf_counter() - t0

        train_acc = float(_acc(logits, y, dataset.train_mask))
        test_acc = float(_acc(logits, y, dataset.test_mask))

        if num_shards > 1:
            from repro.dist.collectives import wire_bytes
            sync_bytes = wire_bytes(params, grad_sync)
        else:
            sync_bytes = 0

        # drain the device counters once (THE host sync) and mirror them
        # into the metrics registry for the JSONL sink / trace otherData
        drained = stats.drain()
        obs.metrics().counter("train.skipped_steps").inc(drained["skipped"])
        obs.metrics().counter("train.overflow_edges").inc(
            drained["overflow"])

    return MinibatchTrainResult(
        arch=arch, dataset=dataset.name, use_isplib=use_isplib,
        fanouts=tuple(fanouts), batch_size=batch_size, losses=losses,
        train_acc=train_acc, test_acc=test_acc, epoch_time_s=epoch_time,
        compile_time_s=compile_time, infer_time_s=infer_time,
        n_traces=extra_traces + _compiles(step),
        n_buckets=len(signatures),
        plan_kinds=plan_cache.kinds(), epochs=epochs,
        num_shards=num_shards, grad_sync=grad_sync,
        sync_bytes_per_step=sync_bytes, sampler=sampler,
        sample_time_s=sample_time,
        skipped_steps=skipped_base + drained["skipped"],
        overflow_edges=overflow_base + drained["overflow"],
        capacity_escalations=escalations,
        prefetch_restarts=n_prefetch_restarts,
        resumed_step=resumed_step, ckpt_saves=ckpt_saves,
        final_params=jax.device_get(params))
