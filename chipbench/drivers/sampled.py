"""Device-sampled minibatch training: the step ``make_device_minibatch_step``
returns, built as ``train_gnn_minibatch(sampler="device")`` builds it.

Set-up: the graph, the program's CSR and device graph, capacities probed
from three host-sampled batches, per-layer plans from ``BlockPlanCache``,
the step, parameters from ``--seed``, and the first ``CHECK_STEPS`` steps
(these compile the one program shape). Window: the same step fed shuffled
train-split seed batches (``seed_batches``, one slice per shard) for
``--seconds``; at most two steps are in flight. ``seeds_per_s`` counts the
real seeds of every shard.

Once the window has closed, the blocks of the first steps are drawn again
from the sampler with the same seeds and round counters, checked against
the graph (``chipbench/lib/blockcheck.py``), and the plain reference
(``chipbench/reference/sage.py``) follows the first steps on them. The check
relies on the sampler being a function of (seeds, round): the redraw's
capacity-overflow count must equal what the timed step counted for the same
steps (``overflow_gap``), and a step that drew other blocks than the redraw
shows in the loss and gradient gaps.

``--seed`` sets the weights, the seed order and the sampling rounds: the
epoch counter starts at ``seed % EPOCH_BASES``. The sampler's own key and
the probe are fixed by the traffic file, so every seed runs the same
compiled program at the same capacities.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.lib import blockcheck, compare, counts, graphgen, program, trace
from chipbench.reference import sage as ref

CHECK_STEPS = 3
EPOCH_BASES = 5000      # (5000 + epochs) * 100003 * 4 stays inside int32
IN_FLIGHT = 2


class Setup:
    """Everything ``train_gnn_minibatch(sampler="device")`` builds before
    its first step, for one process."""

    def __init__(self, cell, root: str):
        from repro.core import sparse as sp
        from repro.core.patch import patched
        from repro.optim import adamw
        from repro.sampling import (BlockPlanCache, DeviceSampler,
                                    NeighborSampler, device_graph_from_csr)
        from repro.train.gnn_minibatch import make_block_model
        cf, tr = cell.config, cell.traffic
        self.cell = cell
        self.shards = int(tr["shards"])
        self.batch = int(tr["batch_per_shard"])
        self.fanouts = tuple(cf["fanouts"])
        self.g = graphgen.host_graph(tr["dataset"], tr["graph_seed"],
                                     tr.get("scale", 1.0), cache_root=root)
        self.n, self.f, self.classes = (int(v) for v in self.g["meta"])
        self.x = graphgen.device_features(self.n, self.f, self.g["y"],
                                          tr["graph_seed"])
        ds = program.graph_dataset(tr["dataset"], self.g, self.x)
        jax.block_until_ready(self.x)
        self.t_graph = time.perf_counter()
        self.train_ids = np.flatnonzero(self.g["split"] == 0)
        self.mesh = None
        if self.shards > 1:
            from repro.dist import make_data_mesh
            self.mesh = make_data_mesh(self.shards)
        csr = sp.csr_from_coo(ds.coo)
        self.init, _, apply_blocks, self.dims = make_block_model(
            cf["arch"], self.f, cf["hidden"], self.classes, len(self.fanouts))
        opt_hp = cf["optimizer"]
        self.opt = adamw(opt_hp["lr"], weight_decay=opt_hp["weight_decay"])
        dgraph = device_graph_from_csr(csr, mesh=self.mesh)
        host = NeighborSampler(csr, self.fanouts, seed=tr["sampler_seed"])
        probe = [host.sample(self.train_ids[:self.batch], round=r)
                 for r in range(3)]
        hops = len(self.fanouts)
        caps = [int(1.5 * max(p[hops - 1 - j].n_src for p in probe))
                for j in range(hops)]
        self.dev = DeviceSampler(dgraph, self.fanouts, batch_size=self.batch,
                                 seed=tr["sampler_seed"], src_caps=caps)
        plans = BlockPlanCache(semiring=cf["arch"].split("-")[1])
        self.dev.set_plans([
            plans.plan_for(blk, n_dst=bk.n_dst, n_src=bk.n_src, nnz=bk.nnz,
                           k_hint=k, sell_ok=False)
            for blk, bk, k in zip(probe[0], self.dev.buckets, self.dims)])
        self.apply_blocks = apply_blocks
        self._patch = patched(bool(tr["use_isplib"]))
        self._patch.__enter__()       # held for the process, as the trainer
        self.build_step()             # holds it for a run
        self.y = jnp.asarray(self.g["y"])
        self.xs, self.ys = self._replicated((self.x, self.y))
        self._sample = None
        self.t_built = time.perf_counter()

    def build_step(self) -> None:
        from repro.train.gnn_minibatch import make_device_minibatch_step
        self.step = make_device_minibatch_step(
            self.apply_blocks, self.opt, self.dev, batch_size=self.batch,
            mesh=self.mesh, num_shards=self.shards)

    def _replicated(self, tree):
        if self.mesh is None:
            return jax.device_put(tree)
        from repro.dist import replicated_sharding
        return jax.device_put(tree, replicated_sharding(self.mesh))

    def init_state(self, seed: int):
        from repro.train.gnn_minibatch import init_step_stats
        params = self.init(jax.random.PRNGKey(seed))
        return self._replicated((params, self.opt.init(params),
                                 init_step_stats()))

    def feed(self, seed: int):
        """Yield ``(seeds (shards, batch), n_real (shards,), rnd, gstep)``
        per step, as ``run_epoch_device`` feeds the step."""
        from repro.sampling.loader import num_seed_batches, seed_batches
        per_epoch = num_seed_batches(len(self.train_ids), self.batch,
                                     num_shards=self.shards)
        base = seed % EPOCH_BASES
        gstep = 0
        for epoch in range(base, base + 10 ** 6):
            shard_iters = [seed_batches(self.train_ids, self.batch,
                                        shuffle=True, seed=seed, epoch=epoch,
                                        num_shards=self.shards,
                                        shard_index=si)
                           for si in range(self.shards)]
            for bi, group in enumerate(zip(*shard_iters)):
                rnd = (epoch * 100003 + bi) * self.shards
                yield (np.stack([g[0] for g in group]).astype(np.int32),
                       np.asarray([g[1] for g in group], np.int32), rnd, gstep)
                gstep += 1
            assert bi + 1 == per_epoch

    def call(self, state, item):
        """One step through the program's compiled step."""
        params, opt_state, stats = state
        seeds, n_real, rnd, gstep = item
        if self.mesh is None:
            sids, nrs = jnp.asarray(seeds[0]), jnp.asarray(n_real[0])
        else:
            from repro.dist import leading_axis_sharding
            place = leading_axis_sharding(self.mesh, "data")
            sids = jax.device_put(jnp.asarray(seeds), place)
            nrs = jax.device_put(jnp.asarray(n_real), place)
        params, opt_state, loss, _, stats = self.step(
            params, opt_state, sids, nrs, jnp.int32(rnd), self.xs, self.ys,
            jnp.int32(gstep), stats)
        return (params, opt_state, stats), loss

    def blocks(self, item) -> tuple[list, int]:
        """Per shard, the blocks the sampler draws for ``item``, as numpy,
        and the capacity overflow of all shards."""
        if self._sample is None:
            n = self.n

            def sample(g, seeds, n_real, rnd):
                mask = jnp.arange(self.batch) < n_real
                s = jnp.where(mask, seeds, jnp.int32(n))
                return self.dev.with_graph(g).sample_blocks_stats(s, rnd)
            self._sample = jax.jit(sample)
            # one chip redraws every shard's blocks: a Pallas kernel cannot
            # be partitioned over the mesh the replicated graph lives on
            self._graph = jax.device_put(self.dev.graph, jax.devices()[0])
        seeds, n_real, rnd, _ = item
        out, overflow = [], 0
        for si in range(self.shards):
            pbs, ovf = self._sample(self._graph, jnp.asarray(seeds[si]),
                                    jnp.int32(n_real[si]),
                                    jnp.int32(rnd + si))
            out.append([{k: np.asarray(getattr(pb, k)) for k in
                         ("src_ids", "dst_pos", "row", "col", "val")}
                        for pb in pbs])
            overflow += int(ovf)
        return out, overflow

    def reference(self, seed: int, items, dtype=jnp.float32,
                  loss_share=1.0):
        """Check the blocks of ``items`` and follow them with the reference.
        Returns (block faults, the blocks' capacity overflow, losses, first
        gradient, final params, per-step (rows, edges) of each shard's
        blocks)."""
        index = blockcheck.EdgeIndex(self.g["src"], self.g["dst"], self.n)
        faults, overflow, steps, work = 0, 0, [], []
        for item in items:
            seeds, n_real = item[0], item[1]
            shards = []
            drawn, ovf = self.blocks(item)
            overflow += ovf
            for si, blocks in enumerate(drawn):
                bad, views = blockcheck.check_blocks(
                    blocks, seeds[si], int(n_real[si]), self.fanouts, index)
                faults += bad
                if bad:
                    continue
                shards.append((jax.tree_util.tree_map(jnp.asarray, views),
                               seeds[si], int(n_real[si])))
                work.append([(int(np.sum(v["self_pos"] < len(v["src_ids"]))),
                              int(np.sum(v["nbr_row"] < len(v["self_pos"]))))
                             for v in views])
            steps.append(shards)
        if faults:
            return faults, overflow, None, None, None, work
        params = ref.init_params(seed, self.dims)
        losses, grad, final = ref.train(
            params, steps, self.x, lambda s: jnp.take(self.y, jnp.asarray(s)),
            self.cell.config["optimizer"],
            self.cell.config["matmul_precision"], dtype=dtype,
            loss_share=loss_share)
        return faults, overflow, losses, grad, final, work


def numbers(prog: dict, faults, overflow, ref_losses, ref_grad, ref_final,
            ref_p0) -> dict:
    out = {"block_faults": float(faults),
           "overflow_gap": float(abs(overflow - prog["overflow"]))}
    if ref_losses is None:
        return out
    leaves = compare.counted_leaves(ref_grad)
    out["loss1_gap"] = compare.rel_gap(prog["losses"][0], ref_losses[0])
    out["loss_gap"] = max(compare.rel_gap(a, b)
                          for a, b in zip(prog["losses"], ref_losses))
    out["grad_gap"] = compare.leaf_norm_gap(prog["grad"], ref_grad, leaves)[0]
    out["update_gap"] = compare.leaf_norm_gap(
        compare.tree_sub(prog["final"], prog["p0"]),
        compare.tree_sub(ref_final, ref_p0), leaves)[0]
    return out


def first_steps(s: Setup, seed: int, feed) -> tuple:
    """Drive the step from ``seed`` through ``CHECK_STEPS`` steps and keep
    what the comparison reads: each loss, the first gradient as the
    optimizer got it (Adam's first moment after one step over 1 - b1), the
    parameters before and after, and the capacity overflow the step
    counted."""
    state = s.init_state(seed)
    p0 = jax.device_get(state[0])
    ovf0 = int(state[2]["overflow"])
    items, losses, grad = [], [], None
    b1 = s.cell.config["optimizer"]["b1"]
    for _ in range(CHECK_STEPS):
        item = next(feed)
        state, loss = s.call(state, item)
        items.append(item)
        losses.append(loss)
        if grad is None:
            grad = jax.tree_util.tree_map(lambda m: np.asarray(m) / (1 - b1),
                                          jax.device_get(state[1].mu))
    prog = {"losses": [float(v) for v in losses], "grad": grad, "p0": p0,
            "final": jax.device_get(state[0]),
            "overflow": int(state[2]["overflow"]) - ovf0}
    return state, items, prog


def run(spec) -> dict:
    s = Setup(spec.cell, spec.root)
    feed = s.feed(spec.seed)
    state, items, prog = first_steps(s, spec.seed, feed)
    setup_s = time.perf_counter() - spec.t_start
    pending, out = [], []
    prev = np.asarray(jax.device_get(state[2].values), np.int64)
    with trace.capture(spec.trace_dir, window=True):
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < spec.seconds:
            item = next(feed)
            state, loss = s.call(state, item)
            pending.append(loss)
            out.append((item, loss, state[2].values))
            if len(pending) > IN_FLIGHT:
                jax.block_until_ready(pending.pop(0))
        jax.block_until_ready(state)
        window_s = time.perf_counter() - t0
    memory = trace.memory_peak(spec.devices)
    losses, stats = jax.device_get(([o[1] for o in out],
                                    [o[2] for o in out]))
    seeds = sum(int(o[0][1].sum()) for o in out)
    failed = 0
    for loss, st in zip(losses, stats):
        if not np.isfinite(loss) or np.any(st > prev):
            failed += 1
        prev = np.maximum(prev, st)
    state = out = None
    t_ref = time.perf_counter()
    faults, overflow, ref_losses, ref_grad, ref_final, blocks_work = \
        s.reference(spec.seed, items)
    reference_s = time.perf_counter() - t_ref
    checked_seeds = sum(int(i[1].sum()) for i in items)
    flops = sum(counts.sage_step_flops(layers, s.dims)
                for layers in blocks_work)
    work = {"steps": len(losses), "seeds": seeds,
            "flops_per_seed": flops / checked_seeds if blocks_work else 0.0}
    ref_p0 = jax.device_get(ref.init_params(spec.seed, s.dims))
    return {
        "end_to_end": {"setup_s": setup_s, "seeds_per_s": seeds / window_s},
        "numbers": numbers(prog, faults, overflow, ref_losses, ref_grad,
                           ref_final, ref_p0),
        "setup_parts": {"graph": s.t_graph - spec.t_start,
                        "sampler_and_step": s.t_built - s.t_graph,
                        "first_steps": setup_s - (s.t_built - spec.t_start)},
        "reference_s": reference_s,
        "attempted": len(losses), "failed": failed,
        "memory_peak_bytes": memory, "work": work,
    }
