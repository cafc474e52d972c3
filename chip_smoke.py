#!/usr/bin/env python3
"""Chip smoke run: drive the main GNN training paths once on a TPU and
check what comes out.

    python3 chip_smoke.py             # one chip: phases (b)-(d)
    python3 chip_smoke.py --chips 4   # the four-chip paths only: (e), (f)

Everything runs in this one process (a chip belongs to one process). The
graph is full-size synthetic reddit (``make_dataset("reddit", scale=1)``:
232,965 nodes, ~10.3 M edges, 602 features, 41 classes), weights are
random from a fixed seed.

(a) A TPU must be attached; otherwise the script exits non-zero before
    printing any result.
(b) ``patch()`` + ``build_bundle`` + ``train_gnn`` (GCN, hidden 128), the
    path behind ``python -m repro.launch.train --mode gnn``: the tuned plan
    must be a generated kernel, every kernel dispatch it records must run
    as ``backend="pallas"``, losses must be finite, and the first-step
    loss must match the unpatched run.
(c) One SpMM per plan family the tuner may choose on a TPU against the
    ``kernels/ref.py`` oracle — at full reddit where the family's operands
    fit, else at the largest reddit-shaped size that does.
(d) Device-sampled sage-mean steps (``make_device_minibatch_step``,
    fanouts (10, 10), batch 512): zero capacity overflow, finite losses,
    first-step loss matching the unpatched step.
(e) ``--chips 4``: the lockstep data-parallel minibatch step on a 4-way
    data mesh vs the one-device step on the same batch.
(f) ``--chips 4``: ``distributed_spmm_2d`` on the 2x2 grid vs one-device
    SpMM.

Any failed check raises, which exits non-zero. The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# Tolerances, each with its reason.
#
# LOSS_RTOL — patched vs unpatched first-step loss. The patched path sums
# each row's neighbors in a different order (the SELL/ELL kernel, f32 on
# the MXU at HIGHEST precision) and normalizes A once on the host in
# float64 where the unpatched path normalizes in float32 on the device.
# Each logit moves by a few ulp times the row degree; the mean over the
# training nodes averages that out, so 1e-4 leaves two orders of headroom
# over the ~1e-6 expected while still catching a wrong edge or weight.
LOSS_RTOL = 1e-4
# SpMM vs oracle — both are f32 sums of the same deg_i products in
# different orders; each is within deg_i * eps * sum_j |a_ij h_j| of the
# exact value, so their difference is within twice that, per row.
EPS32 = 2.0 ** -23
# Data-parallel vs one device on the same batch — the psum of four equal
# f32 gradients and the mean over four equal losses round at most twice,
# so they agree to a few ulp of the tensor's scale.
DP_RTOL = 1e-6

BATCH, FANOUTS = 512, (10, 10)      # the sampled cells' batch and fanouts


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def row_bound_check(name, out, ref, abs_ref, deg) -> None:
    """|out - ref| <= 2 * deg_i * eps * (|A||H|)_i (+ a denormal floor)."""
    import numpy as np
    out, ref = np.asarray(out, np.float64), np.asarray(ref, np.float64)
    bound = (2.0 * EPS32 * np.maximum(np.asarray(deg), 1)[:, None]
             * np.asarray(abs_ref, np.float64) + 1e-30)
    err = np.abs(out - ref)
    check(np.isfinite(out).all(), f"{name}: non-finite output")
    ratio = float((err / bound).max())
    log(f"  {name}: max|out-ref| {err.max():.3e}, max err/bound "
        f"{ratio:.3e}")
    check(ratio <= 1.0, f"{name}: error exceeds the f32 summation bound")


def timed(fn, *args):
    import jax
    t0 = time.perf_counter()
    out = fn(*args)
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0


# --------------------------------------------------------------------------
# one chip
# --------------------------------------------------------------------------

def phase_train(ds) -> None:
    """(b) patched full-batch training vs the unpatched first step."""
    import numpy as np
    from repro import obs
    from repro.models.gnn import build_bundle
    from repro.train import train_gnn

    t0 = time.perf_counter()
    bundle = build_bundle(ds, k_hint=128)
    log(f"[b] build_bundle (tune + pack, host) "
        f"{time.perf_counter() - t0:.1f}s")
    for name, g in (("A", bundle.tuned), ("A_norm", bundle.tuned_norm)):
        p = g.plan
        log(f"[b] plan {name}: kind={p.kind} sell_c={p.sell_c} "
            f"sell_sigma={p.sell_sigma} br={p.br} bc={p.bc}")
    check(bundle.tuned_norm.plan.kind != "trusted",
          "the tuner chose the trusted XLA path for GCN's operand")

    with obs.profiled(ops=True) as tracer:
        res = train_gnn("gcn", ds, hidden=128, epochs=4, bundle=bundle,
                        use_isplib=True)
        records = [s for s in tracer.snapshot() if s.name.startswith("op.")]
    backends = sorted({(s.name, s.attrs.get("backend", "-"))
                       for s in records})
    log(f"[b] op records: {backends}")
    kernel_ops = [s for s in records if "backend" in s.attrs]
    check(kernel_ops, "no kernel dispatch was recorded")
    check(all(s.attrs["backend"] == "pallas" for s in kernel_ops),
          "a kernel dispatch of the generated plan ran off Pallas")
    log(f"[b] patched gcn: plan={res.plan_kind} compile+first step "
        f"{res.compile_time_s:.2f}s, step {res.epoch_time_s:.4f}s, "
        f"losses {res.losses}")
    check(all(np.isfinite(res.losses)), "non-finite patched loss")

    base = train_gnn("gcn", ds, hidden=128, epochs=2, bundle=bundle,
                     use_isplib=False)
    rel = abs(res.losses[0] - base.losses[0]) / abs(base.losses[0])
    log(f"[b] unpatched gcn: compile+first step {base.compile_time_s:.2f}s, "
        f"step {base.epoch_time_s:.4f}s, first loss {base.losses[0]!r} vs "
        f"patched {res.losses[0]!r}: rel diff {rel:.3e} "
        f"(limit {LOSS_RTOL})")
    check(rel <= LOSS_RTOL, "first-step loss differs from the unpatched run")


def _oracle(coo, h):
    """The kernels/ref.py SpMM oracle and its |A||H| companion."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    from repro.core.semiring import get_semiring
    from repro.kernels.ref import spmm_coo_ref
    sr = get_semiring("sum")
    ref_fn = jax.jit(lambda a, x: spmm_coo_ref(a, x, sr))
    ref = ref_fn(coo, h)
    abs_coo = dataclasses.replace(coo, val=jnp.abs(coo.val))
    return ref, ref_fn(abs_coo, jnp.abs(h))


def _degrees(coo):
    import numpy as np
    return np.bincount(np.asarray(coo.row)[: coo.nse], minlength=coo.nrows)


def phase_families(ds) -> None:
    """(c) one SpMM per TPU-eligible plan family vs the oracle."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import sparse as sp
    from repro.core.autotune import (KernelPlan, graph_stats, plan_fit_error,
                                     probe_hardware)
    from repro.data import DATASETS, make_dataset
    from repro.kernels import ops as kops

    hw = probe_hardware()
    log(f"[c] hardware model {hw.name}: {hw.peak_flops:.3g} FLOP/s, "
        f"{hw.hbm_bw:.3g} B/s, {hw.hbm_bytes / 1e9:.0f} GB HBM")
    rng = np.random.default_rng(0)
    coo = ds.coo
    n = coo.nrows
    h = jnp.asarray(rng.standard_normal((n, 128)).astype(np.float32))
    ref, abs_ref = _oracle(coo, h)
    deg = _degrees(coo)

    # SELL: full reddit, slice height 8, one global degree sort
    stats = graph_stats(coo, tile_candidates=((128, 128),))
    plan = KernelPlan(kind="sell", sell_c=8, sell_sigma=0)
    check(plan_fit_error(stats, plan, hw) is None, "SELL does not fit")
    sell = sp.sell_from_coo(coo, c=plan.sell_c, sigma=plan.sell_sigma)
    out, dt = timed(jax.jit(kops.sell_spmm), sell, h)
    log(f"[c] sell c=8 full reddit ({sell.n_steps} steps, {sell.nslices} "
        f"slices, K=128): first call {dt:.2f}s")
    row_bound_check("sell", out, ref, abs_ref, deg)

    # BSR: tens of GB of tiles at full reddit — the largest reddit-shaped
    # scale whose packed tiles and index tables fit the chip
    bsr_plan = KernelPlan(kind="bsr", br=128, bc=128, fk=128)
    small, scale = ds, ds.num_nodes / DATASETS["reddit"].nodes
    while True:
        why = plan_fit_error(graph_stats(small.coo, ((128, 128),), ()),
                             bsr_plan, hw)
        if why is None:
            break
        log(f"[c] bsr at reddit scale {scale:.4g}: {why}")
        scale /= 2
        small = make_dataset("reddit", scale=scale, seed=0)
    bsr = sp.bsr_from_coo(small.coo, br=128, bc=128)
    hs = jnp.asarray(rng.standard_normal((small.num_nodes, 128))
                     .astype(np.float32))
    out, dt = timed(jax.jit(lambda a, x: kops.bsr_spmm(a, x, fk=128)), bsr,
                    hs)
    log(f"[c] bsr128x128 at reddit scale {scale:.4g} ({small.num_nodes} "
        f"nodes, {bsr.nblocks} blocks): first call {dt:.2f}s")
    ref_s, abs_s = _oracle(small.coo, hs)
    row_bound_check("bsr", np.asarray(out)[: small.num_nodes], ref_s, abs_s,
                    _degrees(small.coo))

    # ELL: the tuner takes it only for bounded degree, which sampled blocks
    # have — full reddit with every row capped at the fanout (10), all
    # 232,965 rows over the 602-wide features
    row = np.asarray(coo.row)[: coo.nse]
    col = np.asarray(coo.col)[: coo.nse]
    start = np.concatenate([[0], np.cumsum(deg)])[:-1]
    keep = (np.arange(coo.nse) - np.repeat(start, deg)) < 10
    capped = sp.coo_from_edges(col[keep], row[keep],
                               np.asarray(coo.val)[: coo.nse][keep], n, n)
    ell = sp.ell_from_coo(capped)
    x = ds.x
    out, dt = timed(jax.jit(kops.ell_spmm), ell, x)
    log(f"[c] ell full reddit capped at 10 ({ell.nrows}x{ell.max_deg}, "
        f"K={x.shape[1]}): first call {dt:.2f}s")
    ref_e, abs_e = _oracle(capped, x)
    row_bound_check("ell", out, ref_e, abs_e, _degrees(capped))
    log("[c] trusted: the XLA path is spmm_coo_ref itself, the oracle")


def phase_device_sampled(ds) -> None:
    """(d) device-sampled sage-mean steps, patched vs unpatched."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import obs
    from repro.core import sparse as sp
    from repro.core.patch import patched
    from repro.optim import adamw
    from repro.sampling import (BlockPlanCache, DeviceSampler,
                                NeighborSampler, device_graph_from_csr)
    from repro.train.gnn_minibatch import (init_step_stats,
                                           make_block_model,
                                           make_device_minibatch_step)

    batch, fanouts = BATCH, FANOUTS
    csr = sp.csr_from_coo(ds.coo)
    init, _, apply_blocks, dims = make_block_model(
        "sage-mean", ds.num_features, 128, ds.num_classes, len(fanouts))
    params = init(jax.random.PRNGKey(0))
    opt = adamw(1e-2, weight_decay=5e-4)
    x, y = jax.device_put(ds.x), jax.device_put(ds.y)
    train_ids = np.nonzero(np.asarray(ds.train_mask))[0].astype(np.int32)

    dev = DeviceSampler(device_graph_from_csr(csr), fanouts,
                        batch_size=batch, seed=0)
    probe = NeighborSampler(csr, fanouts, seed=0).sample(train_ids[:batch])
    cache = BlockPlanCache(semiring="mean")
    dev.set_plans([cache.plan_for(blk, n_dst=bk.n_dst, n_src=bk.n_src,
                                  nnz=bk.nnz, k_hint=k, sell_ok=False)
                   for blk, bk, k in zip(probe, dev.buckets, dims)])
    log(f"[d] device sampler buckets {dev.signature}")

    def run(patch_on: bool, steps: int):
        with patched(patch_on), obs.profiled(ops=True) as tracer:
            step = make_device_minibatch_step(apply_blocks, opt, dev,
                                              batch_size=batch)
            p, s, stats = params, opt.init(params), init_step_stats()
            losses, times = [], []
            for i in range(steps):
                seeds = jnp.asarray(train_ids[i * batch:(i + 1) * batch])
                (p, s, loss, _, stats), dt = timed(
                    step, p, s, seeds, jnp.int32(batch), jnp.int32(i), x, y,
                    jnp.int32(i), stats)
                losses.append(float(loss))
                times.append(dt)
            ops = sorted({(r.name, r.attrs.get("backend", "-"))
                          for r in tracer.snapshot()
                          if r.name.startswith("op.")})
        return losses, times, int(stats["overflow"]), ops

    losses, times, ovf, ops = run(True, 5)
    log(f"[d] patched: op records {ops}")
    log(f"[d] patched: compile+first step {times[0]:.2f}s, steps "
        f"{[round(t, 4) for t in times[1:]]}s, losses {losses}, "
        f"overflow {ovf}")
    check(ovf == 0, "device sampler dropped edges (capacity overflow)")
    check(all(np.isfinite(losses)), "non-finite device-sampled loss")
    check(all(b == "pallas" for _, b in ops if b != "-"),
          "a sampled-block kernel ran off Pallas")
    base, _, _, _ = run(False, 1)
    rel = abs(losses[0] - base[0]) / abs(base[0])
    log(f"[d] unpatched first loss {base[0]!r} vs patched {losses[0]!r}: "
        f"rel diff {rel:.3e} (limit {LOSS_RTOL})")
    check(rel <= LOSS_RTOL, "device-sampled first loss differs unpatched")


# --------------------------------------------------------------------------
# four chips
# --------------------------------------------------------------------------

def _on_all(arr, n: int, what: str) -> None:
    devs = arr.sharding.device_set
    check(len(devs) == n, f"{what} is on {len(devs)} devices, not {n}")


def phase_data_parallel(ds, n: int) -> None:
    """(e) lockstep data-parallel step on a data mesh vs one device."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import sparse as sp
    from repro.core.patch import patched
    from repro.dist import (leading_axis_sharding, make_data_mesh,
                            replicated_sharding)
    from repro.optim import adamw
    from repro.sampling import (BlockPlanCache, NeighborSampler, pack_block,
                                plan_buckets, stack_blocks)
    from repro.train.gnn_minibatch import (init_step_stats,
                                           make_block_model,
                                           make_minibatch_step)

    batch, fanouts = BATCH, FANOUTS
    csr = sp.csr_from_coo(ds.coo)
    seeds = np.nonzero(np.asarray(ds.train_mask))[0][:batch]
    blocks = NeighborSampler(csr, fanouts, seed=0).sample(seeds, round=1)
    buckets = plan_buckets(blocks, batch_size=batch, fanouts=fanouts)
    init, _, apply_blocks, dims = make_block_model(
        "sage-mean", ds.num_features, 128, ds.num_classes, len(fanouts))
    cache = BlockPlanCache(semiring="mean")
    pbs = tuple(
        pack_block(blk, n_dst=bk.n_dst, n_src=bk.n_src, nnz=bk.nnz,
                   plan=cache.plan_for(blk, n_dst=bk.n_dst, n_src=bk.n_src,
                                       nnz=bk.nnz, k_hint=k),
                   ell_width=bk.ell_width, sell_steps=bk.sell_steps)
        for blk, bk, k in zip(blocks, buckets, dims))
    log(f"[e] block plans {[pb.plan_kind for pb in pbs]}")
    params = init(jax.random.PRNGKey(0))
    opt = adamw(1e-2)
    s0 = opt.init(params)
    x, y = jax.device_put(ds.x), jax.device_put(ds.y)
    sids, nr, gi = jnp.asarray(seeds), jnp.int32(batch), jnp.int32(0)

    mesh = make_data_mesh(n)
    with patched(True):
        one = make_minibatch_step(apply_blocks, opt, batch_size=batch)
        (p1, _, l1, g1, _), dt1 = timed(one, params, s0, pbs, sids, nr, x, y,
                                        gi, init_step_stats())
        dp = make_minibatch_step(apply_blocks, opt, batch_size=batch,
                                 mesh=mesh, num_shards=n)
        place = leading_axis_sharding(mesh)
        spbs = jax.device_put(tuple(stack_blocks([pb] * n) for pb in pbs),
                              place)
        ssids = jax.device_put(jnp.stack([sids] * n), place)
        snr = jax.device_put(jnp.stack([nr] * n), place)
        _on_all(ssids, n, "the stacked seed batch")
        rep = replicated_sharding(mesh)
        pr, sr, xr, yr = jax.device_put((params, s0, x, y), rep)
        (pn, _, ln, gn, _), dtn = timed(dp, pr, sr, spbs, ssids, snr, xr,
                                        yr, gi, init_step_stats())
    for leaf in jax.tree_util.tree_leaves(pn):
        _on_all(leaf, n, "a data-parallel parameter")
    leaves = jax.tree_util.tree_leaves
    gerr = max(float(jnp.abs(a - b).max() / jnp.maximum(jnp.abs(a).max(),
                                                       1e-30))
               for a, b in zip(leaves(g1), leaves(gn)))
    perr = max(float(jnp.abs(a - b).max() / jnp.maximum(jnp.abs(a).max(),
                                                       1e-30))
               for a, b in zip(leaves(p1), leaves(pn)))
    lerr = abs(float(l1) - float(ln)) / abs(float(l1))
    log(f"[e] {n}-way data parallel vs 1 device, same batch: loss "
        f"{float(l1)!r} vs {float(ln)!r} (rel {lerr:.3e}), grads rel "
        f"{gerr:.3e}, params rel {perr:.3e} (limit {DP_RTOL}); compile+step "
        f"1 device {dt1:.2f}s, {n} devices {dtn:.2f}s")
    check(max(lerr, gerr, perr) <= DP_RTOL,
          "data-parallel step differs from the one-device step")


def phase_2d(ds, n: int) -> None:
    """(f) 2-D vertex-cut SpMM on the grid mesh vs one device."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.autotune import KernelPlan
    from repro.dist import distributed_spmm_2d, make_grid_mesh, partition_2d

    grid = make_grid_mesh(n)
    pr, pc = grid.shape["row"], grid.shape["col"]
    t0 = time.perf_counter()
    g2 = partition_2d(ds.coo, pr, pc, plan=KernelPlan(kind="sell", sell_c=8))
    log(f"[f] partition_2d {pr}x{pc} (host) {time.perf_counter() - t0:.1f}s")
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.standard_normal((ds.num_nodes, 128))
                    .astype(np.float32))
    with grid:                  # the tiles are an argument, not constants
        out, dt = timed(jax.jit(
            lambda g, hh: distributed_spmm_2d(g, hh, grid)), g2, h)
    _on_all(out, n, "the 2-D SpMM output")
    ref, abs_ref = _oracle(ds.coo, h)
    log(f"[f] distributed_spmm_2d on {pr}x{pc}: compile+call {dt:.2f}s")
    row_bound_check("spmm_2d", np.asarray(out)[: ds.num_nodes], ref, abs_ref,
                    _degrees(ds.coo))


# --------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":                       # (a)
        print(f"chip_smoke: no TPU attached (found "
              f"{devices[0].platform}); nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              f"device(s) attached", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import configure_compile_cache
    log(f"[a] {len(devices)} x {devices[0].device_kind} "
        f"({devices[0].platform}); compile cache "
        f"{configure_compile_cache(ROOT)}")

    from repro.core.patch import patched
    from repro.data import make_dataset
    t0 = time.perf_counter()
    ds = make_dataset("reddit", scale=1, seed=0)
    log(f"[a] reddit scale 1: {ds.num_nodes} nodes, {ds.coo.nse} edges, "
        f"{ds.num_features} features, {ds.num_classes} classes "
        f"(host, {time.perf_counter() - t0:.1f}s)")
    t_all = time.perf_counter()
    if args.chips == 1:
        with patched(True):
            phase_train(ds)
            phase_families(ds)
            phase_device_sampled(ds)
    else:
        phase_data_parallel(ds, args.chips)
        phase_2d(ds, args.chips)
    log(f"[a] phases took {time.perf_counter() - t_all:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
