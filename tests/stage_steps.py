"""The training steps at tiny size, compiled, with each optimized-HLO
instruction's stage frames: the helpers of ``tests/test_stages.py``, also
imported by its multi-device subprocess."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:                 # chipbench.lib.xplane
    sys.path.insert(0, ROOT)

STAGES_FILE = "repro/obs/stages.py"


def hlo_instructions(compiled) -> dict:
    """Instruction name -> (opcode, name stack, creating stack) of a
    compiled program's optimized HLO, read as the benchmark reads the
    profiler's copy (``chipbench.lib.xplane``)."""
    from chipbench.lib import xplane
    mod = compiled.runtime_executable().hlo_modules()[0] \
        .as_serialized_hlo_module_proto()
    n, head = len(mod), bytearray(b"\x0a")     # HloProto.hlo_module = 1
    while True:
        head.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            break
    return xplane.instructions(bytes(head) + mod)


def stage_frames(compiled) -> list:
    """(instruction, opcode, stage functions in its stack) of every
    instruction that has a creating stack."""
    return [(name, opcode, [fn for f, fn in stack
                            if f.endswith(STAGES_FILE)])
            for name, (opcode, _, stack) in hlo_instructions(compiled).items()
            if stack]


def _fresh_traces() -> None:
    """Drop JAX's cached traces. A jnp function that JAX jits on its own
    (``sort``, ``searchsorted``, ...) keeps the ops of its first trace in
    the process, creating stacks and all; an earlier call of the sampler
    or the tuner outside any step would leave its stacks, without a stage,
    in the step compiled here."""
    jax.clear_caches()


def fullbatch_step(ds, use_isplib: bool, arch: str = "gcn", plan=None):
    """``train_gnn``'s step of ``arch`` (GCN by default), compiled."""
    from repro.core.patch import patched
    from repro.models.gnn import build_bundle, make_gnn
    from repro.optim import adamw
    from repro.train.gnn import make_gnn_step
    with patched(use_isplib):
        bundle = build_bundle(ds, k_hint=16, plan=plan,
                              slot_perm=arch == "gat")
        init, apply = make_gnn(arch, ds.num_features, 16, ds.num_classes)
        params = init(jax.random.PRNGKey(0))
        opt = adamw(1e-2)
        step = make_gnn_step(apply, opt)
        _fresh_traces()
        return step.lower(params, opt.init(params), bundle, ds.x, ds.y,
                          ds.train_mask).compile()


def device_step(ds, shards: int = 1, batch: int = 32):
    """The device-sampled GraphSAGE-mean step, compiled; ``shards`` > 1
    runs it data parallel over that many devices."""
    from repro.core import sparse as sp
    from repro.core.patch import patched
    from repro.optim import adamw
    from repro.sampling import (BlockPlanCache, DeviceSampler,
                                NeighborSampler, device_graph_from_csr)
    from repro.train.gnn_minibatch import (init_step_stats, make_block_model,
                                           make_device_minibatch_step)
    fanouts = (5, 5)
    mesh = None
    if shards > 1:
        from repro.dist import make_data_mesh
        mesh = make_data_mesh(shards)
    csr = sp.csr_from_coo(ds.coo)
    init, _, apply_blocks, dims = make_block_model(
        "sage-mean", ds.num_features, 16, ds.num_classes, len(fanouts))
    opt = adamw(1e-2)
    dev = DeviceSampler(device_graph_from_csr(csr, mesh=mesh), fanouts,
                        batch_size=batch, seed=0)
    probe = NeighborSampler(csr, fanouts, seed=0).sample(
        np.arange(batch), round=0)
    plans = BlockPlanCache(semiring="mean")
    dev.set_plans([plans.plan_for(blk, n_dst=bk.n_dst, n_src=bk.n_src,
                                  nnz=bk.nnz, k_hint=k, sell_ok=False)
                   for blk, bk, k in zip(probe, dev.buckets, dims)])
    params = init(jax.random.PRNGKey(0))
    seeds = np.arange(shards * batch, dtype=np.int32).reshape(shards, batch)
    n_real = np.full((shards,), batch, np.int32)
    if shards == 1:
        seeds, n_real = seeds[0], n_real[0]
    with patched(True):
        step = make_device_minibatch_step(apply_blocks, opt, dev,
                                          batch_size=batch, mesh=mesh,
                                          num_shards=shards)
        _fresh_traces()
        return step.func.lower(
            *step.args, params, opt.init(params), jnp.asarray(seeds),
            jnp.asarray(n_real), jnp.int32(0), ds.x, ds.y, jnp.int32(0),
            init_step_stats()).compile()
