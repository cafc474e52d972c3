"""What ``repro.obs`` records for the device trace and the set-up: the
stages inside the compiled training steps, spans on the profiler's clock,
the compile log, the set-up parts, and the benchmark's readers of them."""
import glob
import os
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import pytest

from repro import obs

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import stage_steps as S  # noqa: E402

from chipbench.lib import cells, trace  # noqa: E402

_HERE = os.path.dirname(os.path.abspath(__file__))


def _assert_one_stage_each(compiled):
    frames = S.stage_frames(compiled)
    assert frames, "no instruction carries a creating stack"
    off = [(name, opcode, st) for name, opcode, st in frames if len(st) != 1]
    assert not off, off[:10]
    staged = {st[0] for _, _, st in frames}
    assert "aggregate" in staged and staged <= set(obs.stages.STAGES)
    return staged


@pytest.mark.parametrize("use_isplib", [True, False],
                         ids=["patched", "unpatched"])
def test_fullbatch_step_ops_each_carry_one_stage(tiny_dataset, use_isplib):
    staged = _assert_one_stage_each(S.fullbatch_step(tiny_dataset,
                                                     use_isplib))
    assert {"dense", "loss", "optimizer"} <= staged
    assert ("normalize" in staged) is not use_isplib


@pytest.mark.parametrize("kind", ["sell", "ell"])
def test_gat_step_ops_each_carry_one_stage(tiny_dataset, kind):
    """The published GAT on a gather plan: the weights, their backward
    and the SDDMM under ``attention``, the SpMMs under ``aggregate``."""
    from repro.core.autotune import KernelPlan
    staged = _assert_one_stage_each(S.fullbatch_step(
        tiny_dataset, True, arch="gat", plan=KernelPlan(kind=kind)))
    assert {"attention", "aggregate", "dense", "loss",
            "optimizer"} <= staged


def test_device_step_ops_each_carry_one_stage(tiny_dataset):
    staged = _assert_one_stage_each(S.device_step(tiny_dataset))
    assert {"sample", "gather", "dense", "loss", "optimizer"} <= staged
    assert "grad_sync" not in staged          # one shard: no collective


def test_device_step_four_shards_ops_each_carry_one_stage():
    code = textwrap.dedent(f"""
    import os, sys
    os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'
    sys.path.insert(0, {_HERE!r})
    import stage_steps as S
    from repro.data import make_dataset
    ds = make_dataset('reddit', scale=1 / 512, seed=1)
    frames = S.stage_frames(S.device_step(ds, shards=4))
    off = [f for f in frames if len(f[2]) != 1]
    assert frames and not off, off[:10]
    by_stage = {{}}
    for _, opcode, (st,) in frames:
        by_stage.setdefault(st, set()).add(opcode)
    assert 'all-reduce' in by_stage['grad_sync'], by_stage['grad_sync']
    assert not any('all-reduce' in ops for st, ops in by_stage.items()
                   if st != 'grad_sync'), by_stage
    assert 'aggregate' in by_stage
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(S.ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600, env=env)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"


def test_span_lands_in_profiler_trace_around_its_dispatch(tmp_path):
    from jax.profiler import ProfileData

    def dispatched(x):
        return jnp.sin(x) @ x.T

    f = jax.jit(dispatched)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with obs.profiled(ops=False):
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            with obs.span("train.step", step=1):
                f(x).block_until_ready()
        finally:
            jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:CPU")
              for line in plane.lines for e in line.events]
    (_, s0, s1), = [e for e in events if e[0] == "train.step"]
    calls = [e for e in events if e[0] == "PjitFunction(dispatched)"]
    assert calls and all(s0 <= c0 and c1 <= s1 for _, c0, c1 in calls)
    # and on the program's own timeline
    assert [s.name for s in obs.get_tracer().snapshot()] == ["train.step"]


def test_compile_log_records_each_compile_of_a_fresh_function():
    def fresh_for_compile_log(x):
        return x * 3 + 1

    f = jax.jit(fresh_for_compile_log)
    compiles_before = obs.metrics().counter("jit.compiles").value
    f(jnp.ones(2))
    f(jnp.ones(3))                              # a new shape compiles again
    f(jnp.ones(3))                              # a cache hit does not
    phases = obs.compiles.program("jit(fresh_for_compile_log)")
    assert {ph: c for ph, (c, _) in phases.items()} == \
        {"trace": 2, "lower": 2, "compile": 2}
    assert all(s > 0 for _, s in phases.values())
    # the profiler's module name finds the same program
    assert obs.compiles.program("jit_fresh_for_compile_log") == phases
    assert obs.metrics().counter("jit.compiles").value >= compiles_before + 2


def test_build_bundle_counts_each_setup_part(tiny_dataset):
    from repro.models.gnn import build_bundle
    obs.metrics().reset()
    t0 = time.perf_counter()
    build_bundle(tiny_dataset, k_hint=16)
    wall = time.perf_counter() - t0
    snap = obs.metrics().snapshot()
    parts = [snap[f"setup.{p}_s"]
             for p in ("normalize", "transpose", "tune", "pack")]
    assert all(v > 0 for v in parts), parts
    assert sum(parts) <= wall


def _op(name, stack, dur, chip=0):
    return trace.Op(chip=chip, name=name, program="jit_step(1)", start=0.0,
                    dur=dur, opcode="fusion", stack=stack)


def _view(ops, chips=1, **work):
    return trace.View(window=(0.0, 1e9), chips=chips, ops=ops, gaps=[],
                      work=work, peaks={}, busy_ns=0.0)


AGG = ("/x/src/repro/obs/stages.py", "aggregate")
DENSE = ("/x/src/repro/obs/stages.py", "dense")
LAYER = ("/x/src/repro/models/gnn/layers.py", "gcn_conv")


@pytest.mark.parametrize("metric, chips", [("aggregate_ms.fullbatch", 1),
                                           ("aggregate_ms.sampled", 1),
                                           ("aggregate_ms.sampled", 4)])
def test_aggregate_readers(metric, chips):
    read = cells.metric_reader(metric).read
    ops = [_op("a", (AGG, LAYER), 3e6), _op("b", (DENSE, LAYER), 5e6),
           _op("c", ((AGG[0][:-3] + "_other.py", "aggregate"),), 7e6),
           _op("d", (("/x/kernels/ops.py", "sell_spmm"), AGG, LAYER), 1e6,
               chip=chips - 1)]
    assert read(_view(ops, chips, steps=2)) == pytest.approx(
        (3.0 + 1.0) / chips / 2)
    assert read(_view(ops[1:3], chips, steps=2)) is None


def test_step_compile_reader():
    read = cells.metric_reader("step_compile_s.fullbatch").read

    def step_for_compile_reader(x):
        return x - 1

    jax.jit(step_for_compile_reader)(jnp.ones(4))
    phases = obs.compiles.program("jit(step_for_compile_reader)")
    assert read(_view([], step_module="jit_step_for_compile_reader")) == \
        pytest.approx(sum(s for _, s in phases.values()))
    assert read(_view([], step_module="jit_never_compiled_here")) is None


def test_pack_reader():
    read = cells.metric_reader("pack_s.fullbatch").read
    obs.metrics().reset()
    assert read(_view([])) is None
    obs.metrics().counter("setup.pack_s").inc(1.25)
    assert read(_view([])) == 1.25
    obs.metrics().reset()
