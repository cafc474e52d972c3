"""The trace reduction on traces with known contents."""
import os

import pytest

from chipbench.lib import peaks, trace
from chipbench.tests import xspace as xs

HERE = os.path.dirname(os.path.abspath(__file__))
SAMPLER = ("/x/src/repro/sampling/device_graph.py",
           "DeviceSampler.sample_blocks_stats")
AGG = ("/x/src/repro/sampling/blocks.py", "block_spmm")
STEP = ("/x/src/repro/train/gnn_minibatch.py", "update")


def synthetic(tmp_path) -> str:
    """One chip. Window [1000, 11000] ns. Ops: A [1000, 3000] and B
    [2500, 4000] from the sampler, C [6000, 9000] from the aggregation with
    D [6500, 7000] nested in it, E [10500, 12000] cut by the window's end.
    Busy 3000 + 3000 + 500 = 6500 ns of 10000. Idle gaps: [4000, 6000] under
    a host dispatch span, [9000, 10500] under the window alone."""
    prog = "jit_update(42)"
    ops = [("a.1", 1000, 2000, 1), ("b.2", 2500, 1500, 1),
           ("c.3", 6000, 3000, 2), ("d.4", 6500, 500, 2),
           ("e.5", 10500, 1500, 3)]
    device = xs.plane(1, "/device:TPU:0", [
        xs.line(1, "XLA Modules", [xs.event(1, 1000, 11000)]),
        xs.line(2, "XLA Ops", [xs.event(10 + i, s, d)
                               for i, (_, s, d, _) in enumerate(ops)]),
    ], [xs.event_meta(1, prog)] + [
        xs.event_meta(10 + i, f"%{n} = f32[8] fusion()")
        for i, (n, _, _, _) in enumerate(ops)])
    host = xs.plane(2, "/host:CPU", [
        xs.line(1, "python3", [xs.event(1, 1000, 10000),
                               xs.event(2, 3900, 2200)]),
    ], [xs.event_meta(1, trace.WINDOW),
        xs.event_meta(2, "PjitFunction(update)")])
    hlo = xs.hlo_proto(
        [(n, "fusion", f) for n, _, _, f in ops],
        [[SAMPLER, STEP], [AGG, STEP], [STEP]])
    meta = xs.metadata_plane(3, {prog: hlo})
    path = os.path.join(tmp_path, "t.xplane.pb")
    with open(path, "wb") as f:
        f.write(device + host + meta)
    return path


def test_busy_idle_and_op_times(tmp_path):
    v = trace.reduce(synthetic(tmp_path), {"steps": 1}, 1,
                     peaks.peaks_for("TPU v5 lite"))
    assert v.window == (1000.0, 11000.0)
    assert v.window_s == pytest.approx(10000e-9)
    assert v.busy_s == pytest.approx(6500e-9)
    assert [op.name for op in v.ops] == ["a.1", "b.2", "c.3", "e.5"]
    sampler = v.seconds(lambda op: trace.in_stack(
        op, "repro/sampling/device_graph.py",
        "DeviceSampler.sample_blocks_stats"))
    agg = v.seconds(lambda op: trace.in_stack(op, "repro/sampling/blocks.py",
                                              "block_spmm"))
    assert sampler == pytest.approx(3500e-9)
    assert agg == pytest.approx(3000e-9)
    assert v.seconds(lambda op: True) == pytest.approx(7000e-9)
    assert sorted(v.gaps) == [(1500.0, trace.WINDOW),
                              (2000.0, "PjitFunction(update)")]
    b = v.breakdown()
    assert b["device_ops"][0] == ["c.3 fusion repro/sampling/blocks.py:"
                                  "block_spmm", pytest.approx(3000e-9)]
    assert b["idle_gaps"][0] == ["PjitFunction(update)",
                                 pytest.approx(2000e-9)]


def test_idle_share_reader(tmp_path):
    from chipbench.lib import cells
    v = trace.reduce(synthetic(tmp_path), {"steps": 2}, 1,
                     peaks.peaks_for("TPU v5 lite"))
    assert cells.metric_reader("idle_share.sampled").read(v) == \
        pytest.approx(35.0)
    assert cells.metric_reader("sample_ms.sampled").read(v) == \
        pytest.approx(3500e-9 * 1000 / 2)


def test_recorded_tpu_trace():
    """Ten device-sampled sage-mean steps (batch 1024, fanouts (25, 10)) on
    full-size reddit, recorded on a TPU v5e (runtime host threads dropped).
    The window runs from the end of the first ``jit_update`` to the end of
    the tenth: nine steps of ~118.6 ms. Values read once by hand from the
    trace's events and kept as the reduction's expected output."""
    path = os.path.join(HERE, "data", "sampled_steps.xplane.pb")
    v = trace.reduce(path, {"step_module": "jit_update", "steps": 9}, 1,
                     peaks.peaks_for("TPU v5 lite"))
    assert v.window == (162765984.0, 1230498460.0)
    assert v.busy_s == pytest.approx(1.067481301, rel=1e-9)
    assert all(op.program.startswith("jit_update(") for op in v.ops)
    sampler = v.seconds(lambda op: trace.in_stack(
        op, "repro/sampling/device_graph.py",
        "DeviceSampler.sample_blocks_stats"))
    agg = v.seconds(lambda op: trace.in_stack(op, "repro/sampling/blocks.py",
                                              "block_spmm"))
    assert sampler == pytest.approx(0.88607282, rel=1e-6)
    assert agg == pytest.approx(0.143620766, rel=1e-6)
    assert sampler + agg < v.busy_s
    kernels = [op for op in v.ops if op.opcode == "custom-call" and
               trace.in_stack(op, "repro/kernels/gather_spmm.py")]
    assert len(kernels) == 9          # the layer-1 ELL kernel, once a step


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")
