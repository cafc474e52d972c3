"""Whole runs with the timed path broken underneath come out not correct:
once for each fault a cell can have. The harness's look for a chip is
skipped; everything else is the run the benchmark makes, at a tiny size."""
import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

from chipbench.tests.tiny import run_tiny

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def unchanged(monkeypatch, module):
    """The step hands its parameters back unchanged."""
    monkeypatch.setattr(module, "apply_updates", lambda p, u: p)


def half_batch(monkeypatch, module):
    """The loss leaves out the second half of the batch (of the training
    nodes in full batch) and takes the mean over the rest."""
    xent = module._xent

    def half(logits, y, mask):
        m = mask.astype(bool)
        return xent(logits, y, m & (jnp.cumsum(m) <= jnp.sum(m) // 2))
    monkeypatch.setattr(module, "_xent", half)


def altered_answer(monkeypatch, _module):
    """The sampler hands back one neighbour of each block moved to the next
    source row: a wrong answer where it is produced."""
    import repro.sampling.device_graph as dg
    relabel = dg._device_relabel

    def moved(*args, **kw):
        src_ids, col, ok = relabel(*args, **kw)
        n_src = src_ids.shape[0]
        col = col.at[0, 0].set(jnp.where(ok[0, 0], (col[0, 0] + 1) % n_src,
                                         col[0, 0]))
        return src_ids, col, ok
    monkeypatch.setattr(dg, "_device_relabel", moved)


def other_round(monkeypatch, module):
    """The step feeds its sampler the next round counter, so it trains on
    other blocks than the ones drawn again for the check."""
    make = module.make_device_minibatch_step

    def made(*args, **kw):
        step = make(*args, **kw)
        return lambda p, s, seeds, n_real, rnd, *rest: step(
            p, s, seeds, n_real, rnd + 1, *rest)
    monkeypatch.setattr(module, "make_device_minibatch_step", made)


PATCHED = {"use_isplib": True}
UNPATCHED = {"use_isplib": False}     # the same cell with patch() off
CASES = ([("gcn-reddit.full", t, f) for t in (PATCHED, UNPATCHED)
          for f in (unchanged, half_batch)]
         + [("sage-reddit.sampled", {}, f)
            for f in (unchanged, half_batch, altered_answer, other_round)])


@pytest.mark.parametrize(
    "workload,traffic,fault", CASES,
    ids=[f"{w}{'-unpatched' if t == UNPATCHED else ''}-{f.__name__}"
         for w, t, f in CASES])
def test_fault_is_not_correct(monkeypatch, workload, traffic, fault):
    import repro.train.gnn as full
    import repro.train.gnn_minibatch as sampled
    assert run_tiny(workload, **traffic)["correct"]
    fault(monkeypatch, full if workload.startswith("gcn") else sampled)
    assert run_tiny(workload, **traffic)["correct"] is False


DP4 = """
import json, sys, types
sys.path[:0] = [{root!r}, {src!r}]
import pytest
from chipbench import calibrate
from chipbench.tests import test_faults as tf
from chipbench.tests.tiny import run_tiny, tiny_cell, within
import repro.dist.collectives as coll
import repro.train.gnn_minibatch as mb
W, four = "sage-reddit.sampled-dp4", {{}}
out = {{"sound": run_tiny(W, **four)["correct"]}}
def no_exchange(mp, _):
    mp.setattr(coll, "sync_grads", lambda tree, axis_name, **kw: tree)
for fault in (tf.unchanged, tf.half_batch, tf.altered_answer, tf.other_round,
              no_exchange):
    mp = pytest.MonkeyPatch()
    fault(mp, mb)
    out[fault.__name__] = run_tiny(W, **four)["correct"]
    mp.undo()
cell, rows = tiny_cell(W, **four), []
calibrate.sampled(cell, types.SimpleNamespace(
    seeds=[], control_seeds=[5, 6, 7], fault_seeds=[]), rows)
out["control"] = [within({{k: v for k, v in r.items()
                          if k not in ("kind", "seed")}}, cell.limits)
                  for r in rows]
print(json.dumps(out))
"""


def test_four_chip_faults_are_not_correct():
    """The four-chip cell (lockstep data parallel) over four virtual CPU
    devices, in a process of its own: sound, then each fault including the
    all-reduce left out, then the bf16 control on three seeds."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = DP4.format(root=ROOT, src=os.path.join(ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    import json
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result == {"sound": True, "unchanged": False, "half_batch": False,
                      "altered_answer": False, "other_round": False,
                      "no_exchange": False,
                      "control": [False, False, False]}
