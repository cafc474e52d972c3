"""Fault-injection suite: the robustness layer of the minibatch trainer.

Every failure mode `repro.testing.faults` can inject is exercised against
`train_gnn_minibatch`, on the 1-shard path in-process and on a forced-CPU
2-shard mesh in a subprocess (the main pytest process must stay
single-device, like tests/test_multidevice.py):

* kill mid-epoch + resume → bitwise-identical final params (host AND
  device samplers, 1 and 2 shards) — the deterministic-resume tentpole;
* NaN gradient on one shard → both shards skip that update in lockstep
  (no psum deadlock) and training converges near the clean run;
* prefetch-worker death → bounded restart, bitwise-equal outcome;
* device-sampler capacity overflow → counted, surfaced, escalated;
* straggler delay → watchdog flags it.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(body: str, devices: int = 2, timeout: int = 560) -> str:
    code = ("import os\n"
            f"os.environ['XLA_FLAGS'] = "
            f"'--xla_force_host_platform_device_count={devices}'\n"
            + textwrap.dedent(body))
    env = dict(os.environ, PYTHONPATH=os.path.join(_ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout, env=env)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


@pytest.fixture(scope="module")
def ds():
    from repro.data import make_dataset
    return make_dataset("reddit", scale=1 / 512, seed=1)


_KW = dict(fanouts=(4, 4), batch_size=64, hidden=32, epochs=3, seed=0)


def _train(dataset, **over):
    from repro.train import train_gnn_minibatch
    kw = dict(_KW)
    kw.update(over)
    return train_gnn_minibatch("sage-mean", dataset, **kw)


def _leaves(params):
    import jax
    return jax.tree_util.tree_leaves(params)


def _assert_bitwise(pa, pb, what):
    for a, b in zip(_leaves(pa), _leaves(pb)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), what


# -------------------------------------------------------------------------
# kill + resume: bitwise determinism (the tentpole claim)
# -------------------------------------------------------------------------

@pytest.mark.parametrize("sampler", ["host", "device"])
def test_kill_resume_bitwise_single_shard(ds, tmp_path, sampler):
    """A run killed mid-epoch resumes from its checkpoint and finishes
    with final params bitwise-identical to the uninterrupted run. The
    kill (step 7) does not land on the ckpt cadence (every 3), so the
    resume replays steps 6..7 — the loader fast-forward path, not just a
    state reload."""
    from repro.testing import FaultPlan, expect_kill

    clean = _train(ds, sampler=sampler)
    d = str(tmp_path / sampler)
    exc = expect_kill(_train, ds, sampler=sampler, ckpt_dir=d,
                      ckpt_every=3, faults=FaultPlan(step_exception_at=7))
    assert "step 7" in str(exc)
    r = _train(ds, sampler=sampler, ckpt_dir=d, ckpt_every=3)
    assert r.resumed_step == 6, r.resumed_step        # last multiple of 3
    assert r.losses == clean.losses
    _assert_bitwise(clean.final_params, r.final_params,
                    f"{sampler}: resumed params diverged from clean run")


def test_resume_after_complete_is_noop(ds, tmp_path):
    """Resuming a finished run replays nothing and returns the same
    params and loss history (idempotent restarts — what a preempted-then-
    rescheduled job does when the preemption hit after the last step)."""
    from repro.sampling import num_seed_batches
    d = str(tmp_path / "done")
    r1 = _train(ds, ckpt_dir=d, ckpt_every=3)
    r2 = _train(ds, ckpt_dir=d, ckpt_every=3)
    spe = num_seed_batches(int(np.asarray(ds.train_mask).sum()),
                           _KW["batch_size"])
    assert r2.resumed_step == _KW["epochs"] * spe, r2.resumed_step
    assert r2.losses == r1.losses
    _assert_bitwise(r1.final_params, r2.final_params,
                    "re-run of a complete run changed params")


# -------------------------------------------------------------------------
# non-finite guard
# -------------------------------------------------------------------------

def test_nan_grad_skipped_single_shard(ds):
    """An injected NaN gradient is skipped (params/opt state keep their
    pre-step values), counted, and the run stays finite and close to the
    clean run."""
    clean = _train(ds)
    from repro.testing import FaultPlan
    r = _train(ds, faults=FaultPlan(nan_grad_at=(4, 0)))
    assert r.skipped_steps == 1, r.skipped_steps
    assert all(np.isfinite(r.losses)), r.losses
    # one skipped update out of ~15: the final loss stays in the clean
    # run's neighborhood
    assert abs(r.losses[-1] - clean.losses[-1]) < 0.5, \
        (r.losses, clean.losses)


def test_nan_guard_off_poisons_params(ds):
    """Control: with skip_nonfinite=False the same injection propagates —
    proving the guard (not luck) is what keeps the guarded run finite."""
    from repro.testing import FaultPlan
    r = _train(ds, faults=FaultPlan(nan_grad_at=(4, 0)),
               skip_nonfinite=False)
    assert not all(np.isfinite(r.losses)), r.losses


# -------------------------------------------------------------------------
# prefetch-worker death
# -------------------------------------------------------------------------

def test_prefetch_death_recovers_bitwise(ds):
    """The prefetch producer dying mid-epoch restarts from the delivered
    batch count; the recovered run is bitwise-identical to a clean one
    (no dropped and no replayed batch)."""
    clean = _train(ds)
    from repro.testing import FaultPlan
    r = _train(ds, faults=FaultPlan(prefetch_death_at=5))
    assert r.prefetch_restarts == 1, r.prefetch_restarts
    assert r.losses == clean.losses
    _assert_bitwise(clean.final_params, r.final_params,
                    "prefetch-restarted run diverged")


def test_prefetch_restarts_exhausted_raises(ds):
    """With a zero restart budget the producer's exception propagates —
    bounded retry, not infinite self-healing."""
    from repro.testing import FaultPlan, InjectedFault
    with pytest.raises(InjectedFault):
        _train(ds, faults=FaultPlan(prefetch_death_at=5),
               prefetch_restarts=0)


# -------------------------------------------------------------------------
# device-sampler capacity overflow
# -------------------------------------------------------------------------

def test_device_overflow_counted_and_escalated(ds):
    """Starving the device sampler's per-hop capacities drops edges: the
    drops must be counted (never silent) and the trainer must escalate —
    rebuild the sampler with doubled capacities — at the epoch boundary."""
    with pytest.warns(UserWarning, match="capacity overflow"):
        r = _train(ds, sampler="device", device_caps=[128, 128],
                   max_escalations=2)
    assert r.overflow_edges > 0, "starved caps must drop (and count) edges"
    assert r.capacity_escalations >= 1, r.capacity_escalations
    assert all(np.isfinite(r.losses)), r.losses
    # escalation rebuilds the step: its compile is accounted, not lost
    assert r.n_traces >= 1 + r.capacity_escalations, \
        (r.n_traces, r.capacity_escalations)


def test_device_ample_caps_no_overflow(ds):
    """Control: the probed capacities see no overflow and no escalation."""
    r = _train(ds, sampler="device")
    assert r.overflow_edges == 0 and r.capacity_escalations == 0


# -------------------------------------------------------------------------
# straggler watchdog
# -------------------------------------------------------------------------

def test_straggler_flagged(ds):
    """An injected delay on one step is flagged by the watchdog (EMA
    threshold), and only steps near it — aggregates stay bounded."""
    from repro.testing import FaultPlan
    from repro.train.fault_tolerance import StragglerWatchdog
    wd = StragglerWatchdog(threshold=3.0)
    _train(ds, faults=FaultPlan(straggler_at=6, straggler_delay_s=0.5),
           watchdog=wd, double_buffer=False)
    flagged = [e.step for e in wd.events if e.straggler]
    assert 6 in flagged, flagged
    assert wd.straggler_count >= 1
    assert wd.total_steps == len(wd.events)   # window bound not hit here
    # (the max_events deque bound itself is unit-tested in test_ckpt_ft)


# -------------------------------------------------------------------------
# 2-shard lockstep (forced-CPU subprocess)
# -------------------------------------------------------------------------

def test_kill_resume_bitwise_two_shards():
    """Kill/resume determinism on a data=2 mesh, host and device
    samplers: the lockstep schedule replay must also restore every
    shard's round counters."""
    _run("""
    import tempfile, numpy as np, jax
    from repro.data import make_dataset
    from repro.train import train_gnn_minibatch
    from repro.testing import FaultPlan, expect_kill
    ds = make_dataset('reddit', scale=1/512, seed=1)
    mesh = jax.make_mesh((2,), ('data',),
                         axis_types=(jax.sharding.AxisType.Auto,) * 1)
    kw = dict(fanouts=(4, 4), batch_size=64, hidden=32, epochs=3, seed=0,
              mesh=mesh)
    for sampler in ('host', 'device'):
        clean = train_gnn_minibatch('sage-mean', ds, sampler=sampler, **kw)
        assert clean.num_shards == 2
        with tempfile.TemporaryDirectory() as d:
            expect_kill(train_gnn_minibatch, 'sage-mean', ds,
                        sampler=sampler, ckpt_dir=d, ckpt_every=2,
                        faults=FaultPlan(step_exception_at=5), **kw)
            r = train_gnn_minibatch('sage-mean', ds, sampler=sampler,
                                    ckpt_dir=d, ckpt_every=2, **kw)
        assert r.resumed_step == 4, r.resumed_step
        assert r.losses == clean.losses, (sampler, r.losses, clean.losses)
        for a, b in zip(jax.tree_util.tree_leaves(clean.final_params),
                        jax.tree_util.tree_leaves(r.final_params)):
            assert np.array_equal(a, b), sampler
        print(sampler, 'bitwise OK')
    """, devices=2)


def test_nan_lockstep_skip_two_shards():
    """The acceptance criterion: a NaN gradient injected on ONE shard of
    a 2-shard run is skipped by BOTH shards in the same step (the skip
    decision is itself a psum — no deadlock; a hang would trip the
    subprocess timeout), exactly one step is skipped run-wide, and the
    run converges to within tolerance of the clean run. Exercised on
    both gradient wires — the int8 path's shared pmax'd scale is the one
    a stray NaN would poison cross-shard."""
    _run("""
    import numpy as np, jax
    from repro.data import make_dataset
    from repro.train import train_gnn_minibatch
    from repro.testing import FaultPlan
    ds = make_dataset('reddit', scale=1/512, seed=1)
    mesh = jax.make_mesh((2,), ('data',),
                         axis_types=(jax.sharding.AxisType.Auto,) * 1)
    kw = dict(fanouts=(4, 4), batch_size=64, hidden=32, epochs=3, seed=0,
              mesh=mesh)
    clean = train_gnn_minibatch('sage-mean', ds, **kw)
    for wire in ('fp32', 'int8'):
        r = train_gnn_minibatch('sage-mean', ds, grad_sync=wire,
                                faults=FaultPlan(nan_grad_at=(4, 1)), **kw)
        assert r.skipped_steps == 1, (wire, r.skipped_steps)
        assert all(np.isfinite(r.losses)), (wire, r.losses)
        assert abs(r.losses[-1] - clean.losses[-1]) < 0.5, \
            (wire, r.losses, clean.losses)
        print(wire, 'lockstep skip OK', r.losses[-1])
    """, devices=2)
