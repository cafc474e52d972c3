"""Auto-tuner (§3.2): eligibility rules, cost model monotonicity, tuning
curve, measurement override, tuning DB persistence."""
import numpy as np
import pytest

import importlib

# the package re-exports the autotune *function*, shadowing the submodule
# attribute — resolve the module explicitly
at = importlib.import_module("repro.core.autotune")
from repro.core.autotune import KernelPlan, TuningDB, autotune, tuning_curve
from conftest import random_coo


def _graph(rng, n=256, m=256, nnz=4000):
    coo, _ = random_coo(rng, n, m, nnz)
    return coo


def test_lane_alignment_rule(rng):
    """Paper: non-VLEN-multiple K -> trusted kernel. TPU: K % 128."""
    a = _graph(rng)
    assert autotune(a, 100).kind == "trusted"
    assert autotune(a, 130).kind == "trusted"


def test_semiring_rule(rng):
    """Paper §3.4: only sum (and post-scaled mean) has generated kernels."""
    a = _graph(rng)
    assert autotune(a, 128, semiring_reduce="max").kind == "trusted"
    assert autotune(a, 128, semiring_reduce="min").kind == "trusted"
    assert autotune(a, 128, semiring_reduce="sum").kind in ("bsr", "ell",
                                                            "trusted")


def test_dense_graph_prefers_bsr(rng):
    """Near-dense adjacency -> block tiles are full -> MXU kernel wins under
    the v5e model; an ultra-sparse one must not pick BSR."""
    dense_g = _graph(rng, 256, 256, 256 * 200)
    plan = autotune(dense_g, 128)
    assert plan.kind == "bsr"
    assert plan.predicted_speedup > 1
    sparse_g = _graph(rng, 4096, 4096, 5000)
    plan2 = autotune(sparse_g, 128)
    assert plan2.kind != "bsr" or plan2.est_generated_s <= plan2.est_trusted_s


def test_tuning_curve_and_suggestion(rng):
    a = _graph(rng)
    curve = tuning_curve(a, ks=(16, 32, 64, 128, 256))
    assert len(curve) == 5
    ks = [r["k"] for r in curve]
    assert ks == [16, 32, 64, 128, 256]
    best = at.suggest_embedding_size(curve)
    assert best in ks
    # non-aligned K rows must report speedup 1 (trusted)
    for r in curve:
        if r["k"] % 128 != 0:
            assert r["speedup"] == 1.0


def test_measure_override_runs(rng):
    a = _graph(rng, 128, 128, 2000)
    plan = autotune(a, 128, measure=True)
    assert np.isfinite(plan.est_trusted_s) and plan.est_trusted_s > 0
    # the measured pass always times at least one generated candidate
    # (SELL is eligible for any degree distribution), so both est fields
    # come back finite on CPU
    assert np.isfinite(plan.est_generated_s) and plan.est_generated_s > 0


def test_sell_candidates_swept(rng):
    """graph_stats carries per-(C, σ) packed sizes and the sweep considers
    them; a low-degree-variance sparse graph should pick SELL (BSR tiles
    are nearly empty, ELL pays the (1, K) sublane penalty)."""
    a = _graph(rng, 4096, 4096, 5000)
    stats = at.graph_stats(a)
    assert stats.sell_counts
    for c, sigma, steps in stats.sell_counts:
        assert steps * c >= a.nse           # slots can never undercount nse
        assert stats.sell_steps(c, sigma) == steps
    plan = autotune(a, 128)
    assert plan.kind == "sell"
    assert plan.sell_c in (8, 16, 32)
    assert plan.predicted_speedup > 1


def test_sell_plan_json_roundtrip():
    plan = KernelPlan(kind="sell", sell_c=16, sell_sigma=256, k_hint=128,
                      est_generated_s=1e-4, est_trusted_s=2e-4)
    assert KernelPlan.from_json(plan.to_json()) == plan


def test_tuning_db_roundtrip(tmp_path, rng):
    a = _graph(rng)
    db = TuningDB(path=str(tmp_path / "db.json"))
    plan = autotune(a, 128)
    db.put(a, 128, plan)
    db.save()
    db2 = TuningDB(path=str(tmp_path / "db.json"))
    got = db2.get(a, 128)
    assert got == plan
    assert db2.get(a, 256) is None


def test_tuning_db_schema_envelope(tmp_path, rng):
    """save() writes the versioned envelope and a fresh load resolves the
    plans stored under it."""
    import json
    a = _graph(rng)
    path = str(tmp_path / "db.json")
    db = TuningDB(path=path)
    db.put(a, 128, KernelPlan(kind="ell", k_hint=128))
    db.save()
    with open(path) as f:
        raw = json.load(f)
    assert raw["schema"] == TuningDB._SCHEMA_VERSION
    assert set(raw) == {"schema", "plans"}
    db2 = TuningDB(path=path)
    assert db2.get(a, 128).kind == "ell"


def test_tuning_db_legacy_flat_dict_loads(tmp_path, rng):
    """Pre-envelope DBs (a bare key->plan dict) still load."""
    import json
    a = _graph(rng)
    path = str(tmp_path / "db.json")
    flat = {TuningDB.key(a, 128): KernelPlan(kind="ell", k_hint=128).to_json()}
    with open(path, "w") as f:
        json.dump(flat, f)
    db = TuningDB(path=path)
    assert db.get(a, 128).kind == "ell"


def test_tuning_db_corrupt_file_quarantined(tmp_path, rng):
    """A corrupt DB must not kill training (the tuner would re-tune from
    scratch anyway): it is renamed to <path>.corrupt for post-mortem, a
    warning fires, and the tuner starts empty."""
    import os
    from repro.testing import corrupt_file
    a = _graph(rng)
    path = str(tmp_path / "db.json")
    db = TuningDB(path=path)
    db.put(a, 128, autotune(a, 128))
    db.save()
    corrupt_file(path)
    with pytest.warns(UserWarning, match="quarantined"):
        db2 = TuningDB(path=path)
    assert len(db2) == 0
    assert not os.path.exists(path)
    assert os.path.exists(path + ".corrupt")
    # the quarantined DB does not block a fresh save at the same path
    db2.put(a, 128, KernelPlan(kind="ell", k_hint=128))
    db2.save()
    assert TuningDB(path=path).get(a, 128).kind == "ell"


def test_tuning_db_future_schema_quarantined(tmp_path):
    """A DB written by a *newer* schema is unreadable by contract —
    quarantine, don't guess."""
    import json, os
    path = str(tmp_path / "db.json")
    with open(path, "w") as f:
        json.dump({"schema": 99, "plans": {}}, f)
    with pytest.warns(UserWarning, match="quarantined"):
        db = TuningDB(path=path)
    assert len(db) == 0
    assert os.path.exists(path + ".corrupt")


def test_tuning_db_empty_file_is_empty_db(tmp_path):
    """Zero-length files (e.g. /dev/null as a scratch path) are an empty
    DB, not corruption — no quarantine, no warning."""
    import os, warnings as w
    path = str(tmp_path / "db.json")
    open(path, "wb").close()
    with w.catch_warnings():
        w.simplefilter("error")
        db = TuningDB(path=path)
    assert len(db) == 0
    assert os.path.exists(path)               # left untouched
    assert not os.path.exists(path + ".corrupt")


def test_tuning_db_key_structural(rng):
    """Equivalent graphs (same sparsity pattern, different values) share a
    key; a different pattern of the same size must not collide."""
    from repro.core import coo_from_edges
    src = np.array([0, 1, 2, 3]); dst = np.array([1, 2, 3, 0])
    a = coo_from_edges(src, dst, np.ones(4, np.float32), 8, 8)
    b = coo_from_edges(src, dst, 5 * np.ones(4, np.float32), 8, 8)
    other = coo_from_edges(dst, src, np.ones(4, np.float32), 8, 8)
    assert TuningDB.key(a, 64) == TuningDB.key(b, 64)
    assert TuningDB.key(a, 64) != TuningDB.key(a, 128)
    assert TuningDB.key(a, 64) != TuningDB.key(other, 64)
    # storage order must not matter (key sorts before fingerprinting)
    import dataclasses, jax.numpy as jnp
    shuf = dataclasses.replace(a, row=jnp.asarray(a.row)[::-1],
                               col=jnp.asarray(a.col)[::-1],
                               val=jnp.asarray(a.val)[::-1])
    assert TuningDB.key(a, 64) == TuningDB.key(shuf, 64)


def test_tuning_db_wired_into_cached_graph(tmp_path, rng):
    """build_cached_graph(db=...) persists the decision and short-circuits
    the sweep on the next run (the §3.2 tune-once amortization)."""
    from repro.core import build_cached_graph
    a = _graph(rng, 256, 256, 4000)
    path = str(tmp_path / "db.json")
    db = TuningDB(path=path)
    assert len(db) == 0
    g = build_cached_graph(a, k_hint=128, db=db)
    assert len(db) == 1
    import os
    assert os.path.exists(path)
    # a fresh process-equivalent DB serves the stored plan verbatim
    db2 = TuningDB(path=path)
    g2 = build_cached_graph(a, k_hint=128, db=db2)
    assert g2.plan == g.plan
    # a sentinel plan proves the DB short-circuits instead of re-tuning
    db3 = TuningDB(path=path)
    db3.put(a, 64, KernelPlan(kind="ell", k_hint=64))
    g3 = build_cached_graph(a, k_hint=64, db=db3)
    assert g3.plan.kind == "ell"


def test_tuning_db_key_per_semiring(rng):
    """Measured rows are keyed (graph, K, semiring); sum keeps the legacy
    suffix-free key so pre-existing DB rows still resolve."""
    a = _graph(rng)
    k_sum = TuningDB.key(a, 128)
    assert TuningDB.key(a, 128, semiring="sum") == k_sum
    k_mean = TuningDB.key(a, 128, semiring="mean")
    k_max = TuningDB.key(a, 128, semiring="max")
    assert len({k_sum, k_mean, k_max}) == 3
    db = TuningDB(path="/dev/null")
    db._db = {}
    db.put(a, 128, KernelPlan(kind="ell", k_hint=128), semiring="mean")
    assert db.get(a, 128) is None
    assert db.get(a, 128, semiring="mean").kind == "ell"


def test_measured_tuning_per_semiring(rng):
    """mean is timed with its post-scale; max/min (no generated kernels)
    still come back with a real measured trusted wall-clock."""
    a = _graph(rng, 128, 128, 2000)
    p_mean = autotune(a, 128, measure=True, semiring_reduce="mean")
    assert np.isfinite(p_mean.est_trusted_s) and p_mean.est_trusted_s > 0
    p_max = autotune(a, 128, measure=True, semiring_reduce="max")
    assert p_max.kind == "trusted"
    assert np.isfinite(p_max.est_trusted_s) and p_max.est_trusted_s > 0


def test_sigma_candidates_from_degree_histogram(rng):
    """The σ sweep is derived from the Lorenz-curve knee, not a static
    set: a skewed graph yields a finite window scaled to its heavy-row
    count, a regular graph collapses toward the global sort, and the
    degenerate (empty) graph falls back to the static pair."""
    # heavy-tailed: 32 hub rows + 4064 near-empty rows
    deg = np.concatenate([np.full(32, 500), np.ones(4064)])
    cands = at.sell_sigma_candidates(deg)
    assert 0 in cands and len(cands) >= 2
    finite = [s for s in cands if s > 0]
    assert finite and all(32 <= s < 4096 for s in finite)
    # regular degrees: no knee worth a window — tiny candidate set
    reg = at.sell_sigma_candidates(np.full(1024, 7))
    assert 0 in reg
    # degenerate
    assert at.sell_sigma_candidates(np.zeros(0)) == (0, 256)
    # the full (C, σ) product feeds graph_stats and stays consistent
    a = _graph(rng, 1024, 1024, 8000)
    stats = at.graph_stats(a)
    sigmas = {s for _, s, _ in stats.sell_counts}
    degrees = np.bincount(np.asarray(a.row)[: a.nse], minlength=a.nrows)
    assert sigmas == set(at.sell_sigma_candidates(degrees))
    for c, s, steps in stats.sell_counts:
        assert steps * c >= a.nse


def test_vmem_constraint():
    hw = at.HardwareModel(vmem_bytes=64 * 1024)   # tiny VMEM
    assert not at._vmem_ok(256, 256, 512, hw)
    assert at._vmem_ok(8, 128, 128, at.HardwareModel())


def test_hardware_probe():
    hw = at.probe_hardware()
    assert hw.peak_flops > 0 and hw.hbm_bw > 0 and hw.lane == 128


class _FakeTPU:
    platform = "tpu"

    def __init__(self, kind: str):
        self.device_kind = kind


@pytest.mark.parametrize("kind,name", [("TPU v5 lite", "tpu-v5e"),
                                       ("TPU v4", "tpu-v4"),
                                       ("TPU v5", "tpu-v5p"),
                                       ("TPU v6 lite", "tpu-v6e")])
def test_hardware_probe_keys_by_device_kind(monkeypatch, kind, name):
    """The probe reads the kind the device reports (a v5e says
    'TPU v5 lite'), and takes that chip's published peaks."""
    monkeypatch.setattr(at.jax, "devices", lambda: [_FakeTPU(kind)])
    hw = at.probe_hardware()
    assert hw.name == name
    assert hw.peak_flops == at.TPU_PEAKS[kind]["peak_flops"]
    assert hw.hbm_bw == at.TPU_PEAKS[kind]["hbm_bw"]


def test_hardware_probe_unknown_tpu_raises(monkeypatch):
    """A TPU missing from the peak table is an error, never v5e peaks."""
    monkeypatch.setattr(at.jax, "devices", lambda: [_FakeTPU("TPU v9x")])
    with pytest.raises(ValueError, match="TPU v9x"):
        at.probe_hardware()


# full-size reddit as the tuner sees it (R-MAT, scale 1, GCN-normalized)
_REDDIT = at.GraphStats(nrows=232_965, ncols=232_965, nse=10_543_000,
                        avg_deg=45.3, max_deg=30_615, p99_deg=767,
                        tile_counts=((128, 128, 1_000_226),),
                        sell_counts=((8, 0, 1_340_151), (32, 0, 351_672)))


@pytest.mark.parametrize("plan,fits,word", [
    (KernelPlan(kind="bsr", br=128, bc=128), False, "HBM"),
    (KernelPlan(kind="ell"), False, "HBM"),
    (KernelPlan(kind="sell", sell_c=8), True, None),
    (KernelPlan(kind="sell", sell_c=32), True, None),
    (KernelPlan(kind="trusted"), True, None),
])
def test_fit_rule_at_full_reddit(plan, fits, word):
    """BSR's ~1 M dense tiles and ELL's global-max-degree padding cannot
    be held by a 16 GB chip; SELL's slice table fits SMEM."""
    why = at.plan_fit_error(_REDDIT, plan, at.HardwareModel())
    assert (why is None) == fits, why
    if word:
        assert word in why and at._plan_label(plan) in why


def test_fit_rule_removes_candidates_from_the_sweep(rng):
    """A plan the chip cannot hold leaves the candidate set (and the
    sweep records why) instead of being picked and failing on the chip."""
    from repro import obs
    a = _graph(rng, 256, 256, 256 * 200)       # dense: BSR wins if it fits
    assert autotune(a, 128).kind == "bsr"
    small = at.HardwareModel(hbm_bytes=1 << 20)
    with obs.profiled(ops=False) as tracer:
        plan = autotune(a, 128, hw=small)
    assert plan.kind != "bsr"
    sweep = [s for s in tracer.snapshot() if s.name == "tuning.sweep"][-1]
    assert any(label.startswith("bsr") and "HBM" in why
               for label, why in sweep.attrs["unfit"])
    why = at.plan_fit_error(at.graph_stats(a),
                            KernelPlan(kind="sell", sell_c=8),
                            at.HardwareModel(smem_bytes=64))
    assert why is not None and "SMEM" in why


def test_sigma_candidates_capped_and_deduped():
    """Degenerate degree histograms must not inflate the measured sweep:
    a constant-degree graph (every sort window is a no-op permutation)
    collapses to {0}, and the candidate list never exceeds the cap."""
    assert at.sell_sigma_candidates(np.full(4096, 12)) == (0,)
    # through graph_stats: a ring (constant degree 1) sweeps |C| variants,
    # not |C| x |σ|
    from repro.core import coo_from_edges
    src = np.arange(64); dst = (src + 1) % 64
    a = coo_from_edges(src, dst, np.ones(64, np.float32), 64, 64)
    stats = at.graph_stats(a)
    assert {s for _, s, _ in stats.sell_counts} == {0}
    assert len(stats.sell_counts) == len(at._SELL_C_VALUES)
    rng = np.random.default_rng(0)
    for _ in range(8):
        deg = rng.integers(0, 1000, size=int(rng.integers(1, 5000)))
        assert len(at.sell_sigma_candidates(deg)) <= at._SELL_SIGMA_MAX
