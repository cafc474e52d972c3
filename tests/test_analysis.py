"""repro.analysis: fixture-based known-bad snippets per pass (each
asserting its exact finding code), the baseline gating mechanics, and the
self-audit — the analyzer over this repo's own src/ must be clean modulo
the committed baseline."""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.analysis import CODES, Finding, load_baseline, write_baseline
from repro.analysis.findings import format_finding, findings_to_json, \
    sort_findings

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _codes(findings):
    return [f.code for f in findings]


# --------------------------------------------------------------------------
# findings / baseline mechanics
# --------------------------------------------------------------------------

def test_finding_registry_consistency():
    for code, (sev, desc) in CODES.items():
        assert sev in ("error", "warning", "info"), code
        assert desc
        # report codes (x100+) are info; defect codes gate
        is_report = int(code[3:]) >= 100
        assert (sev == "info") == is_report or code.startswith("RTB"), code


def test_unregistered_code_rejected():
    with pytest.raises(AssertionError):
        Finding(code="XXX999", file="f", obj="o", message="m")


def test_baseline_split_and_unused(tmp_path):
    f_known = Finding(code="PAL004", file="k.py", obj="kern", message="m")
    f_new = Finding(code="LNT001", file="l.py", obj="fn", message="m")
    f_info = Finding(code="COL100", file="c.py", obj="t", message="m")
    path = tmp_path / "bl.json"
    path.write_text(json.dumps({"schema": 1, "suppressions": [
        {"code": "PAL004", "file": "k.py", "obj": "kern", "reason": "r"},
        {"code": "COL003", "file": "gone.py", "obj": "*", "reason": "r"},
    ]}))
    bl = load_baseline(str(path))
    new, suppressed, unused = bl.split([f_known, f_new, f_info])
    assert new == [f_new]
    assert suppressed == [f_known]
    assert [u.file for u in unused] == ["gone.py"]    # stale entry surfaced


def test_baseline_requires_reason(tmp_path):
    path = tmp_path / "bl.json"
    path.write_text(json.dumps({"schema": 1, "suppressions": [
        {"code": "PAL004", "file": "k.py", "obj": "kern", "reason": ""}]}))
    with pytest.raises(AssertionError):
        load_baseline(str(path))


def test_write_baseline_roundtrip(tmp_path):
    f = Finding(code="LNT002", file="a.py", obj="patch", message="m")
    path = str(tmp_path / "bl.json")
    bl = write_baseline(path, [f])
    new, suppressed, _ = bl.split([f])
    assert not new and suppressed == [f]


def test_json_output_statuses():
    f_new = Finding(code="LNT001", file="l.py", obj="fn", message="m")
    f_info = Finding(code="RTB001", file="r.py", obj="cfg", message="m")
    payload = json.loads(findings_to_json(
        sort_findings([f_info, f_new]), new=[f_new], suppressed=[]))
    assert payload["schema"] == 1
    by_code = {d["code"]: d for d in payload["findings"]}
    assert by_code["LNT001"]["status"] == "new"
    assert by_code["RTB001"]["status"] == "info"
    assert "error" == by_code["LNT001"]["severity"]


# --------------------------------------------------------------------------
# pass 1 — collective safety (jaxpr walk)
# --------------------------------------------------------------------------

def _mesh1():
    return jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))


def _shard_jaxpr(body):
    from jax.sharding import PartitionSpec as P
    fn = jax.shard_map(body, mesh=_mesh1(), in_specs=(P(),), out_specs=P(),
                       check_vma=False)
    return jax.make_jaxpr(fn)(jnp.ones((4,)))


def test_collectives_divergent_cond_is_col001():
    """The PR 5 deadlock seeded back: a psum only one cond branch runs."""
    from repro.analysis.collectives import walk_jaxpr

    def body(x):
        return jax.lax.cond(x.sum() > 0,
                            lambda v: jax.lax.psum(v, "data"),
                            lambda v: v * 2.0, x)

    findings = []
    walk_jaxpr(_shard_jaxpr(body).jaxpr, findings=findings,
               file="fx.py", obj="body")
    assert "COL001" in _codes(findings), [format_finding(f)
                                          for f in findings]


def test_collectives_lockstep_cond_is_clean():
    """Both branches psum -> same sequence -> no divergence finding."""
    from repro.analysis.collectives import walk_jaxpr

    def body(x):
        return jax.lax.cond(x.sum() > 0,
                            lambda v: jax.lax.psum(v, "data"),
                            lambda v: jax.lax.psum(v * 2.0, "data"), x)

    findings = []
    seq = walk_jaxpr(_shard_jaxpr(body).jaxpr, findings=findings,
                     file="fx.py", obj="body")
    assert not findings
    assert any(s.startswith("cond:psum") for s in seq), seq


def test_collectives_while_loop_is_col002():
    from repro.analysis.collectives import walk_jaxpr

    def body(x):
        def cond(c):
            return c.sum() < 10.0

        def step(c):
            return jax.lax.psum(c, "data") + 1.0

        return jax.lax.while_loop(cond, step, x)

    findings = []
    walk_jaxpr(_shard_jaxpr(body).jaxpr, findings=findings,
               file="fx.py", obj="body")
    assert "COL002" in _codes(findings)


def test_collectives_scan_is_safe_and_in_contract():
    from repro.analysis.collectives import walk_jaxpr

    def body(x):
        def step(c, _):
            return jax.lax.psum(c, "data"), None

        out, _ = jax.lax.scan(step, x, None, length=3)
        return out

    findings = []
    seq = walk_jaxpr(_shard_jaxpr(body).jaxpr, findings=findings,
                     file="fx.py", obj="body")
    assert not findings
    assert any(s.startswith("scan[3](psum") for s in seq), seq


def test_collectives_unbound_axis_is_col003():
    """Walking the shard_map's inner jaxpr WITHOUT its axis binding —
    the shape of a collective referencing an axis nothing binds."""
    from repro.analysis.collectives import walk_jaxpr

    def body(x):
        return jax.lax.psum(x, "data")

    closed = _shard_jaxpr(body)
    inner = next(e.params["jaxpr"] for e in closed.jaxpr.eqns
                 if e.primitive.name == "shard_map")
    findings = []
    walk_jaxpr(inner, findings=findings, file="fx.py", obj="body")
    assert "COL003" in _codes(findings)


def test_collectives_rle_compresses_contract():
    from repro.analysis.collectives import collective_contract
    from jax.sharding import PartitionSpec as P

    def body(x):
        return tuple(jax.lax.psum(x * i, "data") for i in range(4))

    fn = jax.shard_map(body, mesh=_mesh1(), in_specs=(P(),),
                       out_specs=(P(),) * 4, check_vma=False)
    seq = collective_contract(fn, jnp.ones((4,)))
    assert seq == ["psum(data) x4"], seq


def test_collectives_real_targets_emit_contracts():
    """distributed_spmm / _2d trace on one device and carry the expected
    rendezvous in their COL100 contracts; no gating findings."""
    from repro.analysis.collectives import TARGETS, analyze_collectives
    subset = tuple(t for t in TARGETS if t.name.startswith("distributed"))
    findings = analyze_collectives(subset)
    assert all(f.severity == "info" for f in findings), \
        [format_finding(f) for f in findings]
    contracts = {f.obj: f.detail["contract"] for f in findings
                 if f.code == "COL100"}
    assert any("all_gather(data)" in c
               for c in contracts["distributed_spmm[ell]"])
    assert any("reduce_scatter" in s
               for s in contracts["distributed_spmm_2d"])


# --------------------------------------------------------------------------
# pass 2 — Pallas kernel audit
# --------------------------------------------------------------------------

def _audit_one(launch):
    from repro.analysis.pallas_audit import audit_capture, \
        capture_pallas_calls
    with capture_pallas_calls() as records:
        launch()
    assert len(records) == 1
    return audit_capture(records[0], file="fx.py", obj="fx")


def test_pallas_oob_index_map_is_pal002():
    """Seeded regression: a grid-indexed BlockSpec routing one block past
    the end of its operand."""
    from jax.experimental import pallas as pl

    def launch():
        pl.pallas_call(
            lambda x_ref, o_ref: None,
            grid=(4,),
            in_specs=[pl.BlockSpec((1, 8), lambda i: (i + 1, 0))],  # OOB
            out_specs=pl.BlockSpec((1, 8), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((4, 8), jnp.float32),
        )(jnp.ones((4, 8), jnp.float32))

    codes = _codes(_audit_one(launch))
    assert "PAL002" in codes and "PAL005" not in codes


def test_pallas_sentinel_routing_oob_is_pal005():
    """A scalar-prefetch gather whose table routes past the operand —
    the missing-sentinel-row bug."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def launch():
        idx = jnp.array([0, 2, 5, 1], jnp.int32)      # 5 OOB for 4 rows
        pl.pallas_call(
            lambda idx_ref, h_ref, o_ref: None,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(4,),
                in_specs=[pl.BlockSpec((1, 128), lambda i, ix: (ix[i], 0))],
                out_specs=pl.BlockSpec((1, 128), lambda i, ix: (i, 0)),
            ),
            out_shape=jax.ShapeDtypeStruct((4, 128), jnp.float32),
        )(idx, jnp.ones((4, 128), jnp.float32))

    codes = _codes(_audit_one(launch))
    assert "PAL005" in codes and "PAL002" not in codes


def test_pallas_vmem_overflow_is_pal001():
    from jax.experimental import pallas as pl

    def launch():
        pl.pallas_call(
            lambda x_ref, o_ref: None,
            grid=(1,),
            in_specs=[pl.BlockSpec((4096, 1024), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((4096, 1024), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((4096, 1024), jnp.float32),
        )(jnp.ones((4096, 1024), jnp.float32))

    assert "PAL001" in _codes(_audit_one(launch))


def test_pallas_sublane_shape_is_pal004():
    from jax.experimental import pallas as pl

    def launch():
        pl.pallas_call(
            lambda x_ref, o_ref: None,
            grid=(4,),
            in_specs=[pl.BlockSpec((1, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((1, 128), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((4, 128), jnp.float32),
        )(jnp.ones((4, 128), jnp.float32))

    assert "PAL004" in _codes(_audit_one(launch))


def test_pallas_divisibility_is_pal003():
    from jax.experimental import pallas as pl

    def launch():
        pl.pallas_call(
            lambda x_ref, o_ref: None,
            grid=(2,),
            in_specs=[pl.BlockSpec((3, 8), lambda i: (i, 0))],  # 7 % 3
            out_specs=pl.BlockSpec((3, 8), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((7, 8), jnp.float32),
        )(jnp.ones((7, 8), jnp.float32))

    assert "PAL003" in _codes(_audit_one(launch))


def test_pallas_real_kernels_audit():
    """All registered kernels capture and audit with no gating finding:
    ELL now writes full-sublane (8, K) row tiles, so the (1, K) sublane
    penalty (PAL004) it used to carry is gone."""
    from repro.analysis.pallas_audit import analyze_pallas
    findings = analyze_pallas()
    kernels_seen = {f.obj for f in findings if f.code == "PAL100"}
    assert {"ell_spmm", "sell_spmm", "gather_spmm_heads", "gather_sddmm",
            "bsr_spmm_pallas", "flat_gather"} <= kernels_seen
    gating = [f for f in findings if f.gating]
    assert _codes(gating) == [], [format_finding(f) for f in gating]


# --------------------------------------------------------------------------
# pass 3 — AST lint
# --------------------------------------------------------------------------

def _lint(src, **kw):
    from repro.analysis.lint import lint_source
    return lint_source(src, file="fx.py", **kw)


def test_lint_captured_constant_is_lnt001():
    """PR 5's trace-bloat bug seeded back."""
    findings = _lint("""
import numpy as np, jax
def make_step(n):
    table = np.arange(n * 1000)
    @jax.jit
    def step(x):
        return x + table.sum()
    return step
""")
    assert _codes(findings) == ["LNT001"]
    assert findings[0].obj == "step"


def test_lint_jnp_constant_is_clean():
    """jnp.asarray'd closures are device arrays, not trace constants."""
    findings = _lint("""
import jax, jax.numpy as jnp
def make_step(n):
    table = jnp.arange(n * 1000)
    @jax.jit
    def step(x):
        return x + table.sum()
    return step
""")
    assert findings == []


def test_lint_argument_passed_array_is_clean():
    findings = _lint("""
import numpy as np, jax
def make_step(n):
    table = np.arange(n)
    @jax.jit
    def step(x, table):
        return x + table.sum()
    return step
""")
    assert findings == []


def test_lint_indirectly_traced_function():
    """jax.jit(f) / shard_map(f, ...) call forms count as traced too."""
    findings = _lint("""
import numpy as np, jax
def make_step():
    lut = np.ones(10)
    def body(x):
        return x * lut
    return jax.jit(body)
""")
    assert _codes(findings) == ["LNT001"]


def test_lint_shadowed_import_is_lnt002():
    """PR 9's bug seeded back, against the real repo shadow map."""
    from repro.analysis.lint import collect_shadowed_names
    shadowed = collect_shadowed_names(os.path.join(_ROOT, "src"))
    assert ("repro.core", "patch") in shadowed   # the PR 9 rebind idiom
    findings = _lint("from repro.core import patch\n", shadowed=shadowed)
    assert _codes(findings) == ["LNT002"]
    # importing the module via its full path is the sanctioned spelling
    ok = _lint("from repro.core.patch import patch_sparse_ops\n",
               shadowed=shadowed)
    assert ok == []


def test_lint_np_random_in_traced_is_lnt003():
    findings = _lint("""
import numpy as np, jax
@jax.jit
def step(x):
    return x + np.random.normal(size=3)
""")
    assert _codes(findings) == ["LNT003"]


def test_lint_time_call_in_traced_is_lnt003():
    findings = _lint("""
import time, jax
@jax.jit
def step(x):
    return x * time.time()
""")
    assert _codes(findings) == ["LNT003"]


def test_lint_meta_field_mutation_is_lnt004():
    findings = _lint("def resize(a):\n    a.nrows = 5\n",
                     meta_fields=frozenset({"nrows"}))
    assert _codes(findings) == ["LNT004"]


def test_lint_meta_fields_collected_from_repo():
    from repro.analysis.lint import collect_meta_fields
    fields = collect_meta_fields(os.path.join(_ROOT, "src"))
    # the sparse formats' static shape fields must be in the registry
    assert {"nrows", "ncols", "sell_c", "c"} <= fields


# --------------------------------------------------------------------------
# retrace-budget pass
# --------------------------------------------------------------------------

def test_retrace_budget_exceeded_is_rtb002():
    from repro.analysis.retrace import RetraceConfig, analyze_retrace
    bad = RetraceConfig("fx", "fx.py", batch_size=512, fanouts=(10, 10),
                        base=8, growth=1.05)    # absurdly fine ladder
    codes = _codes(analyze_retrace((bad,)))
    assert "RTB002" in codes


def test_retrace_full_neighbor_is_rtb003():
    from repro.analysis.retrace import RetraceConfig, analyze_retrace
    cfg = RetraceConfig("fx", "fx.py", batch_size=512, fanouts=(None, 10))
    codes = _codes(analyze_retrace((cfg,)))
    assert "RTB003" in codes and "RTB002" not in codes


def test_retrace_sane_config_reports_only():
    from repro.analysis.retrace import RetraceConfig, analyze_retrace
    cfg = RetraceConfig("fx", "fx.py", batch_size=512, fanouts=(10, 10))
    findings = analyze_retrace((cfg,))
    assert _codes(findings) == ["RTB001"]
    d = findings[0].detail
    assert d["signatures"] <= 64
    assert d["level_rungs"][0] == 1          # seed level pinned


def test_retrace_matches_runtime_ladder():
    """The analyzer's rung count agrees with the actual round_bucket
    ladder the runtime pads with."""
    from repro.analysis.retrace import ladder_rungs
    from repro.sampling import round_bucket
    for bound in (1, 128, 129, 1000, 5632, 61952):
        values = {round_bucket(n) for n in range(1, bound + 1, 7)} \
                 | {round_bucket(bound)}
        assert ladder_rungs(bound) == len(values), bound


def test_retrace_observed_signature_count():
    from repro.analysis.retrace import count_observed_signatures
    from repro.sampling.buckets import LayerBucket
    a = LayerBucket(128, 256, 1280, 10, None)
    b = LayerBucket(128, 512, 1280, 10, None)
    assert count_observed_signatures([[a], [a], [b]]) == 2


# --------------------------------------------------------------------------
# self-audit: the analyzer over this repo is clean modulo the baseline
# --------------------------------------------------------------------------

def test_self_audit_clean_modulo_baseline():
    """Lint + Pallas + retrace over src/ (the fast, device-independent
    passes; CI runs the full CLI including collectives) must produce no
    gating finding without a committed suppression."""
    from repro.analysis.cli import run_passes
    os.chdir(_ROOT)   # lint paths + baseline file are repo-relative
    findings = run_passes(["src"], ("pallas", "lint", "retrace"))
    bl = load_baseline(os.path.join(_ROOT, "analysis-baseline.json"))
    new, suppressed, _unused = bl.split(findings)
    assert new == [], [format_finding(f) for f in new]
    assert suppressed, "the committed baseline entries should match"


def test_baseline_file_reasons_are_real():
    bl = load_baseline(os.path.join(_ROOT, "analysis-baseline.json"))
    assert bl.suppressions, "expected committed suppressions"
    for s in bl.suppressions:
        assert len(s.reason) > 40, \
            f"{s.code} needs a substantive reason, got {s.reason!r}"
        assert "placeholder" not in s.reason
        assert "--write-baseline" not in s.reason
