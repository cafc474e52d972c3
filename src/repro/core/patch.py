"""patch()/unpatch() — the paper's two-lines-of-code integration (§3.6).

iSpLib monkey-patches PyG's spmm so existing model code silently runs the
tuned kernels. The JAX-native equivalent implemented here is an *op registry
interception*: every GNN layer in this repo routes its aggregation through
``resolve('spmm')`` (etc.), and ``patch()`` swaps the registry's binding from
the baseline implementation (uncached, untuned — the PyTorch-equivalent) to
the tuned iSpLib-style implementation. ``unpatch()`` restores it;
``patched()`` is a context manager; ``@patch_fn`` is the paper's
single-function decorator.

Because jitted functions close over the binding at *trace* time, patch state
is part of the cache key: we bump a version counter that layers fold into
their static config, so switching patch state retraces rather than silently
reusing stale kernels.

Profile mode (``repro.obs``): when op profiling is enabled
(``obs.enable(ops=True)`` / ``obs.profiled()``), ``resolve`` hands back a
recording wrapper — every dispatch through the registry logs the op name,
operand shapes, and whether the tuned or baseline binding served it, as
an instant ``op.<name>.trace`` marker (inside ``jit`` it fires at trace
time; see ``obs.op_record``). Disabled, ``resolve`` returns the raw
callable: the hot path pays one module-flag check at trace time only.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Any, Callable

__all__ = ["patch", "unpatch", "patched", "patch_fn", "resolve",
           "register_baseline", "register_tuned", "is_patched",
           "patch_version"]

_BASELINE: dict[str, Callable] = {}
_TUNED: dict[str, Callable] = {}
_ACTIVE = False
_VERSION = 0


def register_baseline(name: str, fn: Callable) -> None:
    _BASELINE[name] = fn


def register_tuned(name: str, fn: Callable) -> None:
    _TUNED[name] = fn


def is_patched() -> bool:
    return _ACTIVE


def patch_version() -> int:
    """Fold into static/hash state of jitted callers (retrace on toggle)."""
    return _VERSION


def bump_version() -> None:
    """Invalidate traced-in bindings without changing patch state. The
    obs layer calls this when op profiling toggles, so jitted callers
    re-resolve and pick up (or shed) the recording wrapper."""
    global _VERSION
    _VERSION += 1


def patch() -> None:
    """Route every registered op to the tuned implementation."""
    global _ACTIVE, _VERSION
    if not _ACTIVE:
        _ACTIVE = True
        _VERSION += 1


def unpatch() -> None:
    global _ACTIVE, _VERSION
    if _ACTIVE:
        _ACTIVE = False
        _VERSION += 1


@contextlib.contextmanager
def patched(enable: bool = True):
    prev = _ACTIVE
    (patch if enable else unpatch)()
    try:
        yield
    finally:
        (patch if prev else unpatch)()


def resolve(name: str) -> Callable:
    """The binding GNN layers call at trace time."""
    table = _TUNED if _ACTIVE else _BASELINE
    variant = "tuned" if _ACTIVE else "baseline"
    if name not in table:
        other = _BASELINE if _ACTIVE else _TUNED
        if name in other:   # graceful: fall through to whichever exists
            table, variant = other, ("baseline" if _ACTIVE else "tuned")
        else:
            raise KeyError(f"op {name!r} is not registered")
    fn = table[name]
    from repro.obs import op_profiling_enabled
    if op_profiling_enabled():
        return _profiled_binding(name, variant, fn)
    return fn


def _profiled_binding(name: str, variant: str, fn: Callable) -> Callable:
    """Recording wrapper handed out by ``resolve`` in profile-ops mode."""
    from repro.obs import op_record

    @functools.wraps(fn)
    def recorded(*args, **kwargs):
        out = fn(*args, **kwargs)
        op_record(name, *args, variant=variant)
        return out
    return recorded


def patch_fn(fn: Callable) -> Callable:
    """Decorator form (paper: 'a decorator for patching a single function'):
    the wrapped function runs with the tuned bindings active."""
    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        with patched(True):
            return fn(*args, **kwargs)
    return wrapper


# --------------------------------------------------------------------------
# Default registrations: baseline = uncached/untuned PT-equivalent,
# tuned = the CachedGraph-aware iSpLib path. Layers call resolve('spmm').
# --------------------------------------------------------------------------

def _register_defaults() -> None:
    from repro.core.spmm import spmm as _tuned_spmm
    from repro.core import baselines

    register_tuned("spmm", _tuned_spmm)
    register_baseline("spmm", baselines.spmm_uncached)
    register_tuned("gat_attention", _import_tuned_gat_attention)
    register_baseline("gat_attention", baselines.gat_attention_uncached)


def _import_tuned_gat_attention(g, z, s_dst, s_src, **kw):
    from repro.core.fusedmm import gat_attention
    return gat_attention(g, z, s_dst, s_src, **kw)


# deferred: baselines imports this module's registry at import time
def _ensure_defaults() -> None:
    if "spmm" not in _TUNED:
        _register_defaults()
