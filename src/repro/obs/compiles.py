"""Compile log: what each jitted program cost to trace, lower and compile.

Installed once, on ``import repro.obs``, as a listener on
``jax.monitoring``'s duration events. Per program it keeps the count and
seconds of three events:

=======  ==============================================  ===============
phase    JAX event                                        what it times
=======  ==============================================  ===============
trace    ``/jax/core/compile/jaxpr_trace_duration``       Python -> jaxpr
lower    ``/jax/core/compile/jaxpr_to_mlir_module_...``  jaxpr -> MLIR
compile  ``/jax/core/compile/backend_compile_duration``  XLA compile, or
                                                          a load from the
                                                          persistent cache
=======  ==============================================  ===============

A program is named as JAX names its lowering, ``jit(<function>)``; the
profiler's module events name the same program ``jit_<function>``. The
events also feed two always-live counters: ``jit.compiles`` (compile
events) and ``jit.compile_s`` (seconds of all three). With tracing on, each
compile is also a ``jit.compile`` span.
"""
from __future__ import annotations

import threading
import time

from jax import monitoring

from repro.obs.metrics import metrics
from repro.obs.tracer import get_tracer

__all__ = ["EVENTS", "log", "program"]

EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}

_LOCK = threading.Lock()
_LOG: dict[str, dict[str, list]] = {}    # program -> phase -> [count, s]


def _program_name(fun_name: str) -> str:
    # the trace event names the bare function, the other two jit(<function>)
    return fun_name if fun_name.startswith("jit(") else f"jit({fun_name})"


def _on_duration(event: str, seconds: float, **kwargs) -> None:
    phase = EVENTS.get(event)
    if phase is None:
        return
    name = _program_name(str(kwargs.get("fun_name", "?")))
    with _LOCK:
        rec = _LOG.setdefault(name, {}).setdefault(phase, [0, 0.0])
        rec[0] += 1
        rec[1] += seconds
    reg = metrics()
    reg.counter("jit.compile_s").inc(seconds)
    if phase == "compile":
        reg.counter("jit.compiles").inc()
        get_tracer().add_span("jit.compile",
                              time.perf_counter_ns() - int(seconds * 1e9),
                              int(seconds * 1e9), program=name)


def log() -> dict[str, dict[str, tuple[int, float]]]:
    """``{program: {phase: (count, seconds)}}`` since ``repro.obs`` was
    imported."""
    with _LOCK:
        return {name: {ph: (c, s) for ph, (c, s) in phases.items()}
                for name, phases in _LOG.items()}


def program(name: str) -> dict[str, tuple[int, float]]:
    """The phases of one program, ``jit(step)`` or, as the profiler names
    it, ``jit_step``."""
    if name.startswith("jit_"):
        name = f"jit({name[4:]})"
    return log().get(name, {})


monitoring.register_event_duration_secs_listener(_on_duration)
