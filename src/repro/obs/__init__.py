"""repro.obs — unified observability: span tracing + metrics + export.

One process-wide :class:`~repro.obs.tracer.Tracer` and one
:class:`~repro.obs.metrics.MetricsRegistry` serve every layer of the
stack — kernels/autotune dispatch, minibatch/full-batch training, the
sampling loader's prefetch daemon thread, and the serving tier — so a
profiled run produces a single timeline instead of four private stat
piles. Spans are **disabled by default**: the hot-loop cost of a
disabled ``obs.span(...)`` is one module-flag check returning a shared
no-op context manager, measured in the test suite against an explicit
per-call bound. Counters are always live.

Quickstart::

    from repro import obs

    with obs.profiled():                       # enable tracing + op records
        train_gnn_minibatch(..., profile=True)
    obs.write_chrome_trace("trace.json")       # chrome://tracing / Perfetto
    print(obs.metrics().snapshot())

    # or attribution without leaving the terminal:
    #   PYTHONPATH=src python tools/trace_summary.py trace.json

One trace with the program's spans and the device ops: an enabled span
also enters a ``jax.profiler.TraceAnnotation`` of its name, so ::

    with obs.profiled(ops=False):
        jax.profiler.start_trace(logdir)
        train_gnn(...)
        jax.profiler.stop_trace()

writes one ``.xplane.pb`` (XProf, TensorBoard, Perfetto) whose host plane
holds ``train.*`` / ``loader.*`` / ``serve.*`` / ``setup.*`` spans on the
profiler's clock, beside the device ops, which XProf can group by stage.

Stages (``repro.obs.stages``): the jitted training steps run each piece
under one named stage, and every op it creates carries that stage in its
name scope and its creating stack:

=========  ======================================================
stage      ops
=========  ======================================================
sample     the device sampler
gather     feature-row gathers
normalize  the in-step GCN normalisation (unpatched path)
aggregate  every SpMM / block-SpMM of a model layer
dense      the layers' products, biases and activations
loss       the cross-entropy
grad_sync  every collective of the step
optimizer  the non-finite guard, the AdamW update and the selects
=========  ======================================================

Layer conventions (span and counter name prefixes):

========  ====================================================
prefix    layer
========  ====================================================
train.    trainer stages: sample / pack / h2d / step / ckpt / infer
loader.   host pipeline (prefetch stalls — recorded from the
          consumer side; producer-side sample/pack spans carry the
          daemon thread's tid)
setup.    one-time graph set-up parts: normalize / transpose /
          tune / pack / slot_perm — spans when enabled, and always
          counted in ``setup.<part>_s`` (``obs.counted_span``)
jit.      compiles: the compile log (``repro.obs.compiles``: per
          program, count and seconds of trace, lowering and
          compile-or-load), the always-live ``jit.compiles`` and
          ``jit.compile_s`` counters, ``jit.compile`` spans
op.       kernel dispatch records (profile-ops mode): trace-time
          ``op.<name>.trace`` counts, shapes and plans, no time —
          device time per kernel comes from the device trace;
          ``ell_spmm`` / ``sell_spmm`` / ``gather_spmm_heads`` /
          ``gather_sddmm`` on Pallas add the row-gather kernel's
          ``rows_per_step``, ``depth`` and ``elements`` (and ``heads``)
kernels.  counter ``kernels.attention_slots``: slots times heads of each
          traced GAT attention call on a gather plan; counter
          ``kernels.attention_row_indices``: indices its softmax's
          row broadcasts and reductions issue per trace (one per
          SELL step for all heads, none on ELL)
tuning.   autotuner decisions (instant events: candidates,
          timings, winner)
serve.    serving tier: queue_wait / sample / pack / gather /
          apply per flush
watchdog. StragglerWatchdog step events
========  ====================================================
"""
from repro.obs.tracer import (Span, Tracer, counted_span, disable, enable,
                              enabled, get_tracer, instant,
                              op_profiling_enabled, op_record, profiled,
                              reset, span)
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               metrics, metrics_to_jsonl)
from repro.obs.export import (to_chrome_trace, validate_chrome_trace,
                              write_chrome_trace)
from repro.obs.device_counters import (DeviceCounters, device_counters)
from repro.obs import compiles, stages

__all__ = [
    "Span", "Tracer", "span", "counted_span", "instant", "op_record",
    "profiled", "compiles", "stages",
    "enable", "disable", "enabled", "reset", "get_tracer",
    "op_profiling_enabled",
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "metrics",
    "metrics_to_jsonl",
    "to_chrome_trace", "write_chrome_trace", "validate_chrome_trace",
    "DeviceCounters", "device_counters",
]
