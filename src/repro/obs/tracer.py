"""Thread-safe nestable span tracer with a disabled no-op fast path.

Design constraints, in order:

1. **Near-zero disabled cost.** ``span()`` when tracing is off is one
   module-global check returning a shared no-op context manager — no
   allocation, no lock, no clock read. The training hot loop calls it
   unconditionally; the overhead bound is pinned by a test.
2. **Daemon-thread safety.** The sampling loader packs batches in a
   daemon thread (``sampling.loader.prefetch``) and the serving tier
   answers from worker + client threads. Span *nesting* state is
   ``threading.local`` (each thread owns its stack); finished spans are
   appended to one shared list under a lock — a single short critical
   section per span *end*, never during the timed region.
3. **Monotonic clock.** All timestamps are ``time.perf_counter_ns``
   relative to the tracer's epoch; wall-clock never appears in a
   duration. The epoch's wall time is kept once for export metadata.
4. **The profiler's clock too.** An enabled span also enters a
   ``jax.profiler.TraceAnnotation`` of its name, so under
   ``jax.profiler.trace`` it lands in the same ``.xplane.pb`` as the
   device ops (host plane), and idle gaps can be read against it.

A :class:`Span` is a finished record (open spans live only on their
thread's stack). ``instant()`` records zero-duration marker events —
the autotuner's decision log uses these. ``add_span()`` admits
externally-timed intervals (the straggler watchdog reconstructs its
step windows this way).
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from typing import Any, Iterator, Optional

from jax.profiler import TraceAnnotation

from repro.obs.metrics import metrics

__all__ = ["Span", "Tracer", "get_tracer", "span", "counted_span", "instant",
           "op_record", "profiled", "enable", "disable", "enabled", "reset",
           "op_profiling_enabled"]


@dataclasses.dataclass
class Span:
    """One finished (or instant) event on the shared timeline."""

    name: str
    t_start_ns: int          # relative to the tracer epoch
    dur_ns: int              # 0 for instant events
    tid: int                 # python thread ident
    tname: str               # thread name at record time
    depth: int               # nesting depth within the recording thread
    attrs: dict = dataclasses.field(default_factory=dict)

    @property
    def t_end_ns(self) -> int:
        return self.t_start_ns + self.dur_ns

    @property
    def category(self) -> str:
        """Name prefix before the first dot — the layer convention."""
        return self.name.split(".", 1)[0]


class _OpenSpan:
    """Context manager for one live span; created only when enabled."""

    __slots__ = ("_tracer", "name", "attrs", "_t0", "_annotation")

    def __init__(self, tracer: "Tracer", name: str, attrs: dict):
        self._tracer = tracer
        self.name = name
        self.attrs = attrs

    def __enter__(self) -> "_OpenSpan":
        self._tracer._stack().append(self)
        self._annotation = TraceAnnotation(self.name)
        self._annotation.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter_ns()
        self._annotation.__exit__(*exc)
        tr = self._tracer
        stack = tr._stack()
        # tolerate a foreign unwind (an exception popped our parent):
        # pop down to and including this span
        while stack and stack.pop() is not self:
            pass
        tr._record(Span(
            name=self.name, t_start_ns=self._t0 - tr.epoch_ns,
            dur_ns=t1 - self._t0, tid=threading.get_ident(),
            tname=threading.current_thread().name, depth=len(stack),
            attrs=self.attrs))


class _NoopSpan:
    """The shared disabled-path context manager."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None


_NOOP = _NoopSpan()


class Tracer:
    """Collects finished spans; one process singleton via :func:`get_tracer`.

    ``enabled`` gates span creation; ``ops_enabled`` additionally gates
    the (chattier) kernel-dispatch records. ``max_spans`` bounds memory:
    past the bound new spans are dropped and counted (``n_dropped``) —
    a profiled run should export and :meth:`reset`, not grow forever.
    """

    def __init__(self, max_spans: int = 1_000_000):
        self.enabled = False
        self.ops_enabled = False
        self.max_spans = int(max_spans)
        self.n_dropped = 0
        self.epoch_ns = time.perf_counter_ns()
        self.epoch_unix_s = time.time()
        self.spans: list[Span] = []
        self._lock = threading.Lock()
        self._tls = threading.local()

    # -- internals ---------------------------------------------------------
    def _stack(self) -> list:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def _record(self, s: Span) -> None:
        with self._lock:
            if len(self.spans) >= self.max_spans:
                self.n_dropped += 1
                return
            self.spans.append(s)

    # -- recording API -----------------------------------------------------
    def span(self, name: str, **attrs):
        """Context manager timing a region; no-op when disabled."""
        if not self.enabled:
            return _NOOP
        return _OpenSpan(self, name, attrs)

    def instant(self, name: str, **attrs) -> None:
        """Zero-duration marker event (decision logs, faults, refreshes)."""
        if not self.enabled:
            return
        self._record(Span(
            name=name, t_start_ns=time.perf_counter_ns() - self.epoch_ns,
            dur_ns=0, tid=threading.get_ident(),
            tname=threading.current_thread().name,
            depth=len(self._stack()), attrs=attrs))

    def add_span(self, name: str, t_start_ns: int, dur_ns: int,
                 **attrs) -> None:
        """Record an externally-timed interval. ``t_start_ns`` is absolute
        ``time.perf_counter_ns`` (the tracer converts to its epoch) —
        callers that measured a duration ending "now" pass
        ``time.perf_counter_ns() - dur_ns``."""
        if not self.enabled:
            return
        self._record(Span(
            name=name, t_start_ns=int(t_start_ns) - self.epoch_ns,
            dur_ns=max(int(dur_ns), 0), tid=threading.get_ident(),
            tname=threading.current_thread().name, depth=0, attrs=attrs))

    # -- lifecycle ---------------------------------------------------------
    def reset(self) -> None:
        """Drop collected spans (enable state unchanged). Thread stacks are
        per-thread and self-healing; the epoch moves so a fresh profile
        starts near t=0."""
        with self._lock:
            self.spans = []
            self.n_dropped = 0
            self.epoch_ns = time.perf_counter_ns()
            self.epoch_unix_s = time.time()

    def snapshot(self) -> list[Span]:
        with self._lock:
            return list(self.spans)


_TRACER = Tracer()


def get_tracer() -> Tracer:
    """The process-wide tracer singleton."""
    return _TRACER


def enabled() -> bool:
    return _TRACER.enabled


def op_profiling_enabled() -> bool:
    return _TRACER.ops_enabled


def _sync_patch_version(prev_ops: bool) -> None:
    # Jitted layers bind resolve()'s result at trace time keyed on
    # patch_version(); an ops-profiling toggle must invalidate those
    # traces so the recording wrapper is picked up / shed.
    if prev_ops != _TRACER.ops_enabled:
        try:
            # NB: symbol import — ``repro.core`` re-exports ``patch`` the
            # *function*, shadowing the submodule on the package object
            from repro.core.patch import bump_version
            bump_version()
        except ImportError:                          # pragma: no cover
            pass


def enable(*, ops: bool = True) -> None:
    """Turn tracing on (``ops`` additionally records kernel dispatches)."""
    prev_ops = _TRACER.ops_enabled
    _TRACER.enabled = True
    _TRACER.ops_enabled = bool(ops)
    _sync_patch_version(prev_ops)


def disable() -> None:
    prev_ops = _TRACER.ops_enabled
    _TRACER.enabled = False
    _TRACER.ops_enabled = False
    _sync_patch_version(prev_ops)


def reset() -> None:
    _TRACER.reset()


def span(name: str, **attrs):
    """Module-level shorthand: ``with obs.span("train.step", plan="ell"):``.
    The disabled path is one flag check + shared no-op."""
    if not _TRACER.enabled:
        return _NOOP
    return _OpenSpan(_TRACER, name, attrs)


@contextlib.contextmanager
def counted_span(name: str, **attrs) -> Iterator[None]:
    """A span that also adds its seconds to the always-live counter
    ``<name>_s``, on or off: for set-up parts (``setup.*``), whose cadence
    makes the clock reads free."""
    t0 = time.perf_counter()
    try:
        with span(name, **attrs):
            yield
    finally:
        metrics().counter(f"{name}_s").inc(time.perf_counter() - t0)


def instant(name: str, **attrs) -> None:
    _TRACER.instant(name, **attrs)


@contextlib.contextmanager
def profiled(*, ops: bool = True, fresh: bool = True) -> Iterator[Tracer]:
    """Enable tracing for a ``with`` region, restoring the previous state
    after. ``fresh=True`` resets collected spans on entry so the region's
    export starts clean; spans stay in the tracer afterwards for
    :func:`repro.obs.export.write_chrome_trace`."""
    prev = (_TRACER.enabled, _TRACER.ops_enabled)
    if fresh:
        _TRACER.reset()
    enable(ops=ops)
    try:
        yield _TRACER
    finally:
        prev_ops = _TRACER.ops_enabled
        _TRACER.enabled, _TRACER.ops_enabled = prev
        _sync_patch_version(prev_ops)


# --------------------------------------------------------------------------
# Kernel-dispatch records (profile-ops mode)
# --------------------------------------------------------------------------

def _shape_of(x: Any):
    shp = getattr(x, "shape", None)
    return None if shp is None else tuple(int(d) for d in shp)


def op_record(name: str, *operands, plan: Optional[str] = None,
              **attrs) -> None:
    """Record one kernel dispatch from ``kernels/ops`` / ``core.patch`` /
    ``block_spmm`` as an instant ``op.<name>.trace`` marker: op name,
    operand shapes, chosen plan. Inside ``jit`` it fires at trace time,
    once per traced call, so counts and plans are exact but there is no
    time: device time per kernel comes from the device trace, by stage
    (``repro.obs.stages``)."""
    if not _TRACER.ops_enabled:
        return
    shapes = [s for s in (_shape_of(o) for o in operands) if s is not None]
    if plan is not None:
        attrs["plan"] = plan
    attrs["shapes"] = shapes
    _TRACER.instant(f"op.{name}.trace", **attrs)
