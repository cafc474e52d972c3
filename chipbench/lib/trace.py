"""Capture a profiler trace of the window and reduce it to what the
per-layer metrics read.

The window on the trace's clock is the host annotation ``chipbench.window``
when the driver wraps its loop in one; otherwise it runs from the end of the
first execution of the driver's step program (``work["step_module"]``,
whose first call is outside the timed epochs) to the end of its last.

A device op is an event of a TPU plane's ``XLA Ops`` line that no other op
event contains (a ``while`` holds its body's ops; its time is theirs); busy
time is the union of those intervals inside the window, averaged over the
chips the cell uses. Each op is joined to its instruction in the program's optimized HLO
(``lib/xplane.py``), which gives its opcode and the Python stack that created
it: the metric readers assign ops to layers by that stack.
"""
from __future__ import annotations

import bisect
import collections
import contextlib
import dataclasses
import glob
import os

WINDOW = "chipbench.window"


@contextlib.contextmanager
def capture(trace_dir: str | None, window: bool = False):
    """Profile the block into ``trace_dir`` (no-op when None); with
    ``window`` the block is also the annotated window."""
    if trace_dir is None:
        yield
        return
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # host spans only, not every call
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = True        # the ops' creating stacks
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        if window:
            with jax.profiler.TraceAnnotation(WINDOW):
                yield
        else:
            yield
    finally:
        jax.profiler.stop_trace()


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


@dataclasses.dataclass
class Op:
    chip: int
    name: str          # HLO instruction name
    program: str       # module event name, e.g. "jit_update(7431...)"
    start: float       # ns, trace clock
    dur: float         # ns
    opcode: str
    stack: tuple       # ((file, function), ...) innermost first


def union(intervals) -> float:
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def in_stack(op: Op, module: str, function: str | None = None) -> bool:
    """Whether ``op`` was created under ``function`` of ``module`` (a path
    suffix such as ``repro/core/spmm.py``)."""
    return any(f.endswith(module) and (function is None or fn == function)
               for f, fn in op.stack)


@dataclasses.dataclass
class View:
    window: tuple          # (start ns, end ns)
    chips: int
    ops: list              # Op inside the window, all chips
    gaps: list             # (ns, host activity) idle stretches of chip 0
    work: dict             # what the driver counted
    peaks: dict
    busy_ns: float         # summed over chips

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        return self.busy_ns * 1e-9 / self.chips

    def seconds(self, keep) -> float:
        """Device seconds of the ops ``keep`` selects, per chip."""
        return sum(op.dur for op in self.ops if keep(op)) * 1e-9 / self.chips

    def breakdown(self) -> dict:
        by_op = collections.Counter()
        for op in self.ops:
            by_op[_label(op)] += op.dur * 1e-9 / self.chips
        gaps = collections.Counter()
        for ns, what in self.gaps:
            gaps[what] += ns * 1e-9
        return {"device_ops": [[k, v] for k, v in by_op.most_common(10)],
                "idle_gaps": [[k, v] for k, v in gaps.most_common(10)]}


def _label(op: Op) -> str:
    """Instruction, opcode and innermost frame of the repository's code."""
    where = next((f"{f.rsplit('/src/', 1)[-1]}:{fn}" for f, fn in op.stack
                  if "/repro/" in f), "-")
    return f"{op.name} {op.opcode} {where}"


def _xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {found}")
    return found[0]


def reduce(trace_dir_or_file: str, work: dict, chips: int, peaks: dict
           ) -> View:
    from jax.profiler import ProfileData

    from chipbench.lib import xplane

    path = trace_dir_or_file if trace_dir_or_file.endswith(".pb") \
        else _xplane(trace_dir_or_file)
    pd = ProfileData.from_file(path)
    hlo = xplane.hlo_protos(path)
    tables: dict = {}

    def table(program: str) -> dict:
        if program not in tables:
            tables[program] = (xplane.instructions(hlo[program])
                               if program in hlo else {})
        return tables[program]

    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            idx = plane.name.rsplit(":", 1)[1]
            if idx.isdigit() and int(idx) < chips:
                lines = {ln.name: list(ln.events) for ln in plane.lines}
                devices[int(idx)] = lines
        elif plane.name.startswith("/host:CPU"):
            for ln in plane.lines:
                host.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                            for e in ln.events)
    if len(devices) != chips:
        raise RuntimeError(f"trace holds {len(devices)} of {chips} chips")

    window = next(((s, e) for s, e, n in host if n == WINDOW), None)
    if window is None:
        step = [e for e in devices[0].get("XLA Modules", [])
                if e.name.startswith(work["step_module"] + "(")]
        if len(step) < 2:
            raise RuntimeError("the trace holds fewer than two executions "
                               f"of {work['step_module']}")
        window = (step[0].start_ns + step[0].duration_ns,
                  step[-1].start_ns + step[-1].duration_ns)
    lo, hi = window

    ops, busy_ns, gaps = [], 0.0, []
    for chip, lines in sorted(devices.items()):
        mods = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                      for e in lines.get("XLA Modules", []))
        starts = [m[0] for m in mods]
        intervals, outer_end = [], float("-inf")
        for e in sorted(lines.get("XLA Ops", []), key=lambda e: e.start_ns):
            end = e.start_ns + e.duration_ns
            if end <= outer_end:        # nested in an op already counted
                continue
            outer_end = end
            s, t = max(e.start_ns, lo), min(end, hi)
            if t <= s:
                continue
            i = bisect.bisect_right(starts, e.start_ns) - 1
            program = mods[i][2] if i >= 0 and e.start_ns < mods[i][1] else ""
            name = e.name.split(" = ", 1)[0].lstrip("%")
            opcode, _, stack = table(program).get(name, ("", "", ()))
            ops.append(Op(chip, name, program, s, t - s, opcode, stack))
            intervals.append((s, t))
        busy_ns += union(intervals)
        if chip == 0:
            gaps = _gaps(intervals, lo, hi, host)
    return View(window=window, chips=chips, ops=ops, gaps=gaps, work=work,
                peaks=peaks, busy_ns=busy_ns)


def _gaps(intervals, lo, hi, host, named: int = 200) -> list:
    """Idle stretches of one chip inside the window. The ``named`` longest
    are each named by the shortest host span that covers their middle (what
    the host was doing then); the rest are summed as ``shorter gaps``."""
    import numpy as np
    idle, end = [], lo
    for s, t in sorted(intervals) + [(hi, hi)]:
        if s > end:
            idle.append((s - end, (s + end) / 2))
        end = max(end, t)
    idle.sort(reverse=True)
    b = np.array([h[0] for h in host], np.float64)
    e = np.array([h[1] for h in host], np.float64)
    out = []
    for k, (ns, mid) in enumerate(idle):
        if k >= named:
            out.append((ns, "shorter gaps"))
            continue
        cover = np.flatnonzero((b <= mid) & (e >= mid))
        name = host[cover[np.argmin(e[cover] - b[cover])]][2] \
            if len(cover) else "host idle"
        out.append((ns, name))
    return out
