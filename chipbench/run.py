#!/usr/bin/env python3
"""The chip benchmark: one run of one cell.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``<cell>`` is a ``workloads`` entry of ``BENCHMARK.json``. The run finds the
cell's configuration, traffic and limits files by name (``lib/cells.py``),
hands the traffic's driver (``drivers/<driver>.py``) the set-up and the
window, and prints one JSON line last on standard output:

* ``--trace 0``: the cell's end-to-end metrics;
* ``--trace 1``: the same run under the profiler, and the cell's per-layer
  metrics, each read by ``metrics/<name>.py`` from the reduced trace.

``correct`` holds when every number the cell's limits file names is at or
under its limit; each is printed beside its limit, last on standard error
and as the last key of the result line. Without a TPU, or with fewer chips
than the cell asks for, the run exits 3 before it prints any result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()   # set-up is measured from here

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

NO_CHIP = 3


@dataclasses.dataclass
class Spec:
    """What a driver's ``run(spec)`` gets."""
    cell: object
    seed: int
    seconds: int
    trace_dir: str | None       # profiler output directory, or None
    t_start: float
    root: str
    devices: list


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def configure_cache(root: str) -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    when set, else the fixed ``<checkout>/.jax_cache``. Every program is
    cached, so only a checkout's first run of a cell compiles."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def main(argv=None, *, require_tpu: bool = True, traffic_overrides=None,
         root: str = ROOT) -> int:
    args = parse(argv)
    from chipbench.lib import cells
    cell = cells.resolve(args.workload, root)
    if traffic_overrides:
        cell.traffic.update(traffic_overrides)

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if require_tpu and (platform != "tpu" or len(devices) < cell.chips):
        print(f"chipbench: cell {cell.name} needs {cell.chips} TPU chip(s); "
              f"found {len(devices)} {platform} device(s). Nothing was run.",
              file=sys.stderr)
        return NO_CHIP
    import repro  # noqa: F401  -- the system under test; fail before set-up
    configure_cache(root)
    used = devices[:cell.chips]

    trace_dir = tempfile.mkdtemp(prefix="chipbench-") if args.trace else None
    try:
        out = cells.driver(cell).run(Spec(
            cell=cell, seed=args.seed, seconds=args.seconds,
            trace_dir=trace_dir, t_start=T_START, root=root, devices=used))
        metrics, device_extra, breakdown = {}, {}, None
        if args.trace:
            from chipbench.lib import peaks, trace
            view = trace.reduce(trace_dir, out["work"], cell.chips,
                                peaks.peaks_for(devices[0].device_kind))
            device_extra = {"busy_s": view.busy_s, "window_s": view.window_s}
            breakdown = view.breakdown()
            for m in cell.per_layer:
                value = cells.metric_reader(m["name"]).read(view)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        else:
            for m in cell.end_to_end:
                metrics[m["name"]] = {"value": out["end_to_end"][m["name"]],
                                      "unit": m["unit"]}
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    checks = {}
    for name, limit in cell.limits.items():
        value = out["numbers"].get(name)
        checks[name] = {"value": value, "limit": limit}
    correct = all(c["value"] is not None and c["value"] <= c["limit"]
                  for c in checks.values())
    result = {
        "correct": correct, "attempted": out["attempted"],
        "failed": out["failed"], "metrics": metrics,
        "device": {"platform": platform, "kind": devices[0].device_kind,
                   "count": len(devices),
                   "memory_peak_bytes": out["memory_peak_bytes"],
                   **device_extra},
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for name, secs in out["setup_parts"].items():
        print(f"setup {name} {secs!r} s", file=sys.stderr)
    print(f"reference {out['reference_s']!r} s", file=sys.stderr)
    for name, c in checks.items():
        verdict = "ok" if c["value"] is not None and c["value"] <= c["limit"] \
            else "FAIL"
        print(f"check {name} {c['value']!r} limit {c['limit']!r} {verdict}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
