"""Benchmark entry point: one bench per paper table/figure + extras.

    PYTHONPATH=src python -m benchmarks.run [--fast] [--only a,b] [--no-json]

Two outputs per run:
  * CSV rows streamed to stdout: name,us_per_call,derived;
  * one entry appended to ``BENCH_<name>.json`` at the repo root per bench —
    the machine-readable perf trajectory (timestamp + git rev + structured
    rows), so regressions/speedups are visible across PRs without parsing
    logs. ``--label`` tags the entry (e.g. "baseline" vs "sell").
"""
from __future__ import annotations

import argparse
import datetime
import json
import os
import subprocess
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _git_rev() -> str | None:
    try:
        out = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                             cwd=_ROOT, capture_output=True, text=True,
                             timeout=10)
        return out.stdout.strip() or None
    except Exception:
        return None


def record_json(name: str, rows, label: str | None = None) -> str:
    """Append one run's structured rows to ``BENCH_<name>.json``.

    The file holds a list of runs (the trajectory); each entry is
    ``{ts, git, label, rows}``. Corrupt/absent files start a fresh list.
    """
    path = os.path.join(_ROOT, f"BENCH_{name}.json")
    history: list = []
    if os.path.exists(path):
        try:
            with open(path) as f:
                history = json.load(f)
            if not isinstance(history, list):
                history = []
        except (json.JSONDecodeError, OSError):
            history = []
    history.append({
        "ts": datetime.datetime.now(datetime.timezone.utc)
        .isoformat(timespec="seconds"),
        "git": _git_rev(),
        "label": label,
        "rows": rows,
    })
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(history, f, indent=1, default=str)
    os.replace(tmp, path)
    return path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="smaller datasets / fewer points")
    ap.add_argument("--only", default=None,
                    help="comma-separated bench names")
    ap.add_argument("--no-json", action="store_true",
                    help="skip appending to BENCH_<name>.json")
    ap.add_argument("--label", default=None,
                    help="tag for the BENCH_<name>.json entry")
    args = ap.parse_args()

    from repro.launch.compile_cache import configure_compile_cache
    print(f"# compile cache {configure_compile_cache(_ROOT)}")
    from benchmarks import (bench_cached_backprop, bench_dist2d,
                            bench_gnn_training, bench_kernels, bench_lm_step,
                            bench_moe_dispatch, bench_sampling,
                            bench_serving, bench_tuning_curve)

    scale = 1 / 256 if args.fast else 1 / 64
    benches = {
        "tuning_curve": lambda: bench_tuning_curve.run(
            datasets=("reddit", "ogbn-proteins"), scale=scale,
            ks=(16, 32, 64, 128) if args.fast else (16, 32, 64, 128, 256,
                                                    512)),
        "gnn_training": lambda: bench_gnn_training.run(
            datasets=("reddit", "ogbn-proteins") if args.fast else
            ("reddit", "reddit2", "ogbn-mag", "amazon", "ogbn-products",
             "ogbn-proteins"),
            scale=scale, epochs=5 if args.fast else 10),
        "cached_backprop": lambda: bench_cached_backprop.run(
            datasets=("reddit",) if args.fast else
            ("reddit", "ogbn-products"), scale=scale),
        "kernels": lambda: bench_kernels.run(scale=scale),
        "dist2d": lambda: bench_dist2d.run(
            n=1024 if args.fast else 4096,
            nnz=20_000 if args.fast else 200_000),
        # fast = the CI smoke (tiny fanout, 1/512 scale, 2 epochs); full =
        # the acceptance point (scale 1/32, within-2-points criterion)
        "sampling": lambda: bench_sampling.run(
            scale=1 / 512 if args.fast else 1 / 32,
            fanouts=(5, 5) if args.fast else (10, 10),
            batch_size=128 if args.fast else 512,
            epochs=2 if args.fast else 5,
            fb_epochs=5 if args.fast else 30),
        # fast = the CI smoke (tiny graph, 2 concurrency levels, short
        # volleys); full = the latency/QPS curves at 3 levels x cache on/off
        "serving": lambda: bench_serving.run(
            scale=1 / 512 if args.fast else 1 / 64,
            fanouts=(5, 5) if args.fast else (10, 10),
            hidden=32 if args.fast else 64,
            concurrency=(1, 4) if args.fast else (1, 4, 8),
            n_requests=60 if args.fast else 240,
            cache_rows=(0, 1024) if args.fast else (0, 4096)),
        "moe_dispatch": lambda: bench_moe_dispatch.run(
            t=2048 if args.fast else 8192),
        "lm_step": lambda: bench_lm_step.run(
            archs=("llama3-8b", "mamba2-1.3b") if args.fast else None),
    }
    only = set(args.only.split(",")) if args.only else None

    print("name,us_per_call,derived")
    t0 = time.time()
    for name, fn in benches.items():
        if only and name not in only:
            continue
        print(f"# --- {name} ---", flush=True)
        rows = fn()
        if rows and not args.no_json:
            path = record_json(name, rows, label=args.label)
            print(f"# wrote {os.path.relpath(path, _ROOT)}", flush=True)
    print(f"# total_wall_s={time.time() - t0:.1f}")


if __name__ == "__main__":
    main()
