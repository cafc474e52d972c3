#!/usr/bin/env python3
"""Readings that set a cell's limits, on the chip at the cell's own size.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,... \\
        [--control-seeds 1,2,3] [--fault-seeds 1,2,3] [--out <file>]

One process builds the cell once and then, for each seed, drives the
program's timed path through its first checked steps and compares them with
the plain reference, exactly as a run does after its window (the sound
readings). The same comparison is then made for:

* ``control``: the reference in the program's place, computed in bfloat16
  (the configurations state float32 at the default product precision);
* ``fault.unchanged``: the program with its update left out (the step hands
  back its parameters unchanged);
* ``fault.half_batch``: the reference in the program's place with the loss
  taken over the first half of each batch's real seeds (full batch: of the
  training nodes);
* ``fault.no_exchange`` (cells on several chips): the program with the
  gradient all-reduce left out.

The benchmark's own runs never run this. Each reading is printed as one JSON
line, and all of them are written to ``--out``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def _seeds(text: str) -> list[int]:
    return [int(s) for s in text.split(",") if s]


def _emit(rows: list, kind: str, seed: int, numbers: dict) -> None:
    row = {"kind": kind, "seed": seed, **numbers}
    rows.append(row)
    print(json.dumps(row), flush=True)


def fullbatch(cell, args, rows: list) -> None:
    import jax.numpy as jnp

    import repro.train.gnn as program_gnn
    from chipbench.lib import cells

    drv = cells.driver(cell)
    s = drv.Setup(cell, ROOT)
    steps = drv.CHECK_STEPS
    refs: dict = {}

    def reference(seed):
        if seed not in refs:
            refs[seed] = s.reference(seed)
        return refs[seed]

    for seed in args.seeds:
        res = s.train(seed, epochs=steps)
        _emit(rows, "sound", seed, drv.numbers(res.losses, reference(seed)))
    for seed in args.control_seeds:
        ctrl = s.reference(seed, dtype=jnp.bfloat16)
        _emit(rows, "control", seed, drv.numbers(ctrl, reference(seed)))
    for seed in args.fault_seeds:
        half = s.reference(seed, loss_share=0.5)
        _emit(rows, "fault.half_batch", seed,
              drv.numbers(half, reference(seed)))
    keep = program_gnn.apply_updates
    program_gnn.apply_updates = lambda params, updates: params
    try:
        for seed in args.fault_seeds:
            res = s.train(seed, epochs=steps)
            _emit(rows, "fault.unchanged", seed,
                  drv.numbers(res.losses, reference(seed)))
    finally:
        program_gnn.apply_updates = keep


def sampled(cell, args, rows: list) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import repro.dist.collectives as program_collectives
    import repro.train.gnn_minibatch as program_mb
    from chipbench.lib import cells
    from chipbench.reference import sage as ref

    drv = cells.driver(cell)
    s = drv.Setup(cell, ROOT)
    as_np = lambda t: jax.tree_util.tree_map(np.asarray, t)  # noqa: E731

    def program_numbers(seed):
        _, items, prog = drv.first_steps(s, seed, s.feed(seed))
        return items, prog

    def compare(seed, items, prog, **kw):
        faults, overflow, losses, grad, final, _ = s.reference(seed, items)
        p0 = as_np(ref.init_params(seed, s.dims))
        if kw:
            _, _, k_losses, k_grad, k_final, _ = s.reference(seed, items, **kw)
            p0_low = ref.cast(ref.init_params(seed, s.dims),
                              kw.get("dtype", jnp.float32))
            prog = {"losses": k_losses, "grad": as_np(k_grad),
                    "p0": as_np(p0_low), "final": as_np(k_final),
                    "overflow": overflow}
        return drv.numbers(prog, faults, overflow, losses, as_np(grad),
                           as_np(final), p0)

    for seed in args.seeds:
        items, prog = program_numbers(seed)
        _emit(rows, "sound", seed, compare(seed, items, prog))
    for seed in args.control_seeds:
        items, prog = program_numbers(seed)
        _emit(rows, "control", seed,
              compare(seed, items, prog, dtype=jnp.bfloat16))
    for seed in args.fault_seeds:
        items, prog = program_numbers(seed)
        _emit(rows, "fault.half_batch", seed,
              compare(seed, items, prog, loss_share=0.5))
    plants = [("fault.unchanged", program_mb, "apply_updates",
               lambda params, updates: params)]
    if s.shards > 1:
        plants.append(("fault.no_exchange", program_collectives, "sync_grads",
                       lambda tree, axis_name, **kw: tree))
    for kind, module, name, broken in plants:
        keep = getattr(module, name)
        setattr(module, name, broken)
        try:
            s.build_step()
            for seed in args.fault_seeds:
                items, prog = program_numbers(seed)
                _emit(rows, kind, seed, compare(seed, items, prog))
        finally:
            setattr(module, name, keep)
            s.build_step()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, required=True)
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    ap.add_argument("--fault-seeds", type=_seeds, default=[])
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    from chipbench.lib import cells
    from chipbench.run import configure_cache
    cell = cells.resolve(args.workload, ROOT)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("calibrate: needs the cell's TPU chips", file=sys.stderr)
        return 3
    configure_cache(ROOT)
    rows: list = []
    t0 = time.perf_counter()
    {"fullbatch": fullbatch, "sampled": sampled}[cell.traffic["driver"]](
        cell, args, rows)
    print(f"calibrate {cell.name}: {len(rows)} readings in "
          f"{time.perf_counter() - t0:.1f}s", file=sys.stderr)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
