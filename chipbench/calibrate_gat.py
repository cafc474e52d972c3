#!/usr/bin/env python3
"""Readings that set the GAT cell's limits, on the chip at the cell's size.

    python3 chipbench/calibrate_gat.py --workload gat-reddit.full \\
        --seeds 1,2,... [--control-seeds 1,2,3] [--fault-seeds 1,2,3] \\
        [--out <file>]

Every reading ``calibrate.py`` makes for a full-batch cell (sound seeds,
the bfloat16 control, ``fault.half_batch``, ``fault.unchanged``), made by
its own ``fullbatch`` on the cell's driver, then one more planted fault:

* ``fault.uniform_attention``: the reference in the program's place with
  the attention left out (every entry of a row weighed alike, 1 / deg).

One process builds the cell once; each reference is computed once. Each
reading is printed as one JSON line, and all of them are written to
``--out``. The benchmark's own runs never run this.
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


def main(argv=None) -> int:
    from chipbench import calibrate
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=calibrate._seeds, required=True)
    ap.add_argument("--control-seeds", type=calibrate._seeds, default=[])
    ap.add_argument("--fault-seeds", type=calibrate._seeds, default=[])
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    from chipbench.lib import cells
    from chipbench.run import configure_cache
    cell = cells.resolve(args.workload, ROOT)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("calibrate_gat: needs the cell's TPU chips", file=sys.stderr)
        return 3
    configure_cache(ROOT)

    drv = cells.driver(cell)
    made = []

    class Kept(drv.Setup):
        """The cell's ``Setup``, kept for the last fault, each reference
        computed once."""

        def __init__(self, *a):
            super().__init__(*a)
            self.refs = {}
            made.append(self)

        def reference(self, seed, **kw):
            key = (seed, tuple(sorted((k, str(v)) for k, v in kw.items())))
            if key not in self.refs:
                self.refs[key] = super().reference(seed, **kw)
            return self.refs[key]

    drv.Setup = Kept
    cells.driver = lambda c: drv
    rows: list = []
    t0 = time.perf_counter()
    calibrate.fullbatch(cell, args, rows)
    s = made[0]
    for seed in args.fault_seeds:
        calibrate._emit(rows, "fault.uniform_attention", seed, drv.numbers(
            s.reference(seed, uniform_attention=True), s.reference(seed)))
    print(f"calibrate_gat {cell.name}: {len(rows)} readings in "
          f"{time.perf_counter() - t0:.1f}s", file=sys.stderr)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
