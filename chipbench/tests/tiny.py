"""The cells at a size a CPU test run holds: reddit-shaped at 1/100 of its
nodes, 64 seeds a shard, the same limits."""
import contextlib
import io
import json

from chipbench import run
from chipbench.lib import cells

TINY = {"scale": 0.01}
TINY_BATCH = 64


def tiny_cell(workload: str, **traffic):
    cell = cells.resolve(workload)
    cell.traffic.update(TINY)
    if "batch_per_shard" in cell.traffic:
        cell.traffic["batch_per_shard"] = TINY_BATCH
    cell.traffic.update(traffic)
    return cell


def run_tiny(workload: str, seed: int = 3000000019, **traffic) -> dict:
    """One whole run of ``workload`` without the look for a chip, with
    ``traffic`` keys overridden too; returns its result line."""
    over = dict(TINY, **traffic)
    if "batch_per_shard" in cells.resolve(workload).traffic:
        over.setdefault("batch_per_shard", TINY_BATCH)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", "1", "--trace", "0"],
                      require_tpu=False, traffic_overrides=over)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def within(numbers: dict, limits: dict) -> bool:
    return all(numbers.get(k) is not None and numbers[k] <= v
               for k, v in limits.items())
