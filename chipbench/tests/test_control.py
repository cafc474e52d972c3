"""The control: the plain reference put in the program's place and computed
in bfloat16 (the configurations state float32) must come out not correct
under each cell's limits, while the program comes out correct. The
data-parallel control runs with its faults in ``test_faults.py``."""
import types

import pytest

from chipbench import calibrate
from chipbench.tests.tiny import tiny_cell, within

UNPATCHED = {"use_isplib": False}     # the full-batch cell with patch() off
CASES = [("gcn-reddit.full", {}), ("gcn-reddit.full", UNPATCHED),
         ("sage-reddit.sampled", {})]


@pytest.mark.parametrize(
    "workload,traffic", CASES,
    ids=[w + ("-unpatched" if t else "") for w, t in CASES])
def test_control_is_not_correct(workload, traffic):
    cell = tiny_cell(workload, **traffic)
    rows = []
    args = types.SimpleNamespace(seeds=[5], control_seeds=[5, 6, 7],
                                 fault_seeds=[])
    getattr(calibrate, cell.traffic["driver"])(cell, args, rows)
    numbers = lambda r: {k: v for k, v in r.items()  # noqa: E731
                         if k not in ("kind", "seed")}
    sound = [r for r in rows if r["kind"] == "sound"]
    control = [r for r in rows if r["kind"] == "control"]
    assert len(control) == 3
    assert all(within(numbers(r), cell.limits) for r in sound)
    assert not any(within(numbers(r), cell.limits) for r in control)
