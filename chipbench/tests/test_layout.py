"""Every cell finds its files by name, and BENCHMARK.json keeps to the
benchmark's contract on names, sizes and per-layer metrics."""
import json
import os
import re

import pytest

from chipbench.lib import cells

BENCH = cells.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
WORKLOADS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(cells.ROOT, "BENCHMARK.json")) <= 65536


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_resolves(workload):
    cell = cells.resolve(workload)
    assert cell.chips in (1, 4)
    drv = cells.driver(cell)
    assert callable(drv.run)
    assert cell.limits, "a cell without limits could never be incorrect"
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer, "each cell reports a per-layer metric"
    for m in cell.per_layer:
        assert callable(cells.metric_reader(m["name"]).read)


def test_per_layer_moves_a_metric_each_of_its_cells_reports():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        target = e2e[m["moves"]]
        for w in m.get("workloads", WORKLOADS):
            assert w in WORKLOADS
            assert "workloads" not in target or w in target["workloads"], \
                (m["name"], w)


def test_names_units_and_bounds():
    seen = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in BENCH[group]:
            assert NAME.match(entry["name"]), entry["name"]
            assert entry["name"] not in seen
            seen.add(entry["name"])
            for key in ("why", "layer", "source"):
                if key in entry and group in ("configs", "workloads", "per_layer"):
                    assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key]
            if "unit" in entry:
                assert UNIT.match(entry["unit"])
                assert entry["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(WORKLOADS) // 2)


def test_config_files_hold_what_they_name():
    for c in BENCH["configs"]:
        assert c["file"].startswith("chipbench/")
        with open(os.path.join(cells.ROOT, c["file"])) as f:
            conf = json.load(f)
        assert conf["name"] == c["name"] and conf["source"] == c["source"]
        assert conf["reduced"] == c["reduced"]
