"""Find a cell's files by the names in ``BENCHMARK.json``.

* configuration ``<config>``: the file its ``configs`` entry names;
* traffic ``<traffic>``: ``chipbench/traffic/<traffic>.json``, whose
  ``driver`` key names ``chipbench/drivers/<driver>.py``;
* limits of the numbers that decide ``correct``:
  ``chipbench/limits/<workload>.json``;
* per-layer metric ``<name>``: ``chipbench/metrics/<name>.py``, a module
  with ``read(view) -> float | None``.

So a new configuration, traffic mix, metric or driver is a new file and a
new entry, and no file here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    limits: dict
    end_to_end: list      # BENCHMARK.json entries this cell reports
    per_layer: list


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def _reports(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def resolve(workload: str, root: str = ROOT) -> Cell:
    bench = benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    conf_entry = configs[w["config"]]
    e2e = [m for m in bench["end_to_end"] if _reports(m, workload)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _reports(m, workload) and m["moves"] in e2e_names]
    return Cell(
        name=workload, chips=int(w["chips"]), config_name=w["config"],
        config=load_json(os.path.join(root, conf_entry["file"])),
        traffic_name=w["traffic"],
        traffic=load_json(os.path.join(BENCH_DIR, "traffic",
                                       f"{w['traffic']}.json")),
        limits=load_json(os.path.join(BENCH_DIR, "limits",
                                      f"{workload}.json")),
        end_to_end=e2e, per_layer=per_layer)


def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(cell: Cell):
    name = cell.traffic["driver"]
    return _load_module(os.path.join(BENCH_DIR, "drivers", f"{name}.py"),
                        f"chipbench_driver_{name}")


def metric_reader(name: str):
    return _load_module(os.path.join(BENCH_DIR, "metrics", f"{name}.py"),
                        "chipbench_metric_" + name.replace(".", "_"))
