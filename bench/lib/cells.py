"""Find a cell's files by the names in BENCHMARK.json.

A cell is one entry of ``workloads``: a configuration (``configs/<name>.json``
holds its sizes and names the module that builds it and holds its plain
reference), a traffic mix (``traffic/<name>.json``) and the limits of its
correctness check (``limits/<cell>.json``).  Everything is found by name, so a later
configuration, mix or cell is added by new files and entries only.
"""
from __future__ import annotations

import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(relpath: str, name: str):
    """Import ``<repo>/<relpath>`` under ``name`` (file names may hold '-')."""
    path = os.path.join(REPO, relpath)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark() -> dict:
    return read_json(os.path.join(REPO, "BENCHMARK.json"))


def load_cell(workload: str, bench: dict | None = None) -> dict:
    """Everything one run of ``workload`` needs, as plain data plus the
    configuration's module."""
    bench = bench or benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"have {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = read_json(os.path.join(REPO, configs[w["config"]]["file"]))
    traffic = read_json(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
    limits = read_json(os.path.join(BENCH, "limits", workload + ".json"))
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    per_layer = [m for m in bench["per_layer"]
                 if workload in m.get("workloads", [workload])]
    return {"name": workload, "chips": w["chips"], "config": config,
            "traffic": traffic, "limits": limits, "end_to_end": e2e,
            "per_layer": per_layer}


def config_module(config: dict):
    return load_module(config["module"], "bench_config_" +
                       config["name"].replace("-", "_").replace(".", "_"))


def base_name(name: str) -> str:
    """The quantity a metric measures: its name up to the first '.'.  A
    quantity split by the cells that report it (``device_idle_pct.sim``)
    is read the same way in each part."""
    return name.split(".", 1)[0]


def metric_reader(name: str):
    """``metrics/<name>.py``, else ``metrics/<base name>.py``: a reader with
    ``read(ctx) -> float | None``."""
    for n in (name, base_name(name)):
        rel = os.path.join("bench", "metrics", n + ".py")
        if os.path.exists(os.path.join(REPO, rel)):
            return load_module(rel, "bench_metric_" + n.replace(".", "_"))
    raise FileNotFoundError(f"no reader for metric {name!r} in bench/metrics")
