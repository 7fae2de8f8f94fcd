"""Reads BENCHMARK.json and the data files it names. The harness finds a
cell's configuration, traffic mix, driver and per-layer readers by name, by
looking into directories: there is no list in any Python file that a later PR
would have to edit."""

from __future__ import annotations

import importlib.util
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")


def _read(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    """Import a Python file of the benchmark by its path under the repo."""
    full = path if os.path.isabs(path) else os.path.join(ROOT, path)
    name = "benchmark_file_" + re.sub(r"\W", "_", os.path.relpath(full, ROOT))
    spec = importlib.util.spec_from_file_location(name, full)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(manifest_path: str, workload: str) -> dict:
    """Everything one run needs, as plain data: the manifest's entry of the
    cell, its configuration file, its traffic file, and the names of the
    metrics it reports."""
    man = _read(manifest_path)
    cells = {w["name"]: w for w in man["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in {manifest_path}; "
                         f"there are: {sorted(cells)}")
    cell = cells[workload]
    cfg_entry = next(c for c in man["configs"] if c["name"] == cell["config"])
    cfg_file = os.path.join(ROOT, cfg_entry["file"])
    config = _read(cfg_file)
    # A mix lives beside the configurations it is run with:
    # <dir>/configs/<config>.json and <dir>/traffic/<traffic>.json.
    base = os.path.dirname(os.path.dirname(cfg_file))
    traffic_file = os.path.join(base, "traffic", cell["traffic"] + ".json")
    if not os.path.exists(traffic_file):
        traffic_file = os.path.join(HERE, "traffic", cell["traffic"] + ".json")
    traffic = _read(traffic_file)

    def mine(metrics):
        return [m for m in metrics
                if "workloads" not in m or workload in m["workloads"]]

    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": mine(man["end_to_end"]),
            "per_layer": mine(man["per_layer"]),
            "manifest": man}


def layer_reader(name: str):
    """The reader of one per-layer metric: benchmark/layer_metrics/<name>.py
    and its `read(run)`. None where no such file exists."""
    path = os.path.join(HERE, "layer_metrics", name + ".py")
    if not os.path.exists(path):
        return None
    return load_module(path).read
