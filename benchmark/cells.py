"""Finding a cell's files by name: BENCHMARK.json names a workload, the
workload names a configuration and a traffic mix, and each of those, like
each per-layer metric, is a file of its own under this directory. Nothing
here knows the name of any cell, configuration, mix or metric."""

from __future__ import annotations

import importlib.util
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH_DIR)


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    """A python file as a module, whatever characters its name has."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + os.path.basename(path)[:-3].replace(".", "_"), path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # dataclasses look their module up there
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str, bench_dir: str = BENCH_DIR) -> dict:
    """{"spec", "cell", "config", "traffic", "generator"} for one workload
    name. `rehearse` sizes are applied by the caller."""
    spec = read_json(os.path.join(os.path.dirname(bench_dir), "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json (has: {sorted(cells)})")
    cell = cells[workload]
    entry = next(c for c in spec["configs"] if c["name"] == cell["config"])
    config = read_json(os.path.join(os.path.dirname(bench_dir), entry["file"]))
    traffic = read_json(os.path.join(bench_dir, "traffic", cell["traffic"] + ".json"))
    generator = load_module(os.path.join(bench_dir, "generators", config["generator"] + ".py"))
    return {"spec": spec, "cell": cell, "config": config, "traffic": traffic,
            "generator": generator}


def metrics_of(spec: dict, workload: str, group: str) -> list[dict]:
    """The metrics of `group` ("end_to_end" or "per_layer") that apply to a
    workload: those with no `workloads` key, or that list it."""
    return [m for m in spec[group] if workload in m.get("workloads", [workload])]


def read_layer_metrics(spec: dict, workload: str, run: dict, bench_dir: str = BENCH_DIR) -> dict:
    """{name: {"value", "unit"}} from each metric's own reader,
    layer_metrics/<name>.py::read(run). A reader that finds nothing to read
    returns None and its metric is left out of the line."""
    out = {}
    for m in metrics_of(spec, workload, "per_layer"):
        reader = load_module(os.path.join(bench_dir, "layer_metrics", m["name"] + ".py"))
        value = reader.read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def load_peaks(device_kind: str, bench_dir: str = BENCH_DIR) -> dict:
    """The published peaks of this device kind. A device that is not in
    the table is an error, not a default."""
    table = read_json(os.path.join(bench_dir, "peaks.json"))
    if device_kind not in table["devices"]:
        raise SystemExit(f"device kind {device_kind!r} is not in benchmark/peaks.json "
                         f"(has: {sorted(table['devices'])}); no default peak")
    return table["devices"][device_kind]
