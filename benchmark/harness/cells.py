"""Find a cell's files by the names in BENCHMARK.json.

A cell (one entry of `workloads`) names a configuration and a traffic mix;
a per-layer metric names itself.  Each is a file of its own:

    benchmark/configs/<config>.json
    benchmark/traffic/<traffic>.json
    benchmark/layer_metrics/<metric>.py     (a `read(run)` function)
    benchmark/limits/<cell>.json            (what `correct` holds the cell to)

so a later PR adds cells, mixes and metrics as new files and new entries.
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

BENCHMARK_DIR = Path(__file__).resolve().parent.parent
ROOT = BENCHMARK_DIR.parent


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple      # metric names this cell reports with --trace 0
    per_layer: tuple       # metric names this cell reports with --trace 1
    limits: dict           # benchmark/limits/<cell>.json: what `correct` holds


def load_manifest(path: Path | None = None) -> dict:
    with open(path or ROOT / "BENCHMARK.json", encoding="utf-8") as handle:
        return json.load(handle)


def _reported_in(metric: dict, cell_name: str, moved: set | None) -> bool:
    cells = metric.get("workloads")
    if cells is not None:
        return cell_name in cells
    # no list: an end-to-end metric is every cell's; a per-layer metric
    # belongs to every cell that reports the metric it moves
    return moved is None or metric["moves"] in moved


def load_cell(name: str, manifest: dict | None = None) -> Cell:
    manifest = manifest or load_manifest()
    entries = {entry["name"]: entry for entry in manifest["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"there are {sorted(entries)}")
    entry = entries[name]
    files = {config["name"]: config["file"]
             for config in manifest["configs"]}
    with open(ROOT / files[entry["config"]], encoding="utf-8") as handle:
        config = json.load(handle)
    with open(BENCHMARK_DIR / "traffic" / f"{entry['traffic']}.json",
              encoding="utf-8") as handle:
        traffic = json.load(handle)
    limits_path = BENCHMARK_DIR / "limits" / f"{name}.json"
    if not limits_path.exists():
        raise FileNotFoundError(
            f"cell {name!r} has no limits file at {limits_path}: "
            f"`correct` cannot be decided")
    with open(limits_path, encoding="utf-8") as handle:
        limits = json.load(handle)
    end_to_end = tuple(
        metric["name"] for metric in manifest["end_to_end"]
        if _reported_in(metric, name, None))
    per_layer = tuple(
        metric["name"] for metric in manifest["per_layer"]
        if _reported_in(metric, name, set(end_to_end)))
    return Cell(name=name, chips=int(entry["chips"]), config=config,
                traffic=traffic, end_to_end=end_to_end,
                per_layer=per_layer, limits=limits)


def units(manifest: dict) -> dict:
    return {metric["name"]: metric["unit"]
            for metric in manifest["end_to_end"] + manifest["per_layer"]}


def load_reader(metric_name: str):
    """The `read(run)` of benchmark/layer_metrics/<metric_name>.py."""
    path = BENCHMARK_DIR / "layer_metrics" / f"{metric_name}.py"
    if not path.exists():
        raise FileNotFoundError(
            f"per-layer metric {metric_name!r} has no reader at {path}")
    spec = importlib.util.spec_from_file_location(
        "benchmark_layer_metric_" + metric_name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read
