"""BENCHMARK.json against the contract's limits, and every name in it
against the files the harness finds by name."""
import re

from benchmark.tests import toy  # noqa: F401
from benchmark.harness import cells

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_manifest_keeps_to_the_contract():
    manifest = cells.load_manifest()
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= manifest["run_seconds"] <= 51
    runs = 2 + 14 * 24
    assert runs * (manifest["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    for entry in (manifest["configs"] + manifest["workloads"]
                  + manifest["end_to_end"] + manifest["per_layer"]):
        assert NAME.match(entry["name"]), entry["name"]
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(metric["unit"]) and metric["better"] in (
            "lower", "higher")
    for metric in manifest["end_to_end"]:
        assert set(metric) <= {"name", "unit", "better", "bound", "source",
                               "workloads"}
        assert 0.01 <= metric["bound"] <= 0.1
        assert metric["source"] in ("host_clock", "device_trace")
    assert any(metric["name"] == "setup_s"
               for metric in manifest["end_to_end"])
    end_to_end = {metric["name"] for metric in manifest["end_to_end"]}
    for metric in manifest["per_layer"]:
        assert set(metric) <= {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
        assert metric["moves"] in end_to_end
    for workload in manifest["workloads"]:
        assert workload["chips"] in (1, 4) and len(workload["why"]) <= 200


def test_every_cell_finds_its_files_and_metrics():
    manifest = cells.load_manifest()
    for workload in manifest["workloads"]:
        cell = cells.load_cell(workload["name"], manifest)
        assert "setup_s" in cell.end_to_end and len(cell.end_to_end) >= 2
        assert cell.per_layer
        moved = {metric["name"]: metric["moves"]
                 for metric in manifest["per_layer"]}
        for name in cell.per_layer:
            assert callable(cells.load_reader(name))
            assert moved[name] in cell.end_to_end
        assert any(key != "why" for key in cell.limits)
        for key in cell.config.get("reduced", {}):
            assert key in cell.config


def test_no_metric_divides_a_count_by_the_seconds_asked_for():
    """`--seconds` sets how long the window runs, never a denominator."""
    import inspect

    from benchmark.harness import estimators
    source = inspect.getsource(estimators.rate_between_barriers)
    assert "/ seconds" not in source and "elapsed" in source
