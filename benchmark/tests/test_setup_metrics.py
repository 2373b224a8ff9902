"""The seven start-up metrics (ISSUE 37): every entry has its reader and
an explicit list of accepted cells, each toy cell's traced run reports
all seven, and what they report adds up: boot + weights + state +
compile + unnamed = ready.  The records are the OS process's and these
runs share one, so the numbers here are running totals: the identity
holds of them all the same."""
import importlib
import json
import os
import time

import pytest

from benchmark.harness import cells
from benchmark.tests import toy, toy_dsv2, toy_ouro

SETUP_METRICS = (
    "setup_boot_s", "setup_weights_s", "setup_state_s", "setup_compile_s",
    "setup_cache_hit_pct", "setup_ready_s", "setup_unnamed_s")
TOY_CELLS = {
    "graph.window": toy.toy_cell, "lm.chat": toy.toy_cell,
    "lm.longprompt": toy.toy_cell, "graph.streams": toy.toy_cell,
    "dsv2.longgen": toy_dsv2.toy_cell, "ouro.reason": toy_ouro.toy_cell}


@pytest.mark.parametrize("name", SETUP_METRICS)
def test_every_entry_has_its_reader_and_names_its_cells(name):
    manifest = cells.load_manifest()
    [entry] = [metric for metric in manifest["per_layer"]
               if metric["name"] == name]
    assert entry["moves"] == "setup_s" and entry["layer"] == "start-up"
    assert entry["source"] in ("program_span", "program_counter")
    accepted = [cell["name"] for cell in manifest["workloads"]]
    assert entry["workloads"] and set(entry["workloads"]) <= set(accepted)
    assert callable(cells.load_reader(name))


def test_the_entries_are_the_last_of_the_manifest():
    names = [metric["name"]
             for metric in cells.load_manifest()["per_layer"]]
    assert tuple(names[-len(SETUP_METRICS):]) == SETUP_METRICS


@pytest.mark.parametrize("name", sorted(TOY_CELLS))
def test_a_traced_toy_run_reports_all_seven_and_they_add_up(name):
    cell = TOY_CELLS[name](name)
    driver = importlib.import_module(
        f"benchmark.harness.{cell.config['system']}_driver")
    # the span readers of the other metrics look for the profile where
    # run.py puts it
    line = driver.run(cell, cells.load_manifest(), seed=2 ** 31 + 37,
                      seconds=2.0, trace=True,
                      started_at=time.perf_counter(),
                      out_dir=os.path.join(cells.ROOT, ".bench_out"),
                      require_tpu=False)
    metrics = json.loads(line)["metrics"]
    assert set(SETUP_METRICS) <= set(metrics), sorted(metrics)
    read = {metric: metrics[metric]["value"] for metric in SETUP_METRICS}
    assert read["setup_weights_s"] > 0 and read["setup_compile_s"] > 0
    assert read["setup_boot_s"] > 0
    assert read["setup_state_s"] >= 0 and (
        read["setup_state_s"] > 0 or cell.config["system"] == "graph")
    assert 0 <= read["setup_cache_hit_pct"] <= 100
    assert (read["setup_boot_s"] + read["setup_weights_s"]
            + read["setup_state_s"] + read["setup_compile_s"]
            + read["setup_unnamed_s"]) == pytest.approx(
                read["setup_ready_s"], abs=1e-9)
