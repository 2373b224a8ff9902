"""One toy run of every cell on the CPU, through the drivers the chip
runs use: the last line's keys, and `correct` going false when the timed
path is broken underneath."""
import importlib
import json
import time

import pytest

from benchmark.tests import toy
from benchmark.harness import cells

CELLS = ("graph.window", "lm.chat", "lm.longprompt", "graph.streams")


def _run(name: str, tmp_path, trace: bool = False, seed: int = 2 ** 31 + 5):
    cell = toy.toy_cell(name)
    driver = importlib.import_module(
        f"benchmark.harness.{cell.config['system']}_driver")
    line = driver.run(cell, cells.load_manifest(), seed=seed, seconds=2.0,
                      trace=trace, started_at=time.perf_counter(),
                      out_dir=str(tmp_path), require_tpu=False)
    return cell, json.loads(line)


@pytest.mark.parametrize("name", CELLS)
def test_toy_run_prints_the_contracts_line(name, tmp_path):
    cell, result = _run(name, tmp_path)
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == set(cell.end_to_end)
    assert all(set(metric) == {"value", "unit"} and metric["value"] > 0
               for metric in result["metrics"].values())
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}


@pytest.mark.parametrize("name", ("lm.chat", "graph.streams"))
def test_traced_toy_run_reports_per_layer_metrics(name, tmp_path):
    cell, result = _run(name, tmp_path, trace=True)
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device", "breakdown"}
    assert set(result["metrics"]) <= set(cell.per_layer)
    assert result["metrics"], "no per-layer metric found anything to read"
    assert result["device"]["busy_s"] > 0
    assert result["device"]["window_s"] > result["device"]["busy_s"]
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    assert all(len(entries) <= 10
               for entries in result["breakdown"].values())
    assert not any("fusion." in name.split("/")[0]
                   for name, _ in result["breakdown"]["device_ops"])


def test_a_token_altered_where_it_is_produced_is_not_correct(
        tmp_path, monkeypatch):
    """The engine's decode step returns every token plus one: requests
    still finish and every count looks healthy, and `correct` is false."""
    from aiko_services_tpu.decode import engine

    honest = engine.paged_decode_step

    def altered(params, config, *args):
        pool, tokens = honest(params, config, *args)
        return pool, (tokens + 1) % config.vocab_size

    altered._cache_size = honest._cache_size
    monkeypatch.setattr(engine, "paged_decode_step", altered)
    _, result = _run("lm.chat", tmp_path)
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["correct"] is False


def test_a_group_that_answers_with_other_tokens_is_not_correct(
        tmp_path, monkeypatch):
    """The LM stage's fused group kernel shifts what it generated."""
    from aiko_services_tpu.elements import ml

    honest = ml.generate

    def altered(state, config, tokens, max_new, **kwargs):
        out, cache = honest(state, config, tokens, max_new, **kwargs)
        return (out + 1) % config.vocab_size, cache

    monkeypatch.setattr(ml, "generate", altered)
    _, result = _run("graph.window", tmp_path)
    assert result["correct"] is False


def test_no_tpu_is_an_error_not_a_fallback(tmp_path):
    cell = toy.toy_cell("lm.chat")
    driver = importlib.import_module("benchmark.harness.lm_serve_driver")
    with pytest.raises(SystemExit):
        driver.run(cell, cells.load_manifest(), seed=1, seconds=1.0,
                   trace=False, started_at=time.perf_counter(),
                   out_dir=str(tmp_path))
