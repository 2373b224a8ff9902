"""Toy runs of `qnext.assist` on the CPU through the configuration-driven
driver: the last line's keys, the cell's per-layer readers finding
something in a traced run, the counts at the published sizes, `correct` going false
when the state's mechanism is broken underneath (the state taken at the
bucket's end instead of the prompt's; a slot decoding from its previous
occupant's state), and a program with no reader for the model refused at
once.  (The third control of the mechanism, S rounded to bfloat16 after
every update, flips no first choice among a toy run's few dozen tokens:
it is read on the chip, `limits/qnext.assist.json`.)"""
import importlib
import json
import os
import time

import numpy as np
import pytest

from benchmark.harness import cells
from benchmark.tests import toy_qnext

# what the cell's path feeds beside the setup's: the new reader, and the
# accepted ones of the first chunk (the prefill's seven steps after it)
FED_READERS = {"prefill_mxu_pct.qnext", "engine_prefill_ms.chat",
               "first_chunk_ms.chat", "ingress_wait_ms.chat",
               "prefill_rows_run_pct.chat"}


def _run(out_dir, trace: bool = False, seed: int = 2 ** 31 + 11):
    cell = toy_qnext.toy_cell()
    driver = importlib.import_module(
        f"benchmark.harness.{cell.config['system']}_driver")
    line = driver.run(cell, cells.load_manifest(), seed=seed, seconds=2.0,
                      trace=trace, started_at=time.perf_counter(),
                      out_dir=str(out_dir), require_tpu=False)
    return cell, json.loads(line)


def test_toy_run_prints_the_contracts_line(tmp_path):
    cell, result = _run(tmp_path)
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    # the median gap is not this cell's: one admission falls in every
    # eight steps, so it sits between two modes (PERF.md section 6)
    assert set(result["metrics"]) == set(cell.end_to_end) == {
        "ttft_p50_ms", "setup_s"}
    assert all(metric["value"] > 0 for metric in result["metrics"].values())


def test_traced_toy_run_feeds_the_cells_readers(monkeypatch):
    """The CPU has no peaks: the reader that divides by one is given the
    v5e's, so it runs its whole course.  The number means nothing."""
    from benchmark.harness import common, roofline
    monkeypatch.setattr(common, "peaks_for",
                        lambda device: roofline.peaks("TPU v5 lite"))
    # the readers that go by the program's spans look for the profile
    # where run.py puts it
    cell, result = _run(os.path.join(cells.ROOT, ".bench_out"), trace=True)
    assert set(result["metrics"]) <= set(cell.per_layer)
    assert FED_READERS <= set(result["metrics"])
    assert result["metrics"]["prefill_rows_run_pct.chat"]["value"] == 100


def test_counts_are_the_published_models():
    """The count functions at the published sizes: the weights are the
    parameters ISSUE 42 recounts."""
    from benchmark.harness import qnext_counts as counts
    with open(os.path.join(
            cells.ROOT, "benchmark/configs/qwen3_next_ep4_l8.json"),
            encoding="utf-8") as handle:
        sizes = counts.shape(json.load(handle))
    assert (sizes["delta"], sizes["attention"]) == (6, 2)
    assert counts.delta_matmul_params(sizes) + 8192 * 4 + 64 + 128 \
        == 33_718_464
    assert counts.attention_matmul_params(sizes) + 2 * 256 == 27_263_488
    assert counts.shared_ffn_params(sizes) == 4_196_352
    assert counts.scan_flops(sizes) == 32 * (
        2 * (2 * 64 * 128 + 64 * 64 // 3 + 64 * 256)
        + 2 * (3 * 128 * 128 + 64 * 128)) == 5_854_528
    # ~0.83 GFLOP a token at 6,000 tokens
    assert 0.8e9 < counts.prefill_flops(sizes, 6000) / 6000 < 0.9e9


def _broken_run(tmp_path):
    """A toy run with the jitted programs traced anew (with whatever the
    test broke) and those traces dropped again before any other test."""
    import jax
    jax.clear_caches()
    try:
        _, result = _run(tmp_path)
    finally:
        jax.clear_caches()
    assert result["failed"] == 0 and result["attempted"] > 0
    return result


def test_the_state_taken_at_the_buckets_end_is_not_correct(tmp_path,
                                                          monkeypatch):
    """The prefill hands the slot the state after the bucket's last row,
    padding and all, not after the prompt's: requests finish, every count
    looks healthy, `correct` is false."""
    from aiko_services_tpu.models import transformer
    honest = transformer._delta_layer
    monkeypatch.setattr(
        transformer, "_delta_layer",
        lambda config, layer, h, state, stop=None: honest(
            config, layer, h, state, None))
    assert _broken_run(tmp_path)["correct"] is False


def test_a_slot_left_with_its_previous_occupants_state_is_not_correct(
        tmp_path, monkeypatch):
    """Every prefill writes slot 0's state: the other slots decode from
    what their previous occupant (or nobody) left: `correct` is false."""
    from aiko_services_tpu.decode import DecodeEngine
    monkeypatch.setattr(DecodeEngine, "_slot_of",
                        lambda self, index: {"slot": np.int32(0)})
    _, result = _run(tmp_path)
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["correct"] is False


def test_a_program_without_a_reader_for_the_model_is_refused_at_once(
        tmp_path, monkeypatch):
    """The parent of the PR that brought the configuration: exit code 1
    before anything is built."""
    from aiko_services_tpu.models import configs
    monkeypatch.delitem(configs.PUBLISHED_READERS, "qwen3_next")
    started = time.perf_counter()
    with pytest.raises(SystemExit,
                       match="no reader for model_type qwen3_next"):
        _run(tmp_path)
    assert time.perf_counter() - started < 5.0
