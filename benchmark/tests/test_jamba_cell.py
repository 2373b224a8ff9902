"""Toy runs of `jamba.think` on the CPU through the configuration-driven
driver: the last line's keys, every new per-layer reader finding something
in a traced run, `correct` going false when the state's mechanism is
broken underneath (the state taken at the bucket's end instead of the
prompt's; a slot decoding from its previous occupant's state), and a
program with no reader for the model refused at once."""
import importlib
import json
import os
import time

import numpy as np
import pytest

from benchmark.harness import cells
from benchmark.tests import toy_jamba

NEW_READERS = {
    "decode_hbm_pct.jamba", "prefill_mxu_pct.jamba", "ssm_scan_hbm_pct",
    "ssm_scan_ms", "state_bytes_mean"}


def _run(out_dir, trace: bool = False, seed: int = 2 ** 31 + 11):
    cell = toy_jamba.toy_cell()
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
    assert set(result["metrics"]) == set(cell.end_to_end) == {
        "ttft_p50_ms", "token_gap_p50_ms", "setup_s"}
    assert all(metric["value"] > 0 for metric in result["metrics"].values())


def test_traced_toy_run_feeds_every_new_reader(monkeypatch):
    """The CPU's recording names no kernel and the CPU has no peaks: the
    readers that go by the scan kernel's device time are pointed at every
    operation of the prefill program, and all are given the v5e's peaks,
    so each runs its whole course.  The numbers mean nothing, but the
    host's count does: four slots' state, read and written."""
    from benchmark.harness import common, jamba_counts, roofline
    monkeypatch.setattr(jamba_counts, "SCAN_KERNEL", "")
    monkeypatch.setattr(common, "peaks_for",
                        lambda device: roofline.peaks("TPU v5 lite"))
    # the readers that go by the program's spans look for the profile
    # where run.py puts it
    cell, result = _run(os.path.join(cells.ROOT, ".bench_out"), trace=True)
    assert set(result["metrics"]) <= set(cell.per_layer)
    assert NEW_READERS <= set(result["metrics"])
    state = result["metrics"]["state_bytes_mean"]["value"]
    a_slot = 6 * 128 * (4 * 16 + 3 * 4)
    assert 0 < state <= 2 * 4 * a_slot
    assert result["metrics"]["prefill_rows_run_pct.chat"]["value"] == 100


def test_counts_are_the_published_models():
    """The count functions at the published sizes: the weights are the
    parameters ISSUE 39 recounts, a row of the scan 41,024 B, a position
    1 KiB of K/V."""
    from benchmark.harness import jamba_counts as counts
    with open(os.path.join(cells.ROOT, "benchmark/configs/jamba2_3b.json"),
              encoding="utf-8") as handle:
        sizes = counts.shape(json.load(handle))
    assert (sizes["mamba"], sizes["attention"]) == (26, 2)
    # 3,029,337,472 parameters in bf16 + A_log, D, b_dt in float32
    assert counts.weight_bytes(sizes) == (
        2 * 3_029_337_472 + 2 * 26 * 5120 * 18)
    assert counts.scan_row_bytes(sizes) == 41_024
    assert 2 * counts.cache_row_bytes(sizes) == 1024
    assert counts.mamba_matmul_params(sizes) == 104_038_400
    step = counts.step_bytes(sizes, 2 * 32 * 26 * 358_400, 2 * 32 * 3000)
    assert step == counts.weight_bytes(sizes) + 596_377_600 + 98_304_000


def test_the_state_taken_at_the_buckets_end_is_not_correct(tmp_path,
                                                          monkeypatch):
    """The prefill hands the slot the state after the bucket's last row,
    padding and all, not after the prompt's: requests finish, every count
    looks healthy, `correct` is false."""
    import jax
    from aiko_services_tpu.models import transformer
    honest = transformer._mamba_layer
    monkeypatch.setattr(
        transformer, "_mamba_layer",
        lambda config, layer, h, state, stop=None: honest(
            config, layer, h, state, None))
    # the jitted programs are traced anew, with the broken layer, and
    # those traces are dropped again before any other test runs
    jax.clear_caches()
    try:
        _, result = _run(tmp_path)
    finally:
        jax.clear_caches()
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["correct"] is False


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
    monkeypatch.delitem(configs.PUBLISHED_READERS, "jamba")
    started = time.perf_counter()
    with pytest.raises(SystemExit, match="no reader for model_type jamba"):
        _run(tmp_path)
    assert time.perf_counter() - started < 5.0
