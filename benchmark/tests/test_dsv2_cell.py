"""Toy runs of `dsv2.longgen` on the CPU through its own driver: the last
line's keys, every new per-layer reader finding something in a traced
run, and `correct` going false when an expert is broken underneath."""
import importlib
import json
import os
import time

from benchmark.harness import cells
from benchmark.tests import toy_dsv2

NEW_READERS = {
    "decode_hbm_pct.dsv2", "moe_ffn_hbm_pct", "mla_attn_roofline_pct",
    "moe_step_share_pct", "mla_step_share_pct", "experts_read_mean",
    "mla_prefill_mxu_pct"}


def _run(out_dir, trace: bool = False, seed: int = 2 ** 31 + 7):
    cell = toy_dsv2.toy_cell()
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
    """The CPU's recording names no kernel (the interpreter unrolls
    them) and the CPU has no peaks: the readers that go by a kernel's
    device time are pointed at every operation of the program, and given
    the v5e's peaks, so each runs its whole course.  The numbers mean
    nothing."""
    from benchmark.harness import common, dsv2_counts, roofline
    for kernel in ("EXPERT_KERNEL", "LATENT_KERNEL", "PREFILL_KERNEL"):
        monkeypatch.setattr(dsv2_counts, kernel, "")
    monkeypatch.setattr(common, "peaks_for",
                        lambda device: roofline.peaks("TPU v5 lite"))
    # the readers that go by the program's spans look for the profile
    # where run.py puts it
    cell, result = _run(os.path.join(cells.ROOT, ".bench_out"), trace=True)
    assert set(result["metrics"]) <= set(cell.per_layer)
    assert NEW_READERS <= set(result["metrics"])
    assert 0 < result["metrics"]["experts_read_mean"]["value"] <= 8


def test_an_expert_whose_down_projection_is_zeroed_is_not_correct(
        tmp_path, monkeypatch):
    """Every held expert's w_down is zeroed under the timed path: requests
    still finish and every count looks healthy, and `correct` is false."""
    from aiko_services_tpu.elements import ml

    honest = ml.init_params

    def broken(config, key):
        params = honest(config, key)
        down = params["layers"]["w_down"]["w"]
        params["layers"]["w_down"]["w"] = down.at[:, 0].set(0.0)
        return params

    monkeypatch.setattr(ml, "init_params", broken)
    _, result = _run(tmp_path)
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["correct"] is False
