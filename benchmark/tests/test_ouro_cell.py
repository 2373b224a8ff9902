"""Toy runs of `ouro.reason` on the CPU through the configuration-driven
driver: the last line's keys, every new per-layer reader finding something
in a traced run, `correct` going false when the loop is broken underneath
(one pass fewer; every pass writing pass 0's caches), and a program with
no reader for the model refused at once."""
import importlib
import json
import os
import time

import pytest

from benchmark.harness import cells
from benchmark.tests import toy_ouro

NEW_READERS = {
    "decode_hbm_pct.ouro", "prefill_mxu_pct.ouro", "ut_pass_ms",
    "paged_attn_share_pct.ouro", "cache_rows_mean"}


def _run(out_dir, trace: bool = False, seed: int = 2 ** 31 + 11):
    cell = toy_ouro.toy_cell()
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
    """The CPU's recording names no kernel (the interpreter unrolls it)
    and the CPU has no peaks: the reader that goes by the kernel's device
    time is pointed at every operation of the program, and all are given
    the v5e's peaks, so each runs its whole course.  The numbers mean
    nothing."""
    from benchmark.harness import common, ouro_counts, roofline
    monkeypatch.setattr(ouro_counts, "PAGED_KERNEL", "")
    monkeypatch.setattr(common, "peaks_for",
                        lambda device: roofline.peaks("TPU v5 lite"))
    # the readers that go by the program's spans look for the profile
    # where run.py puts it
    cell, result = _run(os.path.join(cells.ROOT, ".bench_out"), trace=True)
    assert set(result["metrics"]) <= set(cell.per_layer)
    assert NEW_READERS <= set(result["metrics"])
    # 3 passes; rows are live positions x 6 caches
    rows = result["metrics"]["cache_rows_mean"]["value"]
    assert rows > 0 and rows <= 4 * 128 * 6
    step = result["metrics"]["decode_step_ms"]["value"]
    assert result["metrics"]["ut_pass_ms"]["value"] == pytest.approx(
        step / 3)


def test_a_stack_run_one_pass_fewer_is_not_correct(tmp_path, monkeypatch):
    """The program reads the file as two passes where it says three:
    requests finish, every count looks healthy, `correct` is false."""
    from aiko_services_tpu.models import configs

    honest = configs.PUBLISHED_READERS["ouro"]

    def one_fewer(published, *rest):
        return honest(dict(
            published, total_ut_steps=published["total_ut_steps"] - 1),
            *rest)

    monkeypatch.setitem(configs.PUBLISHED_READERS, "ouro", one_fewer)
    _, result = _run(tmp_path)
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["correct"] is False


def test_passes_that_share_one_cache_are_not_correct(tmp_path, monkeypatch):
    """Every pass writes and reads pass 0's caches (one cache a layer, as
    a model of one pass has): `correct` is false."""
    import jax
    from aiko_services_tpu.models import transformer
    monkeypatch.setattr(transformer, "_cache_index",
                        lambda config, step, layer: layer)
    # the jitted programs are traced anew, with the broken index, and
    # those traces are dropped again before any other test runs
    jax.clear_caches()
    try:
        _, result = _run(tmp_path)
    finally:
        jax.clear_caches()
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["correct"] is False


def test_a_program_without_a_reader_for_the_model_is_refused_at_once(
        tmp_path, monkeypatch):
    """The parent of the PR that brought the configuration: exit code 1
    before anything is built."""
    from aiko_services_tpu.models import configs
    monkeypatch.delitem(configs.PUBLISHED_READERS, "ouro")
    started = time.perf_counter()
    with pytest.raises(SystemExit, match="no reader for model_type ouro"):
        _run(tmp_path)
    assert time.perf_counter() - started < 5.0
    # a program from before the table of readers: a reader by name
    monkeypatch.delattr(configs, "PUBLISHED_READERS")
    from benchmark.harness import model_serve_driver
    assert not model_serve_driver.has_reader("olmo")
    assert model_serve_driver.has_reader("deepseek_v2")
