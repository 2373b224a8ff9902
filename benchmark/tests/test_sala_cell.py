"""Toy runs of `sala.longdoc` on the CPU through the configuration-driven
driver: the last line's keys, the cell's per-layer readers finding
something in a traced run, the counts at the published sizes, `correct`
going false when a mechanism is broken underneath (the state taken at the
bucket's end instead of the prompt's; a slot decoding from its previous
occupant's state; the selection made without the compressed keys), and a
program with no reader for the model refused at once.  (The fourth control,
steps that leave the compressed keys as the prefill left them, moves only
tokens more than the local window past the prompt: it is read on the chip,
`limits/sala.longdoc.json`.)"""
import importlib
import json
import os
import time

import numpy as np
import pytest

from benchmark.harness import cells
from benchmark.tests import toy_sala

# what the cell's path feeds beside the setup's: the new reader that goes by
# the program's spans, and the accepted ones of the step
FED_READERS = {"sparse_blocks_read_mean", "state_bytes_mean",
               "decode_batch_mean"}


def _run(out_dir, trace: bool = False, seed: int = 2 ** 31 + 11):
    cell = toy_sala.toy_cell()
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
    # the median first chunk is not this cell's: a window's ~21 prefills of
    # 0.65-1.46 s spread it by 10 % over seeds (PERF.md section 6)
    assert set(result["metrics"]) == set(cell.end_to_end) == {
        "token_gap_p50_ms", "setup_s"}
    assert all(metric["value"] > 0 for metric in result["metrics"].values())


def test_traced_toy_run_feeds_the_cells_readers(monkeypatch):
    """The CPU has no peaks: the readers that divide by one are given the
    v5e's, so they run their whole course.  The numbers mean nothing."""
    from benchmark.harness import common, roofline
    monkeypatch.setattr(common, "peaks_for",
                        lambda device: roofline.peaks("TPU v5 lite"))
    cell, result = _run(os.path.join(cells.ROOT, ".bench_out"), trace=True)
    assert set(result["metrics"]) <= set(cell.per_layer)
    assert FED_READERS <= set(result["metrics"])
    # under dense_len a slot reads every block up to its own, past it 4
    assert 0 < result["metrics"]["sparse_blocks_read_mean"]["value"] <= 8


def test_counts_are_the_published_models():
    """The count functions at the published sizes: the weights are the
    parameters ISSUE 47 counts."""
    from benchmark.harness import sala_counts as counts
    with open(os.path.join(
            cells.ROOT, "benchmark/configs/minicpm_sala_pp4_l8.json"),
            encoding="utf-8") as handle:
        sizes = counts.shape(json.load(handle))
    assert (sizes["lightning"], sizes["sparse"]) == (6, 2)
    assert counts.lightning_matmul_params(sizes) == 285_212_672
    assert counts.sparse_matmul_params(sizes) == 253_755_392
    assert counts.parameters(sizes) == 2_820_545_280
    assert counts.scan_flops(sizes) == 32 * (4 * 128 * 128 + 4 * 128 * 128)
    # a row past dense_len: 64 blocks of 64 rows, scores and values, and
    # its ~1,500 compressed keys
    assert counts.sparse_row_flops(sizes, 24575) == (
        2 * 32 * 128 * (1535 + 2 * 4096))
    assert counts.sparse_prefill_flops(sizes, 8192) == pytest.approx(
        sum(counts.sparse_row_flops(sizes, t) for t in range(8192)))
    assert counts.sparse_prefill_flops(sizes, 9000) == pytest.approx(
        sum(counts.sparse_row_flops(sizes, t) for t in range(9000)),
        rel=1e-3)
    # ~4.6 GFLOP a token at 24k tokens: 1.1e14 a prefill
    assert 1.0e14 < counts.prefill_flops(sizes, 24576) < 1.2e14
    # a step at 16 slots past dense_len: the weights 5.04 GB, S in and out
    # 0.40 GB, the chosen blocks 0.13 GB
    assert counts.weight_bytes(sizes) == pytest.approx(5.04e9, rel=0.01)
    assert 16 * 2 * 2 * 64 * counts.block_bytes(sizes) == 134_217_728


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
    padding and all, not after the prompt's."""
    from aiko_services_tpu.models import transformer
    honest = transformer._lightning_layer
    monkeypatch.setattr(
        transformer, "_lightning_layer",
        lambda config, layer, h, state, stop=None, rope=(): honest(
            config, layer, h, state, None, rope))
    assert _broken_run(tmp_path)["correct"] is False


def test_a_slot_left_with_its_previous_occupants_state_is_not_correct(
        tmp_path, monkeypatch):
    """Every prefill writes slot 0's state: the other slots decode from
    what their previous occupant (or nobody) left."""
    from aiko_services_tpu.decode import DecodeEngine
    monkeypatch.setattr(DecodeEngine, "_slot_of",
                        lambda self, index: {"slot": np.int32(0)})
    _, result = _run(tmp_path)
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["correct"] is False


def test_a_selection_without_the_compressed_keys_is_not_correct(
        tmp_path, monkeypatch):
    """Every block scores alike: a query reads the first block, its local
    window and the lowest-numbered of the rest, whatever the compressed
    keys say."""
    import jax.numpy as jnp
    from aiko_services_tpu.parallel import sparse
    honest = sparse.block_scores
    monkeypatch.setattr(
        sparse, "block_scores",
        lambda *args: jnp.zeros_like(honest(*args)))
    assert _broken_run(tmp_path)["correct"] is False


def test_a_program_without_a_reader_for_the_model_is_refused_at_once(
        tmp_path, monkeypatch):
    """The parent of the PR that brought the configuration: exit code 1
    before anything is built."""
    from aiko_services_tpu.models import configs
    monkeypatch.delitem(configs.PUBLISHED_READERS, "minicpm_sala")
    started = time.perf_counter()
    with pytest.raises(SystemExit,
                       match="no reader for model_type minicpm_sala"):
        _run(tmp_path)
    assert time.perf_counter() - started < 5.0
