"""The two readers of `rows` on `aiko:engine.prefill` (PR 38), on
synthetic profiles: the sum of the rows run over the sum of the buckets, a
chunk call (no `rows`) left out, and None where no span carries `rows` (the
parent of the PR that added it) or the run was not traced."""
from types import SimpleNamespace as NS

import pytest

from benchmark.tests import toy  # noqa: F401
from benchmark.harness import cells, program_spans, trace
from benchmark.harness.common import RunData

MS = 1_000_000
READERS = ("prefill_rows_run_pct.long", "prefill_rows_run_pct.chat")


def _event(name, start_ms, dur_ms, **stats):
    return NS(name=name, start_ns=start_ms * MS, duration_ns=dur_ms * MS,
              stats=list(stats.items()))


def _profile(prefills):
    """A 1000 ms window with one `aiko:engine.prefill` span of 20 ms for
    each of `prefills` (its fields), 50 ms apart."""
    events = [_event("aiko:engine.prefill", 100 + 50 * index, 20,
                     stream=f"r{index}", frame=0, row=0, queue_us=10,
                     **fields)
              for index, fields in enumerate(prefills)]
    return NS(planes=[NS(name="/host:CPU", lines=[
        NS(name="python3", events=[_event("bench:trace_window", 0, 1000)]),
        NS(name="python3", events=events)])])


def _run(profile, monkeypatch):
    spans = program_spans.parse(profile)
    monkeypatch.setattr(program_spans, "of_run", lambda run: spans)
    return RunData({}, cell=NS(name="lm.longprompt"),
                   trace=trace.reduce(profile))


@pytest.mark.parametrize("reader", READERS)
def test_rows_run_over_rows_of_the_buckets(monkeypatch, reader):
    run = _run(_profile([
        dict(bucket=4096, true_len=2049, attention="flash", rows=2560),
        dict(bucket=4096, true_len=4000, attention="flash", rows=4096),
        dict(bucket=8192, true_len=4097, attention="flash", rows=4608),
        # a chunk call walks the table and says no `rows`: left out
        dict(bucket=64, true_len=4097, live_blocks=3, table_blocks=128,
             write="updates")]), monkeypatch)
    assert cells.load_reader(reader)(run) == pytest.approx(
        (2560 + 4096 + 4608) / (4096 + 4096 + 8192) * 100)


@pytest.mark.parametrize("reader", READERS)
def test_a_bucket_run_whole_reads_100(monkeypatch, reader):
    run = _run(_profile([
        dict(bucket=256, true_len=130, attention="einsum", rows=256)]),
        monkeypatch)
    assert cells.load_reader(reader)(run) == 100.0


@pytest.mark.parametrize("reader", READERS)
def test_a_program_without_the_counter_reads_none(monkeypatch, reader):
    """The parent under this PR's benchmark files: its spans carry
    `bucket` and `true_len` and no `rows`."""
    run = _run(_profile([
        dict(bucket=4096, true_len=3000, attention="flash")] * 4),
        monkeypatch)
    assert cells.load_reader(reader)(run) is None
    assert cells.load_reader(reader)(_run(_profile([]), monkeypatch)) is None


@pytest.mark.parametrize("reader", READERS)
def test_an_untraced_run_reads_none(reader):
    assert cells.load_reader(reader)(
        RunData({}, cell=NS(name="lm.longprompt"), trace=None)) is None


def test_the_manifest_names_both_with_their_cells():
    manifest = cells.load_manifest()
    entries = {metric["name"]: metric for metric in manifest["per_layer"]}
    long, chat = (entries[name] for name in READERS)
    assert (long["moves"], long["workloads"]) == (
        "ttft_long_p50_ms", ["lm.longprompt"])
    assert (chat["moves"], chat["workloads"]) == (
        "ttft_p50_ms", ["dsv2.longgen", "ouro.reason"])
    for entry in (long, chat):
        assert (entry["unit"], entry["better"], entry["source"],
                entry["layer"]) == ("%", "lower", "program_span",
                                    "model step")
    assert "prefill_rows_run_pct.long" in cells.load_cell(
        "lm.longprompt", manifest).per_layer
    assert "prefill_rows_run_pct.chat" not in cells.load_cell(
        "lm.chat", manifest).per_layer
