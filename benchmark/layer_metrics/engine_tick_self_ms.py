"""Median, over the engine ticks inside the traced window that decoded,
of `aiko:engine.step` less the spans directly inside it (prefill, decode
dispatch, readback): admission, block tables, surfacing and completion
bookkeeping, ms.  None under 3 ticks."""
from benchmark.harness import program_spans


def read(run):
    spans = program_spans.of_run(run)
    if spans is None:
        return None
    return program_spans.median_or_none([
        span.self_ns() / 1e6 for span in spans.named("engine.step")
        if int(span.stats.get("decoding", 0)) > 0])
