"""Median time between the end of one `aiko:engine.pump` and the start of
the next while the engine has work: how long the re-posted pump message
sits in the replica's mailbox behind arriving frames, ms.  A gap counts
when the next pump's message was already posted by the time the previous
pump ended (its `waited_us` covers the gap).  None under 3 gaps."""
from benchmark.harness import program_spans


def read(run):
    spans = program_spans.of_run(run)
    if spans is None:
        return None
    gaps = []
    by_line: dict = {}
    for span in spans.named("engine.pump"):
        by_line.setdefault(span.line, []).append(span)
    for pumps in by_line.values():
        for previous, following in zip(pumps, pumps[1:]):
            gap_ns = following.start_ns - previous.stop_ns
            if 0 <= gap_ns <= following.waited_ms() * 1e6:
                gaps.append(gap_ns / 1e6)
    return program_spans.median_or_none(gaps)
