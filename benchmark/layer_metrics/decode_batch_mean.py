"""Mean number of slots a decode step decoded, over the engine ticks
inside the traced window that ran one (`decoding` of `aiko:engine.step`,
written where the step is built, not polled).  None under 3 ticks."""
from benchmark.harness import program_spans


def read(run):
    spans = program_spans.of_run(run)
    if spans is None:
        return None
    return program_spans.mean_or_none([
        decoding for decoding in (
            int(span.stats.get("decoding", 0))
            for span in spans.named("engine.step")) if decoding > 0])
