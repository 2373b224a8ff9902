"""Share of the traced window in which the device runs nothing while the
event loop holds a partial group down (`micro_batch_wait_ms`): the
`aiko:sched.hold` spans less the device's program executions under them,
over the window, %.  (The reduced trace names a gap by what covered its
middle, so a hold at the end of a long wait never names one; this reads
the overlap itself.)  None where the program wrote no such span or the
profile has no device line."""
from benchmark.harness import program_spans


def read(run):
    spans = program_spans.of_run(run)
    if spans is None or not spans.named("sched.hold"):
        return None
    idle_ns = spans.idle_overlap_ns(spans.named("sched.hold"))
    if idle_ns is None:
        return None
    return idle_ns / (spans.window[1] - spans.window[0]) * 100
