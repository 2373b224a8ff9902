"""Device time of one pass over the stack inside a decode step, ms: the
median whole `jit_paged_decode_step` execution in the traced window over
the passes the program says it ran (`ut_passes` of its `aiko:engine.decode`
spans).  The head, run once a step, is in it."""
import statistics

from benchmark.harness import ouro_counts as counts
from benchmark.harness.programs import runs_of


def read(run):
    runs = runs_of(run.trace, counts.DECODE_STEP)
    means = counts.step_means(run)
    if not runs or means is None or not means["ut_passes"]:
        return None
    return statistics.median(runs) * 1e3 / means["ut_passes"]
