"""Share of one decode step's device time the dense paged attention kernel
(`paged_attention`, every layer of every pass: 192 calls a step at
Ouro-2.6B's sizes) takes: what a cache for every pass costs."""
from benchmark.harness import ouro_counts as counts
from benchmark.harness.programs import runs_of


def read(run):
    seconds = counts.kernel_seconds_a_step(run, counts.PAGED_KERNEL)
    runs = runs_of(run.trace, counts.DECODE_STEP)
    if not seconds or not runs:
        return None
    return seconds / (sum(runs) / len(runs)) * 100
