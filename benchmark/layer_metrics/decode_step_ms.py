"""Median device time of one whole execution of the engine's decode step
program (`jit_paged_decode_step`) in the traced window, ms."""
import statistics

from benchmark.harness.programs import runs_of


def read(run):
    runs = runs_of(run.trace, "jit_paged_decode_step")
    return statistics.median(runs) * 1e3 if runs else None
