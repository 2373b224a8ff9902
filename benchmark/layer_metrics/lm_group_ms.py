"""Median device time of one whole execution of the lm stage's fused
group program in the traced window, ms."""
import statistics

from benchmark.harness.programs import stage_program


def read(run):
    program = stage_program(run, "lm")
    if program is None or not program["run_seconds"]:
        return None
    return statistics.median(program["run_seconds"]) * 1e3
