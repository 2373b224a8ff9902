"""Seconds jax spent tracing, lowering and compiling (or retrieving from
its persistent cache) inside the program's bracketed calls, outside the
weights and state intervals (histogram `setup.compile_s`: one sample an
`aiko:compile` mark, the sum of jax's own durations, not the call's wall
time).  None where the program keeps no such record."""
from benchmark.harness import startup


def read(run):
    return startup.total_s("compile")
