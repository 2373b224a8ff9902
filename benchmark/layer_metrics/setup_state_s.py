"""Seconds inside `aiko:setup.state` intervals (histogram
`setup.state_s`): the engine's paged pool and its tables made.  None where
the program keeps no such record; 0 in a cell with no engine."""
from benchmark.harness import startup


def read(run):
    return startup.total_s("state")
