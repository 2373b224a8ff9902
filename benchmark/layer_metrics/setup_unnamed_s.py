"""`setup_ready_s` less boot, weights, state and compile: what lies
between the named start-up intervals (first executions of what was
compiled, the warm-up requests served between two compiles).  None where
the program keeps no such record."""
from benchmark.harness import startup


def read(run):
    return startup.unnamed_s()
