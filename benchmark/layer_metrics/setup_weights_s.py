"""Seconds inside `aiko:setup.weights` intervals, summed over the
elements (histogram `setup.weights_s`: one sample an element, three in
the graph cells): `setup()` run, and the state placed.  None where the
program keeps no such record."""
from benchmark.harness import startup


def read(run):
    return startup.total_s("weights")
