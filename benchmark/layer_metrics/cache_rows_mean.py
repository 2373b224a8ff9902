"""Cache rows a decode step attends over: the mean `cache_rows` of the
traced window's `aiko:engine.decode` spans (live positions of the step's
slots x the model's caches, layers x passes).  8 KiB a row at Ouro-2.6B's
sizes."""
from benchmark.harness import ouro_counts as counts


def read(run):
    means = counts.step_means(run)
    return None if means is None else means["cache_rows"]
