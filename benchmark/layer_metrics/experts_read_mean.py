"""Distinct held experts a decode step's tokens chose, a layer: the mean
`experts_read` of the traced window's `aiko:engine.decode` spans over the
expert layers.  What the grouped matmul must read follows it."""
from benchmark.harness import dsv2_counts as counts


def read(run):
    means = counts.step_means(run)
    if means is None:
        return None
    return means["experts_read"] / counts.expert_layers(
        counts.shape(run.cell.config))
