"""Recurrent state a decode step reads and writes, bytes: the mean
`state_bytes` of the traced window's `aiko:engine.decode` spans (the
decoding slots x the Mamba layers x a layer's SSM state and convolution
tail, twice).  358,400 B a layer a slot at Jamba2-3B's sizes: 596 MB a
step at 32 slots, whatever the contexts."""
from benchmark.harness import jamba_counts as counts


def read(run):
    means = counts.step_means(run)
    return None if means is None else means["state_bytes"]
