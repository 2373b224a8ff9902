"""Blocks a decode step's attention reads, a slot a K/V head a sparse
layer: the mean over the traced window's `aiko:engine.decode` spans of
`sparse_blocks_read` over the decoding slots x K/V heads x sparse layers.
At most 64 (topk) once a context is past dense_len, beside the hundreds of
live blocks it chose from (`sparse_blocks_live` of the same spans)."""
from benchmark.harness import sala_counts as counts


def read(run):
    means = counts.step_means(run)
    if means is None or not means["state_slots"]:
        return None
    sizes = counts.shape(run.cell.config)
    return means["sparse_blocks_read"] / (
        means["state_slots"] * sizes["kv_heads"] * sizes["sparse"])
