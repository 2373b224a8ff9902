"""Share of the chip's memory bandwidth a decode step's sparse attention
needs: what it must read (the chosen blocks' K/V, `sparse_blocks_read` of
the `aiko:engine.decode` spans, a block 2 x 64 x 128 values a K/V head a
layer) / peak bytes per second / the device time of the `paged_attention`
kernel in one whole `jit_paged_decode_step` execution of the traced window
(both sparse layers; in this model every call of it is given a table a
(slot, K/V head) of chosen blocks).  The selection before it is a dozen
XLA operations that a device event does not name, and is not in it."""
from benchmark.harness import sala_counts as counts


def read(run):
    seconds = counts.kernel_seconds_a_step(run, counts.PAGED_KERNEL)
    means = counts.step_means(run)
    if not seconds or not run.peaks or means is None:
        return None
    needed = means["sparse_blocks_read"] * counts.block_bytes(
        counts.shape(run.cell.config))
    return needed / run.peaks["hbm_bytes_per_s"] / seconds * 100
