"""Share of the chip's memory bandwidth the grouped expert matmul reaches
in a decode step: the experts the step's tokens chose (`experts_read`,
mean of the traced window's `aiko:engine.decode` spans) x an expert's
gate, up and down / peak bytes per second / the device time of the
`moe_expert_ffn` kernel in one `jit_paged_decode_step`, all layers."""
from benchmark.harness import dsv2_counts as counts


def read(run):
    seconds = counts.kernel_seconds_a_step(run, counts.EXPERT_KERNEL)
    means = counts.step_means(run)
    if not seconds or not run.peaks or means is None:
        return None
    sizes = counts.shape(run.cell.config)
    least = (means["experts_read"] * counts.expert_bytes(sizes)
             / run.peaks["hbm_bytes_per_s"])
    return least / seconds * 100
