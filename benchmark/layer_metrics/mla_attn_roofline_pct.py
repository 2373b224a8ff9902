"""Roofline share of the paged latent attention kernel in a decode step:
the larger of (live latent rows x layers x a row's true bytes / peak bytes
per second) and (the same rows x 128 heads' score and value operations /
peak bf16 operations per second), over the device time of the
`mla_paged_attention` kernel in one `jit_paged_decode_step`, all layers.
At DeepSeek-V2's widths the two bounds are within a percent of each other
(241 FLOP/B against the v5e's ridge of 240)."""
from benchmark.harness import dsv2_counts as counts


def read(run):
    seconds = counts.kernel_seconds_a_step(run, counts.LATENT_KERNEL)
    means = counts.step_means(run)
    if not seconds or not run.peaks or means is None:
        return None
    sizes = counts.shape(run.cell.config)
    walked = means["latent_positions"] * sizes["layers"]
    least = max(
        walked * counts.latent_row_bytes(sizes)
        / run.peaks["hbm_bytes_per_s"],
        walked * counts.latent_attention_flops(sizes)
        / run.peaks["bf16_flops_per_s"])
    return least / seconds * 100
