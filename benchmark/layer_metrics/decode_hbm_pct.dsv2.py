"""Share of the chip's memory bandwidth one decode step of DeepSeek-V2's
share needs: (the weights outside the routed experts once + the experts
the step's tokens chose once + the live latent rows once a layer) / peak
bytes per second / the step's device time.  Experts and rows are the
means of the `aiko:engine.decode` spans in the traced window; the time is
the median of whole `jit_paged_decode_step` executions there."""
import statistics

from benchmark.harness import dsv2_counts as counts
from benchmark.harness.programs import runs_of


def read(run):
    runs = runs_of(run.trace, counts.DECODE_STEP)
    means = counts.step_means(run)
    if not runs or not run.peaks or means is None:
        return None
    sizes = counts.shape(run.cell.config)
    needed = (counts.fixed_step_bytes(sizes)
              + means["experts_read"] * counts.expert_bytes(sizes)
              + means["latent_positions"] * sizes["layers"]
              * counts.latent_row_bytes(sizes))
    least = needed / run.peaks["hbm_bytes_per_s"]
    return least / statistics.median(runs) * 100
