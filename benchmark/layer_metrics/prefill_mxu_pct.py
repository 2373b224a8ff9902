"""Share of the chip's bf16 peak the prefill programs reach: the forward
operations the window's prompts need (their true lengths, not the padded
bucket) over peak, against the device time of whole `jit_paged_prefill`
executions in the traced window."""
import statistics

from benchmark.harness import roofline
from benchmark.harness.programs import runs_of


def read(run):
    runs = runs_of(run.trace, "jit_paged_prefill")
    if not runs or not run.peaks or not run.good:
        return None
    needed = statistics.fmean(
        roofline.prefill_flops(run.shape, len(record["prompt"]))
        for record in run.good)
    least = needed / run.peaks["bf16_flops_per_s"]
    return least / statistics.fmean(runs) * 100
