"""Share of the chip's bf16 peak the looped model's prefill programs reach:
the forward operations the traced window's prefills need (`ut_passes` dense
prefills at the true length, not the padded bucket, and the head at one
position; `ut_passes` and `true_len` of the `aiko:engine.prefill` spans)
over peak, against the device time of whole `jit_paged_prefill` executions
there, mean over mean."""
import statistics

from benchmark.harness import ouro_counts as counts
from benchmark.harness.programs import runs_of


def read(run):
    runs = runs_of(run.trace, counts.PREFILL)
    prefills = counts.prefills(run)
    if not runs or not run.peaks or not prefills:
        return None
    sizes = counts.shape(run.cell.config)
    needed = statistics.fmean(counts.prefill_flops(sizes, passes, length)
                              for passes, length in prefills)
    least = needed / run.peaks["bf16_flops_per_s"]
    return least / statistics.fmean(runs) * 100
