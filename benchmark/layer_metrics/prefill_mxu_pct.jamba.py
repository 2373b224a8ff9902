"""Share of the chip's bf16 peak the hybrid model's prefill programs reach:
the forward operations the traced window's prefills need (every matmul at
the true length, not the padded bucket, the causal half of the two
attention layers' products and the head at one position; `true_len` of the
`aiko:engine.prefill` spans that carry the scan's fields) over peak,
against the device time of whole `jit_paged_prefill` executions there,
mean over mean.  The selective scan's arithmetic, on the vector unit, is
time in the denominator and no operation in the numerator."""
import statistics

from benchmark.harness import jamba_counts as counts
from benchmark.harness.programs import runs_of


def read(run):
    runs = runs_of(run.trace, counts.PREFILL)
    prefills = counts.prefills(run)
    if not runs or not run.peaks or not prefills:
        return None
    sizes = counts.shape(run.cell.config)
    needed = statistics.fmean(counts.prefill_flops(sizes, length)
                              for length, _, _ in prefills)
    least = needed / run.peaks["bf16_flops_per_s"]
    return least / statistics.fmean(runs) * 100
