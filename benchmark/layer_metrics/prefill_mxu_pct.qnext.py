"""Share of the chip's bf16 peak the prefill programs of Qwen3-Next's share
reach: the forward operations the traced window's prefills need (every
matmul at the true length, not the padded bucket, with the held experts'
expected pairs, the causal half of the two attention layers' products, the
chunkwise delta rule's matmuls and the head at one position; `true_len` of
the `aiko:engine.prefill` spans that carry the scan's fields) over peak,
against the device time of whole `jit_paged_prefill` executions there,
mean over mean."""
import statistics

from benchmark.harness import qnext_counts as counts
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
