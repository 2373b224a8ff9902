"""Share of the chip's bf16 peak the prefill's attention kernel reaches:
the operations the traced window's prefills need at their true lengths
(128 heads, 192 to score and 128 to carry, the causal half, every layer;
`true_len` of the `aiko:engine.prefill` spans) over peak, against the
device time of the `mla_flash_attention` kernel in one whole
`jit_paged_prefill` execution, mean over mean."""
from benchmark.harness import dsv2_counts as counts, program_spans


def read(run):
    found = counts.kernel_seconds(run, counts.PREFILL, counts.PREFILL_KERNEL)
    spans = program_spans.of_run(run)
    if found is None or spans is None or not run.peaks:
        return None
    lengths = [int(span.stats["true_len"])
               for span in spans.named("engine.prefill")
               if "true_len" in span.stats]
    if not lengths:
        return None
    sizes = counts.shape(run.cell.config)
    needed = sum(counts.prefill_attention_flops(sizes, length)
                 for length in lengths) / len(lengths)
    least = needed / run.peaks["bf16_flops_per_s"]
    return least / (found[0] / found[1]) * 100
