"""Share of one decode step's device time the paged latent attention
kernel (`mla_paged_attention`, all layers) takes.  The kernel alone:
MLA's projections are XLA fusions whose names the compiler numbers, and
are not in it."""
from benchmark.harness import dsv2_counts as counts
from benchmark.harness.programs import runs_of


def read(run):
    seconds = counts.kernel_seconds_a_step(run, counts.LATENT_KERNEL)
    runs = runs_of(run.trace, counts.DECODE_STEP)
    if not seconds or not runs:
        return None
    return seconds / (sum(runs) / len(runs)) * 100
