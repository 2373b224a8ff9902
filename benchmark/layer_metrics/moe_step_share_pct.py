"""Share of one decode step's device time the grouped expert matmul
(`moe_expert_ffn`, all layers) takes: its operations' time inside whole
`jit_paged_decode_step` executions of the traced window over those
executions' own time."""
from benchmark.harness import dsv2_counts as counts
from benchmark.harness.programs import runs_of


def read(run):
    seconds = counts.kernel_seconds_a_step(run, counts.EXPERT_KERNEL)
    runs = runs_of(run.trace, counts.DECODE_STEP)
    if not seconds or not runs:
        return None
    return seconds / (sum(runs) / len(runs)) * 100
