"""Device time of the selective-scan kernel (`ssm_chunk_scan`, all Mamba
layers) in one whole prefill, ms: its operations inside whole
`jit_paged_prefill` executions of the traced window, over those
executions."""
from benchmark.harness import jamba_counts as counts


def read(run):
    seconds = counts.scan_seconds_a_prefill(run)
    return None if seconds is None else seconds * 1e3
