"""Share of the chip's memory bandwidth one decode step of MiniCPM-SALA's
stage needs: (the weights once + the decoding slots' S read and written +
the chosen K/V blocks + the compressed keys scored) / peak bytes per second
/ the step's device time (`harness/sala_counts.py`).  State bytes, blocks
read and compressed keys are the means of the `aiko:engine.decode` spans in
the traced window; the time is the median of whole `jit_paged_decode_step`
executions there.  The share of the whole step."""
import statistics

from benchmark.harness import sala_counts as counts
from benchmark.harness.programs import runs_of


def read(run):
    runs = runs_of(run.trace, counts.DECODE_STEP)
    means = counts.step_means(run)
    if not runs or not run.peaks or means is None:
        return None
    needed = counts.step_bytes(
        counts.shape(run.cell.config), means["state_bytes"],
        means["sparse_blocks_read"], means["compressed_rows"])
    least = needed / run.peaks["hbm_bytes_per_s"]
    return least / statistics.median(runs) * 100
