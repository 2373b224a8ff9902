"""Share of the chip's memory bandwidth one decode step needs: (weight
bytes + live cache bytes) / peak bytes per second / the step's device time.
The bytes are what the algorithm must read, not the block table's
capacity, so this is a roofline share and cannot pass 100 %."""
import statistics

from benchmark.harness import roofline
from benchmark.harness.programs import runs_of


def read(run):
    runs = runs_of(run.trace, "jit_paged_decode_step")
    if not runs or not run.peaks:
        return None
    needed = roofline.decode_step_bytes(run.shape, run.live_positions)
    least = needed / run.peaks["hbm_bytes_per_s"]
    return least / statistics.median(runs) * 100
