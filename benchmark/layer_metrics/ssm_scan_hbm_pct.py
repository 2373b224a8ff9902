"""Share of the chip's memory bandwidth the selective-scan kernel
(`ssm_chunk_scan`, every Mamba layer of a whole prefill) needs: what it
must move (c, dt, z in, y out, B, C: 41,024 B a row a layer at the
published sizes, over the rows the program says its scan ran, `scan_rows`
of the `aiko:engine.prefill` spans) / peak bytes per second / the kernel's
device time inside whole `jit_paged_prefill` executions of the traced
window.  The one roofline the chip publishes for this kernel: its
arithmetic runs on the vector unit, which has no published peak, so a low
share says the kernel is bound there."""
import statistics

from benchmark.harness import jamba_counts as counts


def read(run):
    seconds = counts.scan_seconds_a_prefill(run)
    prefills = counts.prefills(run)
    if not seconds or not run.peaks or not prefills:
        return None
    needed = counts.scan_bytes(
        counts.shape(run.cell.config),
        statistics.fmean(rows for _, rows, _ in prefills))
    return needed / run.peaks["hbm_bytes_per_s"] / seconds * 100
