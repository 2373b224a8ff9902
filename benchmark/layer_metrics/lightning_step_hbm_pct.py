"""Share of the chip's memory bandwidth the lightning layers' one-row step
(the kernel `lightning_step`, six layers of a decode step) needs: the
decoding slots' S read and written (`state_bytes` of the
`aiko:engine.decode` spans) / peak bytes per second / the kernel's device
time in one whole `jit_paged_decode_step` execution of the traced
window."""
from benchmark.harness import sala_counts as counts


def read(run):
    seconds = counts.kernel_seconds_a_step(run, counts.STEP)
    means = counts.step_means(run)
    if not seconds or not run.peaks or means is None:
        return None
    return (means["state_bytes"] / run.peaks["hbm_bytes_per_s"] / seconds
            * 100)
