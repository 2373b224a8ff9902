"""Device-idle time in which no host span says what the host was doing
(`no_host_span` among the reduced trace's idle gaps), in ms per second of
the traced window."""
from benchmark.harness import program_spans


def read(run):
    seconds = program_spans.idle_gap_seconds(run, "no_host_span")
    if seconds is None:
        return None
    return seconds / run.trace["window_s"] * 1e3
