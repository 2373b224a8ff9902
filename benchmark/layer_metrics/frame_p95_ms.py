"""95th percentile of frame latency from when a frame was due, ms."""
from benchmark.harness.estimators import percentile


def read(run):
    return percentile(run.latency_s, 95) * 1e3 if run.latency_s else None
