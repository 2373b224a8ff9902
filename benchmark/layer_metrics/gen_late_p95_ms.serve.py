"""How late the load generator sent requests, 95th percentile, ms: a
starved generator must not be read as a fast server."""
from benchmark.harness.estimators import percentile


def read(run):
    return percentile(run.late_s, 95) * 1e3 if run.late_s else None
