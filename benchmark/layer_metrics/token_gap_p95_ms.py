"""95th percentile of the per-token gap, pooled like token_gap_p50_ms: the
tail a stream's reader feels when a prefill is admitted beside it.  ISSUE 23
made it the end-to-end metric unless its runs spread too widely for a bound
the contract allows; they did (3.1 %, PERF.md section 6), so the median is
judged and this stands beside it."""
from benchmark.harness.estimators import percentile


def read(run):
    return percentile(run.gaps_s, 95) * 1e3 if run.gaps_s else None
