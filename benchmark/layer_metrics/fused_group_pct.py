"""Share of scheduler groups that ran as one fused program
(`pipeline.fused_groups` against `pipeline.chained_groups`)."""


def read(run):
    counters = run.counters or {}
    groups = counters.get("fused", 0) + counters.get("chained", 0)
    return counters.get("fused", 0) / groups * 100 if groups else None
