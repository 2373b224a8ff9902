"""Mean time a frame waits parked in the micro-batch scheduler on the
speech path, park to coalesced dispatch: the mean of `queue_s:asr` plus
the mean of `queue_s:lm` over the window, ms."""


def read(run):
    total = 0.0
    for node in ("asr", "lm"):
        count, seconds = (run.counters or {}).get(
            f"queue_s:{node}", (0, 0.0))
        if not count:
            return None
        total += seconds / count
    return total * 1e3
