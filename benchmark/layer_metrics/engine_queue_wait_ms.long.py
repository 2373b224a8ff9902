"""Mean wait in the decode engine's queue before a slot (`decode.queue_wait_s`) over the window, ms."""


def read(run):
    count, total = (run.hist or {}).get("decode.queue_wait_s", (0, 0.0))
    return total / count * 1e3 if count else None
