"""Mean admission-to-first-token time in the decode engine (`decode.prefill_s`) over the window, ms."""


def read(run):
    count, total = (run.hist or {}).get("decode.prefill_s", (0, 0.0))
    return total / count * 1e3 if count else None
