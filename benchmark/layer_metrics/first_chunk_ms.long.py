"""Mean time from a request's first token (the end of its prefill) to its
first `token_chunk` of 8 published (the `aiko:engine.chunk` mark with
offset 0; later chunks carry it as `first_us`), over the requests that
published a chunk inside the traced window, ms.  None under 3 of them."""
from benchmark.harness.program_spans import first_chunk_ms as read  # noqa: F401
