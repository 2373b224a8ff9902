"""Share of its bucket's rows a whole prefill runs: the sum of `rows` over
the sum of `bucket` of the traced window's whole `aiko:engine.prefill`
spans, x 100.  The program runs a layer's row-wise work over the row tiles
up to the prompt's length (`models.prefill_rows`), so this is the prompt's
length rounded up to a tile over the bucket; 100 where a bucket runs whole
(under two tiles).  None where no span carries `rows`."""
from benchmark.harness.prefill_rows import rows_run_pct as read  # noqa: F401
