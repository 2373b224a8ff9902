"""The rows a whole prefill runs, out of its bucket's, from the program's
own spans (PR 38): `rows` beside `bucket` on `aiko:engine.prefill`, written
by the engine from `models.prefill_rows`, by which the model step itself
decides.  A chunk call carries no `rows`."""

from __future__ import annotations

from . import program_spans


def rows_run_pct(run) -> float | None:
    """The sum of `rows` over the sum of `bucket` of the traced window's
    `aiko:engine.prefill` spans that carry both, x 100: 100 where every
    bucket runs whole.  None where no span carries `rows`: a program
    without the counter, or a window without a whole prefill."""
    spans = program_spans.of_run(run)
    if spans is None:
        return None
    ran = [(float(span.stats["rows"]), float(span.stats["bucket"]))
           for span in spans.named("engine.prefill")
           if "rows" in span.stats and "bucket" in span.stats]
    if not ran:
        return None
    return sum(rows for rows, _ in ran) / sum(
        bucket for _, bucket in ran) * 100
