"""The arithmetic that turns what a window recorded into a metric.

Pure Python over lists of floats: tested on synthetic inputs in
benchmark/tests/test_estimators.py, and never given a fixed denominator.
"""

from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """Linear-interpolated q-th percentile (q in 0..100) of `values`."""
    ordered = sorted(values)
    if not ordered:
        raise ValueError("percentile of no values")
    if len(ordered) == 1:
        return float(ordered[0])
    rank = (len(ordered) - 1) * q / 100.0
    low = math.floor(rank)
    high = min(low + 1, len(ordered) - 1)
    return float(ordered[low] + (ordered[high] - ordered[low])
                 * (rank - low))


def burst_ends(times) -> list:
    """Indices of completions that close a burst.

    The scheduler completes frames a group at a time: a few completions
    within milliseconds, then one group's time of nothing.  A completion
    closes a burst when the gap to the next one is over half the mean
    gap.  Where completions are evenly spaced every one closes a burst;
    the last completion seen never does (its gap is not known yet)."""
    if len(times) < 3:
        return []
    mean_gap = (times[-1] - times[0]) / (len(times) - 1)
    return [index for index in range(len(times) - 1)
            if times[index + 1] - times[index] > 0.5 * mean_gap]


def rate_between_barriers(times, rows, start: float, seconds: float):
    """Rows completed per second between two completion barriers.

    `times[i]` is when completion i was seen finished on the device
    (ascending), `rows[i]` what it completed.  t0 is the first burst end
    at or after `start`; t1 the first burst end at or after t0 +
    `seconds`.  Both ends sit on a group's completion, so a window that
    fits one group more also lasts one group's time more: the estimate
    has no fixed denominator and no step.  Returns (rate, counted_rows,
    t0, t1), or None when the recording holds no such pair."""
    ends = burst_ends(times)
    first = next((index for index in ends if times[index] >= start), None)
    if first is None:
        return None
    last = next((index for index in ends
                 if times[index] >= times[first] + seconds), None)
    if last is None:
        return None
    counted = sum(rows[first + 1:last + 1])
    elapsed = times[last] - times[first]
    return counted / elapsed, counted, times[first], times[last]


def token_gaps(chunks) -> list:
    """Per-token gaps of one request's stream.

    `chunks` is [(arrival_s, tokens_in_chunk), ...] in arrival order.
    Every chunk after the first gives one sample: the time since the
    previous chunk divided by the tokens it carried, so a stream that
    arrives eight tokens at a time is not read as eight times slower."""
    return [(arrival - chunks[index][0]) / max(count, 1)
            for index, (arrival, count) in enumerate(chunks[1:])]


def spread(values) -> float:
    """Interquartile range as a share of the median, the contract's
    spread (statistics.quantiles, n=4)."""
    quartiles = statistics.quantiles(values, n=4)
    return (quartiles[2] - quartiles[0]) / statistics.median(values)


def queue_growth(samples, start: float, seconds: float):
    """(mean queue over the first third, mean over the last sixth) of
    the window: the knee rule's two readings."""
    first = [depth for at, depth in samples
             if start <= at < start + seconds / 3]
    last = [depth for at, depth in samples
            if start + seconds * 5 / 6 <= at <= start + seconds]
    if not first or not last:
        return None
    return statistics.fmean(first), statistics.fmean(last)
