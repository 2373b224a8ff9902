"""The estimators on synthetic inputs: no step, and gaps per token."""
import numpy as np
import pytest

from benchmark.tests import toy  # noqa: F401  (puts the repo on sys.path)
from benchmark.harness import estimators


def _completions(groups: int, group_s: float = 0.45, frames: int = 4,
                 rows: int = 8):
    times, counts = [], []
    for group in range(1, groups + 1):
        for frame in range(frames):
            times.append(group * group_s + frame * 0.001)
            counts.append(rows)
    return times, counts


@pytest.mark.parametrize("seconds", np.linspace(44.6, 45.4, 17).tolist())
def test_frames_per_s_has_no_step(seconds):
    """450 ms groups of 32 rows: the true rate is 32 / 0.45 whatever the
    window; counting rows inside a fixed 45 s and dividing by 45 (what
    PR 22 did) moves in steps of one group, 1 %."""
    times, rows = _completions(140)
    rate, counted, t0, t1 = estimators.rate_between_barriers(
        times, rows, start=1.0, seconds=seconds)
    assert rate == pytest.approx(32 / 0.45, rel=1e-3)
    assert counted % 32 == 0 and t1 - t0 >= seconds


def test_fixed_denominator_would_step():
    # 452 ms groups do not divide 45 s: a fixed window holds 99 or 100
    times, rows = _completions(140, group_s=0.452)
    fixed = []
    for start in np.linspace(1.0, 1.45, 10):
        inside = sum(count for at, count in zip(times, rows)
                     if start < at <= start + 45.0)
        fixed.append(inside / 45.0)
    barrier = [estimators.rate_between_barriers(times, rows, start, 45.0)[0]
               for start in np.linspace(1.0, 1.45, 10)]
    assert (max(fixed) - min(fixed)) / min(fixed) > 0.009
    assert (max(barrier) - min(barrier)) / min(barrier) < 1e-3


def test_rate_moves_with_the_work_not_the_window():
    """Groups 2 % slower read 2 % slower, in any window."""
    slow = estimators.rate_between_barriers(
        *_completions(140, group_s=0.459), start=1.0, seconds=45.0)[0]
    assert slow == pytest.approx(32 / 0.459, rel=1e-3)


def test_evenly_spaced_completions_are_all_barriers():
    times = [0.1 * index for index in range(200)]
    rate, *_ = estimators.rate_between_barriers(
        times, [8] * 200, start=1.0, seconds=10.0)
    assert rate == pytest.approx(80.0, rel=1e-6)


def test_no_pair_of_barriers_gives_none():
    times, rows = _completions(10)
    assert estimators.rate_between_barriers(
        times, rows, start=1.0, seconds=45.0) is None


def test_token_gap_counts_tokens_not_chunks():
    """Chunks of 8 tokens every 89.9 ms are 11.2 ms a token, not 89.9;
    a last chunk of 3 tokens after 33.7 ms is 11.2 ms a token too."""
    chunks = [(0.5 + 0.0899 * index, 8) for index in range(6)]
    chunks.append((chunks[-1][0] + 0.0337, 3))
    gaps = estimators.token_gaps(chunks)
    assert len(gaps) == len(chunks) - 1
    assert all(gap == pytest.approx(0.01124, rel=2e-3) for gap in gaps)
    assert estimators.token_gaps(chunks[:1]) == []


def test_percentile_and_spread():
    values = list(range(1, 101))
    assert estimators.percentile(values, 50) == pytest.approx(50.5)
    assert estimators.percentile(values, 95) == pytest.approx(95.05)
    assert estimators.spread([10, 10, 10, 10.1, 9.9, 10]) < 0.02


def test_queue_growth_reads_first_third_and_last_sixth():
    samples = [(float(at), at) for at in range(0, 31)]
    first, last = estimators.queue_growth(samples, 0.0, 30.0)
    assert first == pytest.approx(4.5) and last == pytest.approx(27.5)
