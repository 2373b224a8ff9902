"""The generator: the same work for every seed, in another order."""
import numpy as np

from benchmark.tests import toy  # noqa: F401
from benchmark.harness import cells, traffic


def test_seeds_permute_one_set_of_sizes_and_gaps():
    mix = cells.load_cell("lm.chat").traffic
    first = traffic.requests(mix, 1, 40.0, 32000)
    second = traffic.requests(mix, 2 ** 31 + 12345, 40.0, 32000)
    assert len(first) == len(second)
    assert sorted(len(r.prompt) for r in first) == sorted(
        len(r.prompt) for r in second)
    assert sorted(r.answer_tokens for r in first) == sorted(
        r.answer_tokens for r in second)
    assert [len(r.prompt) for r in first] != [len(r.prompt) for r in second]
    assert first[-1].due_s == np.float64(second[-1].due_s) or abs(
        first[-1].due_s - second[-1].due_s) < 1e-6
    again = traffic.requests(mix, 1, 40.0, 32000)
    assert all((a.prompt == b.prompt).all() and a.due_s == b.due_s
               for a, b in zip(first, again))


def test_sizes_follow_the_mix():
    mix = cells.load_cell("lm.chat").traffic
    prompts = traffic.sizes(mix["prompt_tokens"], 400)
    assert prompts.min() >= 32 and prompts.max() <= 2048
    assert 230 <= np.median(prompts) <= 280
    long_mix = cells.load_cell("lm.longprompt").traffic
    made = traffic.requests(long_mix, 5, 30.0, 32000)
    assert len(made) == long_mix["replay_set"]
    assert all(r.due_s is None and r.answer_tokens == 32 for r in made)
    assert all(2048 <= len(r.prompt) <= 4064 for r in made)


def test_poisson_rate_and_gamma_burstiness():
    gaps = traffic.gaps({"process": "poisson", "rate_per_s": 4.0}, 400)
    assert abs(gaps.mean() - 0.25) < 0.005
    bursty = traffic.gaps({"process": "gamma", "rate_per_s": 4.0,
                           "cv": 2.0}, 400)
    assert abs(bursty.mean() - 0.25) < 1e-9
    assert bursty.std() > 1.5 * gaps.std()


def test_streams_have_phases_of_their_own():
    mix = cells.load_cell("graph.streams").traffic
    schedule = traffic.frame_schedule(mix, 9, 30.0)
    boxes = {box for _, box in schedule}
    assert boxes == set(range(mix["streams"]))
    first = {}
    for due, box in schedule:
        first.setdefault(box, due)
    assert all(0 <= due < mix["period_s"] for due in first.values())
    spacing = np.diff(sorted(first.values()))
    assert np.allclose(spacing, mix["period_s"] / mix["streams"])
    other = traffic.frame_schedule(mix, 10, 30.0)
    assert [box for _, box in other[:6]] != [box for _, box in schedule[:6]]
    for box in boxes:
        dues = [due for due, other in schedule if other == box]
        periods = np.diff(dues)
        assert ((periods >= 4.5 - 1e-9) & (periods <= 5.5 + 1e-9)).all()
    assert schedule == sorted(schedule)
