"""The one traffic generator: a mix is a data file, this reads it.

Every seed gets the same set of sizes and the same set of gaps, in
another order: lengths are the distribution's own quantiles at
(i + 0.5) / n, inter-arrival gaps the exponential's, and the seed only
permutes them (and draws the token ids).  So the work in a window does
not depend on the seed, only its order does.

Keys a mix may carry (benchmark/README.md has the full list):

  arrivals       {"process": "poisson", "rate_per_s": r}
                 {"process": "gamma", "rate_per_s": r, "cv": c}
                 {"process": "closed", "callers": n}
  prompt_tokens  {"dist": "lognormal", "median", "sigma", "min", "max"}
  answer_tokens  {"dist": "uniform", "min", "max"} | {"dist": "fixed",
                 "value"}
  shared_prefix_tokens, prefix_pool      leading tokens shared by requests
  streams, period_s, jitter              periodic senders (graph mixes)
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np

# the set of gamma gaps is drawn once from this seed; a run's seed only
# permutes it
_FIXED_SET_SEED = 20260927


@dataclass
class Request:
    index: int
    due_s: float | None        # None in a closed loop: sent when a caller is free
    prompt: np.ndarray         # (length,) int32
    answer_tokens: int


def _quantiles(count: int) -> np.ndarray:
    return (np.arange(count) + 0.5) / count


def sizes(spec: dict, count: int) -> np.ndarray:
    """`count` whole sizes: the distribution's quantiles, unpermuted."""
    kind = spec["dist"]
    if kind == "fixed":
        return np.full((count,), int(spec["value"]), np.int64)
    u = _quantiles(count)
    if kind == "uniform":
        values = spec["min"] + u * (spec["max"] - spec["min"])
    elif kind == "lognormal":
        normal = NormalDist()
        values = np.asarray([
            math.exp(math.log(spec["median"])
                     + spec["sigma"] * normal.inv_cdf(float(q)))
            for q in u])
    else:
        raise ValueError(f"unknown size distribution {kind!r}")
    values = np.clip(np.rint(values), spec.get("min", 1),
                     spec.get("max", np.inf))
    return values.astype(np.int64)


def gaps(arrivals: dict, count: int) -> np.ndarray:
    """`count` inter-arrival gaps in seconds, unpermuted."""
    rate = float(arrivals["rate_per_s"])
    process = arrivals["process"]
    if process == "poisson":
        return -np.log1p(-_quantiles(count)) / rate
    if process == "gamma":
        shape = 1.0 / float(arrivals["cv"]) ** 2
        drawn = np.random.default_rng(_FIXED_SET_SEED).gamma(
            shape, 1.0, count)
        return drawn / drawn.mean() / rate
    raise ValueError(f"unknown open-loop arrival process {process!r}")


def requests(traffic: dict, seed: int, horizon_s: float,
             vocab_size: int) -> list:
    """The requests of one run, in sending order.  An open loop gets
    arrivals over `horizon_s`; a closed loop a replay set."""
    rng = np.random.default_rng(seed)
    arrivals = traffic["arrivals"]
    if arrivals["process"] == "closed":
        count = int(traffic["replay_set"])
        due = [None] * count
    else:
        count = max(1, round(float(arrivals["rate_per_s"]) * horizon_s))
        due = np.cumsum(rng.permutation(gaps(arrivals, count))).tolist()
    prompts = rng.permutation(sizes(traffic["prompt_tokens"], count))
    answers = rng.permutation(sizes(traffic["answer_tokens"], count))
    shared = int(traffic.get("shared_prefix_tokens", 0))
    pool = [rng.integers(1, vocab_size, shared).astype(np.int32)
            for _ in range(int(traffic.get("prefix_pool", 1)))
            ] if shared else []
    made = []
    for index in range(count):
        tokens = rng.integers(
            1, vocab_size, int(prompts[index])).astype(np.int32)
        if shared:
            head = pool[index % len(pool)][:len(tokens) - 1]
            tokens[:len(head)] = head
        made.append(Request(index, due[index], tokens,
                            int(answers[index])))
    return made


def frame_schedule(traffic: dict, seed: int, horizon_s: float) -> list:
    """[(due_s, stream_index), ...] ascending: each of `streams` senders
    posts one frame every `period_s`, from a phase of its own, with
    +-`jitter` of the period on every interval.  The phases are one set
    for every seed, S of them evenly over a period: the seed shifts the
    set as a whole and deals it out to the senders in another order.
    (Phases drawn independently cluster differently from seed to seed,
    and with few senders that, not the system, decides the median.)"""
    rng = np.random.default_rng(seed)
    period = float(traffic["period_s"])
    jitter = float(traffic.get("jitter", 0.0))
    count = int(traffic["streams"])
    phases = (rng.permutation(count) + float(rng.uniform(0.0, 1.0))) \
        / count * period
    schedule = []
    for stream in range(count):
        at = float(phases[stream])
        while at < horizon_s:
            schedule.append((at, stream))
            at += period * (1.0 + float(rng.uniform(-jitter, jitter)))
    schedule.sort()
    return schedule


def tone_frequencies(traffic: dict, seed: int, count: int) -> list:
    """`count` tone frequencies in Hz, one for each row of a few frames."""
    rng = np.random.default_rng(seed + 1)
    band = traffic["tone_hz"]
    return [round(float(value), 1) for value in
            rng.uniform(band["low"], band["high"], count)]
