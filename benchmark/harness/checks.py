"""The comparison that decides `correct` for what an LM stage served.

Greedy decoding with seeded random weights cannot be compared token for
token: the largest logit changes on rounding.  So the served tokens are
fed back to the plain reference (teacher forcing, one pass over prompt +
served tokens) and each is held to how far the reference's logit for it
lies below the reference's best: 0 where both agree on the token, a
rounding error's worth where two logits were all but tied, and far more
where the served path computed something else.
"""

from __future__ import annotations

import numpy as np

from ..reference import transformer as reference


def _batch(samples, pad_to: int):
    longest = max(len(served) for _, served in samples)
    tokens = np.zeros((len(samples), pad_to), np.int32)
    positions = np.zeros((len(samples), longest), np.int32)
    mask = np.zeros((len(samples), longest), bool)
    targets = np.zeros((len(samples), longest), np.int32)
    for row, (prompt, served) in enumerate(samples):
        sequence = np.concatenate([prompt, served]).astype(np.int32)
        if len(sequence) > pad_to:
            raise ValueError(f"sample of {len(sequence)} tokens is over "
                             f"the reference's length {pad_to}")
        tokens[row, :len(sequence)] = sequence
        count = len(served)
        positions[row, :count] = len(prompt) - 1 + np.arange(count)
        mask[row, :count] = True
        targets[row, :count] = served
    return tokens, positions, mask, targets


def served_gaps(lm: dict, seed: int, samples, pad_to: int,
                control: str | None = None) -> dict:
    """Gaps of served tokens below the reference's best logit.

    `samples` is [(prompt int32[], served int32[]), ...].  Returns the
    widest gap, the mean gap, the share of tokens that are not the
    reference's own first choice, and the number compared.  With
    `control` (a lower precision the reference can compute in) the
    tokens judged are that precision's first choices at the same
    positions instead of the served ones."""
    import jax.numpy as jnp
    shape = reference.shape_of(lm)
    tokens, positions, mask, targets = _batch(samples, pad_to)
    logits = reference.logits_at(shape, seed, tokens, positions)
    if control is not None:
        lowered = reference.logits_at(shape, seed, tokens, positions,
                                      precision=control)
        targets = np.asarray(jnp.argmax(lowered, axis=-1), np.int32)
        del lowered
    best = jnp.max(logits, axis=-1)
    chosen = jnp.take_along_axis(
        logits, jnp.asarray(targets)[:, :, None], axis=-1)[..., 0]
    gaps = np.where(mask, np.asarray(best - chosen, np.float64), 0.0)
    compared = int(mask.sum())
    ranked = np.sort(gaps[mask])[::-1]
    return {"gap_max": float(gaps.max()),
            # the 1-in-200 gap: steadier than the widest, which swings
            "gap_p995": float(ranked[compared // 200]),
            "gaps_top": [round(float(gap), 5) for gap in ranked[:24]],
            "gap_mean": float(gaps.sum() / compared),
            "disagree_share": float((gaps > 0).sum() / compared),
            "tokens_compared": compared}


def judge(measured: dict, limits: dict) -> tuple:
    """(correct, lines): each number compared, printed beside its limit."""
    lines, correct = [], True
    for name, limit in limits.items():
        if name == "why":
            continue
        value = measured[name]
        passed = value <= limit
        correct = correct and passed
        lines.append(f"check {name}={value:.6g} limit={limit:.6g} "
                     f"{'ok' if passed else 'OVER'}")
    return correct, lines
