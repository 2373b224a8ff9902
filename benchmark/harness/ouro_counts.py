"""Operations and bytes Ouro's looped decoder must spend: what the `ouro`
per-layer readers divide.

The counts are what the algorithm needs, from the configuration's own
widths.  A decode step runs the stack `ut_passes` times and must read the
layers' weights once a PASS, not once a step: 48 layers are 4.93 GB, and
no chip of this kind keeps that between passes (a v5e has 128 MiB of fast
memory), so a step of four passes reads 19.7 GB of weights whatever the
batch.  That is the one place where these counts multiply where
`roofline.py` (one pass) does not; were a chip to keep the stack, the
share would pass 100 % and this count would be the bug.  Beside the
weights a step reads the tied embedding once as the head and every live
cache row once: `cache_rows` is live positions x caches (layers x passes),
each row 2 x kv_heads x head_dim values.  A prefill is `ut_passes` dense
prefills at the true length (the causal half of scores and values) and
the head at one position.  Passes and rows are the program's own account
(`ut_passes`, `cache_rows` on its `aiko:engine.decode` spans), not the
file's.
"""

from __future__ import annotations

from . import program_spans
from .dsv2_counts import (  # noqa: F401  (the readers take them from here)
    DECODE_STEP, PREFILL, kernel_seconds_a_step)

# the dense paged kernel's name in the device trace (pallas_call name=...)
PAGED_KERNEL = "paged_attention"


def shape(config: dict) -> dict:
    """The sizes from the configuration file's published keys."""
    item = {"bfloat16": 2, "float16": 2, "float32": 4}[
        config.get("torch_dtype", config.get("dtype", "bfloat16"))]
    return {
        "vocab": int(config["vocab_size"]), "d": int(config["hidden_size"]),
        "layers": int(config["num_hidden_layers"]),
        "heads": int(config["num_attention_heads"]),
        "kv_heads": int(config["num_key_value_heads"]),
        "hd": int(config["head_dim"]), "ff": int(config["intermediate_size"]),
        "passes": int(config["total_ut_steps"]), "bytes": item}


def layer_matmul_params(sizes: dict) -> int:
    """wq, wo over the heads, wk, wv over the K/V heads, gate, up, down."""
    d, hd = sizes["d"], sizes["hd"]
    return (d * hd * (2 * sizes["heads"] + 2 * sizes["kv_heads"])
            + 3 * d * sizes["ff"])


def pass_bytes(sizes: dict) -> int:
    """Weights one pass over the stack reads: every layer's matrices and
    its four norms, and the final norm."""
    per_layer = layer_matmul_params(sizes) + 4 * sizes["d"]
    return (sizes["layers"] * per_layer + sizes["d"]) * sizes["bytes"]


def head_bytes(sizes: dict) -> int:
    """The tied embedding, read once a step as the output head."""
    return sizes["vocab"] * sizes["d"] * sizes["bytes"]


def cache_row_bytes(sizes: dict) -> int:
    """One position's keys and values in one cache."""
    return 2 * sizes["kv_heads"] * sizes["hd"] * sizes["bytes"]


def step_bytes(sizes: dict, ut_passes: float, cache_rows: float) -> float:
    """One decode step over any number of slots."""
    return (ut_passes * pass_bytes(sizes) + head_bytes(sizes)
            + cache_rows * cache_row_bytes(sizes))


def prefill_flops(sizes: dict, ut_passes: float, tokens: int) -> float:
    """Forward operations of one causal prefill of `tokens` positions:
    `ut_passes` times every matmul (2 a multiply-add) and the causal half
    of the score and value products, and the head at the one position
    that is used."""
    matmul = 2.0 * tokens * sizes["layers"] * layer_matmul_params(sizes)
    attention = (2.0 * 2.0 * sizes["layers"] * sizes["heads"] * sizes["hd"]
                 * tokens * (tokens + 1) / 2.0)
    return (ut_passes * (matmul + attention)
            + 2.0 * sizes["vocab"] * sizes["d"])


# -- what the traced window holds ---------------------------------------------

def step_means(run) -> dict | None:
    """Means of `ut_passes` and `cache_rows` over the `aiko:engine.decode`
    spans of the traced window (both of the step the span dispatched).
    None under 3 spans, or where the program writes no such fields (the
    parent of the PR that added them)."""
    spans = program_spans.of_run(run)
    if spans is None:
        return None
    decodes = [span.stats for span in spans.named("engine.decode")
               if "ut_passes" in span.stats and "cache_rows" in span.stats]
    if len(decodes) < program_spans.MIN_SAMPLES:
        return None
    return {name: sum(float(stats[name]) for stats in decodes)
            / len(decodes) for name in ("ut_passes", "cache_rows")}


def prefills(run) -> list | None:
    """[(ut_passes, true_len)] of the traced window's whole prefills, from
    their `aiko:engine.prefill` spans; None where there is none."""
    spans = program_spans.of_run(run)
    if spans is None:
        return None
    found = [(float(span.stats["ut_passes"]), int(span.stats["true_len"]))
             for span in spans.named("engine.prefill")
             if "ut_passes" in span.stats and "true_len" in span.stats
             and "attention" in span.stats]
    return found or None
