"""Peaks of the chip, and the operations and bytes a program must spend.

The counts are what the algorithm needs at its shapes, not what the
program as compiled happens to do: weights read once a step, the live
cache read once, never the block table's capacity.  A share over 100 %
therefore means a count here is too high, and is a bug here.
"""

from __future__ import annotations

import json
from pathlib import Path

_PEAKS = Path(__file__).with_name("peaks.json")


def peaks(device_kind: str) -> dict:
    with open(_PEAKS, encoding="utf-8") as handle:
        table = json.load(handle)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"harness/peaks.json has {sorted(table)}")
    return table[device_kind]


def _dtype_bytes(name: str) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}[name]


def lm_shape(config: dict) -> dict:
    """The LM's sizes from either configuration layout."""
    lm = config.get("lm", config)
    return {
        "d": int(lm["hidden_size"]), "ff": int(lm["intermediate_size"]),
        "heads": int(lm["num_attention_heads"]),
        "kv_heads": int(lm["num_key_value_heads"]),
        "hd": int(lm["head_dim"]), "layers": int(lm["num_hidden_layers"]),
        "vocab": int(lm["vocab_size"]),
        "bytes": _dtype_bytes(lm.get("torch_dtype", lm.get("dtype"))),
    }


def lm_layer_params(shape: dict) -> int:
    d, ff, hd = shape["d"], shape["ff"], shape["hd"]
    attention = d * hd * (2 * shape["heads"] + 2 * shape["kv_heads"])
    return attention + 3 * d * ff


def lm_weight_bytes(shape: dict) -> int:
    """Bytes of weights a forward step reads: every layer once, and the
    tied embedding once as the output head."""
    return (shape["layers"] * lm_layer_params(shape)
            + shape["vocab"] * shape["d"]) * shape["bytes"]


def kv_bytes_per_position(shape: dict) -> int:
    return shape["layers"] * 2 * shape["kv_heads"] * shape["hd"] \
        * shape["bytes"]


def decode_step_bytes(shape: dict, live_positions: float) -> float:
    """One decode step over any number of slots: the weights once plus
    the keys and values of every live position once."""
    return lm_weight_bytes(shape) + live_positions * kv_bytes_per_position(
        shape)


def prefill_flops(shape: dict, tokens: int) -> float:
    """Forward operations of one causal prefill of `tokens` positions:
    2 per multiply-add in every matmul, the causal half of the score and
    value products, and the output head at the one position that is
    used."""
    matmul = 2.0 * tokens * shape["layers"] * lm_layer_params(shape)
    attention = (2.0 * 2.0 * shape["layers"] * shape["heads"] * shape["hd"]
                 * tokens * (tokens + 1) / 2.0)
    head = 2.0 * shape["vocab"] * shape["d"]
    return matmul + attention + head
