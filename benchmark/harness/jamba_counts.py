"""Operations and bytes Jamba's hybrid decoder must spend: what the `jamba`
per-layer readers divide.

The counts are what the algorithm needs, from the configuration's own
widths, whatever implements it.  A decode step reads the weights once
(every layer's matrices, the Mamba layers' convolution, A, D and biases,
the norms, and the tied embedding as the head), reads and writes the
recurrent state of the slots it decodes once (`state_bytes` of the
program's `aiko:engine.decode` spans: the host's count of those slots x
the Mamba layers x a layer's SSM state in float32 and convolution tail,
twice), and reads every live K/V row of the attention layers once
(`cache_rows` of the same spans, each row 2 x kv_heads x head size
values).  A prefill is every matmul at the true length (2 a multiply-add),
the causal half of the attention layers' scores and values, and the head
at one position; the selective scan's own arithmetic runs on the vector
unit, which has no published peak, and is not in it.  The scan kernel's
traffic is what it must move a row a Mamba layer: c, dt, z in and out
back (4 x d_inner values) and B, C (2 x d_state values), whatever the
kernel's blocks: 41,024 B at the published sizes.
"""

from __future__ import annotations

from . import program_spans
from .dsv2_counts import (  # noqa: F401  (the readers take them from here)
    DECODE_STEP, PREFILL, kernel_seconds)

# the selective-scan kernel's name in the device trace (pallas_call name=)
SCAN_KERNEL = "ssm_chunk_scan"


def shape(config: dict) -> dict:
    """The sizes from the configuration file's published keys."""
    item = {"bfloat16": 2, "float16": 2, "float32": 4}[
        config.get("torch_dtype", config.get("dtype", "bfloat16"))]
    d, layers = int(config["hidden_size"]), int(config["num_hidden_layers"])
    attention = sum(
        index % int(config["attn_layer_period"])
        == int(config["attn_layer_offset"]) for index in range(layers))
    return {
        "vocab": int(config["vocab_size"]), "d": d, "layers": layers,
        "attention": attention, "mamba": layers - attention,
        "heads": int(config["num_attention_heads"]),
        "kv_heads": int(config["num_key_value_heads"]),
        "hd": d // int(config["num_attention_heads"]),
        "ff": int(config["intermediate_size"]),
        "inner": int(config["mamba_expand"]) * d,
        "states": int(config["mamba_d_state"]),
        "taps": int(config["mamba_d_conv"]),
        "rank": int(config["mamba_dt_rank"]), "bytes": item}


def mamba_matmul_params(sizes: dict) -> int:
    """A Mamba layer's matrices: in, x, dt, out, and the MLP's three."""
    d, inner = sizes["d"], sizes["inner"]
    return (d * 2 * inner + inner * (sizes["rank"] + 2 * sizes["states"])
            + sizes["rank"] * inner + inner * d + 3 * d * sizes["ff"])


def attention_matmul_params(sizes: dict) -> int:
    """wq, wo over the heads, wk, wv over the K/V heads, gate, up, down."""
    d, hd = sizes["d"], sizes["hd"]
    return (d * hd * (2 * sizes["heads"] + 2 * sizes["kv_heads"])
            + 3 * d * sizes["ff"])


def weight_bytes(sizes: dict) -> int:
    """Every weight once: the matrices and norms in the serving dtype, of
    a Mamba layer also the convolution and its bias (serving dtype) and A,
    D and the step's bias (float32); the final norm; the tied embedding."""
    inner, item = sizes["inner"], sizes["bytes"]
    mamba = (item * (mamba_matmul_params(sizes) + 2 * sizes["d"]
                     + sizes["rank"] + 2 * sizes["states"]
                     + (sizes["taps"] + 1) * inner)
             + 4 * inner * (sizes["states"] + 2))
    attention = item * (attention_matmul_params(sizes) + 2 * sizes["d"])
    return (sizes["mamba"] * mamba + sizes["attention"] * attention
            + item * (sizes["d"] + sizes["vocab"] * sizes["d"]))


def cache_row_bytes(sizes: dict) -> int:
    """One position's keys and values in one attention layer's cache."""
    return 2 * sizes["kv_heads"] * sizes["hd"] * sizes["bytes"]


def step_bytes(sizes: dict, state_bytes: float, cache_rows: float) -> float:
    """One decode step: the weights once, the decoding slots' recurrent
    state read and written, every live K/V row once."""
    return (weight_bytes(sizes) + state_bytes
            + cache_rows * cache_row_bytes(sizes))


def prefill_flops(sizes: dict, tokens: int) -> float:
    """Forward operations of one causal prefill of `tokens` positions:
    every matmul (2 a multiply-add), the causal half of the attention
    layers' score and value products, the head at the one position that
    is used."""
    matmul = 2.0 * tokens * (
        sizes["mamba"] * mamba_matmul_params(sizes)
        + sizes["attention"] * attention_matmul_params(sizes))
    attention = (2.0 * 2.0 * sizes["attention"] * sizes["heads"]
                 * sizes["hd"] * tokens * (tokens + 1) / 2.0)
    return matmul + attention + 2.0 * sizes["vocab"] * sizes["d"]


def scan_row_bytes(sizes: dict) -> int:
    """What the selective scan moves a row a Mamba layer: c, dt, z in,
    the gated y out, B and C."""
    return (4 * sizes["inner"] + 2 * sizes["states"]) * sizes["bytes"]


def scan_bytes(sizes: dict, scan_rows: float) -> float:
    return scan_rows * sizes["mamba"] * scan_row_bytes(sizes)


# -- what the traced window holds ---------------------------------------------

def step_means(run) -> dict | None:
    """Means of `state_bytes`, `state_slots` and `cache_rows` over the
    `aiko:engine.decode` spans of the traced window (all of the step the
    span dispatched).  None under 3 spans, or where the program writes no
    such fields (the parent of the PR that added them)."""
    spans = program_spans.of_run(run)
    if spans is None:
        return None
    names = ("state_bytes", "state_slots", "cache_rows")
    decodes = [span.stats for span in spans.named("engine.decode")
               if all(name in span.stats for name in names)]
    if len(decodes) < program_spans.MIN_SAMPLES:
        return None
    return {name: sum(float(stats[name]) for stats in decodes)
            / len(decodes) for name in names}


def prefills(run) -> list | None:
    """[(true_len, scan_rows, scan)] of the traced window's whole
    prefills, from their `aiko:engine.prefill` spans; None where there is
    none that carries the scan's fields."""
    spans = program_spans.of_run(run)
    if spans is None:
        return None
    found = [(int(span.stats["true_len"]), int(span.stats["scan_rows"]),
              str(span.stats["scan"]))
             for span in spans.named("engine.prefill")
             if "scan_rows" in span.stats and "scan" in span.stats
             and "true_len" in span.stats]
    return found or None


def scan_seconds_a_prefill(run) -> float | None:
    """Device seconds of `ssm_chunk_scan`, all Mamba layers, in one whole
    `jit_paged_prefill` execution of the traced window: mean."""
    found = kernel_seconds(run, PREFILL, SCAN_KERNEL)
    return found[0] / found[1] if found else None
