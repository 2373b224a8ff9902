"""Operations a prefill of one chip's share of Qwen3-Next must spend: what
`prefill_mxu_pct.qnext` divides.

The counts are what the algorithm needs, from the configuration's own
widths, whatever implements it.  A prefill is every matmul at the true
length (2 a multiply-add): the mixers' projections, the router, the
shared expert, the held experts' expected pairs (top_k x held / router a
token a layer), the causal half of the attention layers' scores and
values, the gated delta rule in its chunkwise form (`scan_flops`), and
the head at one position.  A share over 100 % means a count here is too
high, and is a bug here.

The chunkwise form, a row a value head (C = 64 rows a chunk): K K^T and
Q K^T (2 x 2 C d_k), T as a triangular solve of C right-hand sides (C C
/ 3 multiply-adds a row, however the program blocks it), W and U (2 C
(d_k + d_v)), and across the chunks W S and Q S (2 x 2 d_k d_v), the
scores times V' (2 C d_v) and K^T V' (2 d_k d_v).

The decode step's counts (the weights outside the experts, the experts
read, the state in and out, the live K/V rows) and their five readers are
not here: they move the median token gap, which this cell does not report
(PERF.md section 7, ROADMAP.md S12).
"""

from __future__ import annotations

from . import program_spans
from .dsv2_counts import PREFILL  # noqa: F401  (the reader takes it from here)

CHUNK = 64      # rows of a chunk of the chunkwise form (parallel/delta.py)


def shape(config: dict) -> dict:
    """The sizes from the configuration file's published keys."""
    item = {"bfloat16": 2, "float16": 2, "float32": 4}[
        config.get("torch_dtype", config.get("dtype", "bfloat16"))]
    layers = int(config["num_hidden_layers"])
    attention = layers // int(config["full_attention_interval"])
    held = int(config["num_experts"])
    return {
        "vocab": int(config["vocab_size"]), "d": int(config["hidden_size"]),
        "layers": layers, "attention": attention,
        "delta": layers - attention,
        "heads": int(config["num_attention_heads"]),
        "kv_heads": int(config["num_key_value_heads"]),
        "hd": int(config["head_dim"]),
        "key_heads": int(config["linear_num_key_heads"]),
        "value_heads": int(config["linear_num_value_heads"]),
        "key_dim": int(config["linear_key_head_dim"]),
        "value_dim": int(config["linear_value_head_dim"]),
        "taps": int(config["linear_conv_kernel_dim"]),
        "router": int(config.get("router_experts", held)), "held": held,
        "top_k": int(config["num_experts_per_tok"]),
        "moe_ff": int(config["moe_intermediate_size"]),
        "shared_ff": int(config["shared_expert_intermediate_size"]),
        "bytes": item}


def conv_channels(sizes: dict) -> int:
    return (2 * sizes["key_heads"] * sizes["key_dim"]
            + sizes["value_heads"] * sizes["value_dim"])


def delta_matmul_params(sizes: dict) -> int:
    """A delta mixer's matrices: [q | k | v | z], [b | a], out."""
    values = sizes["value_heads"] * sizes["value_dim"]
    return sizes["d"] * (conv_channels(sizes) + values
                         + 2 * sizes["value_heads"] + values)


def attention_matmul_params(sizes: dict) -> int:
    """wq (query and gate) and wo over the heads, wk, wv over the K/V
    heads."""
    return sizes["d"] * sizes["hd"] * (3 * sizes["heads"]
                                       + 2 * sizes["kv_heads"])


def shared_ffn_params(sizes: dict) -> int:
    """What a layer's FFN reads whatever the router says: the router, the
    shared expert and its gate."""
    d = sizes["d"]
    return d * sizes["router"] + 3 * d * sizes["shared_ff"] + d


def scan_flops(sizes: dict) -> int:
    """Matmul operations of the chunkwise form, a row a delta layer,
    every value head: the chunks' own terms (K K^T, Q K^T, T's
    triangular solve, W, U) and the recurrence across them (W S, Q S, the scores
    times V', K^T V')."""
    dk, dv = sizes["key_dim"], sizes["value_dim"]
    terms = 2 * (2 * CHUNK * dk + CHUNK * CHUNK // 3 + CHUNK * (dk + dv))
    across = 2 * (3 * dk * dv + CHUNK * dv)
    return sizes["value_heads"] * (terms + across)


def prefill_flops(sizes: dict, tokens: int) -> float:
    """Forward operations of one causal prefill of `tokens` positions:
    every matmul at the true length (2 a multiply-add) with the held
    experts' expected pairs, the causal half of the attention layers'
    score and value products, the chunkwise delta rule, the head at the
    one position that is used."""
    pairs = sizes["top_k"] * sizes["held"] / sizes["router"]
    ffn = shared_ffn_params(sizes) + pairs * 3 * sizes["d"] * sizes["moe_ff"]
    matmul = 2.0 * tokens * (
        sizes["delta"] * delta_matmul_params(sizes)
        + sizes["attention"] * attention_matmul_params(sizes)
        + sizes["layers"] * ffn)
    attention = (2.0 * 2.0 * sizes["attention"] * sizes["heads"]
                 * sizes["hd"] * tokens * (tokens + 1) / 2.0)
    scan = float(tokens) * sizes["delta"] * scan_flops(sizes)
    return matmul + attention + scan + 2.0 * sizes["vocab"] * sizes["d"]


# -- what the traced window holds ---------------------------------------------

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
