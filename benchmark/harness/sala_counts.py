"""Operations and bytes one pipeline stage of MiniCPM-SALA must spend: what
the `sala` per-layer readers divide.

The counts are what the algorithm needs, from the configuration's own
widths, whatever implements it.

A prefill is every matmul at the true length (2 a multiply-add): the
mixers' projections and the MLPs; the lightning rule in its chunkwise form
(`scan_flops`: inside a chunk of C rows Q K^T and (Q K^T * D) V, across the
chunks K^T V, Q S); of a sparse layer the causal half of the scores and
values of the rows under dense_len, and from dense_len on, a row, its
scores against the compressed keys defined for it and its scores and
values over topk blocks (the SAME count whether the program gathers the
blocks or masks a blockwise pass); and the head at one position.  The
selection's top-k and the softmaxes run on the vector unit, which has no
published peak, and are not in it.  A share over 100 % means a count here
is too high, and is a bug here.

A decode step reads the weights once (the embedding's 16 rows are not
counted, the untied head whole), reads and writes the decoding slots' S
once (`state_bytes` of the program's `aiko:engine.decode` spans), reads
the chosen blocks' K/V rows once (`sparse_blocks_read` of the same spans,
a block 2 x block x head size values a K/V head a layer) and the
compressed keys it scores once (`compressed_rows`).
"""

from __future__ import annotations

from . import program_spans
from .dsv2_counts import (  # noqa: F401  (the readers take them from here)
    DECODE_STEP, PREFILL, kernel_seconds, kernel_seconds_a_step)

CHUNK = 128     # rows of a chunk of the chunkwise form (parallel/lightning.py)
SPARSE = {"kernel_size": 32, "kernel_stride": 16, "block_size": 64,
          "topk": 64, "init_blocks": 1, "window_size": 2048,
          "dense_len": 8192}
# the kernels' names in the device trace (pallas_call name=); the program's
# jax scopes (`sparse_select`, `sparse_attention`, `lightning_chunk_scan`)
# are in the compiled program's metadata, but a device event carries its
# instruction's name and three timing stats and nothing else, so XLA's own
# operations under them cannot be told apart here
STEP = "lightning_step"
PAGED_KERNEL = "paged_attention"


def shape(config: dict) -> dict:
    """The sizes from the configuration file's published keys."""
    item = {"bfloat16": 2, "float16": 2, "float32": 4}[
        config.get("torch_dtype", config.get("dtype", "bfloat16"))]
    mixers = list(config["mixer_types"])
    sparse = {**SPARSE, **(config.get("sparse_config") or {})}
    return {
        "vocab": int(config["vocab_size"]), "d": int(config["hidden_size"]),
        "layers": len(mixers), "sparse": mixers.count("minicpm4"),
        "lightning": mixers.count("lightning-attn"),
        "heads": int(config["num_attention_heads"]),
        "kv_heads": int(config["num_key_value_heads"]),
        "hd": int(config["head_dim"]), "ff": int(config["intermediate_size"]),
        "l_heads": int(config["lightning_nh"]),
        "l_hd": int(config["lightning_head_dim"]),
        "kernel": int(sparse["kernel_size"]),
        "stride": int(sparse["kernel_stride"]),
        "block": int(sparse["block_size"]), "topk": int(sparse["topk"]),
        "dense_len": int(sparse["dense_len"]), "bytes": item}


def mlp_params(sizes: dict) -> int:
    return 3 * sizes["d"] * sizes["ff"]


def lightning_matmul_params(sizes: dict) -> int:
    """A lightning layer's matrices: [q | k | v | g], out, the MLP."""
    inner = sizes["l_heads"] * sizes["l_hd"]
    return sizes["d"] * 5 * inner + mlp_params(sizes)


def sparse_matmul_params(sizes: dict) -> int:
    """A sparse layer's matrices: wq (query and gate) and wo over the
    heads, wk, wv over the K/V heads, the MLP."""
    return (sizes["d"] * sizes["hd"] * (3 * sizes["heads"]
                                        + 2 * sizes["kv_heads"])
            + mlp_params(sizes))


def weight_bytes(sizes: dict) -> int:
    """Every weight a step reads: the layers' matrices and gains, the final
    norm and the untied head (the embedding is gathered a row a slot)."""
    lightning = lightning_matmul_params(sizes) + 2 * sizes["d"] \
        + 3 * sizes["l_hd"]
    sparse = sparse_matmul_params(sizes) + 2 * sizes["d"] + 2 * sizes["hd"]
    return sizes["bytes"] * (
        sizes["lightning"] * lightning + sizes["sparse"] * sparse
        + sizes["d"] + sizes["vocab"] * sizes["d"])


def parameters(sizes: dict) -> int:
    """Every parameter held: weight_bytes' and the embedding."""
    return weight_bytes(sizes) // sizes["bytes"] \
        + sizes["vocab"] * sizes["d"]


def scan_flops(sizes: dict) -> int:
    """Matmul operations of the chunkwise lightning rule, a row a layer,
    every head: Q K^T and the weighted scores times V inside a chunk (2 x
    2 C d), K^T V and Q S across them (2 x 2 d d)."""
    d = sizes["l_hd"]
    return sizes["l_heads"] * (2 * 2 * CHUNK * d + 2 * 2 * d * d)


def sparse_row_flops(sizes: dict, position: int) -> float:
    """Matmul operations of a sparse layer's attention for the row at
    `position`, every head: under dense_len scores and values over the
    position + 1 rows it sees; from it on the scores against the defined
    compressed keys, and scores and values over topk blocks."""
    per_key = 2.0 * sizes["heads"] * sizes["hd"]
    if position < sizes["dense_len"]:
        return 2.0 * per_key * (position + 1)
    defined = max((position - sizes["kernel"] + 1) // sizes["stride"] + 1, 0)
    return per_key * defined + 2.0 * per_key * sizes["topk"] * sizes["block"]


def sparse_prefill_flops(sizes: dict, tokens: int) -> float:
    """sparse_row_flops summed over the rows 0 .. tokens - 1 of a layer."""
    dense = min(tokens, sizes["dense_len"])
    per_key = 2.0 * sizes["heads"] * sizes["hd"]
    total = 2.0 * per_key * dense * (dense + 1) / 2.0
    if tokens > dense:
        rows = tokens - dense
        # the defined compressed keys grow by one every `stride` rows
        first = (dense - sizes["kernel"] + 1) / sizes["stride"] + 1
        last = (tokens - 1 - sizes["kernel"] + 1) / sizes["stride"] + 1
        total += per_key * rows * (first + last) / 2.0
        total += 2.0 * per_key * sizes["topk"] * sizes["block"] * rows
    return total


def prefill_flops(sizes: dict, tokens: int) -> float:
    """Forward operations of one causal prefill of `tokens` positions."""
    matmul = 2.0 * tokens * (
        sizes["lightning"] * lightning_matmul_params(sizes)
        + sizes["sparse"] * sparse_matmul_params(sizes))
    scan = float(tokens) * sizes["lightning"] * scan_flops(sizes)
    attention = sizes["sparse"] * sparse_prefill_flops(sizes, tokens)
    return matmul + scan + attention + 2.0 * sizes["vocab"] * sizes["d"]


def block_bytes(sizes: dict) -> int:
    """One block's keys and values of one K/V head of one layer."""
    return 2 * sizes["block"] * sizes["hd"] * sizes["bytes"]


def compressed_row_bytes(sizes: dict) -> int:
    return sizes["hd"] * sizes["bytes"]


def attention_step_bytes(sizes: dict, blocks_read: float,
                         compressed_rows: float) -> float:
    """What a step's selection and attention must read: the chosen blocks'
    K/V and the compressed keys scored (both counted a K/V head a layer a
    slot by the program)."""
    return (blocks_read * block_bytes(sizes)
            + compressed_rows * compressed_row_bytes(sizes))


def step_bytes(sizes: dict, state_bytes: float, blocks_read: float,
               compressed_rows: float) -> float:
    """One decode step: the weights once, the decoding slots' S read and
    written, the chosen K/V blocks and the compressed keys once."""
    return (weight_bytes(sizes) + state_bytes
            + attention_step_bytes(sizes, blocks_read, compressed_rows))


# -- what the traced window holds ---------------------------------------------

STEP_FIELDS = ("state_bytes", "state_slots", "sparse_blocks_read",
               "sparse_blocks_live", "compressed_rows")


def step_means(run) -> dict | None:
    """Means of STEP_FIELDS over the `aiko:engine.decode` spans of the
    traced window.  None under 3 spans, or where the program writes no
    such fields (the parent of the PR that added them)."""
    spans = program_spans.of_run(run)
    if spans is None:
        return None
    decodes = [span.stats for span in spans.named("engine.decode")
               if all(name in span.stats for name in STEP_FIELDS)]
    if len(decodes) < program_spans.MIN_SAMPLES:
        return None
    return {name: sum(float(stats[name]) for stats in decodes)
            / len(decodes) for name in STEP_FIELDS}


def prefills(run) -> list | None:
    """[(true_len, select_rows, scan)] of the traced window's whole
    prefills, from their `aiko:engine.prefill` spans; None where there is
    none that carries the selection's and the scan's fields."""
    spans = program_spans.of_run(run)
    if spans is None:
        return None
    found = [(int(span.stats["true_len"]), int(span.stats["select_rows"]),
              str(span.stats["scan"]))
             for span in spans.named("engine.prefill")
             if "select_rows" in span.stats and "scan" in span.stats
             and "true_len" in span.stats]
    return found or None
