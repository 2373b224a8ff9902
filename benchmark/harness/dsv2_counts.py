"""Operations and bytes DeepSeek-V2's share must spend, and the device
time of its three kernels: what the `dsv2` per-layer readers divide.

The counts are what the algorithm needs, from the configuration's own
widths: a decode step reads every weight outside the routed experts once,
each expert a token chose once (gate, up, down), and each live latent row
once a layer at its true width (kv_lora_rank + qk_rope_head_dim, not the
row padded to the lanes); latent attention multiplies 128 query heads
against that row for the score and against its latent part for the value;
a prefill's attention scores over nope + rope and carries v, the causal
half.  A share over 100 % means a count here is too high, and is a bug
here.
"""

from __future__ import annotations

import os

from . import program_spans, trace
from .programs import runs_of

DECODE_STEP = "jit_paged_decode_step"
PREFILL = "jit_paged_prefill"
# the kernels' names in the device trace (pallas_call name=...)
EXPERT_KERNEL = "moe_expert_ffn"
LATENT_KERNEL = "mla_paged_attention"
PREFILL_KERNEL = "mla_flash_attention"

_OPS: dict = {}


def shape(config: dict) -> dict:
    """The sizes from the configuration file's published keys."""
    item = {"bfloat16": 2, "float16": 2, "float32": 4}[
        config.get("torch_dtype", config.get("dtype", "bfloat16"))]
    return {
        "vocab": int(config["vocab_size"]), "d": int(config["hidden_size"]),
        "layers": int(config["num_hidden_layers"]),
        "dense_layers": int(config["first_k_dense_replace"]),
        "heads": int(config["num_attention_heads"]),
        "q_rank": int(config["q_lora_rank"]),
        "kv_rank": int(config["kv_lora_rank"]),
        "nope": int(config["qk_nope_head_dim"]),
        "rope": int(config["qk_rope_head_dim"]),
        "v": int(config["v_head_dim"]), "ff": int(config["intermediate_size"]),
        "moe_ff": int(config["moe_intermediate_size"]),
        "router": int(config.get("router_experts",
                                 config["n_routed_experts"])),
        "shared": int(config["n_shared_experts"]), "bytes": item}


def expert_layers(sizes: dict) -> int:
    return sizes["layers"] - sizes["dense_layers"]


def attention_params(sizes: dict) -> int:
    """MLA's five projections of one layer."""
    d, heads = sizes["d"], sizes["heads"]
    return (d * sizes["q_rank"]
            + sizes["q_rank"] * heads * (sizes["nope"] + sizes["rope"])
            + d * (sizes["kv_rank"] + sizes["rope"])
            + sizes["kv_rank"] * heads * (sizes["nope"] + sizes["v"])
            + heads * sizes["v"] * d)


def expert_bytes(sizes: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * sizes["d"] * sizes["moe_ff"] * sizes["bytes"]


def fixed_step_bytes(sizes: dict) -> int:
    """Weights a decode step reads whatever the router says: every
    layer's attention, the dense layers' FFN, the expert layers' shared
    experts and router, and the tied embedding as the head."""
    d = sizes["d"]
    per_expert_layer = (3 * d * sizes["shared"] * sizes["moe_ff"]
                        + d * sizes["router"])
    return (sizes["layers"] * attention_params(sizes)
            + sizes["dense_layers"] * 3 * d * sizes["ff"]
            + expert_layers(sizes) * per_expert_layer
            + sizes["vocab"] * d) * sizes["bytes"]


def latent_row_bytes(sizes: dict) -> int:
    return (sizes["kv_rank"] + sizes["rope"]) * sizes["bytes"]


def latent_attention_flops(sizes: dict) -> int:
    """One live position, one layer, one decode step: every head's score
    over the row and its weighted sum over the latent."""
    return 2 * sizes["heads"] * (2 * sizes["kv_rank"] + sizes["rope"])


def prefill_attention_flops(sizes: dict, tokens: int) -> float:
    """One causal prefill of `tokens` positions, every layer: scores over
    nope + rope, values of v, the causal half."""
    return (2.0 * sizes["layers"] * sizes["heads"]
            * (sizes["nope"] + sizes["rope"] + sizes["v"])
            * tokens * (tokens + 1) / 2.0)


# -- what the traced window holds ---------------------------------------------

def step_means(run) -> dict | None:
    """Means over the `aiko:engine.decode` spans of the traced window:
    `experts_read` and `expert_pairs` (of the newest step read back when
    the span opened) and `latent_positions` (of the step the span
    dispatched).  None under 3 spans, or where the program writes no such
    fields (the parent of the PR that added them)."""
    spans = program_spans.of_run(run)
    if spans is None:
        return None
    decodes = [span.stats for span in spans.named("engine.decode")
               if "experts_read" in span.stats
               and "latent_positions" in span.stats]
    if len(decodes) < program_spans.MIN_SAMPLES:
        return None
    return {name: sum(float(stats[name]) for stats in decodes)
            / len(decodes)
            for name in ("experts_read", "expert_pairs",
                         "latent_positions")}


def _device_ops(run) -> list | None:
    """[(start, stop, op, module, program)] of the run's profile, with
    each program's whole executions inside the traced window; loaded
    once a process."""
    if not run.trace or run.cell is None:
        return None
    path = program_spans.profile_path(run.cell.name)
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _OPS:
        profile = trace.load(path)
        planes = trace._device_planes(profile)[:1]
        runs = trace._module_runs(planes[0]) if planes else {}
        ops = trace._device_ops(planes[0], runs) if planes else []
        low, high = program_spans._window(program_spans._host_lines(profile))
        _OPS.clear()
        _OPS[key] = (ops, runs, low, high)
    return _OPS[key]


def kernel_seconds(run, module: str, kernel: str) -> tuple | None:
    """(device seconds of the operations whose name holds `kernel` inside
    whole executions of `module` within the traced window, how many such
    executions).  None where there is no profile, no whole execution,
    or no such operation."""
    loaded = _device_ops(run)
    if loaded is None:
        return None
    ops, runs, low, high = loaded
    whole = sorted((start, stop) for (name, _), executions in runs.items()
                   if name == module for start, stop in executions
                   if start > low and stop < high)
    if whole:
        inside = [op for op in ops if any(
            first <= op[0] and op[1] <= last for first, last in whole)]
        count = len(whole)
    else:
        # a CPU recording has no module line: the reduced trace has told
        # the executions apart by the gaps between operations
        inside = [op for op in ops if op[0] > low and op[1] < high]
        count = len(runs_of(run.trace, module))
    total = sum(stop - start for start, stop, name, op_module, _ in inside
                if op_module == module and kernel in name)
    return (total / 1e9, count) if total and count else None


def kernel_seconds_a_step(run, kernel: str) -> float | None:
    """Device seconds `kernel` takes in one decode step, all layers."""
    found = kernel_seconds(run, DECODE_STEP, kernel)
    return found[0] / found[1] if found else None
