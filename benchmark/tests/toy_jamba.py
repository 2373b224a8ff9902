"""Toy size of `jamba.think`, for rehearsing its driver and readers on the
CPU: Jamba's keys at small widths (8 layers in 2 periods of 4, the
attention layer at offset 1; 4 heads of 16 over one K/V head; d_inner 128,
d_state 16), float32."""

from __future__ import annotations

import dataclasses

from benchmark.harness import cells
from benchmark.tests import toy

TOY_MODEL = {
    "model_type": "jamba", "vocab_size": 512, "hidden_size": 64,
    "num_hidden_layers": 8, "num_attention_heads": 4,
    "num_key_value_heads": 1, "intermediate_size": 128,
    "hidden_act": "silu", "attn_layer_period": 4, "attn_layer_offset": 1,
    "expert_layer_period": 2, "expert_layer_offset": 1, "num_experts": 1,
    "num_experts_per_tok": 1, "mamba_expand": 2, "mamba_d_state": 16,
    "mamba_d_conv": 4, "mamba_dt_rank": 8, "mamba_conv_bias": True,
    "mamba_proj_bias": False, "rms_norm_eps": 1e-06,
    "sliding_window": None, "tie_word_embeddings": True,
    "max_position_embeddings": 4096, "torch_dtype": "float32"}

TOY_SERVE = dict(TOY_MODEL, system="model_serve", reference="jamba",
                 counts="jamba_counts", serve={
                     "decode_slots": 4, "kv_block_size": 8,
                     "max_context": 128, "kv_blocks": 65,
                     "warm_buckets": [32, 64],
                     "gateway_policy": "max_inflight=64;queue=512"})

TOY_TRAFFIC = {
    "arrivals": {"process": "closed", "callers": 4},
    "prompt_tokens": {"dist": "uniform", "min": 20, "max": 60},
    "answer_tokens": {"dist": "uniform", "min": 9, "max": 24},
    "replay_set": 8, "warm_in_s": 0.5, "drain_s": 20.0,
    "check_requests": 2, "trace_seconds": 1.0}


def toy_cell(name: str = "jamba.think") -> cells.Cell:
    cell = cells.load_cell(name)
    return dataclasses.replace(
        cell, config=TOY_SERVE, traffic=dict(cell.traffic, **TOY_TRAFFIC),
        limits=toy.TOY_LIMITS)
