"""Toy size of `ouro.reason`, for rehearsing its driver and readers on the
CPU: Ouro's keys at small widths (2 layers x 3 passes = 6 caches, 4 heads
of 16), float32."""

from __future__ import annotations

import dataclasses

from benchmark.harness import cells
from benchmark.tests import toy

TOY_MODEL = {
    "model_type": "ouro", "vocab_size": 512, "hidden_size": 64,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 16, "intermediate_size": 128,
    "hidden_act": "silu", "layer_types": ["full_attention"] * 2,
    "total_ut_steps": 3, "early_exit_threshold": 1, "rms_norm_eps": 1e-06,
    "rope_theta": 1000000, "rope_scaling": None, "sliding_window": None,
    "use_sliding_window": False, "tie_word_embeddings": False,
    "max_position_embeddings": 4096, "torch_dtype": "float32"}

TOY_SERVE = dict(TOY_MODEL, system="model_serve", reference="ouro",
                 counts="ouro_counts", serve={
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


def toy_cell(name: str = "ouro.reason") -> cells.Cell:
    cell = cells.load_cell(name)
    return dataclasses.replace(
        cell, config=TOY_SERVE, traffic=dict(cell.traffic, **TOY_TRAFFIC),
        limits=toy.TOY_LIMITS)
