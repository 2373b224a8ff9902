"""Toy size of `dsv2.longgen`, for rehearsing its driver and readers on
the CPU: DeepSeek-V2's keys at small widths (1 dense + 2 expert layers,
16 experts in 4 groups of which 8 are held, top 3 of 2 groups), float32."""

from __future__ import annotations

import dataclasses

from benchmark.harness import cells
from benchmark.tests import toy

TOY_MODEL = {
    "model_type": "deepseek_v2", "vocab_size": 512, "hidden_size": 64,
    "num_hidden_layers": 3, "num_attention_heads": 4,
    "num_key_value_heads": 4, "q_lora_rank": 48, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "n_routed_experts": 8, "router_experts": 16, "experts_held": [0, 8],
    "n_shared_experts": 2, "num_experts_per_tok": 3, "n_group": 4,
    "topk_group": 2, "routed_scaling_factor": 16,
    "first_k_dense_replace": 1, "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"type": "yarn", "factor": 40,
                     "original_max_position_embeddings": 32,
                     "beta_fast": 32, "beta_slow": 1, "mscale": 0.707,
                     "mscale_all_dim": 0.707},
    "max_position_embeddings": 4096, "torch_dtype": "float32"}

TOY_SERVE = dict(TOY_MODEL, system="dsv2_serve", serve={
    "decode_slots": 4, "kv_block_size": 8, "max_context": 128,
    "kv_blocks": 80, "warm_buckets": [32, 128],
    "gateway_policy": "max_inflight=64;queue=512"})

TOY_TRAFFIC = {
    "arrivals": {"process": "closed", "callers": 4},
    "prompt_tokens": {"dist": "uniform", "min": 24, "max": 80},
    "answer_tokens": {"dist": "uniform", "min": 9, "max": 24},
    "replay_set": 8, "warm_in_s": 0.5, "drain_s": 20.0,
    "check_requests": 2, "trace_seconds": 1.0}


def toy_cell(name: str = "dsv2.longgen") -> cells.Cell:
    cell = cells.load_cell(name)
    return dataclasses.replace(
        cell, config=TOY_SERVE, traffic=dict(cell.traffic, **TOY_TRAFFIC),
        limits=toy.TOY_LIMITS)
