"""Toy size of `qnext.assist`, for rehearsing its driver and readers on the
CPU: Qwen3-Next's keys at small widths (8 layers in 2 periods of 3 delta
layers and 1 attention layer; 4 heads of 32 over one K/V head; 2 key heads
of 16 serving 4 value heads of 24; 16 of 64 routed experts held, top 6),
float32."""

from __future__ import annotations

import dataclasses

from benchmark.harness import cells
from benchmark.tests import toy

TOY_MODEL = {
    "model_type": "qwen3_next", "vocab_size": 512, "hidden_size": 64,
    "num_hidden_layers": 8, "num_attention_heads": 4,
    "num_key_value_heads": 1, "head_dim": 32, "intermediate_size": 128,
    "hidden_act": "silu", "full_attention_interval": 4,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 16,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_value_head_dim": 24, "moe_intermediate_size": 48,
    "shared_expert_intermediate_size": 48, "num_experts": 16,
    "router_experts": 64, "experts_held": [0, 16],
    "num_experts_per_tok": 6, "norm_topk_prob": True,
    "decoder_sparse_step": 1, "mlp_only_layers": [],
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 10000000,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "max_position_embeddings": 4096, "torch_dtype": "float32"}

TOY_SERVE = dict(TOY_MODEL, system="model_serve", reference="qwen3_next",
                 counts="qnext_counts", serve={
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

# the toy's float32 against the float32 reference, times what the model's
# normed delta heads do to a rounding (tests/test_qwen3_next.py): 10 times
# toy.TOY_LIMITS, still 10 to 1000 times under a broken mechanism
TOY_LIMITS = {"gap_max": 1e-2, "gap_mean": 1e-3}


def toy_cell(name: str = "qnext.assist") -> cells.Cell:
    cell = cells.load_cell(name)
    assert toy.ROOT                      # benchmark importable from the root
    return dataclasses.replace(
        cell, config=TOY_SERVE, traffic=dict(cell.traffic, **TOY_TRAFFIC),
        limits=TOY_LIMITS)
