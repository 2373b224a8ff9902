"""Toy size of `sala.longdoc`, for rehearsing its driver and readers on the
CPU: MiniCPM-SALA's keys at small widths (4 layers: sparse, lightning,
lightning, sparse; 4 query heads of 16 over 2 K/V heads; 4 lightning heads
of 16; blocks of 8 positions, compressed keys of 4 rows every 2, top 4
blocks, dense under 64 positions), float32."""

from __future__ import annotations

import dataclasses

from benchmark.harness import cells
from benchmark.tests import toy

TOY_MODEL = {
    "model_type": "minicpm_sala", "vocab_size": 512, "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 4,
    "mixer_types": ["minicpm4", "lightning-attn", "lightning-attn",
                    "minicpm4"],
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "lightning_nh": 4, "lightning_nkv": 4, "lightning_head_dim": 16,
    "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
    "attn_use_rope": False, "qk_norm": True, "use_output_gate": True,
    "use_output_norm": True, "attn_use_output_gate": True,
    "attention_bias": False, "hidden_act": "silu", "rope_theta": 10000,
    "rms_norm_eps": 1e-06, "scale_emb": 12, "scale_depth": 1.4,
    "mup_denominator": 32, "dim_model_base": 16, "rand_init": False,
    "max_position_embeddings": 256, "tie_word_embeddings": False,
    "torch_dtype": "float32",
    "sparse_config": {"kernel_size": 4, "kernel_stride": 2, "block_size": 8,
                      "topk": 4, "init_blocks": 1, "window_size": 16,
                      "dense_len": 64}}

TOY_SERVE = dict(TOY_MODEL, system="model_serve", reference="minicpm_sala",
                 counts="sala_counts", serve={
                     "decode_slots": 4, "kv_block_size": 8,
                     "max_context": 160, "kv_blocks": 81,
                     "warm_buckets": [64, 128],
                     "gateway_policy": "max_inflight=64;queue=512"})

# prompts on both sides of dense_len (buckets 64 and 128), answers that
# carry a 60-token prompt across it
TOY_TRAFFIC = {
    "arrivals": {"process": "closed", "callers": 4},
    "prompt_tokens": {"dist": "uniform", "min": 40, "max": 120},
    "answer_tokens": {"dist": "uniform", "min": 12, "max": 40},
    "replay_set": 8, "warm_in_s": 0.5, "drain_s": 30.0,
    "check_requests": 2, "trace_seconds": 1.0}

# the toy's float32 against the float32 reference: tests/test_minicpm_sala.py
# reads 2e-6 on logits; 10 to 1000 times under a broken mechanism
TOY_LIMITS = {"gap_max": 1e-2, "gap_mean": 1e-3}


def toy_cell(name: str = "sala.longdoc") -> cells.Cell:
    cell = cells.load_cell(name)
    assert toy.ROOT                      # benchmark importable from the root
    return dataclasses.replace(
        cell, config=TOY_SERVE, traffic=dict(cell.traffic, **TOY_TRAFFIC),
        limits=TOY_LIMITS)
