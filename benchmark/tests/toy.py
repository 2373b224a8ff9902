"""Toy sizes of the four cells, for rehearsing the harness on the CPU."""

from __future__ import annotations

import dataclasses
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import cells  # noqa: E402

TOY_LM = {
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
    "vocab_size": 512, "rope_theta": 10000.0, "torch_dtype": "float32",
    "dtype": "float32", "max_seq_len": 256,
    "mode": "closed_batch_greedy", "max_new_tokens": 4}

TOY_SERVE = dict(TOY_LM, system="lm_serve", serve={
    "decode_slots": 4, "kv_block_size": 8, "max_context": 128,
    "kv_blocks": 64, "gateway_policy": "max_inflight=64;queue=512"})

TOY_GRAPH = {
    "system": "graph", "clip_seconds": 0.5,
    "asr": {"d_model": 32, "encoder_layers": 1, "decoder_layers": 1,
            "encoder_attention_heads": 2, "vocab_size": 512,
            "num_mel_bins": 80, "max_source_positions": 64,
            "transcript_tokens": 4, "dtype": "float32"},
    "lm": TOY_LM,
    "detector": {"n_classes": 4, "base_channels": 8, "image_size": 32,
                 "dtype": "float32"},
    "graph": {"rows_per_frame": 2, "micro_batch": 2,
              "micro_batch_wait_ms": 0}}

TOY_TRAFFIC = {
    "chat": {"arrivals": {"process": "poisson", "rate_per_s": 6.0},
             "prompt_tokens": {"dist": "lognormal", "median": 16,
                               "sigma": 0.5, "min": 4, "max": 40},
             "answer_tokens": {"dist": "lognormal", "median": 12,
                               "sigma": 0.5, "min": 9, "max": 20},
             "warm_in_s": 0.5, "drain_s": 20.0, "check_requests": 3,
             "trace_seconds": 1.0},
    "longprompt": {"arrivals": {"process": "closed", "callers": 2},
                   "prompt_tokens": {"dist": "uniform", "min": 40,
                                     "max": 100},
                   "answer_tokens": {"dist": "fixed", "value": 9},
                   "replay_set": 8, "warm_in_s": 0.0, "drain_s": 20.0,
                   "check_requests": 2, "trace_seconds": 1.0},
    "window": {"loop": "closed", "streams": 1, "frames_in_flight": 4,
               "tone_hz": {"low": 200.0, "high": 2000.0},
               "warm_frames": 4, "check_frames": 2, "trace_seconds": 1.0},
    "streams": {"loop": "open", "streams": 3, "period_s": 0.6,
                "jitter": 0.1, "tone_hz": {"low": 200.0, "high": 2000.0},
                "warm_in_s": 0.5, "drain_s": 20.0, "check_frames": 2,
                "trace_seconds": 1.0}}


# float32 toys agree with the float32 reference on every token
TOY_LIMITS = {"gap_max": 1e-3, "gap_mean": 1e-4}


def toy_cell(name: str) -> cells.Cell:
    """Cell `name` of BENCHMARK.json with toy sizes in place of the
    configuration's and the mix's."""
    cell = cells.load_cell(name)
    config = TOY_GRAPH if cell.config["system"] == "graph" else TOY_SERVE
    mix = dict(cell.traffic, **TOY_TRAFFIC[cell.traffic["name"]])
    return dataclasses.replace(cell, config=config, traffic=mix,
                               limits=TOY_LIMITS)
