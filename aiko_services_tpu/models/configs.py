# Reference-scale model configurations.
#
# The reference's flagship workloads and their scales (BASELINE.md):
#   - Llama-3-8B chat (reference elements_llm.py:137-179 via Ollama)
#   - Whisper tiny..large speech-to-text ladder, 39M..1550M params
#     (reference speech_elements.py:186-192)
#   - YOLOv8 detection (reference yolo.py:51-87)
# These presets instantiate this framework's models at those shapes so the
# same capability runs in-framework, sharded over the mesh, with weights
# ingested through models/weights.py.

from __future__ import annotations

from .asr import AsrConfig
from .detector import DetectorConfig
from .transformer import TransformerConfig

__all__ = [
    "LLAMA3_8B", "LLAMA32_1B", "LM_TOY",
    "WHISPER_TINY", "WHISPER_SMALL",
    "YOLOV8N_SHAPE", "DETECTOR_TOY", "deepseek_v2_config", "ouro_config",
    "jamba_config", "qwen3_next_config", "minicpm_sala_config",
    "PUBLISHED_READERS",
    "transformer_flops_per_token", "asr_flops_per_example",
    "tts_flops_per_example",
    "detector_flops_per_image",
]

# Llama-3-8B architecture (BASELINE config 4: v5e-4, streamed tokens)
LLAMA3_8B = TransformerConfig(
    vocab_size=128256, d_model=4096, n_layers=32, n_heads=32,
    n_kv_heads=8, d_ff=14336, max_seq_len=8192, rope_theta=500000.0,
    dtype="bfloat16")

# Llama-3.2-1B architecture: the largest Llama that decodes comfortably on
# one v5e chip alongside its KV cache (tied embeddings)
LLAMA32_1B = TransformerConfig(
    vocab_size=128256, d_model=2048, n_layers=16, n_heads=32,
    n_kv_heads=8, d_ff=8192, max_seq_len=8192, rope_theta=500000.0,
    dtype="bfloat16")

def deepseek_v2_config(published: dict, max_seq_len: int | None = None,
                       dtype: str | None = None) -> TransformerConfig:
    """TransformerConfig from DeepSeek-V2's published config.json keys
    (huggingface.co/deepseek-ai/DeepSeek-V2), every one under its own
    name.  Two keys beside the published ones describe one process's
    share of an expert-parallel deployment: `router_experts`, the
    router's width where `n_routed_experts` is what is held, and
    `experts_held`, [lo, hi) of the router's numbering."""
    unsupported = {
        "model_type": "deepseek_v2", "hidden_act": "silu",
        "scoring_func": "softmax", "topk_method": "group_limited_greedy",
        "norm_topk_prob": False, "moe_layer_freq": 1,
        "attention_bias": False}
    for key, value in unsupported.items():
        if published.get(key, value) != value:
            raise ValueError(f"deepseek_v2: {key}={published[key]!r} is "
                             f"not implemented (only {value!r})")
    router = int(published.get("router_experts",
                               published["n_routed_experts"]))
    held = tuple(int(edge) for edge in
                 published.get("experts_held", (0, router)))
    if held[1] - held[0] != int(published["n_routed_experts"]):
        raise ValueError(
            f"deepseek_v2: experts_held {held} is not the "
            f"{published['n_routed_experts']} experts n_routed_experts "
            f"says are held")
    scaling = published.get("rope_scaling") or {}
    if scaling and scaling.get("type") != "yarn":
        raise ValueError(f"deepseek_v2: rope_scaling type "
                         f"{scaling.get('type')!r} is not implemented")
    return TransformerConfig(
        vocab_size=int(published["vocab_size"]),
        d_model=int(published["hidden_size"]),
        n_layers=int(published["num_hidden_layers"]),
        n_heads=int(published["num_attention_heads"]),
        n_kv_heads=int(published["num_attention_heads"]),
        d_ff=int(published["intermediate_size"]),
        max_seq_len=int(max_seq_len
                        or published["max_position_embeddings"]),
        rope_theta=float(published["rope_theta"]),
        norm_eps=float(published["rms_norm_eps"]),
        dtype=str(dtype or published.get("torch_dtype", "bfloat16")),
        q_lora_rank=int(published["q_lora_rank"]),
        kv_lora_rank=int(published["kv_lora_rank"]),
        qk_nope_head_dim=int(published["qk_nope_head_dim"]),
        qk_rope_head_dim=int(published["qk_rope_head_dim"]),
        v_head_dim=int(published["v_head_dim"]),
        rope_factor=float(scaling.get("factor", 1.0)),
        rope_original_max=int(scaling.get(
            "original_max_position_embeddings", 4096)),
        rope_beta_fast=float(scaling.get("beta_fast", 32.0)),
        rope_beta_slow=float(scaling.get("beta_slow", 1.0)),
        rope_mscale=float(scaling.get("mscale", 1.0)),
        rope_mscale_all_dim=float(scaling.get("mscale_all_dim", 0.0)),
        top_k=int(published["num_experts_per_tok"]),
        n_routed_experts=router, experts_held=held,
        n_shared_experts=int(published["n_shared_experts"]),
        moe_d_ff=int(published["moe_intermediate_size"]),
        n_groups=int(published["n_group"]),
        topk_groups=int(published["topk_group"]),
        routed_scaling=float(published["routed_scaling_factor"]),
        first_dense_layers=int(published["first_k_dense_replace"]))


def ouro_config(published: dict, max_seq_len: int | None = None,
                dtype: str | None = None) -> TransformerConfig:
    """TransformerConfig from Ouro's published config.json keys
    (huggingface.co/ByteDance/Ouro-2.6B), every one under its own name:
    a dense decoder whose stack runs `total_ut_steps` times a token,
    K/V of its own for every pass, sublayer outputs normed, and an exit
    gate held to `early_exit_threshold`.  Keys whose mechanism is not
    implemented are refused by name."""
    unsupported = {
        "model_type": "ouro", "hidden_act": "silu", "sliding_window": None,
        "rope_scaling": None, "use_sliding_window": False}
    for key, value in unsupported.items():
        if published.get(key, value) != value:
            raise ValueError(f"ouro: {key}={published[key]!r} is not "
                             f"implemented (only {value!r})")
    layers = int(published["num_hidden_layers"])
    kinds = published.get("layer_types") or ["full_attention"] * layers
    if len(kinds) != layers or set(kinds) != {"full_attention"}:
        raise ValueError(
            f"ouro: layer_types must be {layers} x 'full_attention', got "
            f"{len(kinds)} of {sorted(set(kinds))}")
    heads = int(published["num_attention_heads"])
    d_model = int(published["hidden_size"])
    if int(published.get("head_dim", d_model // heads)) * heads != d_model:
        raise ValueError(
            f"ouro: head_dim={published['head_dim']} is not hidden_size "
            f"{d_model} / {heads} heads (the only head size implemented)")
    return TransformerConfig(
        vocab_size=int(published["vocab_size"]), d_model=d_model,
        n_layers=layers, n_heads=heads,
        n_kv_heads=int(published["num_key_value_heads"]),
        d_ff=int(published["intermediate_size"]),
        max_seq_len=int(max_seq_len
                        or published["max_position_embeddings"]),
        rope_theta=float(published["rope_theta"]),
        norm_eps=float(published["rms_norm_eps"]),
        dtype=str(dtype or published.get("torch_dtype", "bfloat16")),
        ut_steps=int(published["total_ut_steps"]),
        exit_threshold=float(published["early_exit_threshold"]),
        sandwich_norm=True)


def jamba_config(published: dict, max_seq_len: int | None = None,
                 dtype: str | None = None) -> TransformerConfig:
    """TransformerConfig from Jamba's published config.json keys
    (huggingface.co/ai21labs/AI21-Jamba2-3B), every one under its own
    name: Mamba layers, with an attention layer where the layer's index
    is `attn_layer_offset` modulo `attn_layer_period`; attention without
    positional encoding; one dense gated MLP a layer (`num_experts` 1).
    Keys whose mechanism is not implemented are refused by name."""
    unsupported = {
        "model_type": "jamba", "hidden_act": "silu", "sliding_window": None,
        "num_experts": 1, "mamba_conv_bias": True, "mamba_proj_bias": False,
        "tie_word_embeddings": True}
    for key, value in unsupported.items():
        if published.get(key, value) != value:
            raise ValueError(f"jamba: {key}={published[key]!r} is not "
                             f"implemented (only {value!r})")
    layers = int(published["num_hidden_layers"])
    period = int(published["attn_layer_period"])
    offset = int(published["attn_layer_offset"])
    d_model = int(published["hidden_size"])
    rank = published.get("mamba_dt_rank", "auto")
    return TransformerConfig(
        vocab_size=int(published["vocab_size"]), d_model=d_model,
        n_layers=layers, n_heads=int(published["num_attention_heads"]),
        n_kv_heads=int(published["num_key_value_heads"]),
        d_ff=int(published["intermediate_size"]),
        max_seq_len=int(max_seq_len
                        or published["max_position_embeddings"]),
        norm_eps=float(published["rms_norm_eps"]),
        dtype=str(dtype or published.get("torch_dtype", "bfloat16")),
        layer_kinds=tuple("attention" if index % period == offset
                          else "mamba" for index in range(layers)),
        ssm_d_inner=int(published["mamba_expand"]) * d_model,
        ssm_d_state=int(published["mamba_d_state"]),
        ssm_d_conv=int(published["mamba_d_conv"]),
        ssm_dt_rank=(-(-d_model // 16) if rank == "auto" else int(rank)),
        rotary=False)


def qwen3_next_config(published: dict, max_seq_len: int | None = None,
                      dtype: str | None = None) -> TransformerConfig:
    """TransformerConfig from Qwen3-Next's published config.json keys
    (huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct), every one under
    its own name: Gated DeltaNet layers (`linear_*`), with a gated
    softmax-attention layer of `head_dim` every `full_attention_interval`
    layers (rotary over `partial_rotary_factor` of a head, q and k normed
    a head); every layer's FFN `num_experts_per_tok` of the routed
    experts, their weights renormalised, plus one shared expert behind a
    gate.  `router_experts` and `experts_held` describe one process's
    share, as deepseek_v2_config has them, `num_experts` being what is
    held.  The head is tied to the embedding whatever
    `tie_word_embeddings` says (seeded weights; the configuration file's
    `assumed`).  Keys whose mechanism is not implemented are refused by
    name."""
    unsupported = {
        "model_type": "qwen3_next", "hidden_act": "silu",
        "sliding_window": None, "use_sliding_window": False,
        "mlp_only_layers": [], "decoder_sparse_step": 1,
        "rope_scaling": None, "norm_topk_prob": True,
        "attention_bias": False}
    for key, value in unsupported.items():
        if published.get(key, value) != value:
            raise ValueError(f"qwen3_next: {key}={published[key]!r} is not "
                             f"implemented (only {value!r})")
    router = int(published.get("router_experts", published["num_experts"]))
    held = tuple(int(edge) for edge in
                 published.get("experts_held", (0, router)))
    if held[1] - held[0] != int(published["num_experts"]):
        raise ValueError(
            f"qwen3_next: experts_held {held} is not the "
            f"{published['num_experts']} experts num_experts says are held")
    expert_width = int(published["moe_intermediate_size"])
    shared, odd = divmod(int(published["shared_expert_intermediate_size"]),
                         expert_width)
    if odd or shared < 1:
        raise ValueError(
            f"qwen3_next: shared_expert_intermediate_size="
            f"{published['shared_expert_intermediate_size']} is not a "
            f"multiple of moe_intermediate_size {expert_width} (the only "
            f"shared width implemented)")
    layers = int(published["num_hidden_layers"])
    interval = int(published["full_attention_interval"])
    return TransformerConfig(
        vocab_size=int(published["vocab_size"]),
        d_model=int(published["hidden_size"]), n_layers=layers,
        n_heads=int(published["num_attention_heads"]),
        n_kv_heads=int(published["num_key_value_heads"]),
        d_ff=int(published["intermediate_size"]),
        max_seq_len=int(max_seq_len
                        or published["max_position_embeddings"]),
        rope_theta=float(published["rope_theta"]),
        norm_eps=float(published["rms_norm_eps"]),
        dtype=str(dtype or published.get("torch_dtype", "bfloat16")),
        top_k=int(published["num_experts_per_tok"]),
        n_routed_experts=router, experts_held=held,
        n_shared_experts=shared, moe_d_ff=expert_width,
        norm_topk=True, shared_expert_gate=True,
        layer_kinds=tuple("attention" if (index + 1) % interval == 0
                          else "delta" for index in range(layers)),
        attn_head_dim=int(published["head_dim"]),
        rotary_fraction=float(published["partial_rotary_factor"]),
        qk_norm=True, gated_attention=True,
        delta_key_heads=int(published["linear_num_key_heads"]),
        delta_value_heads=int(published["linear_num_value_heads"]),
        delta_key_dim=int(published["linear_key_head_dim"]),
        delta_value_dim=int(published["linear_value_head_dim"]),
        delta_conv=int(published["linear_conv_kernel_dim"]))


# InfLLM-v2's sizes as the MiniCPM4 family publishes them (`sparse_config`
# of openbmb/MiniCPM4-8B's config.json); MiniCPM-SALA's config.json as the
# catalog has it names none, so a file may carry its own `sparse_config`
_MINICPM_SPARSE = {"kernel_size": 32, "kernel_stride": 16, "block_size": 64,
                   "topk": 64, "init_blocks": 1, "window_size": 2048,
                   "dense_len": 8192}


def minicpm_sala_config(published: dict, max_seq_len: int | None = None,
                        dtype: str | None = None) -> TransformerConfig:
    """TransformerConfig from openbmb/MiniCPM-SALA's config.json keys
    (`model_type` minicpm_sala), or a cut of it: `mixer_types` names each
    layer "minicpm4" (attention without positional encoding that selects
    its blocks, InfLLM-v2; q and k normed a head; the output gated) or
    "lightning-attn" (lightning attention with rotary q and k, an output
    norm and an output gate); muP's scale_emb on the embedding, scale_depth
    / sqrt(mup_denominator) on every residual branch (the published depth,
    whatever the cut: `mup_denominator`, else num_hidden_layers), hidden_size
    / dim_model_base under the logits; an untied head.  Anything the
    program does not implement raises by name."""
    unsupported = {
        "model_type": "minicpm_sala", "hidden_act": "silu",
        "attention_bias": False, "attn_use_rope": False, "qk_norm": True,
        "lightning_use_rope": True, "use_output_gate": True,
        "use_output_norm": True, "attn_use_output_gate": True,
        "tie_word_embeddings": False, "lightning_scale": "1/sqrt(d)",
        "rope_scaling": None}
    for key, value in unsupported.items():
        if published.get(key, value) != value:
            raise ValueError(f"minicpm_sala: {key}={published[key]!r} is "
                             f"not implemented (only {value!r})")
    kinds = {"minicpm4": "attention", "lightning-attn": "lightning"}
    mixers = list(published["mixer_types"])
    layers = int(published["num_hidden_layers"])
    if len(mixers) != layers or set(mixers) - set(kinds):
        raise ValueError(
            f"minicpm_sala: mixer_types must name {layers} layers of "
            f"{sorted(kinds)}, got {len(mixers)} of {sorted(set(mixers))}")
    heads, hd = int(published["lightning_nh"]), int(
        published["lightning_head_dim"])
    if int(published.get("lightning_nkv", heads)) != heads:
        raise ValueError(
            f"minicpm_sala: lightning_nkv={published['lightning_nkv']} is "
            f"not implemented (only lightning_nh, {heads}: a head its own "
            f"key and value)")
    sparse = {**_MINICPM_SPARSE, **(published.get("sparse_config") or {})}
    block = int(sparse["block_size"])
    hidden = int(published["hidden_size"])
    return TransformerConfig(
        vocab_size=int(published["vocab_size"]), d_model=hidden,
        n_layers=layers, n_heads=int(published["num_attention_heads"]),
        n_kv_heads=int(published["num_key_value_heads"]),
        d_ff=int(published["intermediate_size"]),
        max_seq_len=int(max_seq_len
                        or published["max_position_embeddings"]),
        rope_theta=float(published["rope_theta"]),
        norm_eps=float(published["rms_norm_eps"]),
        dtype=str(dtype or published.get("torch_dtype", "bfloat16")),
        layer_kinds=tuple(kinds[mixer] for mixer in mixers),
        attn_head_dim=int(published["head_dim"]), rotary=False,
        qk_norm=True, gated_attention=True,
        lightning_heads=heads, lightning_head_dim=hd,
        sparse_topk=int(sparse["topk"]), sparse_block=block,
        sparse_kernel=int(sparse["kernel_size"]),
        sparse_stride=int(sparse["kernel_stride"]),
        sparse_init=int(sparse["init_blocks"]),
        sparse_local=int(sparse["window_size"]) // block,
        sparse_dense_len=int(sparse["dense_len"]),
        embed_scale=float(published["scale_emb"]),
        residual_scale=float(published["scale_depth"]) / float(
            published.get("mup_denominator", layers)) ** 0.5,
        logit_divisor=hidden / float(published["dim_model_base"]),
        untied_head=True)


# model_type of a published config.json -> its reader (elements/ml.py
# hands LMGenerate's `model` parameter to it whole)
PUBLISHED_READERS = {"deepseek_v2": deepseek_v2_config, "ouro": ouro_config,
                     "jamba": jamba_config, "qwen3_next": qwen3_next_config,
                     "minicpm_sala": minicpm_sala_config}


# small config for hermetic tests / CPU runs
LM_TOY = TransformerConfig(
    vocab_size=4096, d_model=256, n_layers=4, n_heads=8, n_kv_heads=4,
    d_ff=768, max_seq_len=512, dtype="float32")

# Whisper ladder shapes (reference speech_elements.py:186-192:
# tiny 39M 32x ... small 244M 6x); multilingual vocab 51865.  Special
# token ids keep the AsrConfig defaults (sot 1 / eot 2) so natively
# trained checkpoints decode unchanged; SpeechToText switches to the real
# HF ids (50258/50257) only when an HF checkpoint is ingested.
WHISPER_TINY = AsrConfig(
    n_mels=80, d_model=384, enc_layers=4, dec_layers=4, n_heads=6,
    vocab_size=51865, max_frames=1500, max_text_len=448, dtype="bfloat16")

WHISPER_SMALL = AsrConfig(
    n_mels=80, d_model=768, enc_layers=12, dec_layers=12, n_heads=12,
    vocab_size=51865, max_frames=1500, max_text_len=448, dtype="bfloat16")

# YOLOv8-n operating shape: 640x640 input, 80 classes (reference
# yolo.py:51-87 runs YOLOv8 on webcam frames)
YOLOV8N_SHAPE = DetectorConfig(
    n_classes=80, base_channels=16, image_size=640, stride=16,
    max_detections=300, score_threshold=0.25, dtype="bfloat16")

DETECTOR_TOY = DetectorConfig(
    n_classes=16, base_channels=8, image_size=64, max_detections=8,
    dtype="float32")


# -- analytic FLOP models (for MFU reporting in bench.py) -------------------

def transformer_flops_per_token(config: TransformerConfig,
                                seq_len: int | None = None) -> float:
    """Forward FLOPs per token: 2*params for the matmuls plus the
    attention score/value terms (2 * 2 * L * d per token when seq_len is
    given -- the quadratic part)."""
    d, ff = config.d_model, config.d_ff
    hd = config.head_dim
    attn_proj = 2 * d * (config.n_heads * hd          # wq
                         + 2 * config.n_kv_heads * hd  # wk, wv
                         + config.n_heads * hd)        # wo
    mlp = 2 * d * ff * 3                               # gate, up, down
    per_layer = attn_proj + mlp
    if seq_len:
        per_layer += 2 * 2 * seq_len * d               # qk^T and att@v
    head = 2 * d * config.vocab_size                   # logits
    return config.n_layers * per_layer + head


def asr_flops_per_example(config: AsrConfig, n_frames: int,
                          n_tokens: int) -> float:
    """Encoder over n_frames mel positions + decoder over n_tokens with
    cross-attention; 2*weight-size per matmul, plus attention terms."""
    d = config.d_model
    attn = 8 * d * d
    mlp = 2 * d * (4 * d) * 2
    enc_layer = (attn + mlp) * n_frames + 4 * n_frames * n_frames * d
    dec_layer = ((2 * attn + mlp) * n_tokens
                 + 4 * n_tokens * n_tokens * d
                 + 4 * n_tokens * n_frames * d)
    head = 2 * d * config.vocab_size * n_tokens
    return (config.enc_layers * enc_layer
            + config.dec_layers * dec_layer + head)


def tts_flops_per_example(config, n_chars: int) -> float:
    """chars -> waveform FLOPs: conv stack over upsampled frames + mel
    head + Griffin-Lim's per-iteration STFT/ISTFT pair as DFT matmuls
    (tts.py synthesize)."""
    frames = n_chars * config.frames_per_char
    d = config.d_model
    conv = config.n_conv_layers * 2 * config.kernel_size * d * d * frames
    mel_head = 2 * d * config.n_mels * frames
    bins = config.n_fft // 2 + 1
    griffin = (config.griffin_lim_iters
               * 2 * 2 * frames * config.n_fft * bins)
    return conv + mel_head + griffin


def detector_flops_per_image(config: DetectorConfig) -> float:
    """Conv backbone FLOPs: 2 * k*k * C_in * C_out * H_out * W_out summed
    over the backbone's 8 conv stages + head (detector.py:45-58)."""
    c = config.base_channels
    size = config.image_size
    stages = [  # (c_in, c_out, stride) mirroring init_detector_params
        (3, c, 2), (c, c * 2, 2), (c * 2, c * 2, 1), (c * 2, c * 4, 2),
        (c * 4, c * 4, 1), (c * 4, c * 8, 2), (c * 8, c * 8, 1),
    ]
    total = 0.0
    h = size
    for c_in, c_out, stride in stages:
        h = h // stride
        total += 2 * 9 * c_in * c_out * h * h
    total += 2 * 1 * (c * 8) * (5 + config.n_classes) * h * h  # 1x1 head
    return total
