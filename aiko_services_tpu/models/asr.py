# Whisper-style encoder-decoder speech recognizer.
#
# Replaces the reference's PE_WhisperX element (reference:
# src/aiko_services/examples/speech/speech_elements.py:186-262: WhisperX on
# CUDA, tiny..large ladder, 5 s windows).  Same shape of capability --
# log-mel audio in, token text out -- built TPU-first: conv subsampling +
# bidirectional transformer encoder, causal transformer decoder with
# cross-attention, all pure-JAX pytrees jit-compiled with the flash kernel
# for every attention flavor, greedy decode as one jit (scan over steps).
#
# Sharding: encoder/decoder matmuls follow the same megatron TP pattern as
# the LM (param_specs), batch on "data".

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..parallel.attention import flash_attention
from .layers import dense, init_dense, init_norm, layer_norm

__all__ = ["AsrConfig", "init_asr_params", "asr_param_specs",
           "make_asr_train_step", "transcribe_audio", "transcribe_rescore",
           "encode_audio", "decode_tokens", "asr_forward", "transcribe"]


@dataclass(frozen=True)
class AsrConfig:
    n_mels: int = 80
    d_model: int = 384
    enc_layers: int = 4
    dec_layers: int = 4
    n_heads: int = 6
    vocab_size: int = 1024
    max_frames: int = 1500        # mel frames after conv (30 s @ 10 ms hop)
    max_text_len: int = 128
    sot_token: int = 1            # start-of-transcript
    eot_token: int = 2            # end-of-transcript
    dtype: str = "bfloat16"

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)


def _sinusoids(length: int, channels: int) -> np.ndarray:
    """Whisper-style fixed sinusoidal positions (length, channels)."""
    log_timescale = np.log(10000.0) / (channels // 2 - 1)
    inv_timescales = np.exp(-log_timescale * np.arange(channels // 2))
    scaled = np.arange(length)[:, None] * inv_timescales[None, :]
    return np.concatenate([np.sin(scaled), np.cos(scaled)],
                          axis=1).astype(np.float32)


def _init_attention(key, d_model: int, dtype) -> dict:
    keys = jax.random.split(key, 4)
    return {
        "wq": init_dense(keys[0], d_model, d_model, dtype),
        "wk": init_dense(keys[1], d_model, d_model, dtype),
        "wv": init_dense(keys[2], d_model, d_model, dtype),
        "wo": init_dense(keys[3], d_model, d_model, dtype),
    }


def _init_mlp(key, d_model: int, dtype) -> dict:
    k1, k2 = jax.random.split(key)
    return {"w1": init_dense(k1, d_model, d_model * 4, dtype),
            "w2": init_dense(k2, d_model * 4, d_model, dtype)}


def _init_enc_layer(key, config: AsrConfig) -> dict:
    k1, k2 = jax.random.split(key)
    d, dtype = config.d_model, config.jnp_dtype
    return {
        "attn_norm": init_norm(d, dtype), "attn": _init_attention(k1, d, dtype),
        "mlp_norm": init_norm(d, dtype), "mlp": _init_mlp(k2, d, dtype),
    }


def _init_dec_layer(key, config: AsrConfig) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    d, dtype = config.d_model, config.jnp_dtype
    return {
        "self_norm": init_norm(d, dtype), "self": _init_attention(k1, d, dtype),
        "cross_norm": init_norm(d, dtype), "cross": _init_attention(k2, d, dtype),
        "mlp_norm": init_norm(d, dtype), "mlp": _init_mlp(k3, d, dtype),
    }


def _stack(layer_list):
    return jax.tree_util.tree_map(lambda *leaves: jnp.stack(leaves),
                                  *layer_list)


def init_asr_params(config: AsrConfig, key) -> dict:
    keys = jax.random.split(key, config.enc_layers + config.dec_layers + 4)
    d, dtype = config.d_model, config.jnp_dtype
    conv1 = {"w": (jax.random.normal(
        keys[0], (d, config.n_mels, 3), jnp.float32)
        / np.sqrt(config.n_mels * 3)).astype(dtype),
        "b": jnp.zeros((d,), dtype)}
    conv2 = {"w": (jax.random.normal(
        keys[1], (d, d, 3), jnp.float32) / np.sqrt(d * 3)).astype(dtype),
        "b": jnp.zeros((d,), dtype)}
    enc = [_init_enc_layer(keys[2 + i], config)
           for i in range(config.enc_layers)]
    dec = [_init_dec_layer(keys[2 + config.enc_layers + i], config)
           for i in range(config.dec_layers)]
    return {
        "conv1": conv1,
        "conv2": conv2,
        "enc_positions": jnp.asarray(
            _sinusoids(config.max_frames, d), dtype),
        "enc_layers": _stack(enc),
        "enc_norm": init_norm(d, dtype),
        "token_embed": {"w": (jax.random.normal(
            keys[-2], (config.vocab_size, d), jnp.float32) * 0.02
            ).astype(dtype)},
        "dec_positions": (jax.random.normal(
            keys[-1], (config.max_text_len, d), jnp.float32) * 0.01
            ).astype(dtype),
        "dec_layers": _stack(dec),
        "dec_norm": init_norm(d, dtype),
    }


def asr_param_specs(config: AsrConfig) -> dict:
    attention = {"wq": {"w": P(None, "fsdp", "model")},
                 "wk": {"w": P(None, "fsdp", "model")},
                 "wv": {"w": P(None, "fsdp", "model")},
                 "wo": {"w": P(None, "model", "fsdp")}}
    mlp = {"w1": {"w": P(None, "fsdp", "model")},
           "w2": {"w": P(None, "model", "fsdp")}}
    norm = {"scale": P(None, None)}
    return {
        "conv1": {"w": P(None, None, None), "b": P(None)},
        "conv2": {"w": P(None, None, None), "b": P(None)},
        "enc_positions": P(None, None),
        "enc_layers": {"attn_norm": norm, "attn": attention,
                       "mlp_norm": norm, "mlp": mlp},
        "enc_norm": {"scale": P(None)},
        "token_embed": {"w": P(None, "fsdp")},
        "dec_positions": P(None, None),
        "dec_layers": {"self_norm": norm, "self": attention,
                       "cross_norm": norm, "cross": attention,
                       "mlp_norm": norm, "mlp": mlp},
        "dec_norm": {"scale": P(None)},
    }


# -- model ------------------------------------------------------------------

def _split_heads(x, n_heads: int):
    batch, length, _ = x.shape
    return x.reshape(batch, length, n_heads, -1).transpose(0, 2, 1, 3)


def _merge_heads(x):
    batch, heads, length, dim = x.shape
    return x.transpose(0, 2, 1, 3).reshape(batch, length, heads * dim)


def _attend(attention, x, memory, n_heads: int, causal: bool):
    q = _split_heads(dense(attention["wq"], x), n_heads)
    k = _split_heads(dense(attention["wk"], memory), n_heads)
    v = _split_heads(dense(attention["wv"], memory), n_heads)
    out = flash_attention(q, k, v, causal=causal)
    return dense(attention["wo"], _merge_heads(out))


def _conv1d(params, x, stride: int):
    """x (B, T, C_in), w (C_out, C_in, K) -> (B, T/stride, C_out)."""
    out = jax.lax.conv_general_dilated(
        x, params["w"].astype(x.dtype).transpose(2, 1, 0),
        window_strides=(stride,), padding="SAME",
        dimension_numbers=("NWC", "WIO", "NWC"),
        preferred_element_type=jnp.float32)
    return (out + params["b"].astype(jnp.float32)).astype(x.dtype)


def encode_audio(params: dict, config: AsrConfig, mel):
    """mel (B, n_mels, frames) -> encoder memory (B, frames//2, d)."""
    x = mel.astype(config.jnp_dtype).transpose(0, 2, 1)  # (B, T, mels)
    x = jax.nn.gelu(_conv1d(params["conv1"], x, stride=1))
    x = jax.nn.gelu(_conv1d(params["conv2"], x, stride=2))
    # whisper-style fixed context window: audio beyond max_frames post-conv
    # positions is truncated (callers chunk longer audio -- AudioFraming)
    x = x[:, :config.max_frames]
    x = x + params["enc_positions"][:x.shape[1]]

    def enc_layer(h, layer):
        h = h + _attend(layer["attn"],
                        layer_norm(layer["attn_norm"], h),
                        layer_norm(layer["attn_norm"], h),
                        config.n_heads, causal=False)
        normed = layer_norm(layer["mlp_norm"], h)
        h = h + dense(layer["mlp"]["w2"],
                      jax.nn.gelu(dense(layer["mlp"]["w1"], normed)))
        return h, None

    x, _ = jax.lax.scan(enc_layer, x, params["enc_layers"])
    return layer_norm(params["enc_norm"], x)


def decode_tokens(params: dict, config: AsrConfig, tokens, memory):
    """tokens (B, T) + encoder memory -> logits (B, T, vocab)."""
    h = jnp.take(params["token_embed"]["w"], tokens, axis=0, mode="clip")
    h = h + params["dec_positions"][:tokens.shape[1]]

    def dec_layer(h, layer):
        h = h + _attend(layer["self"],
                        layer_norm(layer["self_norm"], h),
                        layer_norm(layer["self_norm"], h),
                        config.n_heads, causal=True)
        h = h + _attend(layer["cross"],
                        layer_norm(layer["cross_norm"], h), memory,
                        config.n_heads, causal=False)
        normed = layer_norm(layer["mlp_norm"], h)
        h = h + dense(layer["mlp"]["w2"],
                      jax.nn.gelu(dense(layer["mlp"]["w1"], normed)))
        return h, None

    h, _ = jax.lax.scan(dec_layer, h, params["dec_layers"])
    h = layer_norm(params["dec_norm"], h)
    return jnp.einsum("btd,vd->btv", h.astype(jnp.float32),
                      params["token_embed"]["w"].astype(jnp.float32))


def asr_forward(params: dict, config: AsrConfig, mel, tokens):
    """Teacher-forced forward (training/scoring): logits (B, T, vocab)."""
    return decode_tokens(params, config, tokens,
                         encode_audio(params, config, mel))


def _cross_kv(params: dict, config: AsrConfig, memory):
    """Cross-attention K/V for every decoder layer, computed ONCE per
    transcription -- the rescore loop recomputed them at every step.
    Returns (L, B, H, M, hd) stacked pairs."""
    def layer_kv(_, layer):
        k = _split_heads(dense(layer["cross"]["wk"], memory),
                         config.n_heads)
        v = _split_heads(dense(layer["cross"]["wv"], memory),
                         config.n_heads)
        return None, (k, v)

    _, (ks, vs) = jax.lax.scan(layer_kv, None, params["dec_layers"])
    return ks, vs


def _attend_cached(q, k, v):
    """(B, H, 1, hd) query over cached keys/values, f32 softmax."""
    scale = 1.0 / np.sqrt(q.shape[-1])
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    att = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", att, v)


def _decode_step(params: dict, config: AsrConfig, token, index,
                 self_k, self_v, cross_k, cross_v):
    """One incremental decode step: token (B, 1) consumed at buffer
    position `index` (traced int32).  Self K/V caches (L, B, H, T, hd)
    update in place at `index`; attention masks positions > index.
    Returns (next-position logits (B, vocab) f32, self_k, self_v)."""
    h = jnp.take(params["token_embed"]["w"], token, axis=0, mode="clip")
    h = h + jax.lax.dynamic_slice(
        params["dec_positions"], (index, 0),
        (1, params["dec_positions"].shape[1]))[None, 0:1]
    max_tokens = self_k.shape[3]
    mask = (jnp.arange(max_tokens) > index)[None, None, None, :]

    def dec_layer(h, xs):
        layer, sk, sv, ck, cv = xs
        x = layer_norm(layer["self_norm"], h)
        q = _split_heads(dense(layer["self"]["wq"], x), config.n_heads)
        k_new = _split_heads(dense(layer["self"]["wk"], x), config.n_heads)
        v_new = _split_heads(dense(layer["self"]["wv"], x), config.n_heads)
        sk = jax.lax.dynamic_update_slice(sk, k_new, (0, 0, index, 0))
        sv = jax.lax.dynamic_update_slice(sv, v_new, (0, 0, index, 0))
        scale = 1.0 / np.sqrt(q.shape[-1])
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, sk,
                            preferred_element_type=jnp.float32) * scale
        scores = jnp.where(mask, -1e30, scores)
        att = jax.nn.softmax(scores, axis=-1).astype(sv.dtype)
        self_out = jnp.einsum("bhqk,bhkd->bhqd", att, sv)
        h = h + dense(layer["self"]["wo"], _merge_heads(self_out))
        xc = layer_norm(layer["cross_norm"], h)
        qc = _split_heads(dense(layer["cross"]["wq"], xc), config.n_heads)
        h = h + dense(layer["cross"]["wo"],
                      _merge_heads(_attend_cached(qc, ck, cv)))
        normed = layer_norm(layer["mlp_norm"], h)
        h = h + dense(layer["mlp"]["w2"],
                      jax.nn.gelu(dense(layer["mlp"]["w1"], normed)))
        return h, (sk, sv)

    (h), (self_k, self_v) = jax.lax.scan(
        dec_layer, h,
        (params["dec_layers"], self_k, self_v, cross_k, cross_v))
    h = layer_norm(params["dec_norm"], h)
    logits = jnp.einsum("btd,vd->btv", h.astype(jnp.float32),
                        params["token_embed"]["w"].astype(jnp.float32))
    return logits[:, 0], self_k, self_v


@partial(jax.jit, static_argnames=("config", "max_tokens"))
def transcribe_rescore(params: dict, config: AsrConfig, mel,
                       max_tokens: int = 32):
    """Greedy transcription by FULL re-score per step (no KV cache): the
    simple quadratic loop, kept as the numerics oracle for the
    incremental path (and for tiny configs where cache setup dominates)."""
    memory = encode_audio(params, config, mel)
    batch = mel.shape[0]
    tokens = jnp.full((batch, max_tokens + 1), config.eot_token, jnp.int32)
    tokens = tokens.at[:, 0].set(config.sot_token)
    finished = jnp.zeros((batch,), bool)

    def step(carry, index):
        tokens, finished = carry
        logits = decode_tokens(params, config, tokens[:, :-1], memory)
        next_token = jnp.argmax(logits[:, index], axis=-1).astype(jnp.int32)
        next_token = jnp.where(finished, config.eot_token, next_token)
        tokens = tokens.at[:, index + 1].set(next_token)
        finished = jnp.logical_or(finished,
                                  next_token == config.eot_token)
        return (tokens, finished), None

    (tokens, _), _ = jax.lax.scan(
        step, (tokens, finished), jnp.arange(max_tokens))
    return tokens[:, 1:]


def make_asr_train_step(config: AsrConfig, optimizer):
    """Returns train_step(params, opt_state, mel, tokens) -> (params,
    opt_state, loss): teacher-forced next-token cross-entropy (same
    convention as transformer.make_train_step).  The trainable path
    makes transcription a LEARNED capability, not a shape: fit
    mel -> token targets and transcribe() decodes them greedily --
    functional parity with the reference's pretrained WhisperX seat
    (speech_elements.py:229-262) proven by training to correctness on
    synthetic data (no published checkpoints exist in this image)."""

    def loss_fn(params, mel, tokens):
        logits = asr_forward(params, config, mel, tokens[:, :-1])
        targets = tokens[:, 1:]
        log_probs = jax.nn.log_softmax(logits, axis=-1)
        taken = jnp.take_along_axis(
            log_probs, targets[..., None], axis=-1, mode="clip")[..., 0]
        return -jnp.mean(taken)

    @partial(jax.jit, donate_argnums=(0, 1))
    def train_step(params, opt_state, mel, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(params, mel, tokens)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = jax.tree_util.tree_map(
            lambda p, u: p + u.astype(p.dtype), params, updates)
        return params, opt_state, loss

    return train_step


@partial(jax.jit, static_argnames=("config", "max_tokens"))
def transcribe_audio(params: dict, config: AsrConfig, audio,
                     max_tokens: int = 32):
    """audio (B, samples) 16 kHz f32 -> (B, max_tokens) token ids: the
    log-mel frontend AND the full transcription as ONE device program.
    One launch, one readback: the serving path must never split
    frontend and model into separate dispatches."""
    from ..ops import log_mel_spectrogram
    mel = log_mel_spectrogram(audio, n_mels=config.n_mels)
    return transcribe(params, config, mel, max_tokens=max_tokens)


@partial(jax.jit, static_argnames=("config", "max_tokens"))
def transcribe(params: dict, config: AsrConfig, mel, max_tokens: int = 32):
    """Greedy transcription: mel (B, n_mels, frames) -> (B, max_tokens)
    token ids (eot-padded).  One jit: encoder once, cross K/V cached
    once, then an INCREMENTAL KV-cached decode loop -- one position
    through the decoder per step instead of the full buffer (the rescore
    loop cost max_tokens x the whole decoder + logits head; this is
    ~max_tokens x cheaper and the bench-critical ASR path)."""
    memory = encode_audio(params, config, mel)
    cross_k, cross_v = _cross_kv(params, config, memory)
    batch = mel.shape[0]
    n_heads = config.n_heads
    head_dim = config.d_model // n_heads
    shape = (config.dec_layers, batch, n_heads, max_tokens, head_dim)
    self_k = jnp.zeros(shape, config.jnp_dtype)
    self_v = jnp.zeros(shape, config.jnp_dtype)
    tokens = jnp.full((batch, max_tokens + 1), config.eot_token, jnp.int32)
    tokens = tokens.at[:, 0].set(config.sot_token)
    finished = jnp.zeros((batch,), bool)

    def step(carry, index):
        tokens, finished, self_k, self_v = carry
        token = jax.lax.dynamic_slice(tokens, (0, index), (batch, 1))
        logits, self_k, self_v = _decode_step(
            params, config, token, index, self_k, self_v,
            cross_k, cross_v)
        next_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        next_token = jnp.where(finished, config.eot_token, next_token)
        tokens = tokens.at[:, index + 1].set(next_token)
        finished = jnp.logical_or(finished,
                                  next_token == config.eot_token)
        return (tokens, finished, self_k, self_v), None

    (tokens, _, _, _), _ = jax.lax.scan(
        step, (tokens, finished, self_k, self_v), jnp.arange(max_tokens))
    return tokens[:, 1:]
