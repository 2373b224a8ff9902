# Text-to-speech: the framework's TTS seat, filling the reference's Coqui
# TTS element (reference: src/aiko_services/examples/speech/
# speech_elements.py:109-146 -- PE_TextToSpeech wrapping TTS
# "tts_models/en/vctk/vits" on CUDA, 594 MB VRAM).
#
# TPU-first design -- everything from characters to waveform is ONE jit:
#   chars (B, L) -> embedding -> static-duration upsample (frames_per_char,
#   jit-friendly static shapes; no autoregressive loop) -> 1D conv decoder
#   -> mel (B, n_mels, T) -> mel-to-linear (precomputed filterbank
#   pseudo-inverse, an MXU matmul) -> Griffin-Lim phase recovery
#   (lax.fori_loop of STFT/ISTFT round-trips on jnp.fft) -> waveform.
#
# Weights are random-initialized at the element level (same policy as the
# LM/ASR/detector families: real checkpoints load through
# models/weights.py load_pytree); the synthesis chain, shapes, and the
# vocoder are the production path.

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.audio import mel_filterbank
from .layers import dense, init_dense

__all__ = [
    "TTSConfig", "init_tts_params", "synthesize_mel", "griffin_lim",
    "synthesize", "encode_chars", "make_tts_train_step",
]


@dataclass(frozen=True)
class TTSConfig:
    vocab_size: int = 256          # byte-level characters
    d_model: int = 256
    n_conv_layers: int = 4
    kernel_size: int = 5
    n_mels: int = 80
    sample_rate: int = 16000
    n_fft: int = 400
    hop: int = 200                 # 12.5 ms
    frames_per_char: int = 6       # ~75 ms per character
    griffin_lim_iters: int = 30
    dtype: str = "float32"

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)


def encode_chars(text: str, max_len: int | None = None) -> np.ndarray:
    """Byte-level character ids (1, L) int32; optionally padded/truncated
    to max_len with zeros (id 0 = padding/silence)."""
    ids = np.frombuffer(text.encode("utf-8", "replace"),
                        np.uint8).astype(np.int32)
    if max_len is not None:
        ids = ids[:max_len]
        ids = np.pad(ids, (0, max_len - len(ids)))
    return ids[None]


def init_tts_params(config: TTSConfig, key) -> dict:
    """Conv layers are STACKED on a leading axis (like every model
    family here) so save_pytree/load_pytree/shard_pytree apply
    unchanged; synthesize_mel runs them with lax.scan."""
    keys = jax.random.split(key, config.n_conv_layers + 3)
    dtype = config.jnp_dtype
    scale = 1.0 / np.sqrt(config.d_model * config.kernel_size)
    conv_w = jnp.stack([
        (jax.random.normal(
            keys[2 + index],
            (config.kernel_size, config.d_model, config.d_model),
            jnp.float32) * scale).astype(dtype)
        for index in range(config.n_conv_layers)])
    return {
        "embed": {"w": (jax.random.normal(
            keys[0], (config.vocab_size, config.d_model), jnp.float32)
            * 0.02).astype(dtype)},
        "convs": {"w": conv_w,
                  "b": jnp.zeros(
                      (config.n_conv_layers, config.d_model), dtype)},
        "mel_out": init_dense(keys[1], config.d_model, config.n_mels,
                              dtype),
    }


def synthesize_mel(params: dict, config: TTSConfig, chars) -> jnp.ndarray:
    """chars (B, L) int32 -> mel (B, n_mels, L * frames_per_char).

    Static-duration upsampling keeps every shape known at trace time (no
    data-dependent durations -> no recompiles, scan-free decode)."""
    h = jnp.take(params["embed"]["w"], chars, axis=0, mode="clip")   # (B, L, D)
    h = jnp.repeat(h, config.frames_per_char, axis=1)   # (B, T, D)
    # position-within-char phase feature lets the convs shape transients
    phase = jnp.tile(
        jnp.arange(config.frames_per_char, dtype=jnp.float32)
        / config.frames_per_char, chars.shape[1])
    h = h + jnp.sin(2 * jnp.pi * phase)[None, :, None].astype(h.dtype)

    def conv_block(h, conv):
        y = jax.lax.conv_general_dilated(
            h, conv["w"], window_strides=(1,), padding="SAME",
            dimension_numbers=("NWC", "WIO", "NWC"))
        return h + jnp.tanh(y + conv["b"]), None        # residual

    h, _ = jax.lax.scan(conv_block, h, params["convs"])
    mel = dense(params["mel_out"], h)                   # (B, T, n_mels)
    return mel.transpose(0, 2, 1)                       # (B, n_mels, T)


def _frame(signal, n_fft: int, hop: int):
    """(B, S) -> (B, frames, n_fft) strided windows.  When hop divides
    n_fft (the config default: 400/100) the frames assemble from STATIC
    slices of hop-sized blocks -- TPU gathers are serial and this
    framing sits inside the Griffin-Lim loop; the gather fallback
    covers exotic hop settings."""
    frames = 1 + (signal.shape[-1] - n_fft) // hop
    if n_fft % hop == 0:
        ratio = n_fft // hop
        usable = frames + ratio - 1          # hop-blocks covering frames
        blocks = signal[:, :usable * hop].reshape(
            signal.shape[0], usable, hop)
        return jnp.concatenate(
            [blocks[:, s:s + frames] for s in range(ratio)],
            axis=2)
    index = (jnp.arange(frames)[:, None] * hop
             + jnp.arange(n_fft)[None, :])
    return signal[:, index]


def _dft_matrices(n_fft: int):
    """rfft as a pair of real matmuls: the shared cos/-sin bases
    (ops/audio.py dft_basis -- same math as the ASR conv-STFT kernel).
    TPU-first: a 400x201 matmul rides the MXU while XLA's complex FFT
    at this size runs on the scalar/vector pipeline -- the Griffin-Lim
    loop is 2 transforms x 30 iterations deep, so the transform IS the
    workload."""
    from ..ops.audio import dft_basis
    cos_m, sin_m = dft_basis(n_fft)
    return jnp.asarray(cos_m), jnp.asarray(sin_m)


def _irfft_weights(n_fft: int):
    """Hermitian bin weights for the real inverse: DC and Nyquist count
    once, interior bins twice (their conjugate halves are implicit)."""
    bins = n_fft // 2 + 1
    weights = np.full((bins,), 2.0, np.float32)
    weights[0] = 1.0
    if n_fft % 2 == 0:
        weights[-1] = 1.0
    return jnp.asarray(weights / n_fft, jnp.float32)


def _stft_ri(signal, n_fft: int, hop: int, window, cos_m, sin_m):
    """(B, S) -> (real, imag) each (B, frames, bins), via MXU matmuls.
    Precision.HIGHEST: the default TPU matmul precision loses ~3
    decimal digits on the DFT's cancellation-heavy sums (measured in
    ops/audio.py), and Griffin-Lim feeds each iteration's error into
    the next."""
    frames = _frame(signal, n_fft, hop) * window
    highest = jax.lax.Precision.HIGHEST
    return (jnp.matmul(frames, cos_m, precision=highest),
            jnp.matmul(frames, sin_m, precision=highest))


def _window_norm(window_np: np.ndarray, hop: int, n_frames: int,
                 length: int):
    """Overlap-add normalization for the GIVEN window: depends only on
    the window and the shapes, so it is a numpy-built constant, never
    device work."""
    n_fft = window_np.shape[0]
    window_sq = np.asarray(window_np, np.float32) ** 2
    total = np.zeros((length,), np.float32)
    for frame in range(n_frames):
        total[frame * hop:frame * hop + n_fft] += window_sq
    return jnp.asarray(np.maximum(total, 1e-8))


def _overlap_add(frames, n_fft: int, hop: int, length: int):
    """(B, F, n_fft) windowed frames -> (B, length) sum at hop offsets.
    When hop divides n_fft this is `ratio` STATIC-slice adds on a
    hop-blocked accumulator (the scatter fallback is the single
    slowest op a TPU can run, and it sat inside the Griffin-Lim
    loop: 30 x ~4 ms/iteration was the whole TTS budget)."""
    batch, n_frames, _ = frames.shape
    if n_fft % hop == 0:
        ratio = n_fft // hop
        blocks = frames.reshape(batch, n_frames, ratio, hop)
        acc = jnp.zeros((batch, n_frames + ratio - 1, hop),
                        frames.dtype)
        for s in range(ratio):
            acc = acc.at[:, s:s + n_frames].add(blocks[:, :, s])
        return acc.reshape(batch, -1)[:, :length]
    signal = jnp.zeros((batch, length), frames.dtype)
    positions = (jnp.arange(n_frames)[:, None] * hop
                 + jnp.arange(n_fft)[None, :])       # (frames, n_fft)
    return signal.at[:, positions.reshape(-1)].add(
        frames.reshape(batch, -1))


def _istft_ri(real, imag, n_fft: int, hop: int, window, length: int,
              cos_m, sin_m, weights, norm):
    """Inverse of _stft_ri + windowed overlap-add against the
    precomputed window normalization (`norm` from _window_norm -- it is
    loop-invariant, built once per griffin_lim call, and MUST match the
    `window` actually applied here).  x[n] = sum_k w_k (real_k cos -
    imag_k sin(angle)) -- two HIGHEST-precision matmuls against the
    transposed bases (see _stft_ri)."""
    highest = jax.lax.Precision.HIGHEST
    frames = (jnp.matmul(real * weights, cos_m.T, precision=highest)
              + jnp.matmul(imag * weights, sin_m.T,
                           precision=highest)) * window
    signal = _overlap_add(frames, n_fft, hop, length)
    return signal / norm[None, :]


def griffin_lim(magnitude, config: TTSConfig) -> jnp.ndarray:
    """Phase recovery: magnitude (B, n_fft//2+1, T) -> waveform (B, S).

    Classic Griffin-Lim as a lax.fori_loop of ISTFT/STFT round-trips --
    fully on-device, jit-compiled with the synthesis net.  The
    transforms run as real DFT matmuls (MXU) rather than complex FFTs,
    and the loop carries only the phase ANGLE (real), so no complex
    dtype exists anywhere."""
    n_fft, hop = config.n_fft, config.hop
    magnitude = magnitude.transpose(0, 2, 1)            # (B, T, bins)
    frames = magnitude.shape[1]
    length = (frames - 1) * hop + n_fft
    window_np = np.hanning(n_fft).astype(np.float32)
    window = jnp.asarray(window_np)
    cos_m, sin_m = _dft_matrices(n_fft)
    weights = _irfft_weights(n_fft)
    norm = _window_norm(window_np, hop, frames, length)
    angles = jnp.zeros_like(magnitude)                  # deterministic

    def body(_, angles):
        signal = _istft_ri(magnitude * jnp.cos(angles),
                           magnitude * jnp.sin(angles),
                           n_fft, hop, window, length,
                           cos_m, sin_m, weights, norm)
        real, imag = _stft_ri(signal, n_fft, hop, window, cos_m, sin_m)
        return jnp.arctan2(imag, real)

    angles = jax.lax.fori_loop(0, config.griffin_lim_iters, body, angles)
    return _istft_ri(magnitude * jnp.cos(angles),
                     magnitude * jnp.sin(angles),
                     n_fft, hop, window, length, cos_m, sin_m, weights,
                     norm)


def make_tts_train_step(config: TTSConfig, optimizer):
    """Returns train_step(params, opt_state, chars, target_mel) ->
    (params, opt_state, loss): mel-regression MSE through the synthesis
    net (same convention as transformer.make_train_step).  The trainable
    path makes TTS a capability, not a shape: fit character->spectral
    targets (phoneme templates, or real aligned mel data) and
    synthesize() renders them through the same Griffin-Lim vocoder
    (reference parity: the Coqui element produces learned speech,
    speech_elements.py:109-146)."""

    def loss_fn(params, chars, target_mel):
        mel = synthesize_mel(params, config, chars)
        return jnp.mean(
            (mel.astype(jnp.float32) - target_mel.astype(jnp.float32))
            ** 2)

    @partial(jax.jit, donate_argnums=(0, 1))
    def train_step(params, opt_state, chars, target_mel):
        loss, grads = jax.value_and_grad(loss_fn)(params, chars,
                                                  target_mel)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = jax.tree_util.tree_map(
            lambda p, u: p + u.astype(p.dtype), params, updates)
        return params, opt_state, loss

    return train_step


@partial(jax.jit, static_argnames=("config",))
def synthesize(params: dict, config: TTSConfig, chars) -> jnp.ndarray:
    """chars (B, L) int32 -> waveform (B, S) float32 in [-1, 1]: the full
    text->speech chain as ONE jit (filterbank pinv is a trace-time
    constant)."""
    mel = synthesize_mel(params, config, chars)
    filterbank = mel_filterbank(
        sample_rate=config.sample_rate, n_fft=config.n_fft,
        n_mels=config.n_mels)                            # (n_mels, bins)
    inverse = jnp.asarray(np.linalg.pinv(np.asarray(filterbank)),
                          jnp.float32)                   # (bins, n_mels)
    energy = jnp.exp(mel.astype(jnp.float32))            # log-mel -> mel
    linear = jnp.maximum(
        jnp.einsum("bmt,fm->bft", energy, inverse), 0.0)
    magnitude = jnp.sqrt(linear + 1e-8)
    waveform = griffin_lim(magnitude, config)
    peak = jnp.max(jnp.abs(waveform), axis=-1, keepdims=True)
    return (waveform / jnp.maximum(peak, 1e-6)).astype(jnp.float32)
