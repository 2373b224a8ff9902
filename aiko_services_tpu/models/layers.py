# Shared neural-net layers as pure functions over parameter pytrees.
#
# No reference counterpart: the reference delegates all model math to
# third-party torch libraries (reference: src/aiko_services/examples/
# yolo/yolo.py:51, speech/speech_elements.py:233).  Here models are plain
# JAX -- params are dicts of jax.Array, layers are pure functions, so the
# whole model jits, shards with NamedSharding, and differentiates without
# framework machinery.
#
# Conventions: weights stored (in_features, out_features) so forward is
# x @ w (dense), except the projections into attention heads that a decode
# step would otherwise re-lay out every layer, stored (out_features,
# in_features) and contracted by head (dense_heads); attention heads live
# in the last-but-one axis (B, H, L, D);
# everything computes in the dtype of the incoming activations with f32
# accumulation for matmuls and reductions.

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "dense", "rms_norm", "layer_norm", "rotary_embedding", "apply_rotary",
    "yarn_frequencies", "yarn_mscale",
    "swiglu", "init_dense", "init_norm", "repeat_kv", "conv2d", "init_conv",
    "dense_heads", "init_dense_t",
]


def init_dense(key, in_features: int, out_features: int,
               dtype=jnp.float32) -> dict:
    scale = 1.0 / np.sqrt(in_features)
    return {"w": (jax.random.normal(key, (in_features, out_features),
                                    jnp.float32) * scale).astype(dtype)}


def init_dense_t(key, in_features: int, out_features: int,
                 dtype=jnp.float32) -> dict:
    """init_dense's numbers, bit for bit, held (out_features,
    in_features): the orientation checkpoints publish, which dense_heads
    contracts split by head."""
    return {"w": init_dense(key, in_features, out_features, dtype)["w"].T}


def _dense(equation: str, params: dict, x, scale_shape: tuple):
    """einsum(equation, x, w) with float32 accumulation, then an int8
    weight's per-output-channel scale, reshaped to `scale_shape` to
    broadcast over the product, and the bias."""
    w = params["w"]
    if w.dtype == jnp.int8:
        # weight-only int8 (transformer.quantize_weights_int8): weights
        # stream from HBM as 8-bit codes -- the convert fuses into the
        # dot's operand load -- and the per-output-channel scale folds
        # in AFTER the f32 accumulation (scales factor out of the
        # contraction), so the matmul itself never sees a dequantized
        # copy in memory
        out = jnp.einsum(equation, x, w.astype(x.dtype),
                         preferred_element_type=jnp.float32)
        out = out * params["w_scale"].reshape(scale_shape).astype(
            jnp.float32)
    else:
        out = jnp.einsum(equation, x, w,
                         preferred_element_type=jnp.float32)
    if "b" in params:
        out = out + params["b"]
    return out.astype(x.dtype)


def dense(params: dict, x):
    """x @ w over a weight held (in, out); int8 scales (1, out)."""
    return _dense("...i,io->...o", params, x, (-1,))


def dense_heads(params: dict, x):
    """x (B, L, in) through a projection into attention heads whose
    weight, stored (heads * depth, in), is handed over split by head,
    (heads, depth, in), and contracted on its last axis as it lies ->
    (B, heads, L, depth); int8 scales (heads, depth, 1).  A layer scan's
    slice of a stacked (layers, in, heads * depth) leaf, whose product is
    split into heads and rotated, is copied transposed before its matmul
    every layer of every decode step; this way the matmul reads the slice
    once, in place (tests/test_paged_attention.py holds the compiled
    step to that)."""
    heads, depth, _ = params["w"].shape
    return _dense("bli,hdi->bhld", params, x, (heads, 1, depth))


def init_norm(features: int, dtype=jnp.float32) -> dict:
    return {"scale": jnp.ones((features,), dtype)}


def rms_norm(params: dict, x, eps: float = 1e-6):
    x_f32 = x.astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(x_f32 * x_f32, axis=-1, keepdims=True)
                        + eps)
    return (x_f32 * rms).astype(x.dtype) * params["scale"]


def layer_norm(params: dict, x, eps: float = 1e-5):
    x_f32 = x.astype(jnp.float32)
    mean = jnp.mean(x_f32, axis=-1, keepdims=True)
    var = jnp.var(x_f32, axis=-1, keepdims=True)
    out = (x_f32 - mean) * jax.lax.rsqrt(var + eps)
    out = out.astype(x.dtype) * params["scale"]
    if "bias" in params:
        out = out + params["bias"]
    return out


def rotary_embedding(positions, head_dim: int, theta: float = 10000.0,
                     frequencies=None):
    """positions (..., L) int -> cos/sin tables (..., L, head_dim//2).
    `frequencies` (head_dim//2,) replaces theta's own (yarn_frequencies)."""
    if frequencies is None:
        frequencies = 1.0 / (theta ** (
            jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    angles = positions[..., None].astype(jnp.float32) * frequencies
    return jnp.cos(angles), jnp.sin(angles)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature: 0.1 * mscale * ln(factor) + 1 for a
    context stretched by `factor` > 1, else 1."""
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(head_dim: int, theta: float, factor: float,
                     original_max: int, beta_fast: float,
                     beta_slow: float) -> np.ndarray:
    """YaRN's head_dim//2 rotary frequencies.  A dimension that turns more
    than beta_fast times within the original context keeps theta's
    frequency, one that turns fewer than beta_slow times is interpolated
    (divided by `factor`), and a linear ramp over the dimensions between
    blends the two."""
    plain = 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float64)
                             / head_dim))

    def dimension_turning(rotations: float) -> float:
        return (head_dim * math.log(original_max
                                    / (rotations * 2.0 * math.pi))
                / (2.0 * math.log(theta)))

    low = max(math.floor(dimension_turning(beta_fast)), 0)
    high = min(math.ceil(dimension_turning(beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(head_dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    return (plain / factor * ramp + plain * (1.0 - ramp)).astype(np.float32)


def apply_rotary(x, cos, sin):
    """x (B, H, L, D); cos/sin (L, D//2) or broadcastable (B, 1, L, D//2)."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    rotated = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return rotated.astype(x.dtype)


def swiglu(gate_params: dict, up_params: dict, down_params: dict, x):
    return dense(down_params,
                 jax.nn.silu(dense(gate_params, x)) * dense(up_params, x))


def repeat_kv(x, repeats: int):
    """Expand grouped KV heads to full head count: (B, Hkv, L, D) ->
    (B, Hkv*repeats, L, D)."""
    if repeats == 1:
        return x
    batch, kv_heads, length, dim = x.shape
    x = jnp.broadcast_to(x[:, :, None],
                         (batch, kv_heads, repeats, length, dim))
    return x.reshape(batch, kv_heads * repeats, length, dim)


def init_conv(key, in_channels: int, out_channels: int, kernel: int,
              dtype=jnp.float32, bias: bool = True) -> dict:
    fan_in = in_channels * kernel * kernel
    params = {"w": (jax.random.normal(
        key, (kernel, kernel, in_channels, out_channels), jnp.float32)
        / np.sqrt(fan_in)).astype(dtype)}
    if bias:
        params["b"] = jnp.zeros((out_channels,), dtype)
    return params


def conv2d(params: dict, x, stride: int = 1, padding="SAME"):
    """x (B, H, W, C), w (kh, kw, I, O) -> (B, H', W', O).

    NHWC/HWIO: channels ride the TPU lane dimension so XLA maps the conv
    onto the MXU directly (NCHW forces layout shuffles that collapse conv
    throughput ~100x on TPU -- measured in bench.py round 2)."""
    out = jax.lax.conv_general_dilated(
        x, params["w"].astype(x.dtype),
        window_strides=(stride, stride), padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.float32)
    if "b" in params:
        out = out + params["b"].astype(jnp.float32)
    return out.astype(x.dtype)
