"""Plain reference for the Mistral-style decoder the LM cells serve.

float32 jax.numpy at `default_matmul_precision("highest")`, no kernels, no
cache, no batching tricks: embedding, then for every layer RMSNorm,
rotary grouped-query causal attention, RMSNorm, SwiGLU, and at the end
RMSNorm and the tied output head.  It imports nothing of the program and
takes nothing the program made: the weights are made here from the seed,
layer by layer, by the same draws the program's seeded initialiser makes
(normal / sqrt(fan_in) rounded to the serving dtype, embedding normal *
0.02), so one layer's float32 copy is alive at a time and the reference
fits beside nothing else.

Departures from the published Mistral-7B-v0.1, each also in the
configuration file's `assumed`: the output head is the embedding (the
program's seeded initialiser makes no untied head), rms_norm_eps is 1e-6
(the program cannot be given another), attention is full causal (every
context is within the 4096 sliding window).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

NORM_EPS = 1e-6
_QUERY_BLOCK = 512
_DENSE = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


@dataclass(frozen=True)
class Shape:
    vocab: int
    d: int
    layers: int
    heads: int
    kv_heads: int
    hd: int
    ff: int
    theta: float = 10000.0
    dtype: str = "bfloat16"


def shape_of(lm: dict) -> Shape:
    """From a configuration file's published keys."""
    return Shape(
        vocab=int(lm["vocab_size"]), d=int(lm["hidden_size"]),
        layers=int(lm["num_hidden_layers"]),
        heads=int(lm["num_attention_heads"]),
        kv_heads=int(lm["num_key_value_heads"]), hd=int(lm["head_dim"]),
        ff=int(lm["intermediate_size"]),
        theta=float(lm.get("rope_theta", 10000.0)),
        dtype=str(lm.get("torch_dtype", lm.get("dtype", "bfloat16"))))


# -- weights from the seed ---------------------------------------------------

def _keys(shape: Shape, seed: int):
    embed_key, *layer_keys = jax.random.split(
        jax.random.PRNGKey(seed), shape.layers + 1)
    return embed_key, layer_keys


def _dense(key, rows: int, cols: int, dtype):
    # drawn, scaled and rounded as three separate operations, as a seeded
    # initialiser run eagerly does: fused into one program the scaling
    # can round the last float32 bit another way
    return (jax.random.normal(key, (rows, cols), jnp.float32)
            * (1.0 / np.sqrt(rows))).astype(dtype)


def _layer_weights(key, shape: Shape) -> dict:
    keys = jax.random.split(key, 8)
    d, ff = shape.d, shape.ff
    q, kv = shape.heads * shape.hd, shape.kv_heads * shape.hd
    dtype = jnp.dtype(shape.dtype)
    return {"wq": _dense(keys[0], d, q, dtype),
            "wk": _dense(keys[1], d, kv, dtype),
            "wv": _dense(keys[2], d, kv, dtype),
            "wo": _dense(keys[3], q, d, dtype),
            "w_gate": _dense(keys[4], d, ff, dtype),
            "w_up": _dense(keys[5], d, ff, dtype),
            "w_down": _dense(keys[6], ff, d, dtype)}


def _embedding(key, shape: Shape):
    return (jax.random.normal(key, (shape.vocab, shape.d), jnp.float32)
            * 0.02).astype(jnp.dtype(shape.dtype))


def _int8_round_trip(w, axis: int):
    """Symmetric absmax int8 along `axis`, back to float32: what
    weight-only int8 serving would compute with."""
    w = w.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=axis, keepdims=True),
                        1e-12) / 127.0
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def _as_computed(weights, precision: str, axis: int = 0):
    """float32 copy of seeded weights as `precision` would hold them:
    "stated" is the configuration's dtype, "int8" the control."""
    if precision == "stated":
        return weights.astype(jnp.float32)
    if precision == "int8":
        return _int8_round_trip(weights, axis)
    raise ValueError(f"unknown precision {precision!r}")


# -- the model ---------------------------------------------------------------

def _rms_norm(x):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + NORM_EPS)


def _rotary(x, positions, theta: float):
    """x (B, H, L, hd): rotate the two halves of every head."""
    half = x.shape[-1] // 2
    frequencies = 1.0 / (theta ** (
        jnp.arange(0, 2 * half, 2, dtype=jnp.float32) / (2 * half)))
    angles = positions[:, None].astype(jnp.float32) * frequencies
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("shape",))
def _layer(h, w: dict, shape: Shape):
    """One decoder layer over h (B, L, d) float32, full causal."""
    batch, length, _ = h.shape
    positions = jnp.arange(length)
    x = _rms_norm(h)

    def heads(y, count):
        return y.reshape(batch, length, count, shape.hd).transpose(
            0, 2, 1, 3)

    q = _rotary(heads(x @ w["wq"], shape.heads), positions, shape.theta)
    k = _rotary(heads(x @ w["wk"], shape.kv_heads), positions, shape.theta)
    v = heads(x @ w["wv"], shape.kv_heads)
    group = shape.heads // shape.kv_heads
    q = q.reshape(batch, shape.kv_heads, group, length, shape.hd)
    outs = []
    for start in range(0, length, _QUERY_BLOCK):
        stop = min(start + _QUERY_BLOCK, length)
        scores = jnp.einsum("bkgqd,bkld->bkgql", q[:, :, :, start:stop],
                            k[:, :, :stop]) / np.sqrt(shape.hd)
        causal = (jnp.arange(stop)[None, :]
                  <= jnp.arange(start, stop)[:, None])
        scores = jnp.where(causal, scores, -jnp.inf)
        outs.append(jnp.einsum("bkgql,bkld->bkgqd",
                               jax.nn.softmax(scores, axis=-1),
                               v[:, :, :stop]))
    attended = jnp.concatenate(outs, axis=3).reshape(
        batch, shape.heads, length, shape.hd)
    h = h + attended.transpose(0, 2, 1, 3).reshape(
        batch, length, shape.heads * shape.hd) @ w["wo"]
    x = _rms_norm(h)
    return h + (jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])) @ w["w_down"]


@jax.jit
def _head(h, embedding):
    return _rms_norm(h) @ embedding.T


def logits_at(shape: Shape, seed: int, tokens, positions,
              precision: str = "stated"):
    """Reference logits (B, P, vocab) float32 of `tokens` (B, L) int32 at
    `positions` (B, P): logits[b, p] scores the token that follows
    tokens[b, :positions[b, p] + 1]."""
    embed_key, layer_keys = _keys(shape, seed)
    tokens = jnp.asarray(tokens, jnp.int32)
    positions = jnp.asarray(positions, jnp.int32)
    with jax.default_matmul_precision("highest"):
        embedding = _as_computed(_embedding(embed_key, shape), precision,
                                 axis=1)
        # ids past the vocabulary take its last row, as the program's
        # embedding does (whisper's ids reach 51864, the LM has 32000)
        h = jnp.take(embedding, jnp.clip(tokens, 0, shape.vocab - 1),
                     axis=0)
        for key in layer_keys:
            stored = _layer_weights(key, shape)
            w = {name: _as_computed(stored[name], precision)
                 for name in _DENSE}
            del stored
            h = _layer(h, w, shape)
            del w
        picked = jnp.take_along_axis(h, positions[:, :, None], axis=1)
        return _head(picked, embedding)
