"""Plain reference for Jamba's hybrid decoder (ai21labs/AI21-Jamba2-3B).

float32 jax.numpy at `default_matmul_precision("highest")`, no kernels, no
cache, no state handed on, no batching: a full causal forward over one
whole sequence at a time, the recurrence a plain `lax.scan` over its rows.
Layer i is an attention layer where i % attn_layer_period ==
attn_layer_offset, else a Mamba layer; every layer, for the hidden rows h:

    h = h + mixer(rms_norm(h))
    m = rms_norm(h);   h = h + (silu(m Wg) * (m Wu)) Wd

and after the last layer one final rms_norm, then the head, which is the
embedding.  The Mamba mixer, for the normed row u_t (d_inner = mamba_expand
x hidden, N = mamba_d_state, K = mamba_d_conv):

    [x_t, z_t] = u_t W_in                          no bias
    c_t = silu(b_conv + sum_{j<K} w_conv[j] * x_{t-K+1+j})
                                    causal, depthwise, rows before 0 zero
    [d_t, B_t, C_t] = c_t W_x, each rms_normed (gains of their own)
    D_t = softplus(d_t W_dt + b_dt)                (d_inner,)
    S_t = exp(D_t (x) 1 * A) * S_{t-1} + (D_t * c_t) (x) B_t
                                    A = -exp(A_log), S_{-1} = 0, float32
    y_t = S_t C_t + D * c_t
    out_t = (y_t * silu(z_t)) W_out                no bias

Attention: num_attention_heads heads of hidden / heads over
num_key_value_heads K/V heads, no bias, causal, full, NO positional
encoding of any kind, scale 1 / sqrt(head size).

A system that serves this model keeps, for a sequence, S (d_inner x N
float32) and the last K - 1 rows of x a Mamba layer, whatever the context,
and K/V a position an attention layer.  Here there is neither: the scan
starts from zero and runs the whole sequence.

It imports nothing of the program and takes nothing the program made: the
weights are made here from the seed, layer by layer, by the draws the
program's seeded initialiser makes (layer i from the i-th of the seed
key's splits; matrices normal / sqrt(fan_in) rounded to the serving dtype,
embedding normal * 0.02, norm gains 1; of a Mamba layer the convolution
normal / sqrt(K) with a bias normal * 0.02, A = -(1 .. N) a channel, D = 1,
softplus(b_dt) log-uniform in [1e-3, 1e-1]).

Departures from the published model, each in the configuration file's
`assumed`: the inner norms' place and the absence of rotary (no key for
either; the published modelling code for `model_type: "jamba"`), seeded
weights, float32 for S, A_log, D and b_dt.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_QUERY_BLOCK = 512
_MATRICES = ("w_in", "w_x", "w_dt", "w_out", "wq", "wk", "wv", "wo",
             "w_gate", "w_up", "w_down")


@dataclass(frozen=True)
class Shape:
    vocab: int
    d: int
    layers: int
    heads: int
    kv_heads: int
    ff: int
    period: int
    offset: int
    inner: int
    states: int
    taps: int
    rank: int
    eps: float = 1e-6
    dtype: str = "bfloat16"

    @property
    def hd(self) -> int:
        return self.d // self.heads

    def attends(self, layer: int) -> bool:
        return layer % self.period == self.offset


def shape_of(lm: dict) -> Shape:
    """From a configuration file's published keys."""
    d = int(lm["hidden_size"])
    return Shape(
        vocab=int(lm["vocab_size"]), d=d,
        layers=int(lm["num_hidden_layers"]),
        heads=int(lm["num_attention_heads"]),
        kv_heads=int(lm["num_key_value_heads"]),
        ff=int(lm["intermediate_size"]),
        period=int(lm["attn_layer_period"]),
        offset=int(lm["attn_layer_offset"]),
        inner=int(lm["mamba_expand"]) * d, states=int(lm["mamba_d_state"]),
        taps=int(lm["mamba_d_conv"]), rank=int(lm["mamba_dt_rank"]),
        eps=float(lm["rms_norm_eps"]),
        dtype=str(lm.get("torch_dtype", lm.get("dtype", "bfloat16"))))


# -- weights from the seed ---------------------------------------------------

def _dense(key, rows: int, cols: int, dtype):
    # drawn, scaled and rounded as three separate operations, as a seeded
    # initialiser run eagerly does
    return (jax.random.normal(key, (rows, cols), jnp.float32)
            * (1.0 / np.sqrt(rows))).astype(dtype)


def _ffn(keys, shape: Shape, dtype) -> dict:
    return {"w_gate": _dense(keys[0], shape.d, shape.ff, dtype),
            "w_up": _dense(keys[1], shape.d, shape.ff, dtype),
            "w_down": _dense(keys[2], shape.ff, shape.d, dtype)}


def _attention_weights(key, shape: Shape) -> dict:
    keys = jax.random.split(key, 8)
    dtype = jnp.dtype(shape.dtype)
    q, kv = shape.heads * shape.hd, shape.kv_heads * shape.hd
    return {"wq": _dense(keys[0], shape.d, q, dtype),
            "wk": _dense(keys[1], shape.d, kv, dtype),
            "wv": _dense(keys[2], shape.d, kv, dtype),
            "wo": _dense(keys[3], q, shape.d, dtype),
            **_ffn(keys[4:7], shape, dtype)}


def _mamba_weights(key, shape: Shape) -> dict:
    keys = jax.random.split(key, 10)
    dtype = jnp.dtype(shape.dtype)
    inner, states = shape.inner, shape.states
    step = jnp.exp(jax.random.uniform(keys[5], (inner,), jnp.float32)
                   * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
    return {
        "w_in": _dense(keys[0], shape.d, 2 * inner, dtype),
        # (taps, channels); published (channels, 1, taps)
        "conv_w": (jax.random.normal(keys[1], (shape.taps, inner),
                                     jnp.float32)
                   / math.sqrt(shape.taps)).astype(dtype),
        "conv_b": (jax.random.normal(keys[2], (inner,), jnp.float32)
                   * 0.02).astype(dtype),
        "w_x": _dense(keys[3], inner, shape.rank + 2 * states, dtype),
        "w_dt": _dense(keys[4], shape.rank, inner, dtype),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        # (d_inner, d_state), as published
        "a_log": jnp.broadcast_to(jnp.log(jnp.arange(
            1, states + 1, dtype=jnp.float32)), (inner, states)),
        "d": jnp.ones((inner,), jnp.float32),
        "w_out": _dense(keys[6], inner, shape.d, dtype),
        **_ffn(keys[7:10], shape, dtype)}


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


def _widened(stored: dict, precision: str) -> dict:
    """One layer's float32 copy: the matrices as `precision` holds them,
    the rest (the convolution, A, D, the step's bias) as they are."""
    return {name: _as_computed(leaf, precision) if name in _MATRICES
            else leaf.astype(jnp.float32) for name, leaf in stored.items()}


def _weights(shape: Shape, seed: int, precision: str) -> tuple:
    """(embedding float32 as `precision` holds it, [a layer's weights as
    stored]).  Norm gains are 1 and are left out.  The layers stay in the
    serving dtype (5.7 GB at the published size) and one layer's float32
    copy is made where it is used: all of them in float32 would be 11.4
    GB."""
    key = jax.random.PRNGKey(seed)
    embed_key, *layer_keys = jax.random.split(key, shape.layers + 1)
    embedding = _as_computed(
        (jax.random.normal(embed_key, (shape.vocab, shape.d), jnp.float32)
         * 0.02).astype(jnp.dtype(shape.dtype)), precision, axis=1)
    return embedding, [
        (_attention_weights if shape.attends(index) else _mamba_weights)(
            layer_key, shape) for index, layer_key in enumerate(layer_keys)]


# -- the model ---------------------------------------------------------------

def _rms_norm(x, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _ffn_out(h, w: dict, shape: Shape):
    m = _rms_norm(h, shape.eps)
    return h + (jax.nn.silu(m @ w["w_gate"]) * (m @ w["w_up"])) @ w["w_down"]


@partial(jax.jit, static_argnames=("shape",))
def _mamba_layer(h, w: dict, shape: Shape):
    """One Mamba layer over one sequence h (L, d) float32, from S = 0."""
    length = h.shape[0]
    u = _rms_norm(h, shape.eps)
    xz = u @ w["w_in"]
    x, z = xz[:, :shape.inner], xz[:, shape.inner:]
    padded = jnp.concatenate(
        [jnp.zeros((shape.taps - 1, shape.inner), x.dtype), x])
    c = jax.nn.silu(w["conv_b"] + sum(
        w["conv_w"][j] * padded[j:j + length] for j in range(shape.taps)))
    projected = c @ w["w_x"]
    rank, states = shape.rank, shape.states
    delta = _rms_norm(projected[:, :rank], shape.eps)
    b = _rms_norm(projected[:, rank:rank + states], shape.eps)
    cc = _rms_norm(projected[:, rank + states:], shape.eps)
    step = jax.nn.softplus(delta @ w["w_dt"] + w["dt_bias"])
    a = -jnp.exp(w["a_log"])                              # (inner, N)

    def row(state, xs):
        step_t, c_t, b_t, cc_t = xs
        state = (jnp.exp(step_t[:, None] * a) * state
                 + (step_t * c_t)[:, None] * b_t[None, :])
        return state, jnp.sum(state * cc_t[None, :], axis=1)

    _, y = jax.lax.scan(
        row, jnp.zeros((shape.inner, states), jnp.float32),
        (step, c, b, cc))
    y = y + w["d"] * c
    return _ffn_out(h + (y * jax.nn.silu(z)) @ w["w_out"], w, shape)


@partial(jax.jit, static_argnames=("shape",))
def _attention_layer(h, w: dict, shape: Shape):
    """One attention layer over one sequence h (L, d) float32: full,
    causal, no positional encoding."""
    length = h.shape[0]
    a = _rms_norm(h, shape.eps)

    def heads(y, count):
        return y.reshape(length, count, shape.hd).transpose(1, 0, 2)

    group = shape.heads // shape.kv_heads
    q = heads(a @ w["wq"], shape.heads).reshape(
        shape.kv_heads, group, length, shape.hd)
    k, v = heads(a @ w["wk"], shape.kv_heads), heads(a @ w["wv"],
                                                     shape.kv_heads)
    outs = []
    for start in range(0, length, _QUERY_BLOCK):
        stop = min(start + _QUERY_BLOCK, length)
        scores = jnp.einsum("kgqd,kld->kgql", q[:, :, start:stop],
                            k[:, :stop]) / np.sqrt(shape.hd)
        causal = (jnp.arange(stop)[None, :]
                  <= jnp.arange(start, stop)[:, None])
        scores = jnp.where(causal, scores, -jnp.inf)
        outs.append(jnp.einsum("kgql,kld->kgqd",
                               jax.nn.softmax(scores, axis=-1), v[:, :stop]))
    attended = jnp.concatenate(outs, axis=2).reshape(
        shape.heads, length, shape.hd)
    h = h + attended.transpose(1, 0, 2).reshape(length, -1) @ w["wo"]
    return _ffn_out(h, w, shape)


def hidden_of(shape: Shape, seed: int, tokens, precision: str = "stated"):
    """tokens (B, L) int32 -> (the final norm's output (B, L, d) float32,
    the embedding): a sequence at a time, a layer at a time."""
    tokens = jnp.asarray(tokens, jnp.int32)
    embedding, layers = _weights(shape, seed, precision)
    rows = [jnp.take(embedding, jnp.clip(sequence, 0, shape.vocab - 1),
                     axis=0) for sequence in tokens]
    for index, stored in enumerate(layers):
        w = _widened(stored, precision)
        layer = _attention_layer if shape.attends(index) else _mamba_layer
        rows = [layer(h, w, shape) for h in rows]
        del w
    return jnp.stack([_rms_norm(h, shape.eps) for h in rows]), embedding


def logits_at(shape: Shape, seed: int, tokens, positions,
              precision: str = "stated"):
    """Reference logits (B, P, vocab) float32 of `tokens` (B, L) int32 at
    `positions` (B, P): logits[b, p] scores the token that follows
    tokens[b, :positions[b, p] + 1]."""
    positions = jnp.asarray(positions, jnp.int32)
    with jax.default_matmul_precision("highest"):
        final, embedding = hidden_of(shape, seed, tokens, precision)
        picked = jnp.take_along_axis(final, positions[:, :, None], axis=1)
        return picked @ embedding.T
