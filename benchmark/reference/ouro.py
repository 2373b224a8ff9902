"""Plain reference for Ouro's looped decoder (ByteDance/Ouro-2.6B).

float32 jax.numpy at `default_matmul_precision("highest")`, no kernels, no
cache, no batching tricks: a full causal forward over the whole sequence,
pass by pass.  For a sequence's hidden rows x, pass t = 0 .. T-1 and layer
l = 0 .. N-1 (a layer's weights are the same on every pass):

    a = rms_norm(x; g1[l])
    q, k, v = a Wq[l], a Wk[l], a Wv[l]     heads of head_dim, no bias;
                                            rotary on q, k
    o = softmax(q k^T / sqrt(head_dim), causal) v
    x = x + rms_norm(o Wo[l]; g2[l])        the sublayer's OUTPUT is normed
    m = rms_norm(x; g3[l])
    x = x + rms_norm((silu(m Wg[l]) * (m Wu[l])) Wd[l]; g4[l])

After layer N-1 of pass t: x = rms_norm(x; g_final), the one final norm,
on every pass (its output is what pass t + 1 starts from); h[t] = x; the
exit gate reads e[t] = h[t] . w_gate + b_gate.  lam[t] = sigmoid(e[t]),
p[t] = lam[t] prod_{s<t} (1 - lam[s]) for t < T-1 and p[T-1] =
prod_{s<T-1} (1 - lam[s]).  A token's logits are h[X] W_head^T, X the
first pass by which p[0] + .. + p[X] reaches early_exit_threshold, else
the last.  Every pass is computed whatever X is.

A system that serves this model keeps K/V for every (pass, layer): pass
t of layer l attends over the keys and values that pass t of layer l
made at the earlier positions, and over no other pass's.  Numbered
t * N + l, a position leaves T x N cache rows behind.  Here there is no
cache: each pass recomputes its own keys and values over the whole
sequence, which is the same thing.

It imports nothing of the program and takes nothing the program made:
the weights are made here from the seed, layer by layer, by the draws the
program's seeded initialiser makes (normal / sqrt(fan_in) rounded to the
serving dtype, embedding normal * 0.02, norm gains 1, the gate's weight
normal / sqrt(hidden) from fold_in(seed key, layers + 1), its bias 0).

Departures from the published model, each in the configuration file's
`assumed`:
  - the output head is the embedding (published `tie_word_embeddings`
    false; the program's seeded initialiser makes no untied head);
  - rotary pairs are the two halves of a head (i, i + head_dim / 2), as
    the published modelling code rotates them;
  - what `config.json` has no key for (where the four norms of a layer
    sit, the final norm between passes, the gate's form and the exit
    rule, no bias anywhere) is as the published modelling code and
    arXiv:2510.25741 have it, as this reference's author knows them.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

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
    passes: int
    exit_threshold: float = 1.0
    theta: float = 10000.0
    eps: float = 1e-6
    dtype: str = "bfloat16"
    # the seeded gate's bias is 0; a test moves it so that exits differ
    gate_bias: float = 0.0


def shape_of(lm: dict) -> Shape:
    """From a configuration file's published keys."""
    return Shape(
        vocab=int(lm["vocab_size"]), d=int(lm["hidden_size"]),
        layers=int(lm["num_hidden_layers"]),
        heads=int(lm["num_attention_heads"]),
        kv_heads=int(lm["num_key_value_heads"]), hd=int(lm["head_dim"]),
        ff=int(lm["intermediate_size"]), passes=int(lm["total_ut_steps"]),
        exit_threshold=float(lm["early_exit_threshold"]),
        theta=float(lm["rope_theta"]), eps=float(lm["rms_norm_eps"]),
        dtype=str(lm.get("torch_dtype", lm.get("dtype", "bfloat16"))))


# -- weights from the seed ---------------------------------------------------

def _dense(key, rows: int, cols: int, dtype):
    # drawn, scaled and rounded as three separate operations, as a seeded
    # initialiser run eagerly does
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


def _weights(shape: Shape, seed: int, precision: str) -> tuple:
    """(embedding float32 as `precision` holds it, [a layer's seven
    matrices as stored], the gate's weight float32).  Norm gains are 1 and
    are left out.  The layers stay in the serving dtype (4.9 GB at the
    published size) and one layer's float32 copy is made where it is used,
    every pass anew: all of them in float32 would be 9.9 GB."""
    key = jax.random.PRNGKey(seed)
    embed_key, *layer_keys = jax.random.split(key, shape.layers + 1)
    dtype = jnp.dtype(shape.dtype)
    embedding = _as_computed(
        (jax.random.normal(embed_key, (shape.vocab, shape.d), jnp.float32)
         * 0.02).astype(dtype), precision, axis=1)
    gate = _dense(jax.random.fold_in(key, shape.layers + 1), shape.d, 1,
                  dtype)[:, 0].astype(jnp.float32)
    return (embedding,
            [_layer_weights(layer_key, shape) for layer_key in layer_keys],
            gate)


# -- the model ---------------------------------------------------------------

def _rms_norm(x, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


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
def _layer(x, w: dict, shape: Shape):
    """One decoder layer of one pass over x (B, L, d) float32, full
    causal; both sublayers' outputs normed before they are added."""
    batch, length, _ = x.shape
    positions = jnp.arange(length)
    a = _rms_norm(x, shape.eps)

    def heads(y, count):
        return y.reshape(batch, length, count, shape.hd).transpose(
            0, 2, 1, 3)

    q = _rotary(heads(a @ w["wq"], shape.heads), positions, shape.theta)
    k = _rotary(heads(a @ w["wk"], shape.kv_heads), positions, shape.theta)
    v = heads(a @ w["wv"], shape.kv_heads)
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
    x = x + _rms_norm(attended.transpose(0, 2, 1, 3).reshape(
        batch, length, shape.heads * shape.hd) @ w["wo"], shape.eps)
    m = _rms_norm(x, shape.eps)
    return x + _rms_norm(
        (jax.nn.silu(m @ w["w_gate"]) * (m @ w["w_up"])) @ w["w_down"],
        shape.eps)


def exit_pdf(gate_logits):
    """(T, ...) gate logits -> (T, ...) chance of leaving after pass t."""
    lam = jax.nn.sigmoid(gate_logits)
    pdf, stayed = [], jnp.ones_like(lam[0])
    for t in range(lam.shape[0] - 1):
        pdf.append(lam[t] * stayed)
        stayed = stayed * (1.0 - lam[t])
    return jnp.stack(pdf + [stayed])


def exit_pass(pdf, threshold: float):
    """The first pass by which the exit probabilities sum to `threshold`,
    else the last."""
    reached = jnp.cumsum(pdf, axis=0) >= threshold
    return jnp.where(reached.any(axis=0), jnp.argmax(reached, axis=0),
                     pdf.shape[0] - 1)


def _forward(shape: Shape, seed: int, tokens, precision: str):
    """The full forward, pass by pass -> (h (T, B, L, d): every pass's
    normed output, gate logits (T, B, L), the embedding)."""
    tokens = jnp.asarray(tokens, jnp.int32)
    embedding, layers, gate = _weights(shape, seed, precision)
    x = jnp.take(embedding, jnp.clip(tokens, 0, shape.vocab - 1), axis=0)
    outputs = []
    for _ in range(shape.passes):
        for stored in layers:
            w = {name: _as_computed(stored[name], precision)
                 for name in _DENSE}
            x = _layer(x, w, shape)
            del w
        x = _rms_norm(x, shape.eps)
        outputs.append(x)
    h = jnp.stack(outputs)
    return h, h @ gate + shape.gate_bias, embedding


def passes_of(shape: Shape, seed: int, tokens, precision: str = "stated"):
    """tokens (B, L) int32 -> (gate logits, exit pdf), each (T, B, L)
    float32: what the gate reads after every pass at every position."""
    with jax.default_matmul_precision("highest"):
        _, gate_logits, _ = _forward(shape, seed, tokens, precision)
    return gate_logits, exit_pdf(gate_logits)


def logits_at(shape: Shape, seed: int, tokens, positions,
              precision: str = "stated"):
    """Reference logits (B, P, vocab) float32 of `tokens` (B, L) int32 at
    `positions` (B, P): logits[b, p] scores the token that follows
    tokens[b, :positions[b, p] + 1]."""
    positions = jnp.asarray(positions, jnp.int32)
    with jax.default_matmul_precision("highest"):
        h, gate_logits, embedding = _forward(shape, seed, tokens, precision)
        chosen = exit_pass(exit_pdf(gate_logits), shape.exit_threshold)
        final = jnp.take_along_axis(h, chosen[None, :, :, None], axis=0)[0]
        del h
        picked = jnp.take_along_axis(final, positions[:, :, None], axis=1)
        return picked @ embedding.T
