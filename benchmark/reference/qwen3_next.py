"""Plain reference for Qwen3-Next's hybrid decoder
(Qwen/Qwen3-Next-80B-A3B-Instruct), or one chip's share of it.

float32 jax.numpy at `default_matmul_precision("highest")`, no kernels, no
cache, no state handed on, no batching: a full causal forward over one
whole sequence at a time, the recurrence a plain `lax.scan` over its rows.
Layer i is a gated softmax-attention layer where (i + 1) %
full_attention_interval == 0, else a Gated DeltaNet layer; every layer,
for the hidden rows h:

    h = h + mixer(norm_1(h));   h = h + moe(norm_2(h))

and after the last layer one final norm, then the head.  Every such norm
is RMS (rms_norm_eps) with the gain 1 + w, w stored, as published.

Gated DeltaNet mixer, for the normed row u_t (K key heads, V value heads of
d_k and d_v; C = 2 K d_k + V d_v channels; T = linear_conv_kernel_dim):

    [q | k | v | z]_t = u_t W_qkvz;   [b | a]_t = u_t W_ba        no bias
    [q | k | v]_t = silu(sum_{j<T} w_conv[j] * [q | k | v]_{t-T+1+j})
                                    causal, depthwise, rows before 0 zero
    a key head's q and k serve V / K consecutive value heads:
    q = q / sqrt(|q|^2 + 1e-6) / sqrt(d_k);   k = k / sqrt(|k|^2 + 1e-6)
    a value head:  beta_t = sigmoid(b_t)
                   g_t = -exp(A_log) softplus(a_t + dt_bias)
        S <- e^g_t S;  d = beta_t (v_t - S^T k_t);  S <- S + k_t d^T
        o_t = S^T q_t                       S (d_k, d_v) float32, S_-1 = 0
    out_t = (o_t / rms(o_t) * w_n * silu(z_t)) W_o
                        the norm a head, its gain w_n (plain, not 1 + w)
                        shared by the heads

Gated attention mixer (H query heads over G K/V heads of head_dim):

    u W_q is H x 2 head_dim: a head's first half its query, its second
    half its gate;   q = norm(q), k = norm(k) a head, gains 1 + w of their
    own;  rotary (rope_theta) over the first partial_rotary_factor x
    head_dim columns of q and k, by halves;  causal softmax attention at
    scale head_dim^-1/2;   out = (attention * sigmoid(gate)) W_o

Experts: p = softmax(x W_r) over all the router's experts, float32; the
top num_experts_per_tok (ties to the lower index), their weights divided
by their sum; experts are SwiGLU of moe_intermediate_size;

    moe(x) = sum_i w_i E_i(x) + sigmoid(x . w_sg) E_shared(x)

`experts_held` = (lo, hi) makes this one chip's share: the router scores
all its experts, and only those in [lo, hi) are computed and added; what
the absent ones would add is left out.  With (0, router_experts) it is the
whole layer.

It imports nothing of the program and takes nothing the program made: the
weights are made here from the seed, layer by layer and expert by expert,
by the draws the program's seeded initialiser makes (layer i from the i-th
of the seed key's splits; matrices normal / sqrt(fan_in) rounded to the
serving dtype, embedding normal * 0.02, every w 0 and w_n 1; the
convolution normal / sqrt(T); A uniform in (0, 16), softplus(dt_bias)
log-uniform in [1e-3, 1e-1] from fold_in(A's key, 1); expert e of
a layer from fold_in(that leaf's key, e)).  `norms` hands in other gains
(tests: so that 1 + w is told from w).

Departures from the published model, each in the configuration file's
`assumed`: the head tied to the embedding, the order of the columns of
W_qkvz, W_ba and W_q (published interleaved by key head), seeded weights,
no multi-token-prediction module.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_QUERY_BLOCK = 512
_LAYER_KEYS = 12
# the matrices the int8 control rounds; the router, the convolution, A,
# dt_bias and the norms stay as they are
_MATRICES = ("w_qkvz", "w_ba", "w_out", "wq", "wk", "wv", "wo",
             "shared_gate", "shared_up", "shared_down", "shared_mix")


@dataclass(frozen=True)
class Shape:
    vocab: int
    d: int
    layers: int
    interval: int           # an attention layer every `interval` layers
    heads: int
    kv_heads: int
    hd: int
    rotary: int             # a head's columns that rotate, from the first
    theta: float
    key_heads: int
    value_heads: int
    key_dim: int
    value_dim: int
    taps: int
    router_experts: int     # the router's outputs
    held: tuple             # [lo, hi) of them computed here
    top_k: int
    moe_ff: int             # one routed expert's width
    shared_ff: int          # the shared expert's
    eps: float = 1e-6
    dtype: str = "bfloat16"

    def attends(self, layer: int) -> bool:
        return (layer + 1) % self.interval == 0

    @property
    def keys(self) -> int:
        return self.key_heads * self.key_dim

    @property
    def values(self) -> int:
        return self.value_heads * self.value_dim


def shape_of(lm: dict) -> Shape:
    """From a configuration file's published keys (`router_experts` and
    `experts_held` where the file holds a share)."""
    router = int(lm.get("router_experts", lm["num_experts"]))
    held = tuple(int(edge) for edge in lm.get("experts_held", (0, router)))
    hd = int(lm["head_dim"])
    return Shape(
        vocab=int(lm["vocab_size"]), d=int(lm["hidden_size"]),
        layers=int(lm["num_hidden_layers"]),
        interval=int(lm["full_attention_interval"]),
        heads=int(lm["num_attention_heads"]),
        kv_heads=int(lm["num_key_value_heads"]), hd=hd,
        rotary=int(hd * float(lm["partial_rotary_factor"])),
        theta=float(lm["rope_theta"]),
        key_heads=int(lm["linear_num_key_heads"]),
        value_heads=int(lm["linear_num_value_heads"]),
        key_dim=int(lm["linear_key_head_dim"]),
        value_dim=int(lm["linear_value_head_dim"]),
        taps=int(lm["linear_conv_kernel_dim"]),
        router_experts=router, held=held,
        top_k=int(lm["num_experts_per_tok"]),
        moe_ff=int(lm["moe_intermediate_size"]),
        shared_ff=int(lm["shared_expert_intermediate_size"]),
        eps=float(lm["rms_norm_eps"]),
        dtype=str(lm.get("torch_dtype", lm.get("dtype", "bfloat16"))))


# -- weights from the seed ---------------------------------------------------

def _dense(key, rows: int, cols: int, dtype):
    # drawn, scaled and rounded as three separate operations, as a seeded
    # initialiser run eagerly does
    return (jax.random.normal(key, (rows, cols), jnp.float32)
            * (1.0 / np.sqrt(rows))).astype(dtype)


def _shared_weights(keys, shape: Shape, dtype) -> dict:
    """The router, the shared expert and its gate; keys = the layer's
    keys[5:12], the routed experts' three first."""
    return {"router": _dense(keys[3], shape.d, shape.router_experts, dtype),
            "shared_gate": _dense(keys[4], shape.d, shape.shared_ff, dtype),
            "shared_up": _dense(keys[5], shape.d, shape.shared_ff, dtype),
            "shared_down": _dense(keys[6], shape.shared_ff, shape.d, dtype),
            "shared_mix": _dense(jax.random.fold_in(keys[4], 1), shape.d, 1,
                                 dtype)}


def _expert_weights(keys, expert: int, shape: Shape, dtype) -> tuple:
    """(gate, up, down) of expert `expert` of the router's numbering."""
    gate, up, down = (jax.random.fold_in(key, expert) for key in keys[:3])
    return (_dense(gate, shape.d, shape.moe_ff, dtype),
            _dense(up, shape.d, shape.moe_ff, dtype),
            _dense(down, shape.moe_ff, shape.d, dtype))


def _attention_weights(keys, shape: Shape, dtype) -> dict:
    q, kv = shape.heads * shape.hd, shape.kv_heads * shape.hd
    return {"wq": _dense(keys[0], shape.d, 2 * q, dtype),
            "wk": _dense(keys[1], shape.d, kv, dtype),
            "wv": _dense(keys[2], shape.d, kv, dtype),
            "wo": _dense(keys[3], q, shape.d, dtype),
            "q_norm": jnp.zeros((shape.hd,), jnp.float32),
            "k_norm": jnp.zeros((shape.hd,), jnp.float32)}


def _delta_weights(keys, shape: Shape, dtype) -> dict:
    channels = 2 * shape.keys + shape.values
    step = jnp.exp(jax.random.uniform(
        jax.random.fold_in(keys[3], 1), (shape.value_heads,), jnp.float32)
        * (np.log(1e-1) - np.log(1e-3)) + np.log(1e-3))
    return {
        "w_qkvz": _dense(keys[0], shape.d, channels + shape.values, dtype),
        # (taps, channels); published (channels, 1, taps)
        "conv": (jax.random.normal(keys[1], (shape.taps, channels),
                                   jnp.float32)
                 / np.sqrt(shape.taps)).astype(dtype),
        "w_ba": _dense(keys[2], shape.d, 2 * shape.value_heads, dtype),
        "a_log": jnp.log(jax.random.uniform(
            keys[3], (shape.value_heads,), jnp.float32) * 16.0),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "gate_norm": jnp.ones((shape.value_dim,), jnp.float32),
        "w_out": _dense(keys[4], shape.values, shape.d, dtype)}


def _layer_weights(key, index: int, shape: Shape) -> tuple:
    """(the layer's weights as stored but its routed experts, its keys):
    the experts are made one at a time where they are used."""
    keys = jax.random.split(key, _LAYER_KEYS)
    dtype = jnp.dtype(shape.dtype)
    mixer = (_attention_weights if shape.attends(index)
             else _delta_weights)(keys, shape, dtype)
    return {**mixer, **_shared_weights(keys[5:], shape, dtype),
            "norm_1": jnp.zeros((shape.d,), jnp.float32),
            "norm_2": jnp.zeros((shape.d,), jnp.float32)}, keys


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
    return {name: _as_computed(leaf, precision) if name in _MATRICES
            else leaf.astype(jnp.float32) for name, leaf in stored.items()}


# -- the model ---------------------------------------------------------------

def _rms_norm(x, w, eps: float):
    """RMS norm with the published gain 1 + w."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * (1.0 + w)


@partial(jax.jit, static_argnames=("shape",))
def _delta_mixer(h, w: dict, shape: Shape):
    """h + the Gated DeltaNet mixer over one sequence h (L, d) float32,
    from S = 0."""
    length = h.shape[0]
    keys, values = shape.keys, shape.values
    heads, per_key = shape.value_heads, shape.value_heads // shape.key_heads
    u = _rms_norm(h, w["norm_1"], shape.eps)
    mixed = u @ w["w_qkvz"]
    z = mixed[:, 2 * keys + values:].reshape(length, heads, shape.value_dim)
    ba = u @ w["w_ba"]
    padded = jnp.concatenate(
        [jnp.zeros((shape.taps - 1, 2 * keys + values), jnp.float32),
         mixed[:, :2 * keys + values]])
    c = jax.nn.silu(sum(w["conv"][j] * padded[j:j + length]
                        for j in range(shape.taps)))

    def unit(x):
        x = x.reshape(length, shape.key_heads, shape.key_dim)
        x = x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)
        return jnp.repeat(x, per_key, axis=1)             # (L, V, d_k)

    q = unit(c[:, :keys]) / np.sqrt(shape.key_dim)
    k = unit(c[:, keys:2 * keys])
    v = c[:, 2 * keys:].reshape(length, heads, shape.value_dim)
    beta = jax.nn.sigmoid(ba[:, :heads])
    g = -jnp.exp(w["a_log"]) * jax.nn.softplus(ba[:, heads:] + w["dt_bias"])

    def row(state, xs):
        q_t, k_t, v_t, g_t, beta_t = xs
        state = state * jnp.exp(g_t)[:, None, None]
        recalled = jnp.sum(state * k_t[:, :, None], axis=1)
        delta = beta_t[:, None] * (v_t - recalled)
        state = state + k_t[:, :, None] * delta[:, None, :]
        return state, jnp.sum(state * q_t[:, :, None], axis=1)

    _, o = jax.lax.scan(
        row, jnp.zeros((heads, shape.key_dim, shape.value_dim), jnp.float32),
        (q, k, v, g, beta))
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                          + shape.eps) * w["gate_norm"]
    return h + (o * jax.nn.silu(z)).reshape(length, -1) @ w["w_out"]


def _rotary(x, shape: Shape):
    """x (heads, L, hd): the first `rotary` columns rotated by halves at
    positions 0 .. L - 1, the rest as they are."""
    width = shape.rotary
    frequencies = 1.0 / (shape.theta ** (
        jnp.arange(0, width, 2, dtype=jnp.float32) / width))
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * frequencies
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :width // 2], x[..., width // 2:width]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., width:]], axis=-1)


@partial(jax.jit, static_argnames=("shape",))
def _attention_mixer(h, w: dict, shape: Shape):
    """h + the gated attention mixer over one sequence h (L, d)."""
    length, hd = h.shape[0], shape.hd
    u = _rms_norm(h, w["norm_1"], shape.eps)
    qg = (u @ w["wq"]).reshape(length, shape.heads, 2 * hd)
    gate = qg[..., hd:].transpose(1, 0, 2)                 # (H, L, hd)

    def heads(y, count):
        return y.reshape(length, count, hd).transpose(1, 0, 2)

    q = _rotary(_rms_norm(qg[..., :hd].transpose(1, 0, 2), w["q_norm"],
                          shape.eps), shape)
    k = _rotary(_rms_norm(heads(u @ w["wk"], shape.kv_heads), w["k_norm"],
                          shape.eps), shape)
    v = heads(u @ w["wv"], shape.kv_heads)
    group = shape.heads // shape.kv_heads
    q = q.reshape(shape.kv_heads, group, length, hd)
    outs = []
    for start in range(0, length, _QUERY_BLOCK):
        stop = min(start + _QUERY_BLOCK, length)
        scores = jnp.einsum("kgqd,kld->kgql", q[:, :, start:stop],
                            k[:, :stop]) / np.sqrt(hd)
        causal = (jnp.arange(stop)[None, :]
                  <= jnp.arange(start, stop)[:, None])
        scores = jnp.where(causal, scores, -jnp.inf)
        outs.append(jnp.einsum("kgql,kld->kgqd",
                               jax.nn.softmax(scores, axis=-1), v[:, :stop]))
    attended = jnp.concatenate(outs, axis=2).reshape(shape.heads, length, hd)
    attended = attended * jax.nn.sigmoid(gate)
    return h + attended.transpose(1, 0, 2).reshape(length, -1) @ w["wo"]


@partial(jax.jit, static_argnames=("shape",))
def route(x, router, shape: Shape):
    """x (T, d) float32 normed input -> (weights (T, k) float32, ids (T,
    k)): the experts each token chose, in the router's numbering, their
    softmax scores divided by their sum."""
    scores = jax.nn.softmax(x @ router, axis=-1)
    weights, ids = jax.lax.top_k(scores, shape.top_k)
    return weights / jnp.sum(weights, axis=-1, keepdims=True), ids


@jax.jit
def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def moe(x, w: dict, keys, shape: Shape, precision: str):
    """sigmoid(x . w_sg) Shared(x) + the held experts' part of the routed
    sum, over x (T, d) float32, normed; one expert's float32 weights alive
    at a time."""
    dtype = jnp.dtype(shape.dtype)
    weights, ids = route(x, w["router"], shape)
    out = jax.nn.sigmoid(x @ w["shared_mix"]) * _swiglu(
        x, w["shared_gate"], w["shared_up"], w["shared_down"])
    for expert in range(*shape.held):
        gate = jnp.sum(jnp.where(ids == expert, weights, 0.0), axis=-1)
        stored = _expert_weights(keys[5:], expert, shape, dtype)
        out = out + gate[:, None] * _swiglu(
            x, *(_as_computed(leaf, precision) for leaf in stored))
        del stored
    return out


def hidden_of(shape: Shape, seed: int, tokens, precision: str = "stated",
              norms: dict | None = None):
    """tokens (B, L) int32 -> (the final norm's output (B, L, d) float32,
    the embedding): a layer at a time, its mixer a sequence at a time.
    `norms` = {"layers": [{name: w} a layer], "final": w} replaces the
    seeded gains (all 0, the delta mixer's own 1)."""
    tokens = jnp.asarray(tokens, jnp.int32)
    embed_key, *layer_keys = jax.random.split(jax.random.PRNGKey(seed),
                                              shape.layers + 1)
    embedding = _as_computed(
        (jax.random.normal(embed_key, (shape.vocab, shape.d), jnp.float32)
         * 0.02).astype(jnp.dtype(shape.dtype)), precision, axis=1)
    h = jnp.take(embedding, jnp.clip(tokens, 0, shape.vocab - 1), axis=0)
    batch, length, d = h.shape
    for index, key in enumerate(layer_keys):
        stored, keys = _layer_weights(key, index, shape)
        w = _widened(stored, precision)
        del stored
        if norms is not None:
            w.update({name: jnp.asarray(gain, jnp.float32)
                      for name, gain in norms["layers"][index].items()})
        mixer = _attention_mixer if shape.attends(index) else _delta_mixer
        h = jnp.stack([mixer(row, w, shape) for row in h])
        x = _rms_norm(h, w["norm_2"], shape.eps).reshape(batch * length, d)
        h = h + moe(x, w, keys, shape, precision).reshape(batch, length, d)
        del w
    final = 0.0 if norms is None else jnp.asarray(norms["final"],
                                                  jnp.float32)
    return _rms_norm(h, final, shape.eps), embedding


def logits_at(shape: Shape, seed: int, tokens, positions,
              precision: str = "stated", norms: dict | None = None):
    """Reference logits (B, P, vocab) float32 of `tokens` (B, L) int32 at
    `positions` (B, P): logits[b, p] scores the token that follows
    tokens[b, :positions[b, p] + 1]."""
    positions = jnp.asarray(positions, jnp.int32)
    with jax.default_matmul_precision("highest"):
        final, embedding = hidden_of(shape, seed, tokens, precision, norms)
        picked = jnp.take_along_axis(final, positions[:, :, None], axis=1)
        return picked @ embedding.T
