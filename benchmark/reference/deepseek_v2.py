"""Plain reference for DeepSeek-V2's decoder, and for one chip's share of it.

float32 jax.numpy at `default_matmul_precision("highest")`, no kernels, no
cache, no batching tricks, the published equations and nothing else:

  h += Attn(RMSNorm(h)); h += FFN(RMSNorm(h)); final RMSNorm; tied head.

  Attn (MLA), x one position's normed input:
    c_q = RMSNorm(W_qa x);  q = W_qb c_q -> per head [q_nope ; q_rope]
    [c_kv ; k_r] = W_kva x; c_kv = RMSNorm(c_kv); k_r = RoPE(k_r), one
    for every head;  [k_nope_h ; v_h] = W_kvb c_kv per head
    p_h = softmax_causal(scale * q_h . [k_nope_h ; k_r]),
    scale = (nope + rope)^-0.5 * m^2, m = 0.1 * mscale_all_dim * ln(factor) + 1
    o = W_o concat_h(p_h v_h);  RoPE is YaRN's (frequencies blended between
    theta^-i and theta^-i / factor by a linear ramp over the dimensions
    that turn beta_fast .. beta_slow times in the original context), its
    cos/sin multiplied by mscale / mscale_all_dim's ratio.

  FFN: the first `first_k_dense_replace` layers a SwiGLU of
    `intermediate_size`; every other layer
    y = Shared(x) + sum_{i in top} g_i E_i(x): Shared a SwiGLU of
    n_shared * moe_intermediate, E_i a SwiGLU of moe_intermediate,
    s = softmax(W_g x) in float32 over all the router's experts, a
    group's score its largest s, the best `topk_group` groups kept, the
    top `num_experts_per_tok` of s among their experts chosen (ties to the
    lower index, as jax.lax.top_k breaks them), g_i = routed_scaling * s_i,
    not renormalised.  No token is dropped.  The auxiliary losses are
    training's and are left out.

`experts_held` = (lo, hi) makes this one chip's share: the router scores
all its experts, and only those in [lo, hi) are computed and added; what
the absent ones would add is left out.  With (0, router_experts) it is
the uncut model.

It imports nothing of the program and takes nothing the program made:
weights are made here from the seed, layer by layer and expert by expert,
by the draws the program's seeded initialiser makes (normal / sqrt(fan_in)
rounded to the serving dtype, embedding normal * 0.02; expert e of a
layer from fold_in(that leaf's key, e), so an expert's numbers do not
depend on which share holds it).

Departures from the published model, each in the configuration's
`assumed`: the output head is the embedding; rotary pairs are the two
halves of the rotary slice, not interleaved (a fixed permutation of
W_qb's and W_kva's rotary columns).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_QUERY_BLOCK = 256


@dataclass(frozen=True)
class Shape:
    vocab: int
    d: int
    layers: int
    heads: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v: int
    ff: int                 # the dense layers' width
    moe_ff: int             # one routed expert's width
    router_experts: int     # the router's outputs
    held: tuple             # [lo, hi) of them computed here
    shared: int
    top_k: int
    groups: int
    topk_groups: int
    routed_scaling: float
    dense_layers: int
    eps: float
    theta: float
    yarn: tuple | None      # (factor, original_max, beta_fast, beta_slow,
                            #  mscale, mscale_all_dim)
    dtype: str = "bfloat16"


def shape_of(lm: dict) -> Shape:
    """From a configuration file's published keys (`router_experts` and
    `experts_held` where the file holds a share)."""
    router = int(lm.get("router_experts", lm["n_routed_experts"]))
    held = tuple(int(edge) for edge in lm.get("experts_held", (0, router)))
    scaling = lm.get("rope_scaling")
    yarn = None
    if scaling:
        yarn = (float(scaling["factor"]),
                int(scaling["original_max_position_embeddings"]),
                float(scaling["beta_fast"]), float(scaling["beta_slow"]),
                float(scaling["mscale"]), float(scaling["mscale_all_dim"]))
    return Shape(
        vocab=int(lm["vocab_size"]), d=int(lm["hidden_size"]),
        layers=int(lm["num_hidden_layers"]),
        heads=int(lm["num_attention_heads"]),
        q_rank=int(lm["q_lora_rank"]), kv_rank=int(lm["kv_lora_rank"]),
        nope=int(lm["qk_nope_head_dim"]), rope=int(lm["qk_rope_head_dim"]),
        v=int(lm["v_head_dim"]), ff=int(lm["intermediate_size"]),
        moe_ff=int(lm["moe_intermediate_size"]), router_experts=router,
        held=held, shared=int(lm["n_shared_experts"]),
        top_k=int(lm["num_experts_per_tok"]), groups=int(lm["n_group"]),
        topk_groups=int(lm["topk_group"]),
        routed_scaling=float(lm["routed_scaling_factor"]),
        dense_layers=int(lm["first_k_dense_replace"]),
        eps=float(lm["rms_norm_eps"]), theta=float(lm["rope_theta"]),
        yarn=yarn,
        dtype=str(lm.get("torch_dtype", lm.get("dtype", "bfloat16"))))


# -- weights from the seed ---------------------------------------------------

_LAYER_KEYS = 12
_ATTENTION = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo")


def _dense(key, rows: int, cols: int, dtype):
    # drawn, scaled and rounded as three separate operations, as a seeded
    # initialiser run eagerly does
    return (jax.random.normal(key, (rows, cols), jnp.float32)
            * (1.0 / np.sqrt(rows))).astype(dtype)


def _attention_weights(keys, shape: Shape) -> dict:
    dtype = jnp.dtype(shape.dtype)
    d, heads = shape.d, shape.heads
    return {
        "wq_a": _dense(keys[0], d, shape.q_rank, dtype),
        "wq_b": _dense(keys[1], shape.q_rank,
                       heads * (shape.nope + shape.rope), dtype),
        "wkv_a": _dense(keys[2], d, shape.kv_rank + shape.rope, dtype),
        "wkv_b": _dense(keys[3], shape.kv_rank,
                        heads * (shape.nope + shape.v), dtype),
        "wo": _dense(keys[4], heads * shape.v, d, dtype)}


def _swiglu_weights(keys, d: int, width: int, dtype) -> tuple:
    return (_dense(keys[0], d, width, dtype),
            _dense(keys[1], d, width, dtype),
            _dense(keys[2], width, d, dtype))


def _expert_weights(keys, expert: int, shape: Shape) -> tuple:
    """Expert `expert` (the router's numbering) of the layer whose three
    expert leaves are keyed keys[0:3]."""
    return _swiglu_weights(
        [jax.random.fold_in(key, expert) for key in keys[:3]],
        shape.d, shape.moe_ff, jnp.dtype(shape.dtype))


def _embedding(key, shape: Shape):
    return (jax.random.normal(key, (shape.vocab, shape.d), jnp.float32)
            * 0.02).astype(jnp.dtype(shape.dtype))


def _int8_round_trip(w, axis: int):
    w = w.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=axis, keepdims=True),
                        1e-12) / 127.0
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def _as_computed(weights, precision: str, axis: int = 0):
    """float32 copy of seeded weights as `precision` would hold them:
    "stated" is the configuration's dtype, "int8" the control (symmetric
    absmax int8 along `axis`)."""
    if precision == "stated":
        return weights.astype(jnp.float32)
    if precision == "int8":
        return _int8_round_trip(weights, axis)
    raise ValueError(f"unknown precision {precision!r}")


# -- the model ---------------------------------------------------------------

def _rms_norm(x, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def rotary_frequencies(shape: Shape) -> np.ndarray:
    """The rope/2 rotary frequencies: theta^(-2i/rope), under YaRN blended
    with theta^(-2i/rope) / factor."""
    dim = shape.rope
    plain = 1.0 / (shape.theta ** (np.arange(0, dim, 2, dtype=np.float64)
                                   / dim))
    if shape.yarn is None:
        return plain.astype(np.float32)
    factor, original, beta_fast, beta_slow, _, _ = shape.yarn

    def correction_dim(rotations: float) -> float:
        return (dim * math.log(original / (rotations * 2.0 * math.pi))
                / (2.0 * math.log(shape.theta)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    # ramp 0: the dimension turns often in the original context and keeps
    # its frequency; ramp 1: it is interpolated by `factor`
    return (plain / factor * ramp + plain * (1.0 - ramp)).astype(np.float32)


def attention_scale(shape: Shape) -> float:
    scale = (shape.nope + shape.rope) ** -0.5
    if shape.yarn is not None:
        m = yarn_mscale(shape.yarn[0], shape.yarn[5])
        scale *= m * m
    return scale


def _rotary(x, positions, shape: Shape):
    """x (..., L, rope): rotate the two halves of the last axis."""
    half = shape.rope // 2
    angles = (positions[:, None].astype(jnp.float32)
              * jnp.asarray(rotary_frequencies(shape)))
    multiplier = 1.0
    if shape.yarn is not None:
        multiplier = (yarn_mscale(shape.yarn[0], shape.yarn[4])
                      / yarn_mscale(shape.yarn[0], shape.yarn[5]))
    cos, sin = jnp.cos(angles) * multiplier, jnp.sin(angles) * multiplier
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("shape",))
def _attention(h, w: dict, shape: Shape):
    """h + MLA(RMSNorm(h)) over h (L, d) float32, full causal, one row of
    the batch, decompressed: every head's keys and values are made."""
    length = h.shape[0]
    heads, nope, rope = shape.heads, shape.nope, shape.rope
    positions = jnp.arange(length)
    x = _rms_norm(h, shape.eps)
    q = (_rms_norm(x @ w["wq_a"], shape.eps) @ w["wq_b"]).reshape(
        length, heads, nope + rope).transpose(1, 0, 2)
    q = jnp.concatenate(
        [q[..., :nope], _rotary(q[..., nope:], positions, shape)], -1)
    latent = x @ w["wkv_a"]
    c_kv = _rms_norm(latent[:, :shape.kv_rank], shape.eps)
    k_r = _rotary(latent[:, shape.kv_rank:], positions, shape)
    kv = (c_kv @ w["wkv_b"]).reshape(length, heads, nope + shape.v
                                     ).transpose(1, 0, 2)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r[None], (heads, length, rope))],
        -1)
    v = kv[..., nope:]
    scale = attention_scale(shape)
    # a block of queries at a time against every key, masked: one body
    # for the compiler whatever the length
    blocks = -(-length // _QUERY_BLOCK)
    padded = jnp.pad(q, ((0, 0), (0, blocks * _QUERY_BLOCK - length),
                         (0, 0)))

    def attend(start):
        queries = jax.lax.dynamic_slice_in_dim(padded, start, _QUERY_BLOCK,
                                               axis=1)
        scores = jnp.einsum("hqd,hld->hql", queries, k) * scale
        causal = (jnp.arange(length)[None, :]
                  <= start + jnp.arange(_QUERY_BLOCK)[:, None])
        scores = jnp.where(causal, scores, -jnp.inf)
        return jnp.einsum("hql,hld->hqd", jax.nn.softmax(scores, axis=-1),
                          v)

    attended = jax.lax.map(attend, jnp.arange(blocks) * _QUERY_BLOCK)
    attended = attended.transpose(1, 0, 2, 3).reshape(
        heads, blocks * _QUERY_BLOCK, shape.v)[:, :length]
    return h + attended.transpose(1, 0, 2).reshape(
        length, heads * shape.v) @ w["wo"]


@jax.jit
def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


@partial(jax.jit, static_argnames=("shape",))
def route(x, router, shape: Shape):
    """x (T, d) float32 normed input -> (g (T, k) float32, ids (T, k)):
    the experts each token chose, in the router's numbering, and their
    weights routed_scaling * s_i."""
    scores = jax.nn.softmax(x @ router, axis=-1)         # (T, E)
    tokens = scores.shape[0]
    per_group = shape.router_experts // shape.groups
    group_scores = scores.reshape(tokens, shape.groups, per_group).max(-1)
    _, kept = jax.lax.top_k(group_scores, shape.topk_groups)
    group_mask = jnp.zeros((tokens, shape.groups), bool).at[
        jnp.arange(tokens)[:, None], kept].set(True)
    masked = jnp.where(jnp.repeat(group_mask, per_group, axis=1),
                       scores, 0.0)
    weights, ids = jax.lax.top_k(masked, shape.top_k)
    return weights * shape.routed_scaling, ids


def _expert_layer_ffn(x, keys, shape: Shape, precision: str):
    """Shared(x) + the held experts' part of the routed sum, over x
    (T, d) float32; one expert's float32 weights alive at a time."""
    dtype = jnp.dtype(shape.dtype)
    router = _as_computed(_dense(keys[3], shape.d, shape.router_experts,
                                 dtype), precision)
    weights, ids = route(x, router, shape)
    shared = _swiglu_weights(keys[4:7], shape.d,
                             shape.shared * shape.moe_ff, dtype)
    out = _swiglu(x, *(_as_computed(w, precision) for w in shared))
    del shared
    for expert in range(*shape.held):
        gate = jnp.sum(jnp.where(ids == expert, weights, 0.0), axis=-1)
        stored = _expert_weights(keys, expert, shape)
        out = out + gate[:, None] * _swiglu(
            x, *(_as_computed(w, precision) for w in stored))
        del stored
    return out


def _layer(h, key, index: int, shape: Shape, precision: str):
    """One decoder layer over h (B, L, d) float32."""
    keys = jax.random.split(key, _LAYER_KEYS)
    stored = _attention_weights(keys, shape)
    w = {name: _as_computed(stored[name], precision)
         for name in _ATTENTION}
    del stored
    h = jnp.stack([_attention(row, w, shape) for row in h])
    del w
    batch, length, d = h.shape
    x = _rms_norm(h, shape.eps).reshape(batch * length, d)
    if index < shape.dense_layers:
        stored = _swiglu_weights(keys[5:8], d, shape.ff,
                                 jnp.dtype(shape.dtype))
        out = _swiglu(x, *(_as_computed(w, precision) for w in stored))
        del stored
    else:
        out = _expert_layer_ffn(x, keys[5:12], shape, precision)
    return h + out.reshape(batch, length, d)


def hidden_states(shape: Shape, seed: int, tokens,
                  precision: str = "stated", layers: int | None = None):
    """(h (B, L, d) after `layers` layers (all by default), embedding)."""
    embed_key, *layer_keys = jax.random.split(
        jax.random.PRNGKey(seed), shape.layers + 1)
    tokens = jnp.asarray(tokens, jnp.int32)
    embedding = _as_computed(_embedding(embed_key, shape), precision,
                             axis=1)
    h = jnp.take(embedding, jnp.clip(tokens, 0, shape.vocab - 1), axis=0)
    for index, key in enumerate(layer_keys[:layers]):
        h = _layer(h, key, index, shape, precision)
    return h, embedding


def logits_at(shape: Shape, seed: int, tokens, positions,
              precision: str = "stated"):
    """Reference logits (B, P, vocab) float32 of `tokens` (B, L) int32 at
    `positions` (B, P): logits[b, p] scores the token that follows
    tokens[b, :positions[b, p] + 1]."""
    positions = jnp.asarray(positions, jnp.int32)
    with jax.default_matmul_precision("highest"):
        h, embedding = hidden_states(shape, seed, tokens, precision)
        picked = jnp.take_along_axis(h, positions[:, :, None], axis=1)
        return _rms_norm(picked, shape.eps) @ embedding.T
