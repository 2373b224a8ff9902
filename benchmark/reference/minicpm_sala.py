"""Plain reference for MiniCPM-SALA's hybrid decoder (openbmb/MiniCPM-SALA),
or a pipeline stage of it.

float32 jax.numpy at `default_matmul_precision("highest")`, no kernels, no
cache, no state handed on, no batching: a full causal forward over one
whole sequence at a time.  `mixer_types[i]` names layer i "minicpm4" (a
sparse layer) or "lightning-attn" (a lightning layer); every layer, for
the hidden rows h, with s = scale_depth / sqrt(mup_denominator) (the
PUBLISHED depth, 32, whatever the cut):

    h = h + s mixer(norm_1(h));   h = h + s mlp(norm_2(h))

h_0 = scale_emb E[token]; after the last layer logits = W_head (norm(h) /
(hidden_size / dim_model_base)), W_head untied.  Every norm is RMS
(rms_norm_eps) times its stored gain w; mlp is SwiGLU.

Lightning layer (H heads of d_h), for the normed row u_t:

    [q | k | v | g]_t = u_t W_qkvg                          no bias
    q, k RMS-normed a head (gains of their own), then rotated (rope_theta,
    the whole head, by halves) at position t;  q = q / sqrt(d_h)
    a head:  S_t = lambda_h S_{t-1} + k_t^T v_t,  o_t = q_t S_t
             S (d_h, d_h) float32, S_-1 = 0, a plain lax.scan over rows
             lambda_h = exp(-2^(-8 h / H)), h = 1 .. H
    out_t = (o_t / rms(o_t) * w_n * sigmoid(g_t)) W_o
                        the norm a head, its gain w_n shared by the heads

Sparse layer (H query heads over G K/V heads of head_dim, NO positional
encoding):

    u W_q is H x 2 head_dim: a head's first half its query, its second
    half its gate;   q = norm(q), k = norm(k) a head, gains of their own
    InfLLM-v2 selection for the query at position t >= dense_len:
      Kc_j = mean(K[stride j : stride j + kernel]), defined once position
             stride j + kernel - 1 <= t
      p_h = softmax_j(q_h . Kc_j / sqrt(head_dim)) over the defined j
      r_g[j] = sum of p_h[j] over the H / G heads of K/V group g
      R_g[b] = max r_g[j] over the j whose span overlaps block b
               (positions block b .. block b + block - 1)
      chosen: the first init_blocks blocks, the window_size / block blocks
              that end with the query's own, and the highest R_g of the
              rest until topk are chosen in all (ties to the lower block)
    o_h = softmax attention of q_h over the positions u <= t of the chosen
    blocks (t < dense_len: over every u <= t), scale head_dim^-1/2
    out = (o * sigmoid(gate)) W_o

*Departure, noted:* the published code switches between dense and selected
attention on the length of the whole call; here it is by the query's own
position, so that a prefill and the steps after it equal one full forward.

It imports nothing of the program and takes nothing the program made: the
weights are made here from the seed, layer by layer, by the draws the
program's seeded initialiser makes (layer i from the i-th of the seed
key's splits; matrices normal / sqrt(fan_in) rounded to the serving dtype,
embedding normal * 0.02, the head from fold_in(the seed key, layers + 2);
every gain 1 but the sparse layers' q gain, 3 sqrt(kernel), so that q . Kc
/ sqrt(head_dim) spreads by about 3 and a choice of blocks matters).
`norms` hands in other gains (tests).

Assumed, each in the configuration file's `assumed`: the decay (not in
the published config.json as the catalog has it), the selection's sizes
(the MiniCPM4 family's `sparse_config`), the dense rule above, no
activation on q and k beyond the norm, gains stored as w, the order of the
columns of W_qkvg and W_q, seeded weights.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_QUERY_BLOCK = 128
_MLP_ROWS = 1024
_LAYER_KEYS = 8
_SPARSE = {"kernel_size": 32, "kernel_stride": 16, "block_size": 64,
           "topk": 64, "init_blocks": 1, "window_size": 2048,
           "dense_len": 8192}
# the matrices the int8 control rounds; the norms stay as they are
_MATRICES = ("w_qkvg", "w_out", "wq", "wk", "wv", "wo", "w_gate", "w_up",
             "w_down")


@dataclass(frozen=True)
class Shape:
    vocab: int
    d: int
    mixers: tuple           # "minicpm4" | "lightning-attn" a layer
    heads: int
    kv_heads: int
    hd: int
    ff: int
    l_heads: int
    l_hd: int
    theta: float
    scale_emb: float
    scale_depth: float
    mup_denominator: float
    dim_base: float
    kernel: int
    stride: int
    block: int
    topk: int
    init: int
    window: int
    dense_len: int
    eps: float = 1e-6
    dtype: str = "bfloat16"

    @property
    def layers(self) -> int:
        return len(self.mixers)

    @property
    def branch(self) -> float:
        return self.scale_depth / float(np.sqrt(self.mup_denominator))


def shape_of(lm: dict) -> Shape:
    """From a configuration file's published keys (`sparse_config` where
    the file carries its own)."""
    sparse = {**_SPARSE, **(lm.get("sparse_config") or {})}
    layers = int(lm["num_hidden_layers"])
    mixers = tuple(lm["mixer_types"])
    assert len(mixers) == layers, (len(mixers), layers)
    return Shape(
        vocab=int(lm["vocab_size"]), d=int(lm["hidden_size"]), mixers=mixers,
        heads=int(lm["num_attention_heads"]),
        kv_heads=int(lm["num_key_value_heads"]), hd=int(lm["head_dim"]),
        ff=int(lm["intermediate_size"]), l_heads=int(lm["lightning_nh"]),
        l_hd=int(lm["lightning_head_dim"]), theta=float(lm["rope_theta"]),
        scale_emb=float(lm["scale_emb"]),
        scale_depth=float(lm["scale_depth"]),
        mup_denominator=float(lm.get("mup_denominator", layers)),
        dim_base=float(lm["dim_model_base"]),
        kernel=int(sparse["kernel_size"]),
        stride=int(sparse["kernel_stride"]), block=int(sparse["block_size"]),
        topk=int(sparse["topk"]), init=int(sparse["init_blocks"]),
        window=int(sparse["window_size"]), dense_len=int(sparse["dense_len"]),
        eps=float(lm["rms_norm_eps"]),
        dtype=str(lm.get("torch_dtype", lm.get("dtype", "bfloat16"))))


# -- weights from the seed ---------------------------------------------------

def _dense(key, rows: int, cols: int, dtype):
    # drawn, scaled and rounded as three separate operations, as a seeded
    # initialiser run eagerly does
    return (jax.random.normal(key, (rows, cols), jnp.float32)
            * (1.0 / np.sqrt(rows))).astype(dtype)


def _layer_weights(key, index: int, shape: Shape) -> dict:
    keys = jax.random.split(key, _LAYER_KEYS)
    dtype = jnp.dtype(shape.dtype)
    ones = lambda width: jnp.ones((width,), jnp.float32)      # noqa: E731
    if shape.mixers[index] == "lightning-attn":
        inner = shape.l_heads * shape.l_hd
        mixer = {"w_qkvg": _dense(keys[0], shape.d, 4 * inner, dtype),
                 "w_out": _dense(keys[1], inner, shape.d, dtype),
                 "q_norm": ones(shape.l_hd), "k_norm": ones(shape.l_hd),
                 "out_norm": ones(shape.l_hd)}
        ffn = keys[2:5]
    else:
        q, kv = shape.heads * shape.hd, shape.kv_heads * shape.hd
        mixer = {"wq": _dense(keys[0], shape.d, 2 * q, dtype),
                 "wk": _dense(keys[1], shape.d, kv, dtype),
                 "wv": _dense(keys[2], shape.d, kv, dtype),
                 "wo": _dense(keys[3], q, shape.d, dtype),
                 "q_norm": ones(shape.hd) * 3.0 * float(
                     np.sqrt(shape.kernel)),
                 "k_norm": ones(shape.hd)}
        ffn = keys[4:7]
    return {**mixer,
            "w_gate": _dense(ffn[0], shape.d, shape.ff, dtype),
            "w_up": _dense(ffn[1], shape.d, shape.ff, dtype),
            "w_down": _dense(ffn[2], shape.ff, shape.d, dtype),
            "norm_1": ones(shape.d), "norm_2": ones(shape.d)}


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
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def _rotary(x, theta: float):
    """x (heads, L, hd): every column rotated, by halves, at positions 0
    .. L - 1."""
    width = x.shape[-1]
    frequencies = 1.0 / (theta ** (
        jnp.arange(0, width, 2, dtype=jnp.float32) / width))
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * frequencies
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :width // 2], x[..., width // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def decays(heads: int):
    """lambda_h (heads,): exp(-2^(-8 h / heads)), h = 1 .. heads."""
    h = np.arange(1, heads + 1, dtype=np.float64)
    return jnp.asarray(np.exp(-(2.0 ** (-8.0 * h / heads))), jnp.float32)


@partial(jax.jit, static_argnames=("shape",))
def _lightning_mixer(h, w: dict, shape: Shape):
    """The lightning mixer's output over one sequence h (L, d) float32,
    from S = 0."""
    length, heads, hd = h.shape[0], shape.l_heads, shape.l_hd
    u = _rms_norm(h, w["norm_1"], shape.eps)
    mixed = (u @ w["w_qkvg"]).reshape(length, 4, heads, hd)
    q, k, v, gate = (mixed[:, part] for part in range(4))     # (L, H, hd)

    def turned(x, gain):
        x = _rms_norm(x, gain, shape.eps).transpose(1, 0, 2)
        return _rotary(x, shape.theta).transpose(1, 0, 2)

    q = turned(q, w["q_norm"]) / np.sqrt(hd)
    k = turned(k, w["k_norm"])
    decay = decays(heads)[:, None, None]

    def row(state, xs):
        q_t, k_t, v_t = xs
        state = decay * state + k_t[:, :, None] * v_t[:, None, :]
        return state, jnp.sum(state * q_t[:, :, None], axis=1)

    _, o = jax.lax.scan(row, jnp.zeros((heads, hd, hd), jnp.float32),
                        (q, k, v))
    o = _rms_norm(o, w["out_norm"], shape.eps) * jax.nn.sigmoid(gate)
    return o.reshape(length, -1) @ w["w_out"]


def block_choice(q, k, positions, shape: Shape):
    """The blocks the queries q (H, T, hd) at `positions` (T,) choose
    among the keys k (G, L, hd) of their sequence, L a multiple of the
    block: (G, T, L // block) bool.  Rows under dense_len choose by the
    same rule (the caller does not ask them)."""
    groups, length, hd = k.shape
    per_group = q.shape[0] // groups
    count = length // shape.stride
    # Kc_j, j = 0 .. count - 1; the last ones run past L and are never
    # defined for a query of this sequence
    padded = jnp.concatenate(
        [k, jnp.zeros((groups, shape.kernel, hd), k.dtype)], axis=1)
    compressed = jax.vmap(
        lambda start: jnp.mean(jax.lax.dynamic_slice_in_dim(
            padded, start, shape.kernel, axis=1), axis=1),
        out_axes=1)(jnp.arange(count) * shape.stride)         # (G, J, hd)
    scores = jnp.einsum("grtd,gjd->grtj",
                        q.reshape(groups, per_group, -1, hd),
                        compressed) / np.sqrt(hd)
    last = jnp.arange(count) * shape.stride + shape.kernel - 1
    defined = last[None, :] <= positions[:, None]             # (T, J)
    p = jax.nn.softmax(jnp.where(defined, scores, -jnp.inf), axis=-1)
    r = jnp.where(defined, jnp.sum(jnp.where(defined, p, 0.0), axis=1),
                  -jnp.inf)                                   # (G, T, J)
    blocks = length // shape.block
    first = jnp.arange(count) * shape.stride
    overlaps = ((first[None, :] <= (jnp.arange(blocks)[:, None] + 1)
                 * shape.block - 1)
                & (last[None, :] >= jnp.arange(blocks)[:, None]
                   * shape.block))                            # (blocks, J)
    big = jnp.max(jnp.where(overlaps[None, None], r[:, :, None, :],
                            -jnp.inf), axis=-1)               # (G, T, blocks)
    own = positions // shape.block
    index = jnp.arange(blocks)
    local = shape.window // shape.block
    forced = ((index[None, :] < shape.init)
              | ((index[None, :] > own[:, None] - local)
                 & (index[None, :] <= own[:, None])))         # (T, blocks)
    rank = jnp.where(forced[None], jnp.inf, big)
    rank = jnp.where(index[None, None, :] <= own[None, :, None], rank,
                     -jnp.inf)
    order = jnp.argsort(-rank, axis=-1, stable=True)
    place = jnp.argsort(order, axis=-1, stable=True)          # a block's rank
    return (place < shape.topk) & (rank > -jnp.inf)


@partial(jax.jit, static_argnames=("shape",))
def _sparse_mixer(h, w: dict, shape: Shape):
    """The sparse mixer's output over one sequence h (L, d) float32, L a
    multiple of the query block and of the selection's block."""
    length, hd = h.shape[0], shape.hd
    groups, per_group = shape.kv_heads, shape.heads // shape.kv_heads
    u = _rms_norm(h, w["norm_1"], shape.eps)
    qg = (u @ w["wq"]).reshape(length, shape.heads, 2 * hd)
    gate = qg[..., hd:]                                       # (L, H, hd)

    def heads(y, count):
        return y.reshape(length, count, hd).transpose(1, 0, 2)

    q = _rms_norm(qg[..., :hd].transpose(1, 0, 2), w["q_norm"], shape.eps)
    k = _rms_norm(heads(u @ w["wk"], groups), w["k_norm"], shape.eps)
    v = heads(u @ w["wv"], groups)
    at = jnp.arange(length)

    def rows(start):
        positions = start + jnp.arange(_QUERY_BLOCK)
        mine = jax.lax.dynamic_slice_in_dim(q, start, _QUERY_BLOCK, axis=1)
        chosen = block_choice(mine, k, positions, shape)      # (G, T, blocks)
        seen = jnp.repeat(chosen, shape.block, axis=-1)       # (G, T, L)
        seen = jnp.where((positions < shape.dense_len)[None, :, None], True,
                         seen) & (at[None, None, :] <= positions[None, :,
                                                                 None])
        scores = jnp.einsum("grtd,gud->grtu",
                            mine.reshape(groups, per_group, -1, hd),
                            k) / np.sqrt(hd)
        weights = jax.nn.softmax(
            jnp.where(seen[:, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("grtu,gud->grtd", weights, v)

    out = jax.lax.map(rows, jnp.arange(0, length, _QUERY_BLOCK))
    # (blocks, G, R, T, hd) -> (L, H, hd)
    out = out.transpose(0, 3, 1, 2, 4).reshape(length, shape.heads, hd)
    return (out * jax.nn.sigmoid(gate)).reshape(length, -1) @ w["wo"]


@jax.jit
def _swiglu(x, gate, up, down):
    """SwiGLU over x (L, d), L a multiple of _MLP_ROWS: that many rows at
    a time."""
    rows = x.reshape(-1, _MLP_ROWS, x.shape[-1])
    return jax.lax.map(
        lambda part: (jax.nn.silu(part @ gate) * (part @ up)) @ down,
        rows).reshape(x.shape)


def hidden_of(shape: Shape, seed: int, tokens, precision: str = "stated",
              norms: dict | None = None):
    """tokens (L,) int32 of ONE sequence -> the final norm's output (L, d)
    float32 (divided as the logits want it): a layer at a time, one layer's
    float32 weights alive at a time.  The sequence is run padded to whole
    blocks with token 0, which no earlier row sees.  `norms` = {"layers":
    [{name: w} a layer], "final": w} replaces the seeded gains."""
    tokens = jnp.asarray(tokens, jnp.int32)
    length = tokens.shape[0]
    grain = int(np.lcm.reduce([_QUERY_BLOCK, _MLP_ROWS, shape.block]))
    tokens = jnp.pad(tokens, (0, -length % grain))
    embed_key, *layer_keys = jax.random.split(jax.random.PRNGKey(seed),
                                              shape.layers + 1)
    embedding = _as_computed(
        (jax.random.normal(embed_key, (shape.vocab, shape.d), jnp.float32)
         * 0.02).astype(jnp.dtype(shape.dtype)), precision, axis=1)
    h = shape.scale_emb * jnp.take(
        embedding, jnp.clip(tokens, 0, shape.vocab - 1), axis=0)
    del embedding
    for index, key in enumerate(layer_keys):
        w = _widened(_layer_weights(key, index, shape), precision)
        if norms is not None:
            w.update({name: jnp.asarray(gain, jnp.float32)
                      for name, gain in norms["layers"][index].items()})
        mixer = (_lightning_mixer if shape.mixers[index] == "lightning-attn"
                 else _sparse_mixer)
        h = h + shape.branch * mixer(h, w, shape)
        h = h + shape.branch * _swiglu(
            _rms_norm(h, w["norm_2"], shape.eps), w["w_gate"], w["w_up"],
            w["w_down"])
        del w
    final = 1.0 if norms is None else jnp.asarray(norms["final"],
                                                  jnp.float32)
    return (_rms_norm(h, final, shape.eps)
            / (shape.d / shape.dim_base))[:length]


def head_of(shape: Shape, seed: int, precision: str = "stated"):
    """The untied head (vocab, d) float32."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), shape.layers + 2)
    return _as_computed(
        _dense(key, shape.d, shape.vocab, jnp.dtype(shape.dtype)).T,
        precision, axis=1)


def logits_at(shape: Shape, seed: int, tokens, positions,
              precision: str = "stated", norms: dict | None = None):
    """Reference logits (B, P, vocab) float32 of `tokens` (B, L) int32 at
    `positions` (B, P): logits[b, p] scores the token that follows
    tokens[b, :positions[b, p] + 1].  A sequence is run as far as its last
    asked position."""
    positions = np.asarray(positions, np.int32)
    tokens = np.asarray(tokens, np.int32)
    with jax.default_matmul_precision("highest"):
        picked = []
        for row, asked in zip(tokens, positions):
            final = hidden_of(shape, seed, row[:int(asked.max()) + 1],
                              precision, norms)
            picked.append(jnp.take(final, jnp.asarray(asked), axis=0))
            del final
        return jnp.stack(picked) @ head_of(shape, seed, precision).T
