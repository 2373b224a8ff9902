# Decoder-only transformer LM (Llama-family architecture): the framework's
# flagship model, replacing the reference's external-process LLM element
# (reference: src/aiko_services/examples/llm/elements_llm.py:137-179, which
# shells out to Ollama/OpenAI -- no in-framework model exists).
#
# TPU-first design:
#   - params are a plain pytree; layers are STACKED on a leading axis and
#     executed with lax.scan (one compiled layer body, not n_layers copies);
#   - ONE decoder layer (_decoder_layer) over three KV stores: none
#     (training, scoring: the Pallas flash kernel), a preallocated
#     contiguous cache updated in place via dynamic_update_slice and
#     donated across steps (generate(); flash prefill, masked einsum
#     decode), and the paged pool (the decode engine; the paged kernel);
#   - param_specs() gives megatron-style TP over the "model" mesh axis +
#     FSDP over "fsdp"; activation constraints shard batch on "data" and
#     sequence on "seq";
#   - make_train_step() returns a jit-able (params, opt, batch) -> step
#     with f32 cross-entropy and optax updates, shardable over the mesh.

from __future__ import annotations

import math

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..parallel.attention import (
    flash_attention, flash_attention_takes, paged_attention,
    paged_attention_reference, paged_attention_takes, ring_attention,
    sp_decode_attention, ulysses_attention)
from .layers import (
    apply_rotary, dense, init_dense, init_norm, repeat_kv, rms_norm,
    rotary_embedding)

__all__ = [
    "TransformerConfig", "init_params", "param_specs", "forward",
    "init_cache", "cache_specs", "decode_step", "generate",
    "generate_stream", "make_train_step", "count_params",
    "quantize_weights_int8", "quantized_param_specs",
    "init_paged_pool", "paged_prefill", "paged_decode_step",
    "paged_prefill_chunk", "paged_verify_step", "cache_attention_kind",
    "REMAT_POLICIES", "resolve_remat_policy",
]


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 8
    n_heads: int = 8
    n_kv_heads: int = 4
    d_ff: int = 1536
    max_seq_len: int = 2048
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    dtype: str = "bfloat16"
    # True: the long-context path.  Prefill attention shards over the
    # mesh "seq" axis (mechanism below) and cached DECODE runs
    # sp_decode_attention with the cache length sharded over "seq" --
    # lay the cache out with cache_specs(sequence_parallel=True).
    # Requires an ambient jax.set_mesh holding a "seq" axis that divides
    # the sequence length (prefill) and cache length (decode); cached
    # prefill assumes pos=0.
    sequence_parallel: bool = False
    # "ring": KV shards rotate via ppermute (any head count; causal hops
    # skipped).  "ulysses": all-to-all swaps seq-sharding for
    # head-sharding and runs dense flash locally -- fewer collectives
    # when n_heads is divisible by the seq axis.
    sp_mechanism: str = "ring"
    # > 0: the FFN becomes a switch (top-1) mixture of experts with this
    # many experts; expert weights shard over the mesh "expert" axis
    # (param_specs), giving expert parallelism.  0 = dense FFN.
    n_experts: int = 0
    # expert capacity = ceil(moe_capacity_factor * L / E) tokens per
    # batch row; overflow tokens fall through on the residual.  <= 0
    # selects the masked-dense oracle (every expert computes every
    # token -- E x the FLOPs; only for tests/tiny E).
    moe_capacity_factor: float = 1.25
    # weight of the Switch load-balancing aux loss in make_train_step
    moe_aux_weight: float = 0.01
    # "int8": KV cache stores 8-bit codes + a per-(head, position) f32
    # scale -- halves cache HBM (doubling feasible decode batch at fixed
    # memory) and halves the cache-read bandwidth that bounds decode.
    # "" keeps the compute dtype.  Quantization happens at cache WRITE
    # (one rounding per token ever); reads dequantize into the attention
    # einsum, which XLA fuses into the operand load.
    kv_dtype: str = ""

    def __post_init__(self):
        if self.sp_mechanism not in ("ring", "ulysses"):
            raise ValueError(
                f"sp_mechanism must be 'ring' or 'ulysses', got "
                f"{self.sp_mechanism!r}")
        if self.kv_dtype not in ("", "int8"):
            raise ValueError(
                f"kv_dtype must be '' (compute dtype) or 'int8', got "
                f"{self.kv_dtype!r}")
        if self.kv_dtype == "int8" and self.sequence_parallel:
            raise ValueError(
                "kv_dtype='int8' is not supported on the "
                "sequence-parallel decode path (sp_decode_attention "
                "reads the raw cache shards)")

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)


# -- parameters -------------------------------------------------------------

def _init_layer(key, config: TransformerConfig) -> dict:
    keys = jax.random.split(key, 8)
    d, hd, ff = config.d_model, config.head_dim, config.d_ff
    dtype = config.jnp_dtype
    layer = {
        "attn_norm": init_norm(d, dtype),
        "wq": init_dense(keys[0], d, config.n_heads * hd, dtype),
        "wk": init_dense(keys[1], d, config.n_kv_heads * hd, dtype),
        "wv": init_dense(keys[2], d, config.n_kv_heads * hd, dtype),
        "wo": init_dense(keys[3], config.n_heads * hd, d, dtype),
        "mlp_norm": init_norm(d, dtype),
    }
    if config.n_experts > 0:
        experts = config.n_experts

        def expert_weights(key, rows, cols):
            return {"w": (jax.random.normal(
                key, (experts, rows, cols), jnp.float32)
                / jnp.sqrt(jnp.float32(rows))).astype(dtype)}

        layer["router"] = init_dense(keys[7], d, experts, dtype)
        layer["w_gate"] = expert_weights(keys[4], d, ff)
        layer["w_up"] = expert_weights(keys[5], d, ff)
        layer["w_down"] = expert_weights(keys[6], ff, d)
    else:
        layer["w_gate"] = init_dense(keys[4], d, ff, dtype)
        layer["w_up"] = init_dense(keys[5], d, ff, dtype)
        layer["w_down"] = init_dense(keys[6], ff, d, dtype)
    return layer


def init_params(config: TransformerConfig, key) -> dict:
    embed_key, *layer_keys = jax.random.split(key, config.n_layers + 1)
    layers = [_init_layer(k, config) for k in layer_keys]
    stacked = jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *layers)
    return {
        "embed": {"w": (jax.random.normal(
            embed_key, (config.vocab_size, config.d_model), jnp.float32)
            * 0.02).astype(config.jnp_dtype)},
        "layers": stacked,
        "norm_out": init_norm(config.d_model, config.jnp_dtype),
    }


def param_specs(config: TransformerConfig,
                lm_head: bool = False) -> dict:
    """Megatron TP on 'model' + FSDP on 'fsdp' (+ EP on 'expert' for MoE
    weights); stacked-layer leaves carry a leading None for the scan axis.
    (Scaling-book recipe: shard the big matmuls, replicate the norms.)
    lm_head=True adds the untied-output-head spec (checkpoint-loaded
    Llama-3-8B+ params carry one)."""
    layer = {
        "attn_norm": {"scale": P(None, None)},
        "wq": {"w": P(None, "fsdp", "model")},
        "wk": {"w": P(None, "fsdp", "model")},
        "wv": {"w": P(None, "fsdp", "model")},
        "wo": {"w": P(None, "model", "fsdp")},
        "mlp_norm": {"scale": P(None, None)},
    }
    if config.n_experts > 0:
        layer["router"] = {"w": P(None, None, None)}
        layer["w_gate"] = {"w": P(None, "expert", "fsdp", "model")}
        layer["w_up"] = {"w": P(None, "expert", "fsdp", "model")}
        layer["w_down"] = {"w": P(None, "expert", "model", "fsdp")}
    else:
        layer["w_gate"] = {"w": P(None, "fsdp", "model")}
        layer["w_up"] = {"w": P(None, "fsdp", "model")}
        layer["w_down"] = {"w": P(None, "model", "fsdp")}
    specs = {
        "embed": {"w": P(None, "fsdp")},
        "layers": layer,
        "norm_out": {"scale": P(None)},
    }
    if lm_head:
        specs["lm_head"] = {"w": P(None, "fsdp")}
    return specs


def count_params(params) -> int:
    return sum(leaf.size for leaf in jax.tree_util.tree_leaves(params))


# -- weight-only int8 (serving decode) ---------------------------------------

_DENSE_QUANT_KEYS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def quantize_weights_int8(params: dict,
                          config: TransformerConfig) -> dict:
    """Weight-only int8 for SERVING: dense weights become 8-bit codes +
    a per-output-channel f32 scale (kept at the weight's rank so specs
    derive mechanically); embed / lm_head quantize per vocab ROW (one
    scale serves both the gather and the logits matmul, where the
    per-row scale factors out of the contraction).  Small-batch decode
    is weight-streaming-bound, so halving the bytes read per step is
    ~2x decode throughput at fixed batch.  Norms and biases stay f32;
    MoE expert FFNs stay unquantized (their dispatch einsums bypass
    dense()).  NOT for training -- optax rejects int8 leaves loudly."""
    def quant(entry: dict, axis: int) -> dict:
        w = entry["w"].astype(jnp.float32)
        scale = jnp.maximum(
            jnp.max(jnp.abs(w), axis=axis, keepdims=True), 1e-12) / 127.0
        codes = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
        out = {"w": codes, "w_scale": scale}
        if "b" in entry:
            out["b"] = entry["b"]
        return out

    dense_keys = (_DENSE_QUANT_KEYS[:4] if config.n_experts > 0
                  else _DENSE_QUANT_KEYS)
    layers = dict(params["layers"])
    for key in dense_keys:
        layers[key] = quant(layers[key], axis=-2)
    quantized = dict(params)
    quantized["layers"] = layers
    quantized["embed"] = quant(params["embed"], axis=-1)
    if "lm_head" in params:
        quantized["lm_head"] = quant(params["lm_head"], axis=-1)
    return quantized


def quantized_param_specs(config: TransformerConfig,
                          lm_head: bool = False) -> dict:
    """param_specs + a spec per w_scale plane: same layout as its
    weight with the quantization axis (collapsed to 1 by keepdims)
    unsharded -- -2 for dense per-output-channel scales, -1 for the
    embed/lm_head per-row scales."""
    def scale_spec(spec: P, axis: int) -> P:
        entries = list(tuple(spec))
        entries[axis] = None
        return P(*entries)

    specs = param_specs(config, lm_head=lm_head)
    dense_keys = (_DENSE_QUANT_KEYS[:4] if config.n_experts > 0
                  else _DENSE_QUANT_KEYS)
    layer = dict(specs["layers"])
    for key in dense_keys:
        layer[key] = dict(layer[key])
        layer[key]["w_scale"] = scale_spec(layer[key]["w"], -2)
    specs["layers"] = layer
    for name in ("embed", "lm_head"):
        if name in specs:
            specs[name] = dict(specs[name])
            specs[name]["w_scale"] = scale_spec(specs[name]["w"], -1)
    return specs


# -- KV cache ---------------------------------------------------------------

def init_cache(config: TransformerConfig, batch: int,
               max_len: int | None = None) -> dict:
    max_len = max_len or config.max_seq_len
    shape = (config.n_layers, batch, config.n_kv_heads, max_len,
             config.head_dim)
    if config.kv_dtype == "int8":
        scale_shape = shape[:-1] + (1,)
        return {"k": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(scale_shape, jnp.float32),
                "v": jnp.zeros(shape, jnp.int8),
                "v_scale": jnp.zeros(scale_shape, jnp.float32)}
    return {"k": jnp.zeros(shape, config.jnp_dtype),
            "v": jnp.zeros(shape, config.jnp_dtype)}


def cache_specs(sequence_parallel: bool = False,
                quantized: bool = False) -> dict:
    """Cache layout (layers, batch, kv_heads, len, head_dim): batch on
    "data", heads on "model" (TP); with sequence_parallel the cache LENGTH
    also shards over "seq", so long-context decode spreads KV bandwidth
    across the mesh (sp_decode_attention).  quantized=True adds the int8
    cache's per-position scale planes (same layout, head_dim collapsed)."""
    seq = "seq" if sequence_parallel else None
    spec = P(None, "data", "model", seq, None)
    if quantized:
        return {"k": spec, "k_scale": spec, "v": spec, "v_scale": spec}
    return {"k": spec, "v": spec}


def _quantize_kv(x):
    """(B, H, L, D) float -> (int8 codes, f32 scale (B, H, L, 1)):
    symmetric per-(batch, head, position) absmax scaling over head_dim.
    One rounding per written token; dequantization is codes * scale."""
    as_f32 = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(as_f32), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    codes = jnp.clip(jnp.round(as_f32 / scale), -127, 127).astype(jnp.int8)
    return codes, scale


# -- forward ----------------------------------------------------------------

def _project_qkv(config: TransformerConfig, layer, x, cos, sin):
    """x (B, L, d_model) -> rotated q (B, H, L, hd), rotated k and plain v
    (B, Hkv, L, hd)."""
    batch, length, _ = x.shape
    hd = config.head_dim
    q = dense(layer["wq"], x).reshape(
        batch, length, config.n_heads, hd).transpose(0, 2, 1, 3)
    k = dense(layer["wk"], x).reshape(
        batch, length, config.n_kv_heads, hd).transpose(0, 2, 1, 3)
    v = dense(layer["wv"], x).reshape(
        batch, length, config.n_kv_heads, hd).transpose(0, 2, 1, 3)
    return apply_rotary(q, cos, sin), apply_rotary(k, cos, sin), v


def _decoder_layer(config: TransformerConfig, layer, h, cos, sin, attend):
    """THE decoder layer, on every path: attention norm, projections and
    rotary, `attend`, wo and residual, MLP norm, FFN, residual.  Only
    `attend` differs, by where the K/V live: attend(q, k, v) stores the
    new K/V and returns (attention output (B, H, L, hd), the store's new
    leaves) -- _attend_fresh, _attend_cache, _attend_pool.  Returns
    (h, the FFN's aux loss, the store's new leaves)."""
    batch, length, _ = h.shape
    q, k, v = _project_qkv(
        config, layer, rms_norm(layer["attn_norm"], h, config.norm_eps),
        cos, sin)
    out, leaves = attend(q, k, v)
    h = h + dense(layer["wo"],
                  out.transpose(0, 2, 1, 3).reshape(batch, length, -1))
    mlp_out, aux = _mlp_block(
        config, layer, rms_norm(layer["mlp_norm"], h, config.norm_eps))
    return h + mlp_out, aux, leaves


def _sp_prefill(config: TransformerConfig, q, k, v):
    repeats = config.n_heads // config.n_kv_heads
    k, v = repeat_kv(k, repeats), repeat_kv(v, repeats)
    if config.sp_mechanism == "ulysses":
        return ulysses_attention(q, k, v, mesh=None, causal=True)
    return ring_attention(q, k, v, causal=True)


def _attend_fresh(config: TransformerConfig, q, k, v):
    """No KV store (training, scoring): causal attention over the fresh
    K/V, blockwise."""
    if config.sequence_parallel:
        return _sp_prefill(config, q, k, v), None
    return flash_attention(q, k, v, causal=True), None


def _kv_to_write(store: dict, k, v) -> dict:
    """What one layer writes to `store` (a cache's or a pool's leaves)
    for fresh k, v: themselves, or their int8 codes and scales where the
    store is int8."""
    written = {"k": k, "v": v}
    if store["k"].dtype == jnp.int8:
        written["k"], written["k_scale"] = _quantize_kv(k)
        written["v"], written["v_scale"] = _quantize_kv(v)
    return written


def cache_attention_kind(config: TransformerConfig, store: dict, batch: int,
                         length: int, pos=0) -> str:
    """"flash" or "einsum": what a forward of (batch, length) tokens at
    `pos` attends through when its K/V go to a contiguous cache of
    `store`'s dtype (a cache, or the pool paged_prefill scatters its
    cache into: their leaves are alike).  _attend_cache decides by this,
    and the engine names its prefill spans by it."""
    dtype = store["k"].dtype
    if (length > 1 and isinstance(pos, (int, np.integer)) and pos == 0
            and flash_attention_takes(batch, config.n_heads, length, dtype,
                                      dtype)):
        return "flash"
    return "einsum"


def _attend_cache(config: TransformerConfig, cache: dict, pos, q, k, v):
    """Contiguous cache (init_cache; `cache` is one layer's leaves):
    write the new K/V at `pos`, then masked attention over the whole
    buffer -- or, for a prefill from the static position 0 that
    flash_attention_takes, the same causal attention over the fresh K/V
    alone, blockwise."""
    batch, _, length, hd = q.shape
    cache = {name: jax.lax.dynamic_update_slice(cache[name], value,
                                                (0, 0, pos, 0))
             for name, value in _kv_to_write(cache, k, v).items()}
    if config.sequence_parallel and length > 1:
        # cached PREFILL: sequence-parallel attention over the fresh
        # K/V only -- valid solely at pos == 0 (the generate/prefill
        # contract); multi-token cached decode at pos > 0 would need
        # the earlier cache shards too.  Best-effort guard: a traced
        # pos cannot be checked at trace time, so the contract is
        # enforceable only for concrete ints
        if isinstance(pos, (int, np.integer)) and pos != 0:
            raise ValueError(
                "sequence-parallel cached prefill requires pos == 0 "
                f"(got pos={pos}); multi-token cached decode at "
                "pos > 0 is not supported on this path")
        return _sp_prefill(config, q, k, v), cache
    if config.sequence_parallel:
        # long-context decode: cache length sharded over the mesh
        # "seq" axis; per-device attention touches only the local
        # cache shard (GQA heads expand inside the shard), partials
        # merge with a pmax/psum online-softmax
        return sp_decode_attention(q, cache["k"], cache["v"], pos), cache
    if cache_attention_kind(config, cache, batch, length, pos) == "flash":
        # the cache holds nothing before position 0, and columns past
        # `length` were masked anyway, so the causal attention over the
        # fresh K/V is the masked one over the cache buffer -- taken
        # blockwise, grouped K/V as they are, no (length x max_len)
        # scores in HBM.  Blockwise softmax rounds differently: logits
        # agree to tolerance, not bitwise
        return flash_attention(q, k, v, causal=True), cache
    k_eff, v_eff = cache["k"], cache["v"]
    if "k_scale" in cache:
        # dequantize into the einsum operand load (int8 codes x
        # per-position scale); the cache READ stays 8-bit, which is the
        # bandwidth that bounds decode
        k_eff = (k_eff.astype(jnp.float32) * cache["k_scale"]).astype(q.dtype)
        v_eff = (v_eff.astype(jnp.float32) * cache["v_scale"]).astype(q.dtype)
    repeats = config.n_heads // config.n_kv_heads
    k_full = repeat_kv(k_eff, repeats)
    v_full = repeat_kv(v_eff, repeats)
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k_full,
                        preferred_element_type=jnp.float32) * scale
    q_pos = pos + jnp.arange(length)[:, None]
    k_pos = jnp.arange(k_eff.shape[2])[None, :]
    logits = jnp.where(k_pos <= q_pos, logits, -1e30)
    weights = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd",
                      weights.astype(v_full.dtype), v_full), cache


def _router(config: TransformerConfig, layer, x):
    """Top-1 router: returns (best (B,L) chosen expert ids, mask1 (B,L,E)
    its one-hot, weight (B,L,1) its router probability, aux scalar
    load-balancing loss).  aux = E * sum_e mean_tokens(mask1_e) *
    mean_tokens(prob_e) (Switch Transformer loss; minimized at uniform
    routing)."""
    router_logits = jnp.einsum(
        "bld,de->ble", x.astype(jnp.float32),
        layer["router"]["w"].astype(jnp.float32))
    router_probs = jax.nn.softmax(router_logits, axis=-1)
    best = jnp.argmax(router_probs, axis=-1)               # (B, L)
    mask1 = jax.nn.one_hot(best, config.n_experts,
                           dtype=jnp.float32)              # (B, L, E)
    weight = jnp.sum(router_probs * mask1, axis=-1,
                     keepdims=True)                        # (B, L, 1)
    fraction = jnp.mean(mask1, axis=(0, 1))                # (E,)
    prob_mass = jnp.mean(router_probs, axis=(0, 1))        # (E,)
    aux = config.n_experts * jnp.sum(fraction * prob_mass)
    return best, mask1, weight, aux


def _switch_moe_dense(config: TransformerConfig, layer, x):
    """Masked-dense switch dispatch: every expert computes every token, a
    one-hot mask selects the winner.  Exact (no capacity drops) but costs
    E x the dense FFN -- kept as the correctness oracle for the capacity
    dispatch and for tiny expert counts."""
    _, mask1, weight, aux = _router(config, layer, x)
    gate = jnp.einsum("bld,edf->blef", x, layer["w_gate"]["w"],
                      preferred_element_type=jnp.float32)
    up = jnp.einsum("bld,edf->blef", x, layer["w_up"]["w"],
                    preferred_element_type=jnp.float32)
    hidden = jax.nn.silu(gate) * up                        # (B, L, E, F)
    expert_out = jnp.einsum("blef,efd->bled", hidden,
                            layer["w_down"]["w"].astype(jnp.float32))
    mixed = jnp.sum(expert_out * mask1[..., None], axis=2)  # (B, L, D)
    return (mixed * weight).astype(x.dtype), aux


def _switch_moe(config: TransformerConfig, layer, x):
    """Switch (top-1) MoE FFN with CAPACITY-BASED dispatch.

    Each expert processes at most C = ceil(capacity_factor * L / E)
    tokens per batch row: tokens gather into a dense (B, E, C, D) buffer
    via a one-hot dispatch einsum (the TPU-friendly scatter -- static
    shapes, MXU-shaped matmuls, no dynamic indexing), the FFN runs
    batched over experts, and results scatter back weighted by the router
    probability.  Per-token FLOPs are ~capacity_factor x the dense FFN --
    independent of E.  Overflow tokens beyond an expert's capacity are
    dropped (standard Switch behavior; the residual connection carries
    them unchanged).  For short sequences (L < E, incremental decode)
    the capacity floor of one slot per expert would cost E x the FFN, so
    the path switches to per-token weight gather (_switch_moe_gather).

    With expert weights and the (B, E, C, ...) buffers sharded on the
    "expert" mesh axis, each device computes only its local experts:
    per-device FLOPs scale with E_local, not E (true expert parallelism);
    XLA inserts the all-to-all-shaped collectives around the dispatch/
    combine einsums.

    Returns (output (B, L, D), aux load-balancing loss scalar).
    """
    if config.moe_capacity_factor <= 0:                    # oracle path
        return _switch_moe_dense(config, layer, x)
    batch, length, d_model = x.shape
    experts = config.n_experts
    if length < experts:
        # capacity would floor at 1 slot x E experts (E x the FLOPs);
        # gather the chosen expert's weights per token instead
        return _switch_moe_gather(config, layer, x)
    capacity = max(1, math.ceil(
        config.moe_capacity_factor * length / experts))
    capacity = min(capacity, length)

    _, mask1, weight, aux = _router(config, layer, x)
    # position of each token within its expert's queue (per batch row)
    position = jnp.cumsum(mask1, axis=1) * mask1           # 1-based
    keep = mask1 * (position <= capacity)                  # (B, L, E)
    disp = keep[..., None] * jax.nn.one_hot(
        ((position - 1.0) * keep).astype(jnp.int32), capacity,
        dtype=jnp.float32)
    # disp: (B, L, E, C) one-hot dispatch/combine tensor.  Everything
    # stays in model dtype into the MXU matmuls (one-hot selection is
    # exact in bf16); accumulation is f32 via preferred_element_type.
    expert_in = jnp.einsum("bld,blec->becd", x, disp.astype(x.dtype))
    gate = jnp.einsum("becd,edf->becf", expert_in, layer["w_gate"]["w"],
                      preferred_element_type=jnp.float32)
    up = jnp.einsum("becd,edf->becf", expert_in, layer["w_up"]["w"],
                    preferred_element_type=jnp.float32)
    hidden = (jax.nn.silu(gate) * up).astype(x.dtype)      # (B, E, C, F)
    expert_out = jnp.einsum("becf,efd->becd", hidden,
                            layer["w_down"]["w"],
                            preferred_element_type=jnp.float32)
    combine = disp * weight[..., None]                     # (B, L, E, C)
    out = jnp.einsum("becd,blec->bld", expert_out, combine)
    return out.astype(x.dtype), aux


def _switch_moe_gather(config: TransformerConfig, layer, x):
    """Per-token expert-weight GATHER dispatch for short sequences
    (incremental decode, L < E): read only the selected expert's weight
    rows -- per-token FLOPs and HBM reads equal ONE dense FFN, vs the
    capacity path's E floor-of-one slots.  Optimal when expert weights
    are replicated (single chip); under EP sharding the dispatch einsums
    would keep the weights stationary (no caller shards them yet)."""
    best, _, weight, aux = _router(config, layer, x)
    wg = jnp.take(layer["w_gate"]["w"], best, axis=0)      # (B, L, D, F)
    wu = jnp.take(layer["w_up"]["w"], best, axis=0)
    wd = jnp.take(layer["w_down"]["w"], best, axis=0)      # (B, L, F, D)
    gate = jnp.einsum("bld,bldf->blf", x, wg,
                      preferred_element_type=jnp.float32)
    up = jnp.einsum("bld,bldf->blf", x, wu,
                    preferred_element_type=jnp.float32)
    hidden = (jax.nn.silu(gate) * up).astype(x.dtype)
    out = jnp.einsum("blf,blfd->bld", hidden, wd,
                     preferred_element_type=jnp.float32)
    return (out * weight).astype(x.dtype), aux


def _embed(params: dict, config: TransformerConfig, tokens):
    """Token embedding gather shared by forward() and the paged decode
    path (one definition, so the two can never drift bitwise).
    mode="clip": out-of-vocab ids clamp to the last row instead of
    jnp.take's default FILL mode, whose NaN embeddings silently poison
    every downstream activation."""
    h = jnp.take(params["embed"]["w"], tokens, axis=0, mode="clip")
    if h.dtype == jnp.int8:
        # int8 embed (quantize_weights_int8): gather the rows' scales
        # alongside and dequantize only the gathered tokens
        h = (h.astype(jnp.float32)
             * jnp.take(params["embed"]["w_scale"], tokens, axis=0,
                        mode="clip")).astype(config.jnp_dtype)
    return h


def _mlp_block(config: TransformerConfig, layer, mlp_in):
    """One layer's FFN (dense SwiGLU or switch MoE).  Returns
    (output, aux)."""
    if config.n_experts > 0:
        return _switch_moe(config, layer, mlp_in)
    return dense(
        layer["w_down"],
        jax.nn.silu(dense(layer["w_gate"], mlp_in))
        * dense(layer["w_up"], mlp_in)), jnp.zeros((), jnp.float32)


def _lm_head(params: dict, config: TransformerConfig, h):
    """Output norm + logits head shared by forward() and the paged
    decode path.  Untied output head when the checkpoint ships one
    (Llama-3-8B+, models/weights.py load_llama_params); tied embedding
    otherwise."""
    h = rms_norm(params["norm_out"], h, config.norm_eps)
    head = params.get("lm_head", params["embed"])
    logits = jnp.einsum("bld,vd->blv", h.astype(jnp.float32),
                        head["w"].astype(jnp.float32))
    if head["w"].dtype == jnp.int8:
        # per-row scales factor out of the contraction: the einsum
        # streams 8-bit codes, the (V,) scale applies to the result
        logits = logits * head["w_scale"][:, 0]
    return logits


def forward(params: dict, config: TransformerConfig, tokens,
            cache: dict | None = None, pos: int = 0,
            activation_specs: bool = False, return_aux: bool = False,
            remat_policy: str | None = None):
    """tokens (B, L) int32 -> logits (B, L, V) [+ updated cache].

    With cache=None this is a pure causal prefill (training / scoring).
    With a cache, K/V are written at `pos` (traced or static int) and the
    updated cache is returned -- the incremental-decode path.
    return_aux=True (cache-less path only) additionally returns the mean
    MoE load-balancing loss across layers (0.0 for dense FFN).
    remat_policy (cache-less path only) wraps the per-layer scan body in
    jax.checkpoint with the named jax.checkpoint_policies entry, trading
    backward-pass recompute for activation memory (REMAT_POLICIES).
    """
    if return_aux and cache is not None:
        raise ValueError(
            "return_aux is only meaningful on the cache-less (training/"
            "scoring) path; with a cache forward returns (logits, cache)")
    if remat_policy not in (None, "none") and cache is not None:
        raise ValueError(
            "remat_policy is only meaningful on the cache-less "
            "(training/scoring) path; incremental decode saves nothing "
            "by rematerializing")
    if activation_specs:
        # batch on "data", sequence on "seq" -- but only the axes the
        # ambient mesh actually has (an EP-only mesh has no "seq")
        names = jax.sharding.get_abstract_mesh().axis_names
        act_spec = P("data" if "data" in names else None,
                     "seq" if "seq" in names else None, None)
    h = _embed(params, config, tokens)
    if activation_specs:
        h = jax.lax.with_sharding_constraint(h, act_spec)
    positions = pos + jnp.arange(tokens.shape[1])
    cos, sin = rotary_embedding(positions, config.head_dim,
                                config.rope_theta)
    cos, sin = cos[None, None], sin[None, None]  # (1, 1, L, hd/2)

    def layer_step(carry, xs):
        h, aux_sum = carry
        layer, layer_cache = xs
        h, aux, new_cache = _decoder_layer(
            config, layer, h, cos, sin,
            partial(_attend_fresh, config) if layer_cache is None
            else partial(_attend_cache, config, layer_cache, pos))
        aux_sum = aux_sum + aux
        if activation_specs:
            h = jax.lax.with_sharding_constraint(h, act_spec)
        return (h, aux_sum), new_cache

    aux0 = jnp.zeros((), jnp.float32)
    if cache is None:
        body = lambda carry, layer: layer_step(carry, (layer, None))  # noqa: E731
        policy = resolve_remat_policy(remat_policy)
        if policy is not None:
            # remat over the scanned layer body: the standard trade --
            # drop (policy-selected) activations in the forward pass,
            # recompute them during backward.  prevent_cse=False is the
            # documented setting under scan (the scan boundary already
            # blocks the CSE that prevent_cse guards against).
            body = jax.checkpoint(body, policy=policy, prevent_cse=False)
        (h, aux_sum), _ = jax.lax.scan(body, (h, aux0), params["layers"])
        new_cache = None
    else:
        (h, aux_sum), new_cache = jax.lax.scan(
            layer_step, (h, aux0), (params["layers"], cache))
    logits = _lm_head(params, config, h)
    if new_cache is None:
        if return_aux:
            return logits, aux_sum / max(config.n_layers, 1)
        return logits
    return logits, new_cache


# -- generation -------------------------------------------------------------

@partial(jax.jit, static_argnames=("config",), donate_argnums=(2,))
def decode_step(params, config: TransformerConfig, cache, token, pos):
    """One incremental decode step: token (B, 1) at absolute position pos
    (B-shaped traced int32).  Returns (next_token greedy, logits, cache)."""
    logits, cache = forward(params, config, token, cache=cache, pos=pos)
    next_token = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
    return next_token[:, None], logits, cache


@partial(jax.jit, static_argnames=("config", "max_new_tokens"),
         donate_argnums=(3,))
def _generate_compiled(params, config: TransformerConfig, prompt, cache,
                       max_new_tokens: int):
    """Module-level jit (stable function identity, so repeated generate()
    calls hit the compile cache): prefill + fori_loop greedy decode."""
    batch, prompt_len = prompt.shape
    logits, cache = forward(params, config, prompt, cache=cache, pos=0)
    first = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
    out = jnp.zeros((batch, max_new_tokens), jnp.int32)
    out = jax.lax.dynamic_update_slice(out, first, (0, 0))

    def body(step, carry):
        out, cache = carry
        token = jax.lax.dynamic_slice(out, (0, step - 1), (batch, 1))
        step_logits, cache = forward(params, config, token, cache=cache,
                                     pos=prompt_len + step - 1)
        next_token = jnp.argmax(step_logits[:, -1], axis=-1).astype(
            jnp.int32)[:, None]
        out = jax.lax.dynamic_update_slice(out, next_token, (0, step))
        return out, cache

    out, cache = jax.lax.fori_loop(1, max_new_tokens, body, (out, cache))
    return out, cache


def generate(params, config: TransformerConfig, prompt,
             max_new_tokens: int, cache=None):
    """Greedy generation: prefill the prompt, then fori_loop decode inside
    one jit.  Returns (tokens (B, max_new_tokens) int32, cache).  A
    caller-supplied cache (e.g. mesh-sharded) is DONATED to the jit; use
    the returned cache, never the invalidated input buffers."""
    batch, prompt_len = prompt.shape
    if cache is None:
        cache = init_cache(config, batch,
                           max_len=prompt_len + max_new_tokens)
    return _generate_compiled(params, config, prompt, cache,
                              int(max_new_tokens))


@partial(jax.jit, static_argnames=("config",), donate_argnums=(3,))
def _prefill_step(params, config: TransformerConfig, prompt, cache):
    logits, cache = forward(params, config, prompt, cache=cache, pos=0)
    first = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
    return first[:, None], cache


@partial(jax.jit, static_argnames=("config", "chunk"), donate_argnums=(3,))
def _decode_chunk(params, config: TransformerConfig, token, cache, pos,
                  chunk: int):
    """`chunk` greedy steps as ONE device program (lax.fori_loop): one
    dispatch per chunk, so host dispatch latency never rides per-token."""
    batch = token.shape[0]
    out = jnp.zeros((batch, chunk), jnp.int32)

    def body(step, carry):
        out, token, cache = carry
        logits, cache = forward(params, config, token, cache=cache,
                                pos=pos + step)
        token = jnp.argmax(logits[:, -1], axis=-1).astype(
            jnp.int32)[:, None]
        out = jax.lax.dynamic_update_slice(out, token, (0, step))
        return out, token, cache

    out, token, cache = jax.lax.fori_loop(0, chunk, body,
                                          (out, token, cache))
    return out, token, cache


def generate_stream(params, config: TransformerConfig, prompt,
                    max_new_tokens: int, cache=None, chunk: int = 8):
    """Streaming greedy generation: yields (offset, tokens (B, n)) numpy
    chunks as they decode -- the serving path behind LMGenerate's streamed
    token output (reference capability: Ollama token streaming,
    elements_llm.py:137-179).  Prefill is one jit; decode runs in
    on-device chunks of `chunk` steps, so the host sees one dispatch +
    one transfer per chunk."""
    batch, prompt_len = prompt.shape
    if cache is None:
        cache = init_cache(config, batch,
                           max_len=prompt_len + max_new_tokens)
    token, cache = _prefill_step(params, config, prompt, cache)
    yield 0, jax.device_get(token)
    produced = 1
    while produced < max_new_tokens:
        size = min(chunk, max_new_tokens - produced)
        block, token, cache = _decode_chunk(
            params, config, token, cache,
            jnp.int32(prompt_len + produced - 1), int(size))
        yield produced, jax.device_get(block)
        produced += size


# -- paged KV: the continuous-batching decode substrate ----------------------
#
# generate() above is a CLOSED batch: every sequence in the jit must
# finish before a new request touches the chip.  The decode/ subsystem
# keeps one fixed-size POOL of KV blocks plus per-slot block tables, so
# requests are admitted and evicted mid-decode without an array shape
# changing.  The layer is _decoder_layer either way; the pool is its
# third KV store (_attend_pool).  What keeps it token-compatible with
# generate():
#
#   - blocks hold what the contiguous cache would (_kv_to_write; a whole
#     prefill reshapes a forward() cache into blocks), and a step writes
#     its K/V into the donated pool where it lies (_write_window);
#   - the step's attention has _attend_cache's mathematics and mask --
#     bf16/f32 operands, float32 scores and accumulation, a softmax over
#     exactly the positions <= the query's -- taken BLOCKWISE by the
#     paged-attention kernel (parallel/attention.py).  Positions beyond
#     a slot's cursor hold garbage (stale or trash) but get exactly zero
#     weight.  Blockwise softmax rounds differently from one softmax
#     over the whole row, so TOKENS equal generate()'s
#     (tests/test_decode.py) and logits agree to tolerance
#     (tests/test_paged_attention.py, tests/test_transformer.py), not
#     bitwise;
#   - inactive slots compute on a reserved TRASH block (index 0, never
#     allocated) so the step's shapes -- (slots, max_blocks) -- are
#     compile-time constants across any admission/eviction sequence.

def init_paged_pool(config: TransformerConfig, num_blocks: int,
                    block_size: int) -> dict:
    """Preallocated paged KV pool: `num_blocks` blocks of `block_size`
    token positions each, shared by every decode slot through per-slot
    block tables.  Block 0 is the engine's reserved trash block
    (inactive-slot writes land there).  Same leaf names/dtypes as
    init_cache, so the int8 KV path carries over unchanged."""
    shape = (config.n_layers, num_blocks, config.n_kv_heads, block_size,
             config.head_dim)
    if config.kv_dtype == "int8":
        scale_shape = shape[:-1] + (1,)
        return {"k": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(scale_shape, jnp.float32),
                "v": jnp.zeros(shape, jnp.int8),
                "v_scale": jnp.zeros(scale_shape, jnp.float32)}
    return {"k": jnp.zeros(shape, config.jnp_dtype),
            "v": jnp.zeros(shape, config.jnp_dtype)}


@partial(jax.jit, static_argnames=("config",), donate_argnums=(2,))
def paged_prefill(params, config: TransformerConfig, pool, prompt,
                  table_row, true_len):
    """Prefill one request into its pool blocks.  prompt is (1, Lb)
    with Lb a multiple of the pool's block size (the engine right-pads
    to a bucket, so one executable serves every prompt length in the
    bucket); table_row (max_blocks,) names the slot's blocks, of which
    the first Lb//block_size receive the prompt's K/V.  Returns
    (pool, first_token) where first_token is the greedy token after the
    TRUE prompt length -- causal masking makes logits at true_len-1
    independent of the right-padding.  One executable per bucket; the
    decode loop never recompiles (paged_decode_step below)."""
    block_size = pool["k"].shape[3]
    local = init_cache(config, 1, max_len=prompt.shape[1])
    logits, local = forward(params, config, prompt, cache=local, pos=0)
    first = jnp.argmax(logits[0, true_len - 1]).astype(jnp.int32)
    blocks = prompt.shape[1] // block_size
    new_pool = {}
    for name, written in local.items():
        # (nl, 1, H, Lb, d) -> (nl, blocks, H, block_size, d), scattered
        # into the slot's first `blocks` pool entries
        entry = written[:, 0]
        layers, heads, _, depth = entry.shape
        entry = entry.reshape(layers, heads, blocks, block_size,
                              depth).transpose(0, 2, 1, 3, 4)
        new_pool[name] = pool[name].at[:, table_row[:blocks]].set(entry)
    return new_pool, first


def _write_window(leaf, value, layer, write_blocks, write_offsets):
    """Write the window's new K/V (or scales) into one pool leaf where
    it lies.  leaf (L, num_blocks, H, block, d); value (S, H, W, d);
    write_blocks/write_offsets (S, W).  One dynamic_update_slice per
    window position, unrolled: on the chip a scatter, and a rolled loop
    of updates too, make XLA re-lay the whole leaf out (two copies of it
    a layer); these update in place in the layout the kernel reads.
    Later positions win where inert rows share the trash block."""
    slots, _, window, _ = value.shape
    for s in range(slots):
        for i in range(window):
            # (H, d) -> (1, 1, H, 1, d) at [layer, block, :, offset, :]
            leaf = jax.lax.dynamic_update_slice(
                leaf, value[s, :, i][None, None, :, None, :],
                (layer, write_blocks[s, i], 0, write_offsets[s, i], 0))
    return leaf


def _attend_pool(config: TransformerConfig, pool: dict, layer, tables,
                 positions, write_blocks, write_offsets, q, k, v):
    """Paged pool (init_paged_pool; `pool` is the whole pool, `layer`
    this layer's index into it): write the WHOLE window's K/V where it
    lies, then attend through each slot's block table -- so later
    window positions attend to earlier ones causally.  The kernel walks
    the live blocks in place where paged_attention_takes; its oracle,
    the table-wide gather + einsum, serves the rest (an int8 pool,
    whose scales dequantize the gathered view as the contiguous int8
    cache's do; a window too large for VMEM; on the chip a head_dim off
    the 128 lanes)."""
    pool = {name: _write_window(pool[name], value, layer, write_blocks,
                                write_offsets)
            for name, value in _kv_to_write(pool, k, v).items()}
    attend = (paged_attention if paged_attention_takes(
        config.n_heads, q.shape[2], config.head_dim, pool["k"].dtype)
        else paged_attention_reference)
    scales = ((pool["k_scale"], pool["v_scale"]) if "k_scale" in pool
              else ())
    return attend(q, pool["k"], pool["v"], layer, tables, positions,
                  *scales), pool


def _paged_window(params, config: TransformerConfig, pool, tables,
                  positions, tokens, write_blocks, write_offsets):
    """The decoder over a per-slot TOKEN WINDOW and the paged pool --
    the one traced implementation behind paged_decode_step (window 1),
    paged_verify_step (speculative verification, window k+1), and
    paged_prefill_chunk (chunked prefill, window = chunk bucket).

    tokens (slots, W) are consumed left-to-right per slot: window
    position i sits at absolute position positions[slot] + i, its K/V
    lands at (write_blocks[slot, i], write_offsets[slot, i]), and rows
    the engine wants inert point their writes at the trash block.
    Returns (pool, greedy (slots, W)) where greedy[s, i] is the greedy
    token AFTER consuming window positions 0..i -- the tokens W
    successive single-token decode steps would produce, which is the
    identity the chunked-prefill and speculative tests pin."""
    h = _embed(params, config, tokens)
    q_pos = positions[:, None] + jnp.arange(tokens.shape[1])[None, :]
    cos, sin = rotary_embedding(q_pos, config.head_dim,
                                config.rope_theta)
    cos, sin = cos[:, None], sin[:, None]        # (S, 1, W, hd/2)

    def layer_step(carry, xs):
        # the pool rides the loop as CARRY and is written where it lies
        # (indexed by layer): as scan xs -> ys every step rebuilt it
        # whole.  The FFN's aux loss is dropped: nothing here trains
        h, pool = carry
        layer, index = xs
        h, _, pool = _decoder_layer(
            config, layer, h, cos, sin,
            partial(_attend_pool, config, pool, index, tables, positions,
                    write_blocks, write_offsets))
        return (h, pool), None

    (h, new_pool), _ = jax.lax.scan(
        layer_step, (h, pool),
        (params["layers"], jnp.arange(config.n_layers)))
    logits = _lm_head(params, config, h)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return new_pool, greedy


@partial(jax.jit, static_argnames=("config",), donate_argnums=(2,))
def paged_decode_step(params, config: TransformerConfig, pool, tables,
                      positions, tokens, write_blocks, write_offsets):
    """ONE greedy decode step over ALL slots of a continuous-batching
    engine.  tables (slots, max_blocks) int32 maps each slot's logical
    positions onto pool blocks; positions (slots,) is each slot's next
    write position; tokens (slots, 1) the previous greedy token;
    write_blocks/write_offsets (slots,) the precomputed pool location
    of this step's K/V (the engine points INACTIVE slots at the trash
    block, so the call is shape-stable across any admit/evict
    sequence -- zero recompiles after the first step).  Returns
    (pool, next_tokens (slots, 1)); inactive rows are garbage the
    engine ignores.

    Per-slot positions (unlike forward's scalar `pos`) are the whole
    point: slot 3 can be 400 tokens into its completion while slot 0 is
    on its first -- the rotary phase and causal mask resolve per row.
    The window-1 instantiation of _paged_window."""
    return _paged_window(params, config, pool, tables, positions,
                         tokens, write_blocks[:, None],
                         write_offsets[:, None])


@partial(jax.jit, static_argnames=("config",), donate_argnums=(2,))
def paged_verify_step(params, config: TransformerConfig, pool, tables,
                      positions, tokens, write_blocks, write_offsets):
    """Speculative-decoding verification: a decode step with a TOKEN
    WINDOW per slot instead of a single position.  tokens (slots, W)
    holds [last emitted token, draft_1..draft_{W-1}] per slot; the
    target consumes all W positions in ONE batched forward (the
    weight stream is read once for W tokens -- the whole point at
    small batch) and returns greedy (slots, W) where greedy[s, i] is
    the target's greedy token after window position i.  The engine
    accepts the longest prefix with draft_j == greedy[j-1], which
    keeps emitted tokens bit-identical to plain greedy decode.
    write_blocks/write_offsets (slots, W); overflow/inactive window
    positions point at the trash block.  One executable per W."""
    return _paged_window(params, config, pool, tables, positions,
                         tokens, write_blocks, write_offsets)


@partial(jax.jit, static_argnames=("config",), donate_argnums=(2,))
def paged_prefill_chunk(params, config: TransformerConfig, pool, tokens,
                        table_row, start, write_blocks, write_offsets):
    """Prefill ONE request's next `C` prompt tokens into its pool
    blocks, attending to the already-written KV blocks of earlier
    chunks through the block table -- the SARATHI-style chunked
    prefill that bounds per-call attention cost (C x written-so-far
    instead of L x L) so the engine can interleave prefill progress
    with decode steps.  tokens (1, C) is the chunk right-padded to its
    bucket; table_row (max_blocks,) the slot's block table; start the
    chunk's first absolute position; write_blocks/write_offsets (C,)
    the per-token pool locations (padded tail -> trash block).
    Returns (pool, greedy (C,)): greedy[i] is the greedy token after
    prompt position start + i, so the FINAL chunk's entry at the true
    prompt end is the request's first generated token, bit-identical
    to monolithic paged_prefill's.  One executable per power-of-two
    chunk bucket."""
    pool, greedy = _paged_window(
        params, config, pool, table_row[None],
        jnp.reshape(start, (1,)), tokens, write_blocks[None],
        write_offsets[None])
    return pool, greedy[0]


# -- training ---------------------------------------------------------------

# Named jax.checkpoint_policies entries the remat sweep accepts
# (make_train_step(remat_policy=), bench train `remat` knob).  "none"
# keeps today's behavior: no jax.checkpoint wrapper at all, XLA saves
# every scan residual.  The others trade backward-pass recompute for
# activation memory; every policy produces BIT-IDENTICAL losses (the
# recomputed ops are the same ops -- tested), so the sweep is purely a
# time/memory frontier.
REMAT_POLICIES = ("none", "everything_saveable", "nothing_saveable",
                  "dots_saveable", "dots_with_no_batch_dims_saveable")


def resolve_remat_policy(name: str | None):
    """Remat-policy name -> jax.checkpoint policy callable (None =
    don't wrap the layer body at all)."""
    if name is None or name == "none":
        return None
    if name not in REMAT_POLICIES:
        raise ValueError(
            f"unknown remat_policy {name!r}; choose from "
            f"{REMAT_POLICIES}")
    return getattr(jax.checkpoint_policies, name)


def make_train_step(config: TransformerConfig, optimizer,
                    sharded: bool = False,
                    remat_policy: str | None = None):
    """Returns train_step(params, opt_state, tokens) -> (params, opt_state,
    loss).  Next-token cross-entropy in f32; jit with donation.  With
    sharded=True, activation sharding constraints (data/seq) are inserted
    for mesh execution.  remat_policy names a REMAT_POLICIES entry
    applied to the per-layer scan body (ROADMAP #3b: the train-MFU
    recompute-share sweep)."""
    resolve_remat_policy(remat_policy)  # fail fast on typos

    def loss_fn(params, tokens):
        logits, aux = forward(params, config, tokens[:, :-1],
                              activation_specs=sharded, return_aux=True,
                              remat_policy=remat_policy)
        targets = tokens[:, 1:]
        log_probs = jax.nn.log_softmax(logits, axis=-1)
        taken = jnp.take_along_axis(
            log_probs, targets[..., None], axis=-1, mode="clip")[..., 0]
        return -jnp.mean(taken) + config.moe_aux_weight * aux

    @partial(jax.jit, donate_argnums=(0, 1))
    def train_step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = jax.tree_util.tree_map(
            lambda p, u: (p + u.astype(p.dtype)), params, updates)
        return params, opt_state, loss

    return train_step
