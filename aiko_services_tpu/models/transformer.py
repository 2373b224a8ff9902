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
    attention_reference, flash_attention, flash_attention_takes,
    flash_query_block,
    paged_attention, paged_attention_reference, paged_attention_takes,
    paged_attention_writes,
    ring_attention, sp_decode_attention, ulysses_attention)
from ..parallel.delta import (
    delta_scan, delta_step,
    delta_step_reference, delta_step_takes)
from ..parallel.experts import expert_ffn
from ..parallel.lightning import (
    decay_rates, lightning_scan, lightning_step, lightning_step_reference,
    lightning_step_takes)
from ..parallel.sparse import (
    SparseSizes, blocks_read, completed_key, decode_tables, due_compressed,
    sparse_prefill_attention, sparse_tile)
from ..parallel.ssm import (
    ssm_scan, ssm_scan_rows, ssm_scan_takes, ssm_stack_step, ssm_step,
    ssm_step_takes)
from .layers import (
    apply_rotary, dense, dense_heads, init_dense, init_dense_t, init_norm,
    repeat_kv, rms_norm, rotary_embedding, swiglu, yarn_frequencies,
    yarn_mscale)

__all__ = [
    "TransformerConfig", "init_params", "param_specs", "forward",
    "init_cache", "cache_specs", "decode_step", "generate",
    "generate_stream", "make_train_step", "count_params",
    "quantize_weights_int8", "quantized_param_specs",
    "init_paged_pool", "paged_prefill", "paged_decode_step",
    "paged_prefill_chunk", "paged_verify_step", "cache_attention_kind",
    "pool_write_kind", "prefill_rows", "prefill_attention_rows",
    "REMAT_POLICIES",
    "resolve_remat_policy", "init_recurrent_state", "scan_kind",
    "scan_rows", "state_step_kind",
]


# the layer kinds that carry a recurrent state; a model has one of them
_RECURRENT_KINDS = ("mamba", "delta", "lightning")


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
    # -- multi-head latent attention (DeepSeek-V2): kv_lora_rank > 0 ----
    # Queries come through a low-rank bottleneck (q_lora_rank), keys and
    # values through ONE latent of kv_lora_rank a position plus one
    # rotary key of qk_rope_head_dim shared by every head; the KV store
    # holds that row and nothing else.  n_kv_heads and head_dim are not
    # read.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # YaRN: rope_factor > 1 blends every rotary frequency between its own
    # and its own / factor (layers.yarn_frequencies), multiplies cos/sin
    # by mscale's ratio and the softmax scale by mscale_all_dim's square
    rope_factor: float = 1.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0
    # -- routed experts with shared experts (DeepSeek-V2): top_k > 0 ----
    # The router scores n_routed_experts in n_groups groups, keeps the
    # best topk_groups groups by their largest score and the top_k
    # experts among them, weighted routed_scaling x softmax score, not
    # renormalised; nothing is dropped.  This process computes the
    # experts in experts_held = (lo, hi) of the router's numbering (all
    # when empty: the other shares of an expert-parallel deployment hold
    # the rest) plus n_shared_experts always-on experts.  The first
    # first_dense_layers layers keep the dense FFN of d_ff.
    top_k: int = 0
    n_routed_experts: int = 0
    experts_held: tuple = ()
    n_shared_experts: int = 0
    moe_d_ff: int = 0
    n_groups: int = 1
    topk_groups: int = 1
    routed_scaling: float = 1.0
    first_dense_layers: int = 0
    # -- a looped stack (Ouro): ut_steps > 1 ----------------------------
    # The whole stack runs ut_steps times a token on the same weights.
    # Every pass keeps K/V of its own (n_caches = n_layers x ut_steps
    # caches, _cache_index), norm_out closes every pass and its output
    # opens the next, and an exit gate reads each pass's output: the
    # logits are those of the first pass by which the gate's exit
    # probabilities sum to exit_threshold, else of the last (_exit_pass).
    # Every pass is computed whichever is chosen: a later token attends
    # over this one's rows of every pass.
    ut_steps: int = 1
    exit_threshold: float = 1.0
    # a sublayer's OUTPUT is normed too, before the residual add:
    # h + norm(attention(norm(h))), h + norm(FFN(norm(h)))
    sandwich_norm: bool = False
    # -- layer kinds (Jamba, Qwen3-Next): layer_kinds non-empty ----------
    # One entry a layer, "attention" or the model's one recurrent kind,
    # "mamba" or "delta"; empty: every layer attends.  Like layers that
    # follow one another are one stack and one scan (_layer_stacks).  An
    # attention layer's K/V cache is numbered among the attention layers
    # (n_caches), a recurrent layer's state among the recurrent layers
    # (n_states).  What a sequence carries from row to row in a Mamba
    # layer is the mixer's last ssm_d_conv - 1 inputs of its convolution
    # and its SSM state, (ssm_d_state, ssm_d_inner) float32, whatever the
    # context (init_recurrent_state); a delta layer's is below.
    layer_kinds: tuple = ()
    ssm_d_inner: int = 0
    ssm_d_state: int = 0
    ssm_d_conv: int = 0
    ssm_dt_rank: int = 0
    # False: attention with no positional encoding (the Mamba layers
    # carry position)
    rotary: bool = True
    # -- gated attention beside Gated DeltaNet layers (Qwen3-Next) -------
    # attn_head_dim > 0: the heads' size where it is not d_model /
    # n_heads.  rotary_fraction: the share of a head's columns, from the
    # first, that rotate.  qk_norm: q and k RMS-normed a head, with gains
    # of their own, before they rotate.  gated_attention: wq is twice as
    # wide, a head's second half a gate: out = attention * sigmoid(gate).
    attn_head_dim: int = 0
    rotary_fraction: float = 1.0
    qk_norm: bool = False
    gated_attention: bool = False
    # routed experts: the chosen weights divided by their sum; the shared
    # expert behind a gate of its own, sigmoid(x . w)
    norm_topk: bool = False
    shared_expert_gate: bool = False
    # layer kind "delta": a Gated DeltaNet mixer (parallel/delta.py).
    # What a sequence carries from row to row there is the last
    # delta_conv - 1 inputs of the convolution over [q | k | v] and, a
    # value head, S (delta_key_dim, delta_value_dim) float32.
    delta_key_heads: int = 0
    delta_value_heads: int = 0
    delta_key_dim: int = 0
    delta_value_dim: int = 0
    delta_conv: int = 0
    # -- lightning attention beside attention that selects (MiniCPM-SALA) --
    # layer kind "lightning": a linear-attention mixer (parallel/
    # lightning.py) of lightning_heads heads of lightning_head_dim whose
    # decay is fixed a head; what a sequence carries from row to row is S
    # (lightning_head_dim squared) float32 a head and nothing else: no
    # convolution, and its q and k rotate (rope_theta, the whole head).
    lightning_heads: int = 0
    lightning_head_dim: int = 0
    # sparse_topk > 0: an attention layer's query at a position from
    # sparse_dense_len on reads sparse_topk blocks of sparse_block
    # positions (the pool's block), chosen by the compressed keys, the
    # mean of sparse_kernel keys every sparse_stride positions, which a
    # fifth store beside K/V holds (parallel/sparse.py): the first
    # sparse_init blocks, the sparse_local last, the best of the rest.
    sparse_topk: int = 0
    sparse_block: int = 64
    sparse_kernel: int = 32
    sparse_stride: int = 16
    sparse_init: int = 1
    sparse_local: int = 32
    sparse_dense_len: int = 8192
    # muP's three scalars as the model applies them: the embedding times
    # embed_scale, every residual branch times residual_scale, the normed
    # output divided by logit_divisor before the head
    embed_scale: float = 1.0
    residual_scale: float = 1.0
    logit_divisor: float = 1.0
    # the seeded initialiser makes a head of its own ("lm_head")
    untied_head: bool = False

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

        if self.kv_lora_rank and (self.kv_dtype or self.sequence_parallel):
            raise ValueError(
                "latent attention keeps its cache in the compute dtype "
                "and on one device (no kv_dtype, no sequence_parallel)")
        if self.top_k and self.n_experts:
            raise ValueError("top_k (routed experts) and n_experts (the "
                             "top-1 switch FFN) are two FFNs: set one")
        if self.top_k and self.n_routed_experts % self.n_groups:
            raise ValueError(
                f"{self.n_routed_experts} routed experts do not divide "
                f"into {self.n_groups} groups")
        if self.ut_steps < 1:
            raise ValueError(f"ut_steps must be >= 1, got {self.ut_steps}")
        if self.ut_steps > 1 and self.sequence_parallel:
            raise ValueError(
                "a looped stack keeps its caches on one device (no "
                "sequence_parallel)")
        kinds = tuple(self.layer_kinds)
        if kinds and (len(kinds) != self.n_layers
                      or set(kinds) - {"attention", *_RECURRENT_KINDS}
                      or len(set(kinds) & set(_RECURRENT_KINDS)) > 1):
            raise ValueError(
                f"layer_kinds must name {self.n_layers} layers "
                f"'attention' or one of {_RECURRENT_KINDS}, got "
                f"{len(kinds)} of {sorted(set(kinds))}")
        if self.recurrent:
            kind = self.recurrent_kind
            for name in ("kv_dtype", "sequence_parallel", "kv_lora_rank",
                         "n_experts", "sandwich_norm"):
                if getattr(self, name):
                    raise ValueError(
                        f"a model with a recurrent state ({kind} layers) "
                        f"does not take {name}={getattr(self, name)!r}: "
                        f"the state is float32, on one device, beside "
                        f"plain grouped-query attention")
            if self.ut_steps > 1:
                raise ValueError("a model with a recurrent state runs its "
                                 "stack once a token (ut_steps 1)")
            if kind == "mamba" and (
                    min(self.ssm_d_inner, self.ssm_d_state,
                        self.ssm_dt_rank) < 1 or self.ssm_d_conv < 2):
                raise ValueError(
                    "mamba layers need ssm_d_inner, ssm_d_state, "
                    "ssm_dt_rank >= 1 and ssm_d_conv >= 2")
            if kind == "lightning" and min(self.lightning_heads,
                                           self.lightning_head_dim) < 1:
                raise ValueError("lightning layers need lightning_heads "
                                 "and lightning_head_dim >= 1")
            if kind == "delta" and (
                    min(self.delta_key_heads, self.delta_value_heads,
                        self.delta_key_dim, self.delta_value_dim) < 1
                    or self.delta_conv < 2
                    or self.delta_value_heads % self.delta_key_heads):
                raise ValueError(
                    "delta layers need delta_key_dim, delta_value_dim >= "
                    "1, delta_conv >= 2 and delta_value_heads a multiple "
                    "of delta_key_heads >= 1")

        if self.sparse_topk:
            self.sparse_sizes.check()
            for name in ("kv_dtype", "sequence_parallel", "kv_lora_rank"):
                if getattr(self, name):
                    raise ValueError(
                        f"attention that selects its blocks does not take "
                        f"{name}={getattr(self, name)!r}: the compressed "
                        f"keys are means of plain K rows on one device")
            if self.ut_steps > 1:
                raise ValueError("attention that selects its blocks runs "
                                 "its stack once a token (ut_steps 1)")

    @property
    def sparse_sizes(self) -> SparseSizes:
        """The block selection's sizes (parallel/sparse.py)."""
        return SparseSizes(
            block=self.sparse_block, kernel=self.sparse_kernel,
            stride=self.sparse_stride, topk=self.sparse_topk,
            init=self.sparse_init, local=self.sparse_local,
            dense_len=self.sparse_dense_len)

    @property
    def head_dim(self) -> int:
        return self.attn_head_dim or self.d_model // self.n_heads

    @property
    def recurrent_kind(self) -> str:
        """The kind of the layers that carry a recurrent state: "mamba",
        "delta", "lightning", or "" where every layer attends."""
        return next((kind for kind in self.layer_kinds
                     if kind in _RECURRENT_KINDS), "")

    @property
    def n_states(self) -> int:
        """Recurrent states a sequence carries: one a layer of the
        recurrent kind."""
        return sum(kind in _RECURRENT_KINDS for kind in self.layer_kinds)

    @property
    def recurrent(self) -> bool:
        return self.n_states > 0

    @property
    def n_caches(self) -> int:
        """K/V caches a position leaves behind: one an attention layer a
        pass."""
        return (self.n_layers - self.n_states) * self.ut_steps

    @property
    def delta_conv_channels(self) -> int:
        """Channels of a delta layer's convolution: [q | k | v]."""
        return (2 * self.delta_key_heads * self.delta_key_dim
                + self.delta_value_heads * self.delta_value_dim)

    @property
    def state_bytes(self) -> int:
        """Bytes of recurrent state a sequence carries, over the layers
        of the recurrent kind: a layer's state in float32 (mamba: d_state
        x d_inner; delta: key_dim x value_dim a value head; lightning:
        head_dim squared a head) and, where the mixer has one, its
        convolution's tail of inputs in the serving dtype."""
        item = self.jnp_dtype.itemsize
        if self.recurrent_kind == "lightning":
            return (self.n_states * 4 * self.lightning_heads
                    * self.lightning_head_dim ** 2)
        if self.recurrent_kind == "delta":
            return self.n_states * (
                4 * self.delta_value_heads * self.delta_key_dim
                * self.delta_value_dim
                + (self.delta_conv - 1) * self.delta_conv_channels * item)
        return self.n_states * self.ssm_d_inner * (
            4 * self.ssm_d_state + (self.ssm_d_conv - 1) * item)

    @property
    def rotary_dim(self) -> int:
        if self.kv_lora_rank:
            return self.qk_rope_head_dim
        return int(self.head_dim * self.rotary_fraction)

    @property
    def latent_row(self) -> int:
        """Values a position a layer in a latent store: the latent and
        the shared rotary key, padded to the 128 lanes Mosaic slices a
        pool by (512 + 64 -> 640)."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128

    @property
    def attention_scale(self) -> float:
        """The softmax scale: head_dim^-0.5, under YaRN times mscale^2
        of mscale_all_dim."""
        depth = (self.qk_nope_head_dim + self.qk_rope_head_dim
                 if self.kv_lora_rank else self.head_dim)
        m = yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return depth ** -0.5 * m * m

    @property
    def held(self) -> tuple:
        """[lo, hi) of the router's experts computed here."""
        return tuple(self.experts_held) or (0, self.n_routed_experts)

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)


# -- parameters -------------------------------------------------------------

def _init_latent_attention(keys, config: TransformerConfig) -> dict:
    """MLA's projections and two inner norms.  wkv_a's columns are the
    latent then the shared rotary key.  The published wq_b, (q_rank, a
    head's [nope ; rope] queries), and wkv_b, (rank, a head's [nope keys ;
    values]), are drawn whole, as published, and held as the operands the
    decode step's matmuls read where they lie: wq_b (nope + rope, H,
    q_rank), the keys' up-projection wk_b (H, nope, rank) and the values'
    wv_b (H, rank, v), the two the absorbed step contracts."""
    d, heads, dtype = config.d_model, config.n_heads, config.jnp_dtype
    nope, rope = config.qk_nope_head_dim, config.qk_rope_head_dim
    rank, q_rank = config.kv_lora_rank, config.q_lora_rank
    wq_b = init_dense(keys[1], q_rank, heads * (nope + rope),
                      dtype)["w"].reshape(q_rank, heads, -1)
    wkv_b = init_dense(keys[3], rank, heads * (nope + config.v_head_dim),
                       dtype)["w"].reshape(rank, heads, -1)
    return {
        "wq_a": init_dense(keys[0], d, q_rank, dtype),
        "q_norm": init_norm(q_rank, dtype),
        "wq_b": {"w": wq_b.transpose(2, 1, 0)},
        "wkv_a": init_dense(keys[2], d, rank + rope, dtype),
        "kv_norm": init_norm(rank, dtype),
        "wk_b": {"w": wkv_b[..., :nope].transpose(1, 2, 0)},
        "wv_b": {"w": wkv_b[..., nope:].transpose(1, 0, 2)},
        "wo": init_dense(keys[4], heads * config.v_head_dim, d, dtype),
    }


def _init_routed_ffn(keys, config: TransformerConfig) -> dict:
    """The experts held, the router over all of them and the shared
    experts (one SwiGLU of n_shared x moe_d_ff).  Expert e of the
    router's numbering is drawn from fold_in(its leaf's key, e), one
    expert at a time: the same numbers whichever share holds it, and no
    float32 copy of a stacked leaf (5 GB at DeepSeek-V2's widths)."""
    d, ff, dtype = config.d_model, config.moe_d_ff, config.jnp_dtype

    def held(key, rows, cols):
        return {"w": jnp.stack([
            init_dense(jax.random.fold_in(key, expert), rows, cols,
                       dtype)["w"] for expert in range(*config.held)])}

    shared = config.n_shared_experts * ff
    ffn = {
        "w_gate": held(keys[0], d, ff), "w_up": held(keys[1], d, ff),
        "w_down": held(keys[2], ff, d),
        "router": init_dense(keys[3], d, config.n_routed_experts, dtype),
        "shared_gate": init_dense(keys[4], d, shared, dtype),
        "shared_up": init_dense(keys[5], d, shared, dtype),
        "shared_down": init_dense(keys[6], shared, d, dtype),
    }
    if config.shared_expert_gate:
        # the shared expert's own gate, sigmoid(x . w): one column
        ffn["shared_mix"] = init_dense(jax.random.fold_in(keys[4], 1), d, 1,
                                       dtype)
    return ffn


def _init_layer(key, config: TransformerConfig,
                routed: bool = False) -> dict:
    """One layer's weights; `routed` gives it the routed-expert FFN."""
    d, hd, ff = config.d_model, config.head_dim, config.d_ff
    dtype = config.jnp_dtype
    if config.kv_lora_rank or config.top_k:
        # five attention keys, then the FFN's: 3 dense or 7 routed
        keys = jax.random.split(key, 12)
        ffn_keys = keys[5:]
    else:
        keys = jax.random.split(key, 8)
        ffn_keys = keys[4:]
    if config.kv_lora_rank:
        layer = _init_latent_attention(keys, config)
    else:
        layer = {
            # (out, in): the decode step reads them in place (_by_head);
            # gated, a head's query then its gate
            "wq": init_dense_t(
                keys[0], d, config.n_heads * hd
                * (2 if config.gated_attention else 1), dtype),
            "wk": init_dense_t(keys[1], d, config.n_kv_heads * hd, dtype),
            "wv": init_dense(keys[2], d, config.n_kv_heads * hd, dtype),
            "wo": init_dense(keys[3], config.n_heads * hd, d, dtype),
        }
    layer["attn_norm"] = init_norm(d, dtype)
    layer["mlp_norm"] = init_norm(d, dtype)
    if config.qk_norm:
        layer["q_norm"] = init_norm(hd, dtype)
        layer["k_norm"] = init_norm(hd, dtype)
    if config.sparse_topk:
        # with gains of 1 a softmax over thousands of seeded random keys
        # is flat, every choice of blocks gives nearly the same output and
        # nothing downstream could tell selection from none: the seeded q
        # gain makes q . Kc / sqrt(d) of unit-RMS heads spread by about 3
        # (a mean of `kernel` independent unit keys has 1 / sqrt(kernel)
        # of a key's length).  Weights overwrite it
        layer["q_norm"] = {"scale": jnp.full(
            (hd,), 3.0 * math.sqrt(config.sparse_kernel), dtype)}
    if config.sandwich_norm:
        layer["attn_out_norm"] = init_norm(d, dtype)
        layer["mlp_out_norm"] = init_norm(d, dtype)
    if routed:
        layer.update(_init_routed_ffn(ffn_keys, config))
        return layer
    gate_key, up_key, down_key, *more = ffn_keys
    if config.n_experts > 0:
        experts = config.n_experts

        def expert_weights(key, rows, cols):
            return {"w": (jax.random.normal(
                key, (experts, rows, cols), jnp.float32)
                / jnp.sqrt(jnp.float32(rows))).astype(dtype)}

        layer["router"] = init_dense(more[0], d, experts, dtype)
        layer["w_gate"] = expert_weights(gate_key, d, ff)
        layer["w_up"] = expert_weights(up_key, d, ff)
        layer["w_down"] = expert_weights(down_key, ff, d)
    else:
        layer["w_gate"] = init_dense(gate_key, d, ff, dtype)
        layer["w_up"] = init_dense(up_key, d, ff, dtype)
        layer["w_down"] = init_dense(down_key, ff, d, dtype)
    return layer


def _init_mamba_layer(key, config: TransformerConfig) -> dict:
    """One Mamba layer's weights: the mixer's (Jamba's: the projections
    in and out without bias, a depthwise causal convolution with one,
    the three inner norms over delta, B and C, the step size's projection
    with a float32 bias) and the dense FFN's.  `conv` lies (taps,
    channels) and `a_log` (d_state, d_inner), the channels on the lanes;
    published they are (channels, 1, taps) and (d_inner, d_state).
    Seeded as the published initialiser has them where it matters to the
    recurrence: A = -(1 .. d_state) a channel, D = 1, softplus(dt_bias)
    log-uniform in [1e-3, 1e-1]; the matrices as init_dense draws them."""
    d, ff, dtype = config.d_model, config.d_ff, config.jnp_dtype
    inner, states = config.ssm_d_inner, config.ssm_d_state
    rank, taps = config.ssm_dt_rank, config.ssm_d_conv
    keys = jax.random.split(key, 10)
    step = jnp.exp(jax.random.uniform(keys[5], (inner,), jnp.float32)
                   * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
    return {
        "mixer_norm": init_norm(d, dtype),
        "w_in": init_dense(keys[0], d, 2 * inner, dtype),
        "conv": {"w": (jax.random.normal(keys[1], (taps, inner),
                                         jnp.float32)
                       / math.sqrt(taps)).astype(dtype),
                 "b": (jax.random.normal(keys[2], (inner,), jnp.float32)
                       * 0.02).astype(dtype)},
        "w_x": init_dense(keys[3], inner, rank + 2 * states, dtype),
        "dt_norm": init_norm(rank, dtype),
        "b_norm": init_norm(states, dtype),
        "c_norm": init_norm(states, dtype),
        "w_dt": init_dense(keys[4], rank, inner, dtype),
        # softplus's inverse of the step size
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "a_log": jnp.broadcast_to(jnp.log(jnp.arange(
            1, states + 1, dtype=jnp.float32))[:, None], (states, inner)),
        "d": jnp.ones((inner,), jnp.float32),
        "w_out": init_dense(keys[6], inner, d, dtype),
        "mlp_norm": init_norm(d, dtype),
        "w_gate": init_dense(keys[7], d, ff, dtype),
        "w_up": init_dense(keys[8], d, ff, dtype),
        "w_down": init_dense(keys[9], ff, d, dtype),
    }


def _init_delta_layer(key, config: TransformerConfig) -> dict:
    """One Gated DeltaNet layer's weights: the mixer's (Qwen3-Next's: the
    projections without bias, [q | k | v | z] in one and [b | a] in
    another, a depthwise causal convolution over [q | k | v] without one,
    a decay rate and a step bias a value head in float32, the output's
    norm a head with one gain shared by the heads) and the FFN's, routed
    where the model's is.  `conv` lies (taps, channels); published it is
    (channels, 1, taps).  Seeded as the architecture's training
    initialiser has them where it matters to the recurrence: A uniform in
    (0, 16) and softplus(dt_bias) log-uniform in [1e-3, 1e-1], so that a
    head's decay a row spans e^-1.6 to e^-0.0001 and its state carries
    from a row or two to thousands (the published modelling code's
    dt_bias of ones, which weights overwrite, makes every head forget
    within a row or two); the matrices as init_dense draws them."""
    d, dtype = config.d_model, config.jnp_dtype
    channels, taps = config.delta_conv_channels, config.delta_conv
    values = config.delta_value_heads * config.delta_value_dim
    keys = jax.random.split(key, 12)
    step = jnp.exp(jax.random.uniform(
        jax.random.fold_in(keys[3], 1), (config.delta_value_heads,),
        jnp.float32) * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
    layer = {
        "mixer_norm": init_norm(d, dtype),
        "w_qkvz": init_dense(keys[0], d, channels + values, dtype),
        "conv": {"w": (jax.random.normal(keys[1], (taps, channels),
                                         jnp.float32)
                       / math.sqrt(taps)).astype(dtype)},
        "w_ba": init_dense(keys[2], d, 2 * config.delta_value_heads, dtype),
        "a_log": jnp.log(jax.random.uniform(
            keys[3], (config.delta_value_heads,), jnp.float32) * 16.0),
        # softplus's inverse of the step size
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "delta_norm": init_norm(config.delta_value_dim, dtype),
        "w_out": init_dense(keys[4], values, d, dtype),
        "mlp_norm": init_norm(d, dtype),
    }
    if config.top_k:
        layer.update(_init_routed_ffn(keys[5:], config))
    else:
        layer.update(
            w_gate=init_dense(keys[5], d, config.d_ff, dtype),
            w_up=init_dense(keys[6], d, config.d_ff, dtype),
            w_down=init_dense(keys[7], config.d_ff, d, dtype))
    return layer


def _init_lightning_layer(key, config: TransformerConfig) -> dict:
    """One lightning layer's weights: the mixer's (MiniCPM-SALA's: the
    projections without bias, [q | k | v | g] in one, g the output's gate;
    q and k normed a head with gains of their own, the output's norm a
    head with one gain shared by the heads) and the dense FFN's.  The
    decay is no weight: the family's fixed slopes (decay_rates)."""
    d, ff, dtype = config.d_model, config.d_ff, config.jnp_dtype
    hd = config.lightning_head_dim
    inner = config.lightning_heads * hd
    keys = jax.random.split(key, 8)
    return {
        "mixer_norm": init_norm(d, dtype),
        "w_qkvg": init_dense(keys[0], d, 4 * inner, dtype),
        "q_norm": init_norm(hd, dtype), "k_norm": init_norm(hd, dtype),
        "out_norm": init_norm(hd, dtype),
        "w_out": init_dense(keys[1], inner, d, dtype),
        "mlp_norm": init_norm(d, dtype),
        "w_gate": init_dense(keys[2], d, ff, dtype),
        "w_up": init_dense(keys[3], d, ff, dtype),
        "w_down": init_dense(keys[4], ff, d, dtype),
    }


def _stack_layers(layers: list) -> dict:
    """Per-layer weight dicts -> one dict of leaves stacked on a leading
    axis, a leaf at a time, letting each layer's copy go as its stack is
    made: stacked all at once a model is held twice (four expert layers
    of DeepSeek-V2's widths are 9 GB)."""
    flat = [jax.tree_util.tree_flatten(layer) for layer in layers]
    layers.clear()
    treedef = flat[0][1]
    leaves = [layer_leaves for layer_leaves, _ in flat]
    stacked = []
    for index in range(treedef.num_leaves):
        parts = [layer_leaves[index] for layer_leaves in leaves]
        for layer_leaves in leaves:
            layer_leaves[index] = None
        stacked.append(jnp.stack(parts))
        del parts
    return jax.tree_util.tree_unflatten(treedef, stacked)


def _leading_dense(config: TransformerConfig) -> int:
    """Layers that run before the stack of routed-expert layers."""
    return config.first_dense_layers if config.top_k else 0


def _kind_runs(config: TransformerConfig) -> list:
    """[(kind, index of the run's first layer among its kind, how many)]:
    the runs of like layers of a model with layer_kinds, in order."""
    runs, seen = [], dict.fromkeys(("attention", *_RECURRENT_KINDS), 0)
    for kind in config.layer_kinds:
        if runs and runs[-1][0] == kind:
            runs[-1][2] += 1
        else:
            runs.append([kind, seen[kind], 1])
        seen[kind] += 1
    return [tuple(run) for run in runs]


def init_params(config: TransformerConfig, key) -> dict:
    """Seeded weights.  "layers" is the stack the scan runs; a model
    with routed experts whose first layers are dense has those apart,
    as "dense_layers" (their FFN leaves have other shapes); a model with
    layer_kinds has "runs" instead, a stack a run of like layers, layer i
    drawn from the i-th key whatever its kind; a model with untied_head an
    "lm_head" of its own."""
    embed_key, *layer_keys = jax.random.split(key, config.n_layers + 1)
    lead = _leading_dense(config)
    params = {
        "embed": {"w": (jax.random.normal(
            embed_key, (config.vocab_size, config.d_model), jnp.float32)
            * 0.02).astype(config.jnp_dtype)},
        "norm_out": init_norm(config.d_model, config.jnp_dtype),
    }
    if config.untied_head:
        # (vocab, d) as the embedding lies: _head_logits contracts either
        params["lm_head"] = init_dense_t(
            jax.random.fold_in(key, config.n_layers + 2), config.d_model,
            config.vocab_size, config.jnp_dtype)
    if config.layer_kinds:
        params["runs"], first = [], 0
        inits = {"mamba": _init_mamba_layer, "delta": _init_delta_layer,
                 "lightning": _init_lightning_layer,
                 "attention": partial(_init_layer,
                                      routed=config.top_k > 0)}
        for kind, _, count in _kind_runs(config):
            params["runs"].append(_stack_layers(
                [inits[kind](k, config)
                 for k in layer_keys[first:first + count]]))
            first += count
        return params
    params["layers"] = _stack_layers([
        _init_layer(k, config, routed=config.top_k > 0)
        for k in layer_keys[lead:]])
    if lead:
        params["dense_layers"] = _stack_layers(
            [_init_layer(k, config) for k in layer_keys[:lead]])
    if config.ut_steps > 1:
        # the exit gate: one logit a pass from the pass's normed output
        params["exit_gate"] = {
            "w": init_dense(jax.random.fold_in(key, config.n_layers + 1),
                            config.d_model, 1, config.jnp_dtype)["w"][:, 0],
            "b": jnp.zeros((), config.jnp_dtype)}
    return params


def param_specs(config: TransformerConfig,
                lm_head: bool = False) -> dict:
    """Megatron TP on 'model' + FSDP on 'fsdp' (+ EP on 'expert' for MoE
    weights); stacked-layer leaves carry a leading None for the scan axis.
    (Scaling-book recipe: shard the big matmuls, replicate the norms.)
    lm_head=True adds the untied-output-head spec (checkpoint-loaded
    Llama-3-8B+ params carry one)."""
    column, row = P(None, "fsdp", "model"), P(None, "model", "fsdp")
    # a column-parallel weight held (out, in): the same logical axes
    column_t = P(None, "model", "fsdp")
    layer = {
        "attn_norm": {"scale": P(None, None)},
        "wo": {"w": row},
        "mlp_norm": {"scale": P(None, None)},
    }
    if config.sandwich_norm:
        layer["attn_out_norm"] = {"scale": P(None, None)}
        layer["mlp_out_norm"] = {"scale": P(None, None)}
    if config.kv_lora_rank:
        # the low-rank projections in are replicated across "model" (one
        # latent serves every head); those out of them split by head
        layer.update({
            "wq_a": {"w": P(None, "fsdp", None)},
            "q_norm": {"scale": P(None, None)},
            "wq_b": {"w": P(None, None, "model", None)},
            "wkv_a": {"w": P(None, "fsdp", None)},
            "kv_norm": {"scale": P(None, None)},
            "wk_b": {"w": P(None, "model", None, None)},
            "wv_b": {"w": P(None, "model", None, None)}})
    else:
        layer.update({"wq": {"w": column_t}, "wk": {"w": column_t},
                      "wv": {"w": column}})
    dense_ffn = {"w_gate": {"w": column}, "w_up": {"w": column},
                 "w_down": {"w": row}}
    expert_ffn_specs = {
        "router": {"w": P(None, None, None)},
        "w_gate": {"w": P(None, "expert", "fsdp", "model")},
        "w_up": {"w": P(None, "expert", "fsdp", "model")},
        "w_down": {"w": P(None, "expert", "model", "fsdp")}}
    specs = {
        "embed": {"w": P(None, "fsdp")},
        "norm_out": {"scale": P(None)},
    }
    whole2, whole3 = P(None, None), P(None, None, None)
    routed_ffn = dict(
        expert_ffn_specs, shared_gate={"w": column},
        shared_up={"w": column}, shared_down={"w": row})
    if config.shared_expert_gate:
        routed_ffn["shared_mix"] = {"w": whole3}
    if config.qk_norm:
        layer.update(q_norm={"scale": whole2}, k_norm={"scale": whole2})
    if config.layer_kinds:
        # a recurrent layer's big matrices split like the FFN's; what is
        # a channel's or a head's own (the convolution, A, D, the step's
        # bias) and the narrow projections stay whole
        ffn = routed_ffn if config.top_k else dense_ffn
        recurrent = dict(
            ffn, mixer_norm={"scale": whole2}, mlp_norm={"scale": whole2})
        kinds = {
            "attention": dict(layer, **ffn),
            "mamba": dict(
                recurrent, w_in={"w": column},
                conv={"w": whole3, "b": whole2}, w_x={"w": whole3},
                dt_norm={"scale": whole2}, b_norm={"scale": whole2},
                c_norm={"scale": whole2}, w_dt={"w": whole3},
                dt_bias=whole2, a_log=whole3, d=whole2, w_out={"w": row}),
            "delta": dict(
                recurrent, w_qkvz={"w": column}, conv={"w": whole3},
                w_ba={"w": whole3}, a_log=whole2, dt_bias=whole2,
                delta_norm={"scale": whole2}, w_out={"w": row}),
            "lightning": dict(
                recurrent, w_qkvg={"w": column}, q_norm={"scale": whole2},
                k_norm={"scale": whole2}, out_norm={"scale": whole2},
                w_out={"w": row})}
        specs["runs"] = [kinds[kind] for kind, _, _ in _kind_runs(config)]
    elif config.top_k:
        specs["layers"] = dict(layer, **routed_ffn)
        if _leading_dense(config):
            specs["dense_layers"] = dict(layer, **dense_ffn)
    elif config.n_experts > 0:
        specs["layers"] = dict(layer, **expert_ffn_specs)
    else:
        specs["layers"] = dict(layer, **dense_ffn)
    if lm_head or config.untied_head:
        specs["lm_head"] = {"w": P(None, "fsdp")}
    if config.ut_steps > 1:
        specs["exit_gate"] = {"w": P(None), "b": P()}
    return specs


def count_params(params) -> int:
    return sum(leaf.size for leaf in jax.tree_util.tree_leaves(params))


# -- weight-only int8 (serving decode) ---------------------------------------

# the dense weights that quantize, each with the axis it is contracted over,
# which its per-output-channel scale collapses: the last of wq and wk, held
# (out, in), else the one before
_DENSE_QUANT_AXES = {"wq": -1, "wk": -1, "wv": -2, "wo": -2,
                     "w_gate": -2, "w_up": -2, "w_down": -2}


def _quantized_axes(config: TransformerConfig) -> dict:
    """_DENSE_QUANT_AXES of the leaves `config` has as dense weights: a
    switch model's expert FFN stays unquantized."""
    return {key: axis for key, axis in _DENSE_QUANT_AXES.items()
            if config.n_experts == 0 or not key.startswith("w_")}


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
    if config.recurrent:
        raise ValueError(
            f"weight-only int8 is not implemented for a model with a "
            f"recurrent state ({config.recurrent_kind} layers): "
            f"quantize_weights_int8 covers stacks of one kind")
    if config.kv_lora_rank or config.top_k:
        raise ValueError("weight-only int8 covers the grouped-query "
                         "dense and switch layers, not latent attention "
                         "or routed experts")

    def quant(entry: dict, axis: int) -> dict:
        w = entry["w"].astype(jnp.float32)
        scale = jnp.maximum(
            jnp.max(jnp.abs(w), axis=axis, keepdims=True), 1e-12) / 127.0
        codes = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
        out = {"w": codes, "w_scale": scale}
        if "b" in entry:
            out["b"] = entry["b"]
        return out

    layers = dict(params["layers"])
    for key, axis in _quantized_axes(config).items():
        layers[key] = quant(layers[key], axis=axis)
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
    unsharded -- the contracted axis for dense per-output-channel scales,
    -1 for the embed/lm_head per-row scales."""
    def scale_spec(spec: P, axis: int) -> P:
        entries = list(tuple(spec))
        entries[axis] = None
        return P(*entries)

    specs = param_specs(config, lm_head=lm_head)
    layer = dict(specs["layers"])
    for key, axis in _quantized_axes(config).items():
        layer[key] = dict(layer[key])
        layer[key]["w_scale"] = scale_spec(layer[key]["w"], axis)
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
    if config.kv_lora_rank:
        # one leaf: a position's latent and shared rotary key, which is
        # key and value of every head (_project_latent)
        return {"kv": jnp.zeros((config.n_caches, batch, 1, max_len,
                                 config.latent_row), config.jnp_dtype)}
    shape = (config.n_caches, batch, config.n_kv_heads, max_len,
             config.head_dim)
    if config.kv_dtype == "int8":
        scale_shape = shape[:-1] + (1,)
        return {"k": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(scale_shape, jnp.float32),
                "v": jnp.zeros(shape, jnp.int8),
                "v_scale": jnp.zeros(scale_shape, jnp.float32)}
    # a model with recurrent layers carries their state beside the K/V,
    # the batch in the slots' place; attention that selects its blocks,
    # the compressed keys
    return {"k": jnp.zeros(shape, config.jnp_dtype),
            "v": jnp.zeros(shape, config.jnp_dtype),
            **_compressed_store(config, batch, max_len),
            **init_recurrent_state(config, batch)}


def _compressed_store(config: TransformerConfig, blocks: int,
                      block_size: int) -> dict:
    """The store of compressed keys of an attention that selects its
    blocks ({} of any other): "kc" (n_caches, blocks, K/V heads,
    block_size / sparse_stride, head_dim), a block's entries the
    compressed keys that START in it (parallel/sparse.py).  A contiguous
    cache is one block of max_len positions a sequence."""
    if not config.sparse_topk:
        return {}
    return {"kc": jnp.zeros(
        (config.n_caches, blocks, config.n_kv_heads,
         block_size // config.sparse_stride, config.head_dim),
        config.jnp_dtype)}


# the leaves of a cache or a pool that are recurrent state, not K/V, each
# with the axis its sequences (a cache's batch, a pool's slots) lie on
_STATE_LEAVES = {"conv": 2, "ssm": 1, "delta": 1, "lightning": 1}


def _layer_state(config: TransformerConfig, slots: int) -> dict:
    """One recurrent layer's state of `slots` sequences from their
    start, by the model's recurrent kind."""
    if config.recurrent_kind == "lightning":
        return {"lightning": jnp.zeros(
            (slots, config.lightning_heads, config.lightning_head_dim,
             config.lightning_head_dim), jnp.float32)}
    if config.recurrent_kind == "delta":
        return {"conv": jnp.zeros((config.delta_conv - 1, slots,
                                   config.delta_conv_channels),
                                  config.jnp_dtype),
                "delta": jnp.zeros((slots, config.delta_value_heads,
                                    config.delta_key_dim,
                                    config.delta_value_dim), jnp.float32)}
    return {"conv": jnp.zeros((config.ssm_d_conv - 1, slots,
                               config.ssm_d_inner), config.jnp_dtype),
            "ssm": jnp.zeros((slots, config.ssm_d_state,
                              config.ssm_d_inner), jnp.float32)}


def init_recurrent_state(config: TransformerConfig, slots: int) -> dict:
    """The recurrent state of `slots` sequences, zeros: {} for a model
    with no recurrent layer, else that kind of layer's two leaves,
    addressed by sequence and sized by `slots`, not by positions.  Mamba:
    "conv" (n_states, ssm_d_conv - 1, slots, ssm_d_inner), the mixer's
    last inputs of its convolution, oldest first, and "ssm" (n_states,
    slots, ssm_d_state, ssm_d_inner) float32, which a decode step reads
    and writes where it lies (parallel/ssm.py ssm_row_step).  Every minor
    pair of axes fills its tiles: the channels on the lanes, the slots
    (conv) and the states (ssm) on the sublanes.  Held (slots, 3, .) and
    (., d_inner, d_state), as the mixer is published, three rows pad to a
    tile's 16 and 16 lanes to 128.  Delta: "conv" (n_states, delta_conv - 1, slots,
    the [q | k | v] channels) and "delta" (n_states, slots, value heads,
    delta_key_dim, delta_value_dim) float32, a head's S whole in its
    minor pair, so that a decode step reads and writes it where it lies
    (parallel/delta.py gdn_step).  Lightning: "lightning" (n_states, slots,
    heads, head_dim, head_dim) float32 alone, laid out and advanced the
    same way (parallel/lightning.py lightning_step)."""
    if not config.recurrent:
        return {}
    return {name: jnp.zeros((config.n_states,) + leaf.shape, leaf.dtype)
            for name, leaf in _layer_state(config, slots).items()}


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

def _rotate(config: TransformerConfig, x, cos, sin):
    """apply_rotary over the first rotary_dim columns of x's heads (all
    of them but under a rotary_fraction), the rest as they are."""
    width = config.rotary_dim
    if width == x.shape[-1]:
        return apply_rotary(x, cos, sin)
    return jnp.concatenate(
        [apply_rotary(x[..., :width], cos, sin), x[..., width:]], axis=-1)


def _project_qkv(config: TransformerConfig, layer, x, cos, sin):
    """x (B, L, d_model) -> rotated q (B, H, L, hd), rotated k and plain v
    (B, Hkv, L, hd); of a gated attention also, fourth, the heads' gates
    (B, H, L, hd), the second half of what wq gives a head."""
    batch, length, _ = x.shape
    v = dense(layer["wv"], x).reshape(
        batch, length, config.n_kv_heads, config.head_dim
    ).transpose(0, 2, 1, 3)

    def finish(heads, norm):
        # a head's norm where the model has one, then its rotation
        if config.qk_norm:
            heads = rms_norm(layer[norm], heads, config.norm_eps)
        return _rotate(config, heads, cos, sin) if config.rotary else heads

    q, gate = dense_heads(layer["wq"], x), ()
    if config.gated_attention:
        q, gate = q[..., :config.head_dim], (q[..., config.head_dim:],)
    q = finish(q, "q_norm")
    return (q, finish(dense_heads(layer["wk"], x), "k_norm"), v, *gate)


def _project_latent(config: TransformerConfig, layer, x, cos, sin):
    """MLA's projections.  x (B, L, d_model) -> q (B, H, L, nope + rope),
    its rotary slice rotated, and the one row a position leaves behind,
    (B, 1, L, latent_row): [RMSNorm(c_kv) ; RoPE(k_r) ; zeros to the
    lanes] -- every head's key and, through wv_b, its value.  No v."""
    batch, length, _ = x.shape
    nope, rank = config.qk_nope_head_dim, config.kv_lora_rank
    rope = config.qk_rope_head_dim
    c_q = rms_norm(layer["q_norm"], dense(layer["wq_a"], x),
                   config.norm_eps)
    q = jnp.einsum("blr,dhr->bhld", c_q, layer["wq_b"]["w"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    q = jnp.concatenate(
        [q[..., :nope], apply_rotary(q[..., nope:], cos, sin)], axis=-1)
    down = dense(layer["wkv_a"], x)[:, None]           # (B, 1, L, rank+rope)
    latent = jnp.concatenate(
        [rms_norm(layer["kv_norm"], down[..., :rank], config.norm_eps),
         apply_rotary(down[..., rank:], cos, sin),
         jnp.zeros((batch, 1, length, config.latent_row - rank - rope),
                   x.dtype)], axis=-1)
    return q, latent, None


def _latent_expand(config: TransformerConfig, layer, latent):
    """Decompress latent rows (B, 1, L, latent_row) to every head's
    k (B, H, L, nope + rope) and v (B, H, L, v)."""
    batch, _, length, _ = latent.shape
    rank = config.kv_lora_rank
    c_kv = latent[:, 0, :, :rank]
    k_nope = jnp.einsum("blc,hnc->bhln", c_kv, layer["wk_b"]["w"],
                        preferred_element_type=jnp.float32
                        ).astype(latent.dtype)
    v = jnp.einsum("blc,hcv->bhlv", c_kv, layer["wv_b"]["w"],
                   preferred_element_type=jnp.float32).astype(latent.dtype)
    k_rope = jnp.broadcast_to(
        latent[..., rank:rank + config.qk_rope_head_dim],
        (batch, config.n_heads, length, config.qk_rope_head_dim))
    return jnp.concatenate([k_nope, k_rope], axis=-1), v


def _latent_flash(config: TransformerConfig, q, k, v, live=None):
    """Causal blockwise attention of decompressed MLA heads: q.k over
    nope + rope (zero-padded to the lanes, which adds nothing to a
    score), v of its own width, YaRN's softmax scale; `live` a whole
    prefill's true length (flash_attention's)."""
    pad = [(0, 0)] * 3 + [(0, -q.shape[-1] % 128)]
    return flash_attention(jnp.pad(q, pad), jnp.pad(k, pad), v,
                           causal=True, sm_scale=config.attention_scale,
                           live=live)


# Rows of a whole prefill's row tile.  A whole prefill (paged_prefill: a
# contiguous cache from the static position 0, the prompt's true length
# handed in) runs what a layer computes row by row over the tiles that
# hold a live row and leaves the bucket's other rows undone.  Chosen on a
# TPU v5e from {256, 512, 1024} (PR 38; ms a prefill, host clock around
# the call and its readback, median of 5):
#   the 4096 bucket of mistral7b_l16 at true_len 2048 / 3056 / 4064:
#     traced whole 195.4 / 195.3 / 195.3;  256: 116.0 / 160.0 / 204.4;
#     512: 108.7 / 149.5 / 190.1;  1024: 109.3 / 149.3 / 189.4
#   the 8192 bucket of deepseek_v2_ep4_l5 at 4608 / 5632 / 7168:
#     traced whole 345.4 / 345.2 / 345.1;  256: 281.1 / 297.2 / 320.6;
#     512: 274.4 / 289.2 / 311.1;  1024: 280.4 / 294.8 / 310.2
# 256 pays for its trips (every tile live, it is slower than the whole
# trace); 1024 matches 512 tile for tile and rounds a length up further.
_ROW_TILE = 512


def _row_tiles_take(config: TransformerConfig, length: int) -> bool:
    """Whether a whole prefill of `length` rows runs by row tiles: at
    least two whole tiles, and a layer whose row-wise work is a row's
    own (a switch FFN's capacity and a sequence-parallel attention's
    shards span the sequence).  A recurrent layer's rows are not their
    own -- its convolution reads the three rows before a row and its
    state crosses them all -- and it runs by row tiles all the same, the
    convolution's tail and the state carried from tile to tile
    (_stateful_layer)."""
    return (length >= 2 * _ROW_TILE and length % _ROW_TILE == 0
            and config.n_experts == 0 and not config.sequence_parallel)


def _tiled(config: TransformerConfig, true_len, length: int):
    """What _row_tiles takes as `live` of a whole prefill of `length`
    rows for a prompt of `true_len` (traced, or None: no whole prefill):
    true_len where the bucket runs by row tiles, else None."""
    if true_len is None or not _row_tiles_take(config, length):
        return None
    return true_len


def prefill_rows(config: TransformerConfig, bucket: int,
                 true_len: int) -> int:
    """The rows a whole prefill (paged_prefill) of `bucket` rows runs for
    a prompt of `true_len` tokens: true_len rounded up to a row tile
    where the bucket runs by row tiles, else the bucket.  _hidden decides
    by the same predicate, and the engine names its prefill spans by
    this."""
    if not _row_tiles_take(config, bucket):
        return bucket
    return -(-true_len // _ROW_TILE) * _ROW_TILE


def _row_tiles(live, fn, layer, *operands, carry=None, outputs=None):
    """fn(layer, *operands), whose every output row depends on the
    operands' same row alone, rows being the axis before the last:
    (B, L, d), (B, H, L, hd).  live None: the one call.  Else `live` is
    the traced count of rows that matter, `layer` a _LayerAt, and fn runs
    on one _ROW_TILE of rows at a time for the ceil(live / _ROW_TILE)
    tiles that hold one -- a loop with a traced trip count, so one
    program serves every `live` -- and the rows of the other tiles are
    zeros in every output.  An output of one axis (the FFN's stats) is
    summed over the tiles.
    Given a `carry` (what crosses a tile's edge: a recurrent layer's
    state) the loop threads it through the tiles in order:
    fn(layer, carry, rows, *operands), told how many of its tile's rows
    are live, returns (carry, outputs), and so does _row_tiles, the carry
    as the last live tile left it.  Such a caller hands in `outputs`, the
    whole outputs' zeros: a row-wise segment is traced once more for
    their shapes, in milliseconds; a layer with a kernel in it is not."""
    if live is None:
        return fn(layer, *operands)

    def tiles(start):
        return (jax.lax.dynamic_slice_in_dim(x, start, _ROW_TILE, x.ndim - 2)
                for x in operands)

    def put(start, whole, part):
        if part.ndim < 2:
            return whole + part
        return jax.lax.dynamic_update_slice_in_dim(whole, part, start,
                                                   whole.ndim - 2)

    def body(index, state):
        carry, outputs = state
        start = index * _ROW_TILE
        if carry is None:
            parts = fn(layer.within(index), *tiles(start))
        else:
            carry, parts = fn(layer.within(index), carry,
                              jnp.minimum(live - start, _ROW_TILE),
                              *tiles(start))
        return carry, jax.tree_util.tree_map(partial(put, start), outputs,
                                             parts)

    if carry is None:
        length = operands[0].shape[-2]
        outputs = jax.tree_util.tree_map(
            lambda part: jnp.zeros(
                part.shape if part.ndim < 2 else
                part.shape[:-2] + (length,) + part.shape[-1:], part.dtype),
            jax.eval_shape(lambda: fn(layer, *tiles(0))))
    state = jax.lax.fori_loop(0, -(-live // _ROW_TILE), body,
                              (carry, outputs))
    return state[1] if carry is None else state


def _residual(config: TransformerConfig, h, branch):
    """h + residual_scale x branch (muP's depth scale; 1 of every model
    that has none: the plain sum)."""
    if config.residual_scale == 1.0:
        return h + branch
    return h + (branch.astype(jnp.float32)
                * config.residual_scale).astype(h.dtype)


def _decoder_layer(config: TransformerConfig, layer, h, cos, sin, attend,
                   true_len=None):
    """THE decoder layer, on every path: attention norm, projections and
    rotary, `attend`, wo and residual, MLP norm, FFN, residual (under
    sandwich_norm each sublayer's output normed before its add).  Only
    `attend` differs, by where the K/V live: attend(layer, q, k, v)
    stores the new K/V and returns (attention output (B, H, L, hd), the
    store's new leaves) -- _attend_fresh, _attend_cache, _attend_pool.
    Under latent attention k is the latent row and v None; the stores
    keep the row, and attend decompressed (fresh, cache) or absorbed
    (pool).  Everything but `attend` and the routed experts is a row's
    own: given `true_len` (a whole prefill's true length) it runs over the
    live row tiles only where the bucket runs by row tiles (_tiled), q, k,
    v and h zeros past them; the rows at or past it go to no routed expert
    either way (_routed_moe).  A gated attention's output is multiplied by
    sigmoid(gate) before wo.
    Returns (h, the FFN's stats, the store's new leaves)."""
    project = _project_latent if config.kv_lora_rank else _project_qkv

    def attention_in(layer, h, cos, sin):
        return project(
            config, layer, rms_norm(layer["attn_norm"], h, config.norm_eps),
            cos, sin)

    def attention_out(layer, h, out):
        batch, _, length, _ = out.shape
        out = dense(layer["wo"],
                    out.transpose(0, 2, 1, 3).reshape(batch, length, -1))
        if config.sandwich_norm:
            out = rms_norm(layer["attn_out_norm"], out, config.norm_eps)
        h = _residual(config, h, out)
        return h, rms_norm(layer["mlp_norm"], h, config.norm_eps)

    def ffn_out(layer, h, mlp_out):
        if config.sandwich_norm:
            mlp_out = rms_norm(layer["mlp_out_norm"], mlp_out,
                               config.norm_eps)
        return _residual(config, h, mlp_out)

    def close(layer, h, out):
        h, mlp_in = attention_out(layer, h, out)
        mlp_out, stats = _mlp_block(config, layer, mlp_in, true_len)
        return ffn_out(layer, h, mlp_out), stats

    live = _tiled(config, true_len, h.shape[1])
    q, k, v, *gate = _row_tiles(live, attention_in, layer, h, cos, sin)
    out, leaves = attend(layer, q, k, v)
    if gate:
        out = (out.astype(jnp.float32) * jax.nn.sigmoid(
            gate[0].astype(jnp.float32))).astype(out.dtype)
    if live is None or not (config.top_k and "router" in layer):
        h, stats = _row_tiles(live, close, layer, h, out)
    else:
        # the experts group the rows of the whole sequence: between two
        # row loops, the dead rows sent to no expert
        h, mlp_in = _row_tiles(live, attention_out, layer, h, out)
        mlp_out, stats = _routed_moe(config, layer, mlp_in, true_len)
        h = ffn_out(layer, h, mlp_out)
    return h, stats, leaves


def scan_kind(config: TransformerConfig, length: int) -> str:
    """What a recurrent layer's scan runs through in a whole prefill of
    `length` rows: "kernel" or "jnp".  A Mamba layer's selective scan
    (parallel/ssm.py) decides by ssm_scan_takes, as ssm_scan does, on the
    rows a call is handed (a row tile's where the bucket runs by row
    tiles); a delta layer's chunkwise recurrence (parallel/delta.py) is
    XLA's, and so is a lightning layer's (parallel/lightning.py), named
    for what it is.  The engine names its prefill spans by this."""
    if config.recurrent_kind == "lightning":
        return "lightning_chunk"
    if config.recurrent_kind == "delta":
        return "jnp"
    rows = _ROW_TILE if _row_tiles_take(config, length) else length
    return "kernel" if ssm_scan_takes(
        rows, config.ssm_d_inner, config.ssm_d_state,
        config.jnp_dtype) else "jnp"


def state_step_kind(config: TransformerConfig) -> str:
    """What advances the slots' recurrent state in a paged decode step:
    "kernel" (`ssm_row_step` of a Mamba layer, `gdn_step` of a delta
    layer: the layer's blocks of the stacked leaf read once and written
    once where they lie) or "jnp" (XLA's passes over the layer's slice).
    The step hands the mixers the whole leaf and the layer's index, and
    they decide by ssm_step_takes / delta_step_takes, as here.  The
    engine names its decode spans by this."""
    if config.recurrent_kind == "lightning":
        takes = lightning_step_takes(config.lightning_head_dim,
                                     config.lightning_heads)
    elif config.recurrent_kind == "delta":
        takes = delta_step_takes(config.delta_key_dim, config.delta_value_dim,
                                 config.delta_value_heads)
    else:
        takes = ssm_step_takes(config.ssm_d_state, config.ssm_d_inner,
                               jnp.float32)
    return "kernel" if takes else "jnp"


def scan_rows(config: TransformerConfig, bucket: int, true_len: int) -> int:
    """The rows a recurrent layer's scan runs of a whole prefill of
    `bucket` rows for a prompt of `true_len` tokens, of the rows the
    layer runs at all (prefill_rows): a kernel stops after the block of
    rows that holds row true_len - 1, XLA's form runs them all (the rows
    past true_len leaving the state alone)."""
    return ssm_scan_rows(prefill_rows(config, bucket, true_len), true_len,
                         scan_kind(config, bucket) == "kernel")


def _causal_conv(x, tail, taps, stop):
    """A depthwise causal convolution over the rows x (B, L, C) of B
    sequences whose inputs before row 0 are `tail` (taps - 1, B, C),
    oldest first; taps (taps, C) float32.  Returns (the sums (B, L, C)
    float32, the new tail: the inputs of the rows before row `stop`, or
    before the end where it is None)."""
    f32 = jnp.float32
    length, before = x.shape[1], taps.shape[0] - 1
    if length == 1:
        window = jnp.concatenate([tail, x.swapaxes(0, 1)])  # (taps, B, .)
        return (jnp.sum(window.astype(f32) * taps[:, None], axis=0)[:, None],
                window[1:])
    rows = jnp.concatenate([tail.swapaxes(0, 1), x], axis=1)
    conv = sum(rows[:, j:j + length].astype(f32) * taps[j]
               for j in range(before + 1))
    return conv, jax.lax.dynamic_slice_in_dim(
        rows, length if stop is None else stop, before,
        axis=1).swapaxes(0, 1)


def _mamba_mixer(config: TransformerConfig, layer, u, tail, ssm, stop):
    """Jamba's Mamba mixer over the normed rows u (B, L, d) of B
    sequences, each from its own state: `tail` (taps - 1, B, d_inner),
    the convolution's inputs before row 0, oldest first, and `ssm` (B,
    d_state, d_inner) float32 -- or, for a decode step over the slots'
    states, (the stack of the layers' (layers, B, ...), this layer's
    index), which the step's kernel reads and writes where it lies.
    Returns (out (B, L, d), the new tail, the new ssm in the form it
    came): the state after row stop - 1 (`stop` traced, a whole prefill's
    true length; None: after the last row), so that right padding leaves
    nothing in it.

        [x, z] = u W_in;   c = silu(b_conv + sum_j w_conv[j] x_{t-3+j})
        [delta, B, C] = c W_x, each RMS-normed with a gain of its own
        dt = delta W_dt;   the selective scan (parallel/ssm.py), float32
        out = (y * silu(z)) W_out

    One row (a decode step) is ssm_step's update (ssm_stack_step's on a
    stack); more are ssm_scan's."""
    f32 = jnp.float32
    inner, states = config.ssm_d_inner, config.ssm_d_state
    rank, eps = config.ssm_dt_rank, config.norm_eps
    length = u.shape[1]
    xz = dense(layer["w_in"], u)
    x, z = xz[..., :inner], xz[..., inner:]
    # the new tail: the inputs of the rows stop - taps + 1 .. stop - 1
    conv, tail = _causal_conv(x, tail, layer["conv"]["w"].astype(f32), stop)
    c = jax.nn.silu(conv + layer["conv"]["b"].astype(f32)).astype(u.dtype)
    projected = dense(layer["w_x"], c)
    delta = rms_norm(layer["dt_norm"], projected[..., :rank], eps)
    b = rms_norm(layer["b_norm"], projected[..., rank:rank + states], eps)
    cc = rms_norm(layer["c_norm"], projected[..., rank + states:], eps)
    dt = dense(layer["w_dt"], delta)
    a = -jnp.exp(layer["a_log"].astype(f32))
    if length == 1:
        row = (c[:, 0], dt[:, 0], z[:, 0], b[:, 0], cc[:, 0], a, layer["d"],
               layer["dt_bias"])
        if isinstance(ssm, tuple):
            y, stack = ssm_stack_step(*row, *ssm)
            ssm = (stack, ssm[1])
        else:
            y, ssm = ssm_step(*row, ssm)
        y = y[:, None]
    else:
        y, ssm = ssm_scan(c, dt, z, b, cc, a, layer["d"], layer["dt_bias"],
                          ssm, stop)
    return dense(layer["w_out"], y), tail, ssm


def _delta_mixer(config: TransformerConfig, layer, u, tail, state, stop):
    """Qwen3-Next's Gated DeltaNet mixer over the normed rows u (B, L, d)
    of B sequences, each from its own state: `tail` (taps - 1, B,
    channels), the convolution's inputs before row 0, oldest first, and
    `state`, S (B, value heads, d_k, d_v) float32 -- or, for a decode
    step over the slots' states, (the stack of the layers' (layers, B,
    ...), this layer's index), which the step's kernel reads and writes
    where it lies.  Returns (out (B, L, d), the new tail, the new state
    in the form it came): the state after row stop - 1, as _mamba_mixer.

        [q | k | v | z] = u W_qkvz;   [b | a] = u W_ba
        [q | k | v] = silu(conv([q | k | v]))      no bias
        q = q / |q| / sqrt(d_k),  k = k / |k|      a key head, each
                                    serving value_heads / key_heads heads
        beta = sigmoid(b);   g = -exp(A_log) softplus(a + dt_bias)
        o = the gated delta rule (parallel/delta.py), S float32
        out = (rms_norm(o) * gain * silu(z)) W_out     the norm a head

    One row (a decode step) is delta_step's update; more are
    delta_scan's."""
    f32 = jnp.float32
    key_heads, heads = config.delta_key_heads, config.delta_value_heads
    key_dim, value_dim = config.delta_key_dim, config.delta_value_dim
    keys, channels = key_heads * key_dim, config.delta_conv_channels
    batch, length, _ = u.shape
    mixed = dense(layer["w_qkvz"], u)
    z = mixed[..., channels:].reshape(batch, length, heads, value_dim)
    ba = dense(layer["w_ba"], u).astype(f32)
    conv, tail = _causal_conv(mixed[..., :channels], tail,
                              layer["conv"]["w"].astype(f32), stop)
    c = jax.nn.silu(conv).astype(u.dtype)

    def unit(x):
        # (B, L, key heads x d_k) -> (B, value heads, L, d_k) float32,
        # each head of unit length
        x = x.reshape(batch, length, key_heads, key_dim).astype(f32)
        x = x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)
        return jnp.repeat(x, heads // key_heads, axis=2).swapaxes(1, 2)

    q = unit(c[..., :keys]) * key_dim ** -0.5
    k = unit(c[..., keys:2 * keys])
    v = c[..., 2 * keys:].reshape(batch, length, heads,
                                  value_dim).swapaxes(1, 2)
    beta = jax.nn.sigmoid(ba[..., :heads]).swapaxes(1, 2)  # (B, H, L)
    g = (-jnp.exp(layer["a_log"]) * jax.nn.softplus(
        ba[..., heads:] + layer["dt_bias"])).swapaxes(1, 2)
    if length > 1:
        out, state = delta_scan(q.astype(u.dtype), k.astype(u.dtype), v, g,
                                beta, state, stop)
    else:
        row = (q[:, :, 0], k[:, :, 0], v[:, :, 0].astype(f32), g[:, :, 0],
               beta[:, :, 0])
        if isinstance(state, tuple):
            out, stack = delta_step(*row, *state)
            state = (stack, state[1])
        else:
            out, state = delta_step_reference(*row, state)
        out = out[:, :, None]
    out = rms_norm(layer["delta_norm"],
                   out.swapaxes(1, 2).astype(u.dtype), config.norm_eps)
    out = (out.astype(f32) * jax.nn.silu(z.astype(f32))).astype(u.dtype)
    return (dense(layer["w_out"], out.reshape(batch, length, -1)), tail,
            state)


def _lightning_mixer(config: TransformerConfig, layer, u, state, stop, cos,
                     sin):
    """MiniCPM-SALA's lightning-attention mixer over the normed rows u (B,
    L, d) of B sequences whose rows' rotary tables are cos, sin (B or 1,
    1, L, head_dim / 2), each from its own `state`, S (B, heads, d_h, d_h)
    float32 -- or, for a decode step over the slots' states, (the stack of
    the layers' (layers, B, ...), this layer's index), which the step's
    kernel reads and writes where it lies.  Returns (out (B, L, d), the
    new state in the form it came): the state after row stop - 1, as
    _mamba_mixer.

        [q | k | v | g] = u W_qkvg
        q = rope(norm(q)) / sqrt(d_h),  k = rope(norm(k))   a head, gains
                                                            of their own
        S_t = lambda_h S_{t-1} + k_t^T v_t;  o_t = q_t S_t  float32
        out = (rms_norm(o) * gain * sigmoid(g)) W_out       the norm a head

    One row (a decode step) is lightning_step's update; more are
    lightning_scan's."""
    f32 = jnp.float32
    heads, hd = config.lightning_heads, config.lightning_head_dim
    batch, length, _ = u.shape
    mixed, inner = dense(layer["w_qkvg"], u), heads * hd
    q, k, v, gate = (
        mixed[..., part * inner:(part + 1) * inner].reshape(
            batch, length, heads, hd).swapaxes(1, 2) for part in range(4))
    q = apply_rotary(rms_norm(layer["q_norm"], q, config.norm_eps), cos, sin)
    k = apply_rotary(rms_norm(layer["k_norm"], k, config.norm_eps), cos, sin)
    q = (q.astype(f32) * hd ** -0.5).astype(u.dtype)
    decay = decay_rates(heads)
    if length > 1:
        out, state = lightning_scan(q, k, v, decay, state, stop)
    else:
        row = (q[:, :, 0].astype(f32), k[:, :, 0].astype(f32),
               v[:, :, 0].astype(f32), decay)
        if isinstance(state, tuple):
            out, stack = lightning_step(*row, *state)
            state = (stack, state[1])
        else:
            out, state = lightning_step_reference(*row, state)
        out = out[:, :, None]
    out = rms_norm(layer["out_norm"], out.swapaxes(1, 2).astype(u.dtype),
                   config.norm_eps)
    out = (out.astype(f32) * jax.nn.sigmoid(
        gate.swapaxes(1, 2).astype(f32))).astype(u.dtype)
    return dense(layer["w_out"], out.reshape(batch, length, -1)), state


def _layer_kind(layer) -> str:
    """The kind of a layer (or of a stack of like layers), by the leaf
    only that kind's mixer has."""
    if "w_in" in layer:
        return "mamba"
    if "w_qkvg" in layer:
        return "lightning"
    return "delta" if "w_qkvz" in layer else "attention"


# a recurrent kind's mixer and the leaves of its state, as it takes them
_MIXERS = {"mamba": (_mamba_mixer, ("conv", "ssm")),
           "delta": (_delta_mixer, ("conv", "delta")),
           "lightning": (_lightning_mixer, ("lightning",))}


def _lightning_rope(config: TransformerConfig, positions) -> dict:
    """What a lightning layer is handed by name for the rows at
    `positions` (..., L): {"rope": (cos, sin)}, each (..., 1, L,
    lightning_head_dim / 2), a heads axis put in; {} of a model that has
    no such layer."""
    if config.recurrent_kind != "lightning":
        return {}
    return {"rope": tuple(
        jnp.expand_dims(table, -3) for table in rotary_embedding(
            positions, config.lightning_head_dim, config.rope_theta))}


def _recurrent_rows(config: TransformerConfig, layer, carry, stop, h,
                    *rope, apart: bool = False):
    """A recurrent layer over the rows h (B, L, d), a whole sequence's or
    a row tile's, from `carry`, the convolution's tail (where the mixer
    has one) and the recurrent state before row 0: norm, mixer, residual,
    MLP norm, FFN, residual; `rope` a lightning layer's rows' tables.
    Returns (the carry after row stop - 1, (h, the FFN's stats)) -- or,
    `apart` (the routed experts, which group the rows of the whole
    sequence, not of a tile), (the carry, (h before the FFN, the FFN's
    input))."""
    mixer, _ = _MIXERS[_layer_kind(layer)]
    out, *carry = mixer(
        config, layer, rms_norm(layer["mixer_norm"], h, config.norm_eps),
        *carry, stop, *rope)
    h = _residual(config, h, out)
    mlp_in = rms_norm(layer["mlp_norm"], h, config.norm_eps)
    if apart:
        return tuple(carry), (h, mlp_in)
    mlp_out, stats = _mlp_block(config, layer, mlp_in, stop)
    return tuple(carry), (_residual(config, h, mlp_out), stats)


# _recurrent_rows of one row tile, the layer's leaves sliced out of their
# stack handed in: a jit of its own.  Once its layers are _LayerAts every
# run of layers scans a body of its own (Jamba2-3B: three of Mamba layers,
# where the bucket run whole shares one), and start-up pays for each
# (PR 43: +1.45 s a prefill program, refused on setup_s).  The runs' loops
# slice their weights and call this: one traced and one lowered layer,
# scan kernel and all, and since a tile's shapes do not know the bucket,
# one a process, not one a bucket's program
_recurrent_tile = jax.jit(_recurrent_rows,
                          static_argnames=("config", "apart"))


def _stateful_layer(config: TransformerConfig, layer, h, state, stop,
                    rope=()):
    """_mamba_layer, _delta_layer and _lightning_layer: h + mixer(norm(h)),
    then the FFN as a decoder layer has it (_recurrent_rows), the kind by
    the layer's leaves; `rope` a lightning layer's rows' rotary tables (.,
    1, L, .), which ride the row tiles as h does.  Where the bucket runs
    by row tiles (_tiled) the layer runs a tile at a time over the tiles
    that hold a live row, in one loop that
    carries what crosses a tile's edge and nothing else -- the
    convolution's tail and the recurrent state, each tile stopping at its
    share of `stop` -- so the last live tile leaves the state after row
    stop - 1 and the tail at `stop`, as the bucket run whole does, and h
    is zeros past the live tiles.  The routed experts stay between the
    loop and the residual, as in _decoder_layer."""
    _, leaves = _MIXERS[_layer_kind(layer)]
    if state is None:
        state = _layer_state(config, h.shape[0])
    carry = tuple(state[leaf] for leaf in leaves)
    live = _tiled(config, stop, h.shape[1])
    if live is None:
        carry, (h, stats) = _recurrent_rows(config, layer, carry, stop, h,
                                            *rope)
    else:
        apart = bool(config.top_k and "router" in layer)
        zeros = jnp.zeros_like(h)
        carry, (h, stats) = _row_tiles(
            live, lambda layer, *rows: _recurrent_tile(
                config, layer.sliced(), *rows, apart=apart),
            layer, h, *rope, carry=carry, outputs=(
                zeros, zeros if apart else jnp.zeros((_FFN_STATS,),
                                                     jnp.float32)))
        if apart:
            mlp_out, stats = _routed_moe(config, layer, stats, stop)
            h = h + mlp_out
    return h, stats, dict(zip(leaves, carry))


def _mamba_layer(config: TransformerConfig, layer, h, state, stop=None):
    """A Mamba layer: h + mixer(norm(h)), then the dense FFN as a decoder
    layer has it.  `state` is the layer's {"conv", "ssm"} for h's B
    sequences, or None: zeros, a sequence from its start; `stop` (traced,
    a whole prefill's true length; None: every row counts) the mixer's,
    and by it the layer runs by row tiles where the bucket does
    (_stateful_layer).  Returns (h, the FFN's stats, the new state)."""
    return _stateful_layer(config, layer, h, state, stop)


def _delta_layer(config: TransformerConfig, layer, h, state, stop=None):
    """A Gated DeltaNet layer: h + mixer(norm(h)), then the FFN as a
    decoder layer has it.  `state` is the layer's {"conv", "delta"} for
    h's B sequences (its "delta" as _delta_mixer takes it), or None:
    zeros, a sequence from its start; `stop` as _mamba_layer's.  Returns
    (h, the FFN's stats, the new state)."""
    return _stateful_layer(config, layer, h, state, stop)


def _lightning_layer(config: TransformerConfig, layer, h, state, stop=None,
                     rope=()):
    """A lightning-attention layer: h + scale x mixer(norm(h)), then the
    dense FFN as a decoder layer has it.  `state` is the layer's
    {"lightning"} for h's B sequences (as _lightning_mixer takes it), or
    None: zeros, a sequence from its start; `stop` as _mamba_layer's;
    `rope` the rows' rotary tables (_lightning_rope, a heads axis put in).
    Returns (h, the FFN's stats, the new state)."""
    return _stateful_layer(config, layer, h, state, stop, rope)


def _recurrent_layer(layer):
    """The body of a recurrent layer, by its kind; None for a layer that
    attends."""
    return {"mamba": _mamba_layer, "delta": _delta_layer,
            "lightning": _lightning_layer}.get(_layer_kind(layer))


def _sp_prefill(config: TransformerConfig, q, k, v):
    repeats = config.n_heads // config.n_kv_heads
    k, v = repeat_kv(k, repeats), repeat_kv(v, repeats)
    if config.sp_mechanism == "ulysses":
        return ulysses_attention(q, k, v, mesh=None, causal=True)
    return ring_attention(q, k, v, causal=True)


def _attend_selecting(config: TransformerConfig, q, k, v, live=None,
                      flash: bool = True):
    """Causal attention from position 0 of an attention that selects its
    blocks: the rows under sparse_dense_len over every earlier row
    (blockwise where `flash`, else the masked einsum), the rows from it on
    over the blocks they choose (parallel/sparse.py), a sequence at a
    time.  `live` is a whole prefill's true length.  Returns (out, the
    compressed keys (B, G, ceil(L / block) x block / stride, hd))."""
    sizes = config.sparse_sizes
    length = q.shape[2]
    rows = min(length, sizes.dense_len)
    first = [x[:, :, :rows] for x in (q, k, v)]
    if flash:
        out = flash_attention(*first, causal=True, live=(
            None if live is None else jnp.minimum(live, rows)))
    else:
        repeats = config.n_heads // config.n_kv_heads
        out = attention_reference(first[0], repeat_kv(first[1], repeats),
                                  repeat_kv(first[2], repeats), causal=True)
    chosen, compressed = jax.vmap(
        lambda q, k, v: sparse_prefill_attention(
            q[None], k[None], v[None], sizes, live))(q, k, v)
    return jnp.concatenate([out, chosen[:, 0, :, rows:]],
                           axis=2), compressed[:, 0]


def _attend_fresh(config: TransformerConfig, layer, q, k, v):
    """No KV store (training, scoring): causal attention over the fresh
    K/V, blockwise; of an attention that selects, over the blocks each
    row chooses."""
    if config.sparse_topk:
        return _attend_selecting(config, q, k, v)[0], None
    if config.kv_lora_rank:
        return _latent_flash(config, q,
                             *_latent_expand(config, layer, k)), None
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


def _store_leaf(store: dict):
    """The leaf that says a store's shape and dtype: a latent store's
    one leaf, else the keys'."""
    return store["kv"] if "kv" in store else store["k"]


def cache_attention_kind(config: TransformerConfig, store: dict, batch: int,
                         length: int, pos=0) -> str:
    """"flash", "einsum" or, of an attention that selects its blocks past
    sparse_dense_len rows, "sparse": what a forward of (batch, length)
    tokens at `pos` attends through when its K/V go to a contiguous cache of
    `store`'s dtype (a cache, or the pool paged_prefill scatters its
    cache into: their leaves are alike).  _attend_cache decides by this,
    and the engine names its prefill spans by it."""
    return _cache_attention_kind(config, _store_leaf(store).dtype, batch,
                                 length, pos)


def _cache_attention_kind(config: TransformerConfig, dtype, batch: int,
                          length: int, pos) -> str:
    if config.sparse_topk and length > config.sparse_dense_len:
        # its first sparse_dense_len rows attend as a bucket of that many
        return "sparse"
    if (length > 1 and isinstance(pos, (int, np.integer)) and pos == 0
            and flash_attention_takes(batch, config.n_heads, length, dtype,
                                      dtype)):
        return "flash"
    return "einsum"


def prefill_attention_rows(config: TransformerConfig, bucket: int,
                           true_len: int) -> int:
    """The query rows the attention of a whole prefill (paged_prefill) of
    `bucket` rows runs for a prompt of `true_len` tokens: true_len rounded
    up to the kernel's query block where the bucket attends through the
    flash kernel, which is then told the length (flash_attention's
    `live`: the blocks past it take no step and fetch nothing), else the
    bucket.  _attend_cache decides by the same predicate -- the
    attention's own, whether or not the bucket runs by row tiles -- and
    the engine names its prefill spans by this."""
    dtype = jnp.int8 if config.kv_dtype == "int8" else config.jnp_dtype
    kind = _cache_attention_kind(config, dtype, 1, bucket, 0)
    if kind == "sparse":
        # the selection's query tiles past dense_len that hold a live row
        tile = sparse_tile(bucket, config.sparse_sizes)
        return min(bucket, max(config.sparse_dense_len,
                               -(-true_len // tile) * tile))
    if config.sequence_parallel or kind != "flash":
        return bucket
    kv_heads, width = config.n_kv_heads, config.head_dim
    if config.kv_lora_rank:
        # decompressed: every head its own K/V, q.k padded to the lanes
        kv_heads = config.n_heads
        width = -(-(config.qk_nope_head_dim + config.qk_rope_head_dim)
                  // 128) * 128
    block = flash_query_block(config.n_heads, kv_heads, width, bucket,
                              live=True)
    return min(bucket, -(-true_len // block) * block)


def _attend_cache_latent(config: TransformerConfig, cache: dict, pos,
                         layer, q, latent, live=None):
    """_attend_cache for latent attention, decompressed: the fresh rows
    alone through the flash kernel where a prefill from position 0 takes
    it, else the whole buffer's rows made heads again and masked."""
    batch, _, length, _ = q.shape
    cache = {"kv": jax.lax.dynamic_update_slice(cache["kv"], latent,
                                                (0, 0, pos, 0))}
    if cache_attention_kind(config, cache, batch, length, pos) == "flash":
        return _latent_flash(
            config, q, *_latent_expand(config, layer, latent), live), cache
    k, v = _latent_expand(config, layer, cache["kv"])
    return attention_reference(
        q, k, v, causal=True, sm_scale=config.attention_scale,
        q_offset=pos - (k.shape[2] - length)), cache


def _attend_cache_selecting(config: TransformerConfig, cache: dict, pos, q,
                            k, v, live=None):
    """_attend_cache for an attention that selects its blocks: a prefill
    from the static position 0 (_attend_selecting), which also leaves the
    sequence's compressed keys in the cache's "kc".  Anything else -- a
    step or a window at a later position of a contiguous cache -- is
    refused: the compressed keys are carried through the paged pool's
    decode step (_attend_pool) and through nothing else."""
    batch, _, length, _ = q.shape
    if not (isinstance(pos, (int, np.integer)) and pos == 0 and length > 1):
        raise ValueError(
            "a contiguous cache at a later position (decode_step, "
            "generate(), generate_stream()) is not implemented for an "
            "attention that selects its blocks: the store of compressed "
            "keys is carried through the paged pool's decode step only")
    cache = dict(cache, **{
        name: jax.lax.dynamic_update_slice(cache[name], value, (0, 0, 0, 0))
        for name, value in (("k", k), ("v", v))})
    out, compressed = _attend_selecting(
        config, q, k, v, live, flash=_cache_attention_kind(
            config, cache["k"].dtype, batch,
            min(length, config.sparse_dense_len), 0) == "flash")
    compressed = compressed[:, :, :cache["kc"].shape[2]]
    cache["kc"] = jax.lax.dynamic_update_slice(cache["kc"], compressed,
                                               (0, 0, 0, 0))
    return out, cache


def _attend_cache(config: TransformerConfig, cache: dict, pos, layer,
                  q, k, v, live=None):
    """Contiguous cache (init_cache; `cache` is one layer's leaves):
    write the new K/V at `pos`, then masked attention over the whole
    buffer -- or, for a prefill from the static position 0 that
    flash_attention_takes, the same causal attention over the fresh K/V
    alone, blockwise, and told `live`, the true length of a whole
    prefill whose other rows are padding (traced; None: every row is
    read), where there is one."""
    if config.kv_lora_rank:
        return _attend_cache_latent(config, cache, pos, layer, q, k, live)
    if config.sparse_topk:
        return _attend_cache_selecting(config, cache, pos, q, k, v, live)
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
        return flash_attention(q, k, v, causal=True, live=live), cache
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
    if config.embed_scale != 1.0:
        h = h * jnp.asarray(config.embed_scale, h.dtype)
    if h.dtype == jnp.int8:
        # int8 embed (quantize_weights_int8): gather the rows' scales
        # alongside and dequantize only the gathered tokens
        h = (h.astype(jnp.float32)
             * jnp.take(params["embed"]["w_scale"], tokens, axis=0,
                        mode="clip")).astype(config.jnp_dtype)
    return h


_EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def _by_head(config: TransformerConfig, stack: dict) -> dict:
    """A stack of layers with wq and wk (and their int8 scales) as
    (layers, heads, depth, in), what dense_heads contracts: the bytes of
    the stored (layers, heads * depth, in), reshaped once, outside the
    layer loop.  A reshape between the loop's slice of the stack and the
    matmul makes XLA stage the slice whole through fast memory before
    multiplying from there (45 + 12 us a layer for wq at Mistral-7B's
    widths, where the matmul that reads the stack itself takes 45)."""
    if "wq" not in stack:
        return stack
    stack = dict(stack)
    for name, heads in (("wq", config.n_heads), ("wk", config.n_kv_heads)):
        stack[name] = {
            key: leaf.reshape(leaf.shape[0], heads, -1, leaf.shape[-1])
            for key, leaf in stack[name].items()}
    return stack


class _LayerAt:
    """Layer `index` of a stack of layers, each leaf sliced out of the
    stack where it is read: layer["wo"] traces the slice there.  What a
    whole prefill's row loops need: a layer scan's own slice is made
    outside them, and a loop inside the layer takes what it reads as a
    buffer of its own, so XLA copied every weight of every layer (450 MB
    a layer of Mistral-7B's widths) where a matmul that slices the stack
    itself reads it in place."""

    def __init__(self, stack: dict, index, whole: dict):
        self._stack, self._index, self._whole = stack, index, whole

    def within(self, step) -> "_LayerAt":
        """The layer for iteration `step` of a loop inside it: the same
        slices, tied to the loop's counter so that XLA does not move
        them out of the loop again (they are loop-invariant)."""
        index, _ = jax.lax.optimization_barrier((self._index, step))
        return _LayerAt(self._stack, index, self._whole)

    def sliced(self) -> dict:
        """The layer's leaves, each sliced out of its stack here: what a
        jit of the layer's rows takes (the leaves held whole, the routed
        experts', are not among them)."""
        return {name: self[name] for name in self._stack}

    def __contains__(self, name) -> bool:
        return name in self._stack or name in self._whole

    def __getitem__(self, name):
        if name in self._whole:
            return self._whole[name]
        return jax.tree_util.tree_map(lambda leaf: leaf[self._index],
                                      self._stack[name])


def _scan_layers(config: TransformerConfig, step, carry, stack, extra,
                 sliced_where_read: bool = False):
    """jax.lax.scan of step(carry, (layer, extra[i])) over a stack of
    layers, wq and wk split by head (_by_head).  The routed experts'
    weights do not ride the scan: a
    kernel's operand that the loop slices out of the stack is copied
    whole every iteration (1.9 GB a layer at DeepSeek-V2's widths), so
    such a layer carries the whole stacked leaves and its index among
    them as layer["experts"], and the kernel reads the layer where it
    lies (parallel/experts.py).  sliced_where_read: no leaf rides the
    scan, the layer is a _LayerAt."""
    stack = _by_head(config, stack)
    routed = bool(config.top_k and "router" in stack)
    if not (routed or sliced_where_read):
        return jax.lax.scan(step, carry, (stack, extra))
    held = {name: stack[name] for name in _EXPERT_LEAVES} if routed else {}
    rest = {name: leaf for name, leaf in stack.items() if name not in held}
    layers = jax.tree_util.tree_leaves(stack)[0].shape[0]

    def with_index(carry, xs):
        layer, index, extra_i = xs
        whole = {"experts": (held, index)} if routed else {}
        layer = (_LayerAt(rest, index, whole) if sliced_where_read
                 else dict(layer, **whole))
        return step(carry, (layer, extra_i))

    return jax.lax.scan(
        with_index, carry,
        (None if sliced_where_read else rest, jnp.arange(layers), extra))


def _route(config: TransformerConfig, router: dict, x):
    """DeepSeek-V2's group-limited greedy router.  x (T, d) -> (weights
    (T, k) float32, ids (T, k) int32 in the router's numbering): softmax
    scores in float32 over all n_routed_experts, a group's score its
    largest, the best topk_groups groups kept, the top_k scores among
    their experts chosen (ties to the lower index, jax.lax.top_k's
    order), weighted routed_scaling x score, renormalised (divided by
    their sum) only under norm_topk.  One group of all the experts is
    plain top-k."""
    tokens = x.shape[0]
    scores = jax.nn.softmax(jnp.einsum(
        "td,de->te", x.astype(jnp.float32),
        router["w"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST), axis=-1)
    per_group = config.n_routed_experts // config.n_groups
    _, kept = jax.lax.top_k(
        scores.reshape(tokens, config.n_groups, per_group).max(axis=-1),
        config.topk_groups)
    group_mask = jnp.zeros((tokens, config.n_groups), bool).at[
        jnp.arange(tokens)[:, None], kept].set(True)
    weights, ids = jax.lax.top_k(
        jnp.where(jnp.repeat(group_mask, per_group, axis=1), scores, 0.0),
        config.top_k)
    if config.norm_topk:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights * config.routed_scaling, ids


def _routed_moe(config: TransformerConfig, layer, x, true_len=None):
    """Shared(x) + sum over a token's chosen experts of g_i E_i(x), of
    which this process adds the experts it holds (config.held) and
    leaves the rest to the shares that hold them; a token none of whose
    experts is held gets the shared experts only.  No token is dropped:
    every token-expert pair held is computed (parallel/experts.py).
    Given `true_len` (a whole prefill's true length) the rows at or past
    it go to no expert, and the shared experts run over the live row
    tiles where the bucket runs by row tiles (_tiled).  Under
    shared_expert_gate the shared experts' output is multiplied by
    sigmoid(x . shared_mix).  Returns (output, stats) with stats[1:] =
    distinct held experts hit and pairs computed: of a whole prefill the
    live rows'."""
    batch, length, d_model = x.shape
    tokens = x.reshape(batch * length, d_model)
    weights, ids = _route(config, layer["router"], tokens)
    low, high = config.held
    held = (ids >= low) & (ids < high)
    if true_len is not None:
        held &= jnp.tile(jnp.arange(length) < true_len, batch)[:, None]
    stacked, index = layer["experts"]        # _scan_layers
    routed, experts_read, pairs = expert_ffn(
        tokens, *(stacked[name]["w"] for name in _EXPERT_LEAVES),
        jnp.where(held, ids - low, high - low),
        jnp.where(held, weights, 0.0), layer=index)
    shared = _row_tiles(
        _tiled(config, true_len, length), lambda layer, x: swiglu(
            layer["shared_gate"], layer["shared_up"], layer["shared_down"],
            x), layer, x)
    if config.shared_expert_gate:
        shared = shared * jax.nn.sigmoid(dense(layer["shared_mix"], x))
    stats = jnp.stack([jnp.float32(0.0), experts_read.astype(jnp.float32),
                       pairs.astype(jnp.float32)])
    return shared + routed.reshape(x.shape).astype(x.dtype), stats


# what an FFN reports beside its output, summed over layers: the switch
# FFN's load-balancing loss, and of the routed experts the distinct
# experts read and the token-expert pairs computed
_FFN_STATS = 3


def _mlp_block(config: TransformerConfig, layer, mlp_in, true_len=None):
    """One layer's FFN (dense SwiGLU, switch MoE, or routed + shared
    experts where the layer has a router; `true_len` is _routed_moe's).
    Returns (output, stats float32 (_FFN_STATS,))."""
    if config.top_k and "router" in layer:
        return _routed_moe(config, layer, mlp_in, true_len)
    stats = jnp.zeros((_FFN_STATS,), jnp.float32)
    if config.n_experts > 0:
        out, aux = _switch_moe(config, layer, mlp_in)
        return out, stats.at[0].set(aux)
    return dense(
        layer["w_down"],
        jax.nn.silu(dense(layer["w_gate"], mlp_in))
        * dense(layer["w_up"], mlp_in)), stats


def _lm_head(params: dict, config: TransformerConfig, h):
    """Output norm + logits head shared by forward() and the paged
    decode path."""
    h = rms_norm(params["norm_out"], h, config.norm_eps)
    if config.logit_divisor != 1.0:
        h = (h.astype(jnp.float32) / config.logit_divisor).astype(h.dtype)
    return _head_logits(params, h)


def _head_logits(params: dict, h):
    """The logits head over normed h.  Untied output head when the
    checkpoint ships one (Llama-3-8B+, models/weights.py
    load_llama_params); tied embedding otherwise."""
    head = params.get("lm_head", params["embed"])
    logits = jnp.einsum("bld,vd->blv", h.astype(jnp.float32),
                        head["w"].astype(jnp.float32))
    if head["w"].dtype == jnp.int8:
        # per-row scales factor out of the contraction: the einsum
        # streams 8-bit codes, the (V,) scale applies to the result
        logits = logits * head["w_scale"][:, 0]
    return logits


def _rotary_tables(config: TransformerConfig, positions):
    """cos/sin of `positions` over the rotary width: the heads' own, or
    under latent attention the rotary slice's; YaRN's frequencies and
    multiplier where the context is stretched."""
    if config.rope_factor <= 1.0:
        return rotary_embedding(positions, config.rotary_dim,
                                config.rope_theta)
    cos, sin = rotary_embedding(
        positions, config.rotary_dim, frequencies=yarn_frequencies(
            config.rotary_dim, config.rope_theta, config.rope_factor,
            config.rope_original_max, config.rope_beta_fast,
            config.rope_beta_slow))
    multiplier = (yarn_mscale(config.rope_factor, config.rope_mscale)
                  / yarn_mscale(config.rope_factor,
                                config.rope_mscale_all_dim))
    return cos * multiplier, sin * multiplier


def _layer_stacks(params: dict, config: TransformerConfig) -> list:
    """[(stacked layers, index of the first, how many)] in the order they
    run: the leading dense layers of a routed-expert model, then the
    stack every model has; of a model with layer_kinds its runs of like
    layers, a run's first index counted among its own kind (an attention
    layer's among the K/V caches, a recurrent layer's among the states)."""
    if config.layer_kinds:
        return [(stack, first, count) for stack, (_, first, count)
                in zip(params["runs"], _kind_runs(config))]
    lead = _leading_dense(config)
    stacks = [(params["dense_layers"], 0, lead)] if lead else []
    return stacks + [(params["layers"], lead, config.n_layers - lead)]


def _cache_index(config: TransformerConfig, step, layer):
    """Which of a position's n_caches K/V caches layer `layer` writes and
    attends over on pass `step` of a looped stack: pass-major, a pass's
    caches side by side (a model of one pass: the layer's own).  The
    contiguous cache (forward), the pool (_paged_logits), paged_prefill's
    scatter of the one into the other and benchmark/reference/ouro.py's
    description are all laid out by this."""
    return step * config.n_layers + layer


def _run_passes(params: dict, config: TransformerConfig, carry,
                scan_stack):
    """The stacks of layers in the order they run, ut_steps times over.
    scan_stack(carry, stack, first_cache, count) scans one stack once,
    its layers on caches first_cache .. first_cache + count - 1, and
    returns the carry, whose first entry is h.  A looped model closes
    every pass with norm_out, whose output opens the next pass.
    Returns (carry, the passes' normed outputs: [] for one pass, which
    traces nothing it did not trace before there were passes)."""
    outputs = []
    for step in range(config.ut_steps):
        for stack, first, count in _layer_stacks(params, config):
            carry = scan_stack(carry, stack,
                               _cache_index(config, step, first), count)
        if config.ut_steps > 1:
            outputs.append(rms_norm(params["norm_out"], carry[0],
                                    config.norm_eps))
            carry = (outputs[-1], *carry[1:])
    return carry, outputs


def _exit_pdf(params: dict, outputs):
    """The exit gate over the passes' outputs [T x (..., d)], float32:
    lam[t] = sigmoid(h[t] . w + b); p[t] = lam[t] prod_{s<t} (1 - lam[s])
    for t < T - 1 and p[T-1] = prod_{s<T-1} (1 - lam[s]), the chance of
    leaving after pass t.  Returns p (T, ...)."""
    gate = params["exit_gate"]
    lam = jax.nn.sigmoid(jnp.stack([
        jnp.einsum("...d,d->...", h.astype(jnp.float32),
                   gate["w"].astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)
        for h in outputs]) + gate["b"].astype(jnp.float32))
    # stayed[t] = prod_{s<t} (1 - lam[s]): still running when pass t ends
    stayed = jnp.concatenate(
        [jnp.ones_like(lam[:1]), jnp.cumprod(1.0 - lam, axis=0)[:-1]])
    return jnp.concatenate([(lam * stayed)[:-1], stayed[-1:]])


def _exit_pass(pdf, threshold: float):
    """The pass each position leaves after: the first by which the exit
    probabilities (T, ...) sum to `threshold`, else the last."""
    reached = jnp.cumsum(pdf, axis=0) >= threshold
    return jnp.where(reached.any(axis=0), jnp.argmax(reached, axis=0),
                     pdf.shape[0] - 1)


def _logits(params: dict, config: TransformerConfig, h, outputs):
    """Logits from what _run_passes left.  One pass: output norm and
    head over h.  A looped stack: the head over the output (normed
    already) of each position's exit pass (at the published threshold 1
    the last, but for a gate saturated in float32).  Returns (logits,
    the exit's expected pass sum_t (t + 1) p[t] a position, float32;
    None for one pass)."""
    if not outputs:
        return _lm_head(params, config, h), None
    pdf = _exit_pdf(params, outputs)
    chosen = _exit_pass(pdf, config.exit_threshold)
    h = jnp.take_along_axis(jnp.stack(outputs), chosen[None, ..., None],
                            axis=0)[0]
    steps = jnp.arange(1, len(outputs) + 1, dtype=jnp.float32)
    return _head_logits(params, h), jnp.tensordot(steps, pdf, axes=1)


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
    h, outputs, stats_sum, new_cache = _hidden(
        params, config, tokens, cache, pos, activation_specs, remat_policy)
    logits, _ = _logits(params, config, h, outputs)
    if cache is None:
        if return_aux:
            return logits, stats_sum[0] / max(config.n_caches, 1)
        return logits
    return logits, new_cache


def _hidden(params: dict, config: TransformerConfig, tokens, cache, pos,
            activation_specs: bool = False, remat_policy: str | None = None,
            true_len=None):
    """forward before its head: (h, the passes' outputs, the FFNs' stats
    summed over layers and passes, the updated cache or None), what
    _logits takes.  `true_len` (traced) says that only the first true_len
    rows matter, the rest being padding: a call into a cache from the
    static position 0 whose length _row_tiles_take takes then runs every
    layer's work over the live row tiles alone (_decoder_layer's row-wise
    segments; a recurrent layer whole, its state carried from tile to
    tile, _stateful_layer), and h, the outputs and the cache hold zeros
    past them; and, whatever
    _row_tiles_take says, an attention through the flash kernel is told
    the length and runs the query blocks that hold a live row
    (prefill_attention_rows)."""
    if remat_policy not in (None, "none") and cache is not None:
        raise ValueError(
            "remat_policy is only meaningful on the cache-less "
            "(training/scoring) path; incremental decode saves nothing "
            "by rematerializing")
    length = tokens.shape[1]
    # a whole prefill's true length, for the attention (which takes it
    # where it is the flash kernel's, _attend_cache) and for the row-wise
    # work (where the bucket runs by row tiles)
    whole = (true_len is not None and cache is not None
             and isinstance(pos, (int, np.integer)) and pos == 0)
    attended = true_len if whole else None
    live = _tiled(config, attended, length)
    if activation_specs:
        # batch on "data", sequence on "seq" -- but only the axes the
        # ambient mesh actually has (an EP-only mesh has no "seq")
        names = jax.sharding.get_abstract_mesh().axis_names
        act_spec = P("data" if "data" in names else None,
                     "seq" if "seq" in names else None, None)
    h = _embed(params, config, tokens)
    if activation_specs:
        h = jax.lax.with_sharding_constraint(h, act_spec)
    positions = pos + jnp.arange(length)
    cos, sin = _rotary_tables(config, positions)
    cos, sin = cos[None, None], sin[None, None]  # (1, 1, L, hd/2)
    # a lightning layer's own tables, handed to it by name
    rope = _lightning_rope(config, positions)

    def layer_step(carry, xs):
        h, stats_sum = carry
        layer, layer_cache = xs
        recurrent = _recurrent_layer(layer)
        if recurrent:
            # a recurrent layer: its state after row true_len - 1
            h, stats, new_cache = recurrent(config, layer, h, layer_cache,
                                            true_len, **rope)
        else:
            h, stats, new_cache = _decoder_layer(
                config, layer, h, cos, sin,
                partial(_attend_fresh, config) if layer_cache is None
                else partial(_attend_cache, config, layer_cache, pos,
                             live=attended), attended)
        stats_sum = stats_sum + stats
        if activation_specs:
            h = jax.lax.with_sharding_constraint(h, act_spec)
        return (h, stats_sum), new_cache

    carry = (h, jnp.zeros((_FFN_STATS,), jnp.float32))
    body = layer_step
    policy = resolve_remat_policy(remat_policy)
    if policy is not None:
        # remat over the scanned layer body: the standard trade --
        # drop (policy-selected) activations in the forward pass,
        # recompute them during backward.  prevent_cse=False is the
        # documented setting under scan (the scan boundary already
        # blocks the CSE that prevent_cse guards against).
        body = jax.checkpoint(body, policy=policy, prevent_cse=False)
    written = {}

    def scan_stack(carry, stack, first, count):
        if cache is None:
            return _scan_layers(config, body, carry, stack, None)[0]
        # the leaves this stack's layers write: a recurrent run the
        # state's, else the K/V's -- whole where the stack run once has
        # them all, else those it writes on this pass
        names = [name for name in cache if (name in _STATE_LEAVES) == (
            _layer_kind(stack) in _RECURRENT_KINDS)]
        carry, part = _scan_layers(
            config, body, carry, stack,
            {name: cache[name] if count == cache[name].shape[0]
             else cache[name][first:first + count] for name in names},
            sliced_where_read=live is not None)
        for name in names:
            written.setdefault(name, []).append(part[name])
        return carry

    (h, stats_sum), outputs = _run_passes(params, config, carry, scan_stack)
    new_cache = None
    if cache is not None:
        new_cache = {name: parts[0] if len(parts) == 1
                     else jnp.concatenate(parts)
                     for name, parts in written.items()}
    return h, outputs, stats_sum, new_cache


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
#     prefill reshapes a forward() cache into blocks), and a step's K/V
#     goes into the donated pool where it lies: at window 1, the decode
#     step, the paged-attention kernel writes the rows itself, the pool
#     riding the call aliased in place; a wider window's rows, and those
#     of a call the kernel does not take, go in one update each before
#     the attention (_write_window; pool_write_kind says which);
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
    init_cache, so the int8 KV path carries over unchanged.  A model with
    recurrent layers has its slots' recurrent state beside these leaves, in
    the same dict (init_recurrent_state: by slot, not by block); the
    engine makes both."""
    if config.sparse_topk and block_size != config.sparse_block:
        raise ValueError(
            f"an attention that selects blocks of {config.sparse_block} "
            f"positions needs a pool of such blocks (kv_block_size), not "
            f"of {block_size}: a chosen block is a pool block")
    if config.kv_lora_rank:
        # the latent pool: one leaf, one row a position a layer
        return {"kv": jnp.zeros(
            (config.n_caches, num_blocks, 1, block_size,
             config.latent_row), config.jnp_dtype)}
    shape = (config.n_caches, num_blocks, config.n_kv_heads, block_size,
             config.head_dim)
    if config.kv_dtype == "int8":
        scale_shape = shape[:-1] + (1,)
        return {"k": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(scale_shape, jnp.float32),
                "v": jnp.zeros(shape, jnp.int8),
                "v_scale": jnp.zeros(scale_shape, jnp.float32)}
    return {"k": jnp.zeros(shape, config.jnp_dtype),
            "v": jnp.zeros(shape, config.jnp_dtype),
            **_compressed_store(config, num_blocks, block_size)}


@partial(jax.jit, static_argnames=("config",), donate_argnums=(2,))
def paged_prefill(params, config: TransformerConfig, pool, prompt,
                  table_row, true_len, slot=None):
    """Prefill one request into its pool blocks.  prompt is (1, Lb)
    with Lb a multiple of the pool's block size (the engine right-pads
    to a bucket, so one executable serves every prompt length in the
    bucket); table_row (max_blocks,) names the slot's blocks, of which
    the first Lb//block_size receive the prompt's K/V.  Returns
    (pool, first_token) where first_token is the greedy token after the
    TRUE prompt length -- causal masking makes logits at true_len-1
    independent of the right-padding, and the head runs at that one
    position.  One executable per bucket, which does the work of the
    prompt, not of the bucket: a bucket that runs by row tiles
    (_row_tiles_take) runs a layer's row-wise work, and a recurrent layer,
    up to true_len and the blocks past them receive zeros (a decode step
    writes a position before it reads it).  The decode loop never recompiles
    (paged_decode_step below).  A model with recurrent layers is also told
    its `slot` (traced int32): the recurrent state after row true_len - 1
    overwrites the whole of that slot's, whatever the bucket."""
    if config.recurrent and slot is None:
        raise ValueError("paged_prefill of a model with a recurrent state "
                         "needs the slot whose state it writes")
    block_size = _store_leaf(pool).shape[3]
    local = init_cache(config, 1, max_len=prompt.shape[1])
    h, outputs, _, local = _hidden(params, config, prompt, local, 0,
                                   true_len=true_len)
    # the head at the one position whose logits are read
    last = partial(jax.lax.dynamic_slice_in_dim, start_index=true_len - 1,
                   slice_size=1, axis=1)
    logits, _ = _logits(params, config, last(h), [*map(last, outputs)])
    first = jnp.argmax(logits[0, 0]).astype(jnp.int32)
    blocks = prompt.shape[1] // block_size
    new_pool = {}
    for name, axis in _STATE_LEAVES.items():
        # the recurrent state after row true_len - 1, the whole of slot
        # `slot`'s: the slot's previous occupant leaves nothing behind
        if name in local:
            new_pool[name] = jax.lax.dynamic_update_slice_in_dim(
                pool[name], local.pop(name), slot, axis)
    for name, written in local.items():
        # (caches, 1, H, Lb, d) -> (caches, blocks, H, block_size, d),
        # scattered into the slot's first `blocks` pool entries: cache
        # and pool lead with the same axis (_cache_index)
        # (the compressed keys: block_size / stride entries a block)
        entry = written[:, 0]
        layers, heads, rows, depth = entry.shape
        entry = entry.reshape(layers, heads, blocks, rows // blocks,
                              depth).transpose(0, 2, 1, 3, 4)
        new_pool[name] = pool[name].at[:, table_row[:blocks]].set(entry)
    return new_pool, first


def _write_window(leaf, value, layer, write_blocks, write_offsets):
    """Write the window's new K/V (or scales) into one pool leaf where
    it lies, for the calls whose rows the paged kernel does not write
    itself (pool_write_kind "updates": a window over 1, an int8 pool, a
    shape the kernel does not take).  leaf (L, num_blocks, H, block, d);
    value (S, H, W, d); write_blocks/write_offsets (S, W).  One
    dynamic_update_slice per window position, unrolled: on the chip a
    scatter, and a rolled loop of updates too, make XLA re-lay the whole
    leaf out (two copies of it a layer); these update in place in the
    layout the kernel reads, each a device operation of its own.
    Later positions win where inert rows share the trash block."""
    slots, _, window, _ = value.shape
    for s in range(slots):
        for i in range(window):
            # (H, d) -> (1, 1, H, 1, d) at [layer, block, :, offset, :]
            leaf = jax.lax.dynamic_update_slice(
                leaf, value[s, :, i][None, None, :, None, :],
                (layer, write_blocks[s, i], 0, write_offsets[s, i], 0))
    return leaf


def _paged_kernel_takes(config: TransformerConfig, pool: dict,
                        window: int) -> bool:
    """paged_attention_takes for a window over `pool`: its leaf says the
    row's width and dtype, the config whether a row is a latent one."""
    leaf = _store_leaf(pool)
    return paged_attention_takes(
        config.n_heads, window, leaf.shape[-1], leaf.dtype,
        value_dim=config.kv_lora_rank or None)


def pool_write_kind(config: TransformerConfig, pool: dict,
                    window: int) -> str:
    """"kernel" or "updates": who writes a paged step's new rows into
    `pool` at a window of `window` positions.  "kernel": the paged
    attention kernel takes the call and writes the rows itself, the pool
    aliased through it (window 1: the decode step).  "updates":
    _write_window, one dynamic_update_slice a slot a leaf a position,
    before the attention.  _attend_paged decides by this, and the engine
    names its decode and chunk spans by it."""
    return ("kernel" if _paged_kernel_takes(config, pool, window)
            and paged_attention_writes(window) else "updates")


def _attend_pool_latent(config: TransformerConfig, pool: dict, index,
                        tables, positions, write_blocks, write_offsets,
                        layer, q, latent):
    """_attend_pool for latent attention, absorbed: the keys' up-
    projection goes into the query (q~_h = W_k,h^T q_nope_h), the
    scores are q~_h . c_kv + q_rope_h . k_r against the rows as they
    lie, the weighted rows come back (S, H, W, rank) and the values'
    up-projection is applied to them.  128 query heads over ONE key
    head whose first `rank` values are also the value: no head's key or
    value is ever made, and a row is read once."""
    nope, rank = config.qk_nope_head_dim, config.kv_lora_rank
    absorbed = jnp.einsum("shwn,hnc->shwc", q[..., :nope],
                          layer["wk_b"]["w"],
                          preferred_element_type=jnp.float32
                          ).astype(q.dtype)
    pad = config.latent_row - rank - config.qk_rope_head_dim
    query = jnp.concatenate(
        [absorbed, q[..., nope:],
         jnp.zeros(q.shape[:3] + (pad,), q.dtype)], axis=-1)
    weighted, pool = _attend_paged(
        config, pool, {"kv": latent}, index, tables, positions,
        write_blocks, write_offsets, query)
    return jnp.einsum("shwc,hcv->shwv", weighted, layer["wv_b"]["w"],
                      preferred_element_type=jnp.float32
                      ).astype(q.dtype), pool


def _attend_paged(config: TransformerConfig, pool: dict, written: dict,
                  index, tables, positions, write_blocks, write_offsets,
                  q):
    """Put `written` (a row a leaf: _kv_to_write's, or the latent) into
    cache `index` of the pool at (write_blocks, write_offsets) and
    attend q through the block tables; returns (out, the pool's new
    leaves).  Who writes is pool_write_kind's answer: the kernel itself
    at window 1, else _write_window before the kernel or, for the calls
    paged_attention_takes refuses, before its oracle."""
    window = q.shape[2]
    latent = dict(sm_scale=config.attention_scale,
                  value_dim=config.kv_lora_rank) if "kv" in pool else {}
    names = ("kv",) if latent else ("k", "v")
    write = None
    if pool_write_kind(config, pool, window) == "kernel":
        write = (tuple(written[name] for name in names), write_blocks,
                 write_offsets)
    else:
        pool = {name: _write_window(pool[name], rows, index, write_blocks,
                                    write_offsets)
                for name, rows in written.items()}
    leaves = (_store_leaf(pool), pool.get("v"))
    if not _paged_kernel_takes(config, pool, window):
        scales = ((pool["k_scale"], pool["v_scale"]) if "k_scale" in pool
                  else ())
        return paged_attention_reference(
            q, *leaves, index, tables, positions, *scales, **latent), pool
    out, *leaves = paged_attention(q, *leaves, index, tables, positions,
                                   write=write, **latent)
    return out, {**pool, **dict(zip(names, leaves))}


def _attend_pool_selecting(config: TransformerConfig, pool: dict, index,
                           tables, positions, write_blocks, write_offsets,
                           q, k, v):
    """_attend_pool for an attention that selects its blocks, a decode
    step (window 1): the new rows go into the pool where they lie
    (_write_window); a slot whose row completes a compressed key has the
    mean of its `kernel` newest rows put into the store of compressed keys
    (the others write the trash block's); every slot's query scores its
    compressed keys and chooses its blocks, a K/V head; and the paged
    kernel attends over the pool seen a K/V head a page, given the chosen
    pages in order -- every page up to its own of a slot under
    sparse_dense_len -- so that the K/V it reads are the chosen blocks'
    (parallel/sparse.py decode_tables)."""
    slots, heads, window, depth = q.shape
    if window != 1:
        raise ValueError(
            f"a window of {window} positions over the paged pool (a "
            f"speculative verify step, a prefill chunk) is not implemented "
            f"for an attention that selects its blocks: the store of "
            f"compressed keys is advanced a row a step")
    sizes, groups = config.sparse_sizes, config.n_kv_heads
    pool = dict(pool, **{
        name: _write_window(pool[name], rows, index, write_blocks,
                            write_offsets)
        for name, rows in (("k", k), ("v", v))})
    due, which = due_compressed(positions, sizes)
    owner = jnp.take_along_axis(
        tables, (which // sizes.per_block)[:, None], axis=1)[:, 0]
    pool["kc"] = _write_window(
        pool["kc"], completed_key(pool["k"], index, tables, positions,
                                  sizes), index,
        jnp.where(due, owner, 0)[:, None],          # else the trash block
        jnp.where(due, which % sizes.per_block, 0)[:, None])
    pages, at = decode_tables(q, pool["kc"], index, tables, positions, sizes)

    def by_head(leaf):
        # (L, blocks, G, block, d) as (L, blocks x G, 1, block, d): a K/V
        # head's block a page of its own, the bytes where they lie
        return leaf.reshape(leaf.shape[0], -1, 1, *leaf.shape[3:])

    attend = (paged_attention if paged_attention_takes(
        heads // groups, 1, depth, pool["k"].dtype)
        else paged_attention_reference)
    with jax.named_scope("sparse_attention"):
        out = attend(q.reshape(slots * groups, heads // groups, 1, depth),
                     by_head(pool["k"]), by_head(pool["v"]), index, pages,
                     at)
    if isinstance(out, tuple):
        out = out[0]
    return out.reshape(q.shape), pool


def _attend_pool(config: TransformerConfig, pool: dict, index, tables,
                 positions, write_blocks, write_offsets, layer, q, k, v):
    """Paged pool (init_paged_pool; `pool` is the whole pool, `index`
    this layer's index into it): the WHOLE window's K/V goes where it
    lies and the window attends through each slot's block table -- so
    later window positions attend to earlier ones causally.  The kernel
    walks the live blocks in place where paged_attention_takes, and at
    window 1 (the decode step) writes the new rows itself, the pool
    riding the call aliased (pool_write_kind); its oracle, the
    table-wide gather + einsum, serves the rest (an int8 pool, whose
    scales dequantize the gathered view as the contiguous int8 cache's
    do; a window too large for VMEM; on the chip a head_dim off the 128
    lanes), after _write_window."""
    if config.kv_lora_rank:
        return _attend_pool_latent(config, pool, index, tables, positions,
                                   write_blocks, write_offsets, layer, q, k)
    if config.sparse_topk:
        return _attend_pool_selecting(config, pool, index, tables, positions,
                                      write_blocks, write_offsets, q, k, v)
    return _attend_paged(config, pool, _kv_to_write(pool, k, v), index,
                         tables, positions, write_blocks, write_offsets, q)


def _paged_window(params, config: TransformerConfig, pool, tables,
                  positions, tokens, write_blocks, write_offsets):
    """_paged_logits with each window position's greedy token in place
    of its logits, and of the FFNs' stats the experts' counts, int32
    (2,): distinct held experts read, token-expert pairs computed (zeros
    without).  What the three jitted paged programs hand out."""
    pool, logits, stats_sum, exit_steps = _paged_logits(
        params, config, pool, tables, positions, tokens, write_blocks,
        write_offsets)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return pool, greedy, stats_sum[1:].astype(jnp.int32), exit_steps


def _paged_logits(params, config: TransformerConfig, pool, tables,
                  positions, tokens, write_blocks, write_offsets):
    """The decoder over a per-slot TOKEN WINDOW and the paged pool --
    the one traced implementation behind paged_decode_step (window 1),
    paged_verify_step (speculative verification, window k+1), and
    paged_prefill_chunk (chunked prefill, window = chunk bucket).

    tokens (slots, W) are consumed left-to-right per slot: window
    position i sits at absolute position positions[slot] + i, its K/V
    lands at (write_blocks[slot, i], write_offsets[slot, i]), and rows
    the engine wants inert point their writes at the trash block.
    Returns (pool, logits (slots, W, V), stats, exit_steps) where
    logits[s, i] score the token AFTER consuming window positions 0..i
    -- their argmax the tokens W successive single-token decode steps
    would produce, which is the identity the chunked-prefill and
    speculative tests pin -- stats float32 (_FFN_STATS,) the FFNs',
    summed over layers and passes, and exit_steps float32 (slots, W) a
    looped stack's expected exit pass (_logits; None for one pass)."""
    if config.recurrent and tokens.shape[1] != 1:
        raise ValueError(
            f"a window of {tokens.shape[1]} positions over the paged pool "
            f"(a speculative verify step, a prefill chunk) is not "
            f"implemented for a model with a recurrent state: a "
            f"{config.recurrent_kind} layer's state is advanced a row a "
            f"step and cannot be rolled back or carried into a chunk")
    h = _embed(params, config, tokens)
    q_pos = positions[:, None] + jnp.arange(tokens.shape[1])[None, :]
    cos, sin = _rotary_tables(config, q_pos)
    cos, sin = cos[:, None], sin[:, None]        # (S, 1, W, hd/2)
    rope = _lightning_rope(config, q_pos)

    def layer_step(carry, xs):
        # the pool rides the loop (and a looped stack's passes) as CARRY
        # and is written where it lies (indexed by cache): as scan xs ->
        # ys every step rebuilt it whole.  Nothing here trains: of the
        # FFN's stats only the experts' counts go on
        h, pool, stats_sum = carry
        layer, index = xs
        kind = _layer_kind(layer)
        if kind in _MIXERS:
            # a recurrent layer advances every slot's state a row, in
            # place: row s of h is slot s's.  Its S goes to the mixer's
            # step as the whole leaf and the layer's index, and comes
            # back as the leaf
            *tail, leaf = _MIXERS[kind][1]
            h, stats, state = _recurrent_layer(layer)(
                config, layer, h, {
                    **{name: pool[name][index] for name in tail},
                    leaf: (pool[leaf], index)}, **rope)
            pool = {**pool, leaf: state[leaf][0], **{
                name: jax.lax.dynamic_update_index_in_dim(
                    pool[name], state[name], index, 0) for name in tail}}
        else:
            h, stats, pool = _decoder_layer(
                config, layer, h, cos, sin,
                partial(_attend_pool, config, pool, index, tables,
                        positions, write_blocks, write_offsets))
        return (h, pool, stats_sum + stats), None

    def scan_stack(carry, stack, first, count):
        return _scan_layers(config, layer_step, carry, stack,
                            first + jnp.arange(count))[0]

    carry = (h, pool, jnp.zeros((_FFN_STATS,), jnp.float32))
    (h, new_pool, stats_sum), outputs = _run_passes(params, config, carry,
                                                    scan_stack)
    logits, exit_steps = _logits(params, config, h, outputs)
    return new_pool, logits, stats_sum, exit_steps


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
    The window-1 instantiation of _paged_window.  A model with routed
    experts returns a third value, int32 (2,): the distinct held experts
    the step read and the token-expert pairs it computed, over its
    layers.  A looped stack returns, last, float32 (slots,): the pass
    its exit gate expects each slot's token to leave after, sum_t
    (t + 1) p[t] (what a lower exit_threshold would buy)."""
    pool, greedy, counts, exit_steps = _paged_window(
        params, config, pool, tables, positions, tokens,
        write_blocks[:, None], write_offsets[:, None])
    return ((pool, greedy) + ((counts,) if config.top_k else ())
            + ((exit_steps[:, 0],) if config.ut_steps > 1 else ()))


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
                         tokens, write_blocks, write_offsets)[:2]


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
    pool, greedy, *_ = _paged_window(
        params, config, pool, table_row[None],
        jnp.reshape(start, (1,)), tokens, write_blocks[None],
        write_offsets[None])
    return pool, greedy[0]


# -- what a paged call did and counted -------------------------------------
#
# The engines' account of the programs above (observe/trace.py's table):
# host functions, no device work, answering by the predicates the traced
# code decides by.  Each returns (fields, counts): the model's fields of the
# call's span, and what the call adds to the running counters of `stats()`.
# An engine opens the span with the one, sums the other and knows no name in
# either: a choice to report, or a model's own field, is written here.

__all__ += ["RECORD_COUNTERS", "prefill_record", "window_record",
            "step_counts"]

# every counter a record adds to, at its zero: every model's `stats()` has it
RECORD_COUNTERS = {
    "prefill_flash": 0, "prefill_einsum": 0, "prefill_rows_run": 0,
    "prefill_rows_bucket": 0, "prefill_attn_rows": 0, "scan_rows": 0,
    "scan_kernel": 0, "scan_jnp": 0, "writes_kernel": 0,
    "writes_updates": 0, "latent_positions": 0, "ut_passes": 0,
    "cache_rows": 0, "state_slots": 0, "state_bytes": 0,
    "state_step_kernel": 0, "state_step_jnp": 0, "experts_read": 0,
    "expert_pairs": 0, "exit_expected_step": 0.0,
    "prefill_sparse": 0, "scan_lightning_chunk": 0, "select_rows": 0,
    "sparse_blocks_read": 0, "sparse_blocks_live": 0, "compressed_rows": 0}


def _looped_record(config: TransformerConfig, positions: int) -> dict:
    """A looped stack's passes and the rows of its caches that hold the
    `positions` a call leaves behind or attends over; else nothing."""
    if config.ut_steps == 1:
        return {}
    return {"ut_passes": config.ut_steps,
            "cache_rows": positions * config.n_caches}


def prefill_record(config: TransformerConfig, pool: dict, bucket: int,
                   true_len: int):
    """(fields, counts) of a whole prefill (paged_prefill) of `bucket`
    rows into `pool` for a prompt of `true_len` tokens: the `attention`
    the bucket takes, the `rows` the program and the `attn_rows` its
    attention run of it; a recurrent layer's `scan` and `scan_rows`; a
    looped stack's passes over the rows the call leaves behind."""
    attention = cache_attention_kind(config, pool, 1, bucket)
    rows = prefill_rows(config, bucket, true_len)
    attn_rows = prefill_attention_rows(config, bucket, true_len)
    fields = {"attention": attention, "rows": rows, "attn_rows": attn_rows}
    counts = {"prefill_" + attention: 1, "prefill_rows_run": rows,
              "prefill_rows_bucket": bucket, "prefill_attn_rows": attn_rows}
    # the numbers that are a field and a running sum under one name
    sums = _looped_record(config, true_len)
    if config.recurrent:
        fields["scan"] = scan_kind(config, bucket)
        counts["scan_" + fields["scan"]] = 1
        sums["scan_rows"] = scan_rows(config, bucket, true_len)
    if config.sparse_topk:
        # the rows that chose their blocks, an attention layer
        sums["select_rows"] = (max(true_len - config.sparse_dense_len, 0)
                               if attention == "sparse" else 0)
    return {**fields, **sums}, {**counts, **sums}


def window_record(config: TransformerConfig, pool: dict, window: int,
                  positions, decoding=None, true_len=None):
    """(fields, counts) of a call of `window` positions a slot over
    `pool` (_paged_window) at the slots' `positions`: who puts the new
    rows into the pool (`write`).  A decode step names the slots
    `decoding` and says what they attend over, its own rows among them,
    and what advances their recurrent state (`state_step`), each slot's
    read and written once.  A prefill chunk names the prompt's `true_len`
    (a looped stack's rows end there); a verify step names neither."""
    fields = {"write": pool_write_kind(config, pool, window)}
    counts = {"writes_" + fields["write"]: 1}
    sums = {}
    if decoding is not None:
        live = int(positions[decoding].sum()) + len(decoding) * window
        sums.update(_looped_record(config, live))
        if "kv" in pool:
            sums["latent_positions"] = live
        if config.recurrent:
            fields["state_step"] = state_step_kind(config)
            counts["state_step_" + fields["state_step"]] = 1
            sums.update(state_slots=len(decoding),
                        state_bytes=2 * len(decoding) * config.state_bytes,
                        cache_rows=live * config.n_caches)
        if config.sparse_topk:
            # a K/V head of an attention layer: the blocks its query reads
            # beside those it chose from, and the compressed keys it scored
            sizes, at = config.sparse_sizes, positions[decoding]
            each = config.n_kv_heads * config.n_caches
            sums.update(
                sparse_blocks_read=each * int(blocks_read(at, sizes).sum()),
                sparse_blocks_live=each * int((at // sizes.block + 1).sum()),
                compressed_rows=each * int(np.maximum(
                    (at - sizes.kernel + 1) // sizes.stride + 1, 0).sum()))
    elif true_len is not None:
        sums.update(_looped_record(
            config, min(int(positions[0]) + window, true_len)))
    return {**fields, **sums}, {**counts, **sums}


def step_counts(config: TransformerConfig, counted, rows):
    """(fields, counts) of what a paged_decode_step counted on the
    device: `counted`, what it returned after the tokens, read back, and
    `rows`, the slots that decoded: routed experts' two counts, and a
    looped stack's expected exit pass, its mean over those rows (the
    running sum is of these means, a step)."""
    counted, counts = iter(counted), {}
    if config.top_k:
        counts["experts_read"], counts["expert_pairs"] = map(
            int, next(counted))
    fields = dict(counts)
    if config.ut_steps > 1:
        expected = float(next(counted)[list(rows)].mean())
        counts["exit_expected_step"] = expected
        fields["exit_expected_step"] = round(expected, 4)
    return fields, counts


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
    if config.recurrent:
        raise ValueError(
            f"make_train_step is not implemented for a model with a "
            f"recurrent state ({config.recurrent_kind} layers): the scan "
            f"kernels have no backward pass")

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
