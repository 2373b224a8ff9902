# Attention kernels and sequence parallelism.
#
# The reference has NO sequence parallelism -- long audio is handled by
# temporal chunking (reference: src/aiko_services/examples/speech/
# speech_elements.py:54-83) and LLM context is a single prompt.  This module
# supplies the real thing for TPU (SURVEY.md 2.4, 5):
#
#   flash_attention  -- blockwise online-softmax attention as a Pallas TPU
#                       kernel (MXU matmuls, VMEM-resident blocks, f32
#                       accumulation); interpreter mode on CPU for tests.
#   ring_attention   -- sequence-parallel attention: Q stays put, KV blocks
#                       rotate around the mesh "seq" axis via ppermute; each
#                       hop overlaps with blockwise attention compute and
#                       merges via the associative online-softmax update.
#   ulysses_attention - all-to-all alternative: swap seq-sharding for
#                       head-sharding, run dense local attention, swap back.
#
# All take q/k/v shaped (batch, heads, seq, head_dim).

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ..utils.padding import pad_axis_to
from .mesh import create_mesh  # noqa: F401  (re-exported convenience)

__all__ = [
    "attention_reference", "flash_attention", "flash_attention_takes",
    "flash_query_block",
    "paged_attention", "paged_attention_reference", "paged_attention_takes",
    "paged_attention_writes", "paged_live_blocks", "ring_attention",
    "sp_decode_attention", "ulysses_attention",
]

_NEG_INF = -1e30


def _interpret() -> bool:
    """Pallas interpret mode is for the CPU test environment ONLY.  On
    "tpu" every pallas_call goes through Mosaic; any other backend name
    raises, so a kernel can never run interpreted on an accelerator
    without anyone noticing."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels compile for 'tpu' and interpret on 'cpu'; "
        f"jax.default_backend() is {backend!r}")


def attention_reference(q, k, v, causal: bool = False, sm_scale=None,
                        q_offset: int = 0):
    """Plain-XLA softmax attention: the correctness oracle for the kernels
    and the backward pass of the custom-VJP flash kernel."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k,
        preferred_element_type=jnp.float32) * sm_scale
    if causal:
        q_len, k_len = logits.shape[-2], logits.shape[-1]
        q_pos = jnp.arange(q_len)[:, None] + (k_len - q_len) + q_offset
        k_pos = jnp.arange(k_len)[None, :]
        logits = jnp.where(k_pos <= q_pos, logits, _NEG_INF)
    weights = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", weights.astype(v.dtype), v)


# -- Pallas flash attention -------------------------------------------------

_STAT_LANES = 128  # min f32 lane width for the m/l scratch tiles

# Tiles, chosen from the call's shape (flash_attention).  On the v5e at
# (1, 32 q / 8 kv heads, 4096, 128) bf16 causal the forward kernel took
# 5.81 ms with 128 x 128 tiles, 2.20 with 512 x 512, 1.39 with 512 x 1024
# and 1.36 with 1024 x 1024 (1.50 with 512 x 2048: more of a wide tile
# lies above the diagonal): what a K step costs beside its scores -- the
# rescaling of acc, m and l, a grid step's 0.35 us -- is spread over
# `block_k` columns.  A group's query rows (repeats x block_q x head_dim)
# are held to what four heads of 128 lanes bring, so q, the output and the
# float32 accumulator stay inside the VMEM asked for whatever the grouping.
_FLASH_BLOCK = 1024
# Query rows of a tile of a call told a live length (a whole prefill:
# flash_attention's `live`), the grain its dead rows are skipped by, where
# a K/V tile serves a group of query heads.  Measured on the v5e (PR 40),
# {512, 1024} query rows x 1024 keys, the kernel alone in ms a layer at
# live lengths 2032 / 3056 / 3568 / 4080 of 4096, then the whole
# 4096-bucket paged_prefill of mistral7b_l16 in ms at true_len 2304 /
# 2816 / 3328 / 3840 / 4064 (host clock, median of 5; not told: 129.8 /
# 149.9 / 170.0 / 190.5 / 190.1):
#   32 q / 8 kv heads x 128 (not told 1.392):
#     512: 0.606 / 0.996 / 1.221 / 1.440; 119.1 / 142.0 / 165.8 / 189.7 / 189.7
#    1024: 0.559 / 0.940 / 1.394 / 1.395; 121.8 / 142.2 / 169.9 / 189.9 / 190.1
#   128 heads, each its own K/V, 256 to score and 128 to carry, live 4080 /
#   5616 / 7152 / 8176 of 8192 (not told 25.94):
#     512:  15.32 / 21.55 / 27.21 / 31.03;  1024: 11.00 / 18.19 / 22.33 / 26.55
# A step of 512 query rows costs a grouped call 3-6 % more than its half
# of a 1024-row step (four heads share the K/V tile it waits for) and
# skips by half the grain: never slower in the program, 3-4 ms faster
# for half the lengths.  Where every head has K/V of its own it is twice
# the grid steps (0.35 us each, 128 heads x 16 x 8) and twice the K/V
# reads for the same scores: 15-40 % slower, so those calls keep
# _FLASH_BLOCK.  (20 heads to one K/V head: 128 rows, what the VMEM
# leaves.)  A call that is told and skips nothing pays the operand:
# 26.55 against 25.94, 1.395 against 1.392 (27.04 while the index maps
# still divided the length by the block, as they did when the 128-head
# 512 row was read).
_FLASH_LIVE_BLOCK = 512
_FLASH_GROUP_ELEMENTS = 4 * _FLASH_BLOCK * 128
_FLASH_VMEM_BYTES = 48 << 20
# the backward kernels hold four float32 score-sized tiles a step
_FLASH_BACKWARD_BLOCK = 512
# The masked einsum's float32 scores (batch x heads x L x L) beyond which
# a cached prefill attends through the kernel.  Up to here XLA keeps the
# scores in the v5e's 128 MiB of VMEM and one fused softmax beats a
# blockwise kernel whose tiles are few and small; past it they cross HBM
# four times.  Measured on the v5e, 32 q / 8 kv heads, bf16, einsum
# against kernel in us a layer: batch 1 -- L 512 (32 MiB) 53 / 73, L 1024
# (128 MiB) 409 / 158, L 4096 15478 / 1313; batch 8 -- L 256 (64 MiB)
# 91 / 257, L 512 818 / 480; batch 32 -- L 128 (64 MiB) 151 / 453, L 256
# (256 MiB) 1063 / 1059; head_dim 64 alike (L 512 44 / 72, L 1024 406 /
# 156).
_FLASH_MIN_SCORE_BYTES = 64 << 20


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *rest,
                  causal: bool, sm_scale: float, kv_len: int, q_offset: int,
                  with_lse: bool, last=None):
    """One (batch*kv_head, q_block, k_block) grid step of the
    online-softmax recurrence.  K/V stream through VMEM one block per
    step (HBM->VMEM via the grid pipeline -- whole-sequence K/V never
    resides on chip) and serve all `repeats` query heads of their group
    from that one read; m/l/acc scratch persists across the sequential k
    dimension.  The dots take q, k, v (and p, cast to v's dtype) as they
    come -- bf16 in, bf16 on the MXU -- and accumulate in float32.
    `last` (a traced scalar, _flash_live_kernel): the last query block
    that holds a live row; a block past it takes no step, and finishes
    as the zeros it was initialised to."""
    if with_lse:
        lse_ref, m_ref, l_ref, acc_ref = rest
    else:
        m_ref, l_ref, acc_ref = rest
    repeats, block_q = q_ref.shape[1], q_ref.shape[2]
    block_k = k_ref.shape[1]
    # program ids must be read OUTSIDE pl.when bodies (interpret-mode
    # lowering of program_id inside cond is unsupported)
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    num_kb = pl.num_programs(2)
    q_base = qi * block_q + q_offset
    k_base = ki * block_k

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    needed = k_base < kv_len
    # a tile wholly on the visible side of the mask is not masked at all
    inside = k_base + block_k <= kv_len
    if causal:  # skip blocks entirely above the causal diagonal
        needed = jnp.logical_and(needed, k_base <= q_base + block_q - 1)
        inside = jnp.logical_and(inside, k_base + block_k - 1 <= q_base)
    if last is not None:
        needed = jnp.logical_and(needed, qi <= last)

    def update(masked: bool):
        k_blk = k_ref[0]                               # (block_k, d)
        v_blk = v_ref[0]
        if masked:
            k_pos = k_base + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            mask = k_pos < kv_len
            if causal:
                q_pos = q_base + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0)
                mask = jnp.logical_and(mask, k_pos <= q_pos)
        for head in range(repeats):
            s = jax.lax.dot_general(
                q_ref[0, head], k_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            if masked:
                s = jnp.where(mask, s, _NEG_INF)
            m_prev = m_ref[head, :, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[head] = l_ref[head] * alpha + jnp.broadcast_to(
                jnp.sum(p, axis=-1, keepdims=True), l_ref.shape[1:])
            acc_ref[head] = acc_ref[head] * alpha + jax.lax.dot_general(
                p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[head] = jnp.broadcast_to(m_new, m_ref.shape[1:])

    pl.when(jnp.logical_and(needed, inside))(
        functools.partial(update, False))
    pl.when(jnp.logical_and(needed, jnp.logical_not(inside)))(
        functools.partial(update, True))

    @pl.when(ki == num_kb - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(
            l_ref[:, :, :1], 1e-30)).astype(o_ref.dtype)
        if with_lse:
            # per-row logsumexp: the only forward residual the backward
            # kernels need beyond q/k/v/o
            lse_ref[0] = m_ref[...] + jnp.log(
                jnp.maximum(l_ref[...], 1e-30))


def _flash_live_kernel(last_ref, *refs, **static):
    """_flash_kernel told its sequence's last live query block, the one
    scalar-prefetch operand: (groups,) int32."""
    _flash_kernel(*refs, last=last_ref[pl.program_id(0)], **static)


def _pad_seq(x, block: int):
    length = x.shape[2]
    padded = ((length + block - 1) // block) * block
    return pad_axis_to(x, 2, padded)


def _flash_block(length: int, largest: int) -> int:
    """The tile for a sequence axis of `length`: the axis itself while it
    is shorter than a 128-row tile (Mosaic on the v5e takes (37, 37),
    (8, 8) and (1, 128) tiles as they come, forward and backward;
    chip_smoke.py's kernels phase keeps checking), else the fewest tiles
    of at most `largest` rows, evened out to multiples of 128 so the
    padding stays under one 128-row tile a tile (1100 -> two of 640,
    not two of 1024)."""
    if length <= 128:
        return max(length, 1)
    tiles = -(-length // largest)
    return -(-length // (tiles * 128)) * 128


def flash_attention_takes(batch: int, heads: int, length: int, dtype,
                          cache_dtype) -> bool:
    """Whether a cached forward of `length` tokens from position 0
    attends over its own fresh K/V through flash_attention (models/
    transformer.py _attend_cache), decided by what the call is: bf16 or
    float32 K/V written to a cache of their own dtype (an int8 cache is
    attended over as QUANTISED, which the fresh tensors are not), and
    float32 scores too many for the einsum to keep on the chip
    (_FLASH_MIN_SCORE_BYTES: at 32 heads a lone prompt from 1024 tokens,
    32 rows from 256)."""
    dtype = jnp.dtype(dtype)
    return (dtype in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32))
            and jnp.dtype(cache_dtype) == dtype
            and batch * heads * length * length * 4 > _FLASH_MIN_SCORE_BYTES)


def flash_query_block(heads: int, kv_heads: int, head_dim: int,
                      length: int, live: bool = False) -> int:
    """The query rows of a tile of flash_attention over `length` rows of
    `heads` query heads to `kv_heads` K/V heads: _FLASH_BLOCK's, held to
    what the group's rows leave of the VMEM; of a call told a live length
    whose K/V tiles serve a group of heads _FLASH_LIVE_BLOCK's, the grain
    its dead rows are skipped by."""
    repeats = heads // kv_heads
    largest = _FLASH_LIVE_BLOCK if live and repeats > 1 else _FLASH_BLOCK
    return _flash_block(length, max(128, min(
        largest, _FLASH_GROUP_ELEMENTS // (repeats * head_dim) // 128 * 128)))


def flash_attention(q, k, v, causal: bool = False, sm_scale=None,
                    block_q: int | None = None, block_k: int | None = None,
                    q_offset: int = 0, live=None):
    """Blockwise attention: q (B, H, L, D), k (B, Hkv, Lk, D), v (B, Hkv,
    Lk, Dv) with H a multiple of Hkv -- KV head g serves query heads
    g*H/Hkv onward, as repeat_kv lays them out, from one read of its
    tiles; no repeated K/V exists in HBM.  Output (B, H, L, Dv).  Dv is
    D everywhere but under latent attention, whose heads score over 192
    and carry values of 128; that forward is named `mla_flash_attention`
    in the device trace, and its backward is the plain one's.

    q_offset shifts the causal mask for callers whose q shard starts at a
    nonzero global position (ring attention resumes, KV-cached decode).
    block_q/block_k default to tiles sized for the chip from the
    lengths, head_dim and the grouping (_flash_block).

    `live` (a traced int32, a scalar or one a batch row) is what a whole
    prefill knows of its right-padded bucket: only the first `live` rows
    of the output are read.  Under causality those rows attend no key at
    or past `live`, so a query block that starts at or past it takes no
    step and fetches nothing, and its output is zeros (a row past `live`
    in the last live block is computed as it always was; a `live` of 0 is
    taken as 1).  One program
    serves every `live`: it rides the call as a scalar-prefetch operand,
    the grid is the bucket's.  Taken for that call only -- causal,
    q_offset 0, as many keys as queries, forward -- and anything else
    with a `live` raises; without one the call traces what it traced
    before there was one.

    Differentiable end-to-end in Pallas: the forward kernel saves the
    per-row logsumexp, and the backward pass runs two blockwise kernels
    (dq; dk/dv) that recompute p inside VMEM -- backward peak memory is
    O(L x block), never O(L^2).
    """
    batch, heads, q_len, head_dim = q.shape
    kv_heads, kv_len = k.shape[1], k.shape[2]
    if heads % kv_heads:
        raise ValueError(
            f"flash_attention: {heads} query heads are not a multiple of "
            f"{kv_heads} KV heads")
    if live is not None and not (causal and q_offset == 0
                                 and q_len == kv_len):
        raise ValueError(
            "flash_attention: a live length is a whole causal prefill's "
            f"(q_offset 0, as many keys as queries), not causal={causal}, "
            f"q_offset={q_offset}, {q_len} queries over {kv_len} keys")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(head_dim)
    if block_q is None:
        block_q = flash_query_block(heads, kv_heads, head_dim, q_len,
                                    live is not None)
    if block_k is None:
        block_k = _flash_block(kv_len, _FLASH_BLOCK)
    # an explicit block longer than its axis is one block of the axis
    block_q = min(block_q, max(q_len, 1))
    block_k = min(block_k, max(kv_len, 1))

    def attend(q, k, v, *live):
        if live:
            return _flash_live(q, k, v, *live, float(sm_scale),
                               int(block_q), int(block_k))
        return _flash(q, k, v, bool(causal), float(sm_scale), int(block_q),
                      int(block_k), int(q_offset))

    operands = (q, k, v)
    if live is not None:
        operands += (jnp.broadcast_to(
            jnp.asarray(live, jnp.int32), (batch,)),)
    spec = _ambient_mesh_spec(batch, kv_heads)
    if spec is None:
        return attend(*operands)
    # a Mosaic kernel cannot be partitioned automatically (on the chip
    # the lowering raises "wrap the call in a shard_map"; the CPU
    # interpreter never noticed).  Attention is independent across batch
    # rows and KV groups, so under an ambient mesh every shard runs the
    # kernel on its own rows and groups (a group's query heads are
    # contiguous, so a split of the KV heads splits the query heads alike)
    # and is told its own rows' live lengths
    return jax.shard_map(
        attend, in_specs=(spec, spec, spec, P(spec[0]))[:len(operands)],
        out_specs=spec, check_vma=False)(*operands)


def _ambient_mesh_spec(batch: int, heads: int):
    """PartitionSpec for (B, H, L, D) attention operands under the
    ambient mesh (jax.set_mesh): batch over "data", heads over "model",
    each only when the axis divides the extent.  None when there is
    nothing to partition over -- no mesh, one device, or already inside
    a shard_map (ring / Ulysses inner hops)."""
    mesh = jax.sharding.get_abstract_mesh()
    if (mesh.empty or math.prod(mesh.axis_sizes) == 1
            or mesh.manual_axes):
        return None
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))

    def axis(name: str, extent: int):
        size = sizes.get(name, 1)
        return name if size > 1 and extent % size == 0 else None

    return P(axis("data", batch), axis("model", heads), None, None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, sm_scale, block_q, block_k, q_offset):
    # forward only (inference): no logsumexp is written
    return _flash_impl(q, k, v, causal, sm_scale, block_q, block_k,
                       q_offset, with_lse=False)


def _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k, q_offset):
    out, lse = _flash_impl(q, k, v, causal, sm_scale, block_q, block_k,
                           q_offset)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, sm_scale, block_q, block_k, q_offset, residuals,
               cotangent):
    q, k, v, out, lse = residuals
    batch, kv_heads = k.shape[:2]
    repeats = q.shape[1] // kv_heads
    if repeats > 1:
        # the backward kernels take one K/V head a query head: a group's
        # K/V is repeated for them and its query heads' dk/dv summed
        k, v = (jnp.repeat(x, repeats, axis=1) for x in (k, v))
    if v.shape[-1] != q.shape[-1]:
        # the backward kernels hold one head_dim: a value width of its
        # own differentiates through the plain-XLA oracle
        _, vjp = jax.vjp(functools.partial(
            attention_reference, causal=causal, sm_scale=sm_scale,
            q_offset=q_offset), q, k, v)
        dq, dk, dv = vjp(cotangent)
    else:
        dq, dk, dv = _flash_bwd_impl(
            q, k, v, out, lse, cotangent, causal, sm_scale,
            min(block_q, _FLASH_BACKWARD_BLOCK),
            min(block_k, _FLASH_BACKWARD_BLOCK), q_offset)
    if repeats > 1:
        dk, dv = (
            grad.astype(jnp.float32).reshape(
                batch, kv_heads, repeats, *grad.shape[2:]).sum(
                    axis=2).astype(grad.dtype)
            for grad in (dk, dv))
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_jvp, nondiff_argnums=(4, 5, 6))
def _flash_live(q, k, v, live, sm_scale, block_q, block_k):
    """The whole causal prefill told its live lengths: forward only."""
    return _flash_impl(q, k, v, True, sm_scale, block_q, block_k, 0,
                       with_lse=False, live=live)


@_flash_live.defjvp
def _flash_live_jvp(sm_scale, block_q, block_k, primals, tangents):
    raise ValueError(
        "flash_attention: a call with a live length is a prefill's "
        "forward and has no derivative: its dead rows' output is zeros, "
        "not attention")


@functools.partial(
    jax.jit,
    static_argnames=("causal", "sm_scale", "block_q", "block_k", "q_offset",
                     "with_lse"))
def _flash_impl(q, k, v, causal, sm_scale, block_q, block_k, q_offset,
                with_lse: bool = True, live=None):
    """The forward kernel's call: (out, lse) or, with_lse=False, out
    alone -- the (B, H, L) logsumexp is the backward's residual (and the
    ring's merge weight); a forward that nothing differentiates writes
    none.  `live` (B,) int32: flash_attention's, from which the call's
    one scalar-prefetch operand, the last query block with a live row
    for every group; the operand, its grid spec and the index maps'
    terms exist only in the trace that was given one."""
    batch, heads, q_len, head_dim = q.shape
    kv_heads, kv_len = k.shape[1], k.shape[2]
    value_dim = v.shape[3]
    repeats = heads // kv_heads
    groups = batch * kv_heads

    # (B, H, L, d) -> (B*Hkv, repeats, L, d): a group's query heads
    # share the leading index of its K/V head
    q_padded = _pad_seq(q, block_q).reshape(groups, repeats, -1, head_dim)
    k_padded = _pad_seq(k, block_k).reshape(groups, -1, head_dim)
    v_padded = _pad_seq(v, block_k).reshape(groups, -1, value_dim)
    padded_q_len = q_padded.shape[2]
    # k blocks stream through the grid's sequential minor dimension, so
    # VMEM holds one group's (repeats, block_q, d) q tile + one
    # (block_k, d) k/v tile each step regardless of sequence length
    grid = (groups, padded_q_len // block_q, k_padded.shape[1] // block_k)
    offset = int(q_offset) + (kv_len - q_len if causal else 0)

    # index maps take the grid's indices and then the scalar-prefetch
    # operands: none, or the groups' last live query blocks
    def fed(g, qi, told):
        """The query block whose q, k and v the step (g, qi) is fed: its
        own, but past the last live block that one again, so a dead
        block's steps fetch nothing."""
        return jnp.minimum(qi, told[0][g]) if told else qi

    def q_index(g, qi, ki, *told):
        return (g, 0, fed(g, qi, told), 0)

    def kv_index(g, qi, ki, *told):
        if causal:
            # a step above the diagonal is skipped: naming the last block
            # that was needed again, it fetches nothing either
            ki = jnp.minimum(
                ki, (fed(g, qi, told) * block_q + offset + block_q - 1)
                // block_k)
        return (g, ki, 0)

    def out_index(g, qi, ki, *told):
        # every block's own: a dead block's zeros land in its own rows
        return (g, 0, qi, 0)

    q_spec = pl.BlockSpec((1, repeats, block_q, head_dim), q_index,
                          memory_space=pltpu.VMEM)
    kv_spec = pl.BlockSpec((1, block_k, head_dim), kv_index,
                           memory_space=pltpu.VMEM)
    # the values and the output have the values' width: head_dim but
    # under latent attention (192 to score, 128 to carry)
    v_spec = pl.BlockSpec((1, block_k, value_dim), kv_index,
                          memory_space=pltpu.VMEM)
    o_spec = pl.BlockSpec((1, repeats, block_q, value_dim), out_index,
                          memory_space=pltpu.VMEM)
    stat_spec = pl.BlockSpec((1, repeats, block_q, _STAT_LANES), out_index,
                             memory_space=pltpu.VMEM)
    stat_shape = (groups, repeats, padded_q_len, _STAT_LANES)
    static = dict(causal=causal, sm_scale=float(sm_scale), kv_len=kv_len,
                  q_offset=offset, with_lse=with_lse)
    specs = dict(
        grid=grid,
        in_specs=[q_spec, kv_spec, v_spec],
        out_specs=[o_spec, stat_spec][:1 + with_lse],
        scratch_shapes=[
            pltpu.VMEM((repeats, block_q, _STAT_LANES), jnp.float32),  # m
            pltpu.VMEM((repeats, block_q, _STAT_LANES), jnp.float32),  # l
            pltpu.VMEM((repeats, block_q, value_dim), jnp.float32),  # acc
        ])
    if live is None:
        kernel, told = functools.partial(_flash_kernel, **static), ()
    else:
        kernel = functools.partial(_flash_live_kernel, **static)
        # worked out here, once, not by every grid step's index maps (a
        # length of 0 is taken as 1: block 0 runs)
        told = (jnp.repeat(jnp.maximum(live - 1, 0) // block_q, kv_heads),)
        specs = dict(grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, **specs))
    results = pl.pallas_call(
        kernel,
        **specs,
        out_shape=[jax.ShapeDtypeStruct(
            q_padded.shape[:3] + (value_dim,), q.dtype),
                   jax.ShapeDtypeStruct(stat_shape, jnp.float32)
                   ][:1 + with_lse],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_FLASH_VMEM_BYTES),
        # only the forward with a value width of its own is named: the
        # plain one stays what it compiled to
        name="mla_flash_attention" if value_dim != head_dim else None,
        interpret=_interpret(),
    )(*told, q_padded, k_padded, v_padded)
    out = results[0].reshape(batch, heads, padded_q_len,
                             value_dim)[:, :, :q_len]
    if not with_lse:
        return out
    lse = results[1].reshape(batch, heads, padded_q_len,
                             _STAT_LANES)[:, :, :q_len, 0]
    return out, lse


# -- Pallas flash attention backward ----------------------------------------
#
# FlashAttention-2-style: p is recomputed blockwise inside VMEM from the
# saved logsumexp; dq accumulates over the sequential k dimension, dk/dv
# over the sequential q dimension.  delta = rowsum(dO * O) is a cheap
# O(L*D) XLA pass.

def _flash_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     dq_ref, dq_acc_ref, *,
                     causal: bool, sm_scale: float, kv_len: int,
                     q_offset: int):
    block_q = q_ref.shape[1]
    block_k = k_ref.shape[1]
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    num_kb = pl.num_programs(2)
    q_base = qi * block_q + q_offset
    q_pos = (q_base + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0))
    k_pos = (ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1))

    @pl.when(ki == 0)
    def _init():
        dq_acc_ref[:] = jnp.zeros_like(dq_acc_ref)

    needed = ki * block_k < kv_len
    if causal:
        needed = jnp.logical_and(
            needed, ki * block_k <= q_base + block_q - 1)

    @pl.when(needed)
    def _update():
        q = q_ref[0].astype(jnp.float32)
        k_blk = k_ref[0].astype(jnp.float32)
        v_blk = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q * sm_scale, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        mask = k_pos < kv_len
        if causal:
            mask = jnp.logical_and(mask, k_pos <= q_pos)
        p = jnp.where(mask, jnp.exp(s - lse_ref[0][:, :1]), 0.0)
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0][:, :1])
        dq_acc_ref[:] += jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale

    @pl.when(ki == num_kb - 1)
    def _finish():
        dq_ref[0] = dq_acc_ref[:].astype(dq_ref.dtype)


def _flash_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dk_ref, dv_ref, dk_acc_ref, dv_acc_ref, *,
                      causal: bool, sm_scale: float, kv_len: int,
                      q_len: int, q_offset: int):
    block_k = k_ref.shape[1]
    block_q = q_ref.shape[1]
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    num_qb = pl.num_programs(2)
    q_base = qi * block_q + q_offset
    # transposed layout: rows are k positions, columns q positions
    k_pos = (ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_k, block_q), 0))
    q_pos = (q_base + jax.lax.broadcasted_iota(
        jnp.int32, (block_k, block_q), 1))

    @pl.when(qi == 0)
    def _init():
        dk_acc_ref[:] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[:] = jnp.zeros_like(dv_acc_ref)

    needed = qi * block_q < q_len
    if causal:
        # skip q blocks entirely ABOVE this k block's causal reach
        needed = jnp.logical_and(
            needed, q_base + block_q - 1 >= ki * block_k)

    @pl.when(needed)
    def _update():
        q = q_ref[0].astype(jnp.float32)
        k_blk = k_ref[0].astype(jnp.float32)
        v_blk = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        s_t = jax.lax.dot_general(
            k_blk, q * sm_scale, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)   # (block_k, block_q)
        mask = k_pos < kv_len
        if causal:
            mask = jnp.logical_and(mask, k_pos <= q_pos)
        lse_row = lse_ref[0][:, 0]                # (block_q,)
        p_t = jnp.where(mask, jnp.exp(s_t - lse_row[None, :]), 0.0)
        dv_acc_ref[:] += jax.lax.dot_general(
            p_t, do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp_t = jax.lax.dot_general(
            v_blk, do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)   # (block_k, block_q)
        delta_row = delta_ref[0][:, 0]
        ds_t = p_t * (dp_t - delta_row[None, :])
        dk_acc_ref[:] += jax.lax.dot_general(
            ds_t, q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale

    @pl.when(qi == num_qb - 1)
    def _finish():
        dk_ref[0] = dk_acc_ref[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[:].astype(dv_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "sm_scale", "block_q", "block_k", "q_offset"))
def _flash_bwd_impl(q, k, v, out, lse, dout, causal, sm_scale, block_q,
                    block_k, q_offset):
    batch, heads, q_len, head_dim = q.shape
    kv_len = k.shape[2]
    effective_offset = int(q_offset) + (kv_len - q_len if causal else 0)

    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                               # (B, H, Lq)

    q_p = _pad_seq(q, block_q).reshape(batch * heads, -1, head_dim)
    do_p = _pad_seq(dout, block_q).reshape(batch * heads, -1, head_dim)
    k_p = _pad_seq(k, block_k).reshape(batch * heads, -1, head_dim)
    v_p = _pad_seq(v, block_k).reshape(batch * heads, -1, head_dim)
    padded_q_len = q_p.shape[1]
    padded_kv_len = k_p.shape[1]

    def lanes(x, block):  # (B, H, L) -> (B*H, padded L, _STAT_LANES)
        x = pad_axis_to(x[..., None], 2,
                        ((x.shape[2] + block - 1) // block) * block)
        return jnp.broadcast_to(
            x.reshape(batch * heads, -1, 1),
            (batch * heads, x.shape[2], _STAT_LANES))

    lse_p = lanes(lse, block_q)
    delta_p = lanes(delta, block_q)

    q_spec = pl.BlockSpec((1, block_q, head_dim),
                          lambda bh, qi, ki: (bh, qi, 0),
                          memory_space=pltpu.VMEM)
    k_spec = pl.BlockSpec((1, block_k, head_dim),
                          lambda bh, qi, ki: (bh, ki, 0),
                          memory_space=pltpu.VMEM)
    stat_spec = pl.BlockSpec((1, block_q, _STAT_LANES),
                             lambda bh, qi, ki: (bh, qi, 0),
                             memory_space=pltpu.VMEM)

    dq = pl.pallas_call(
        functools.partial(
            _flash_dq_kernel, causal=causal, sm_scale=float(sm_scale),
            kv_len=kv_len, q_offset=effective_offset),
        grid=(batch * heads, padded_q_len // block_q,
              padded_kv_len // block_k),
        in_specs=[q_spec, k_spec, k_spec, q_spec, stat_spec, stat_spec],
        out_specs=pl.BlockSpec((1, block_q, head_dim),
                               lambda bh, qi, ki: (bh, qi, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(
            (batch * heads, padded_q_len, head_dim), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, head_dim), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
    )(q_p, k_p, v_p, do_p, lse_p, delta_p)

    # dk/dv: k blocks are the parallel dimension, q streams sequentially
    q_spec_t = pl.BlockSpec((1, block_q, head_dim),
                            lambda bh, ki, qi: (bh, qi, 0),
                            memory_space=pltpu.VMEM)
    k_spec_t = pl.BlockSpec((1, block_k, head_dim),
                            lambda bh, ki, qi: (bh, ki, 0),
                            memory_space=pltpu.VMEM)
    stat_spec_t = pl.BlockSpec((1, block_q, _STAT_LANES),
                               lambda bh, ki, qi: (bh, qi, 0),
                               memory_space=pltpu.VMEM)
    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_dkv_kernel, causal=causal, sm_scale=float(sm_scale),
            kv_len=kv_len, q_len=q_len, q_offset=effective_offset),
        grid=(batch * heads, padded_kv_len // block_k,
              padded_q_len // block_q),
        in_specs=[q_spec_t, k_spec_t, k_spec_t, q_spec_t, stat_spec_t,
                  stat_spec_t],
        out_specs=[
            pl.BlockSpec((1, block_k, head_dim),
                         lambda bh, ki, qi: (bh, ki, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, head_dim),
                         lambda bh, ki, qi: (bh, ki, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(
                (batch * heads, padded_kv_len, head_dim), k.dtype),
            jax.ShapeDtypeStruct(
                (batch * heads, padded_kv_len, head_dim), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, head_dim), jnp.float32),
                        pltpu.VMEM((block_k, head_dim), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
    )(q_p, k_p, v_p, do_p, lse_p, delta_p)

    dq = dq.reshape(batch, heads, padded_q_len, head_dim)[:, :, :q_len]
    dk = dk.reshape(batch, heads, padded_kv_len, head_dim)[:, :, :kv_len]
    dv = dv.reshape(batch, heads, padded_kv_len, head_dim)[:, :, :kv_len]
    return dq, dk, dv


# -- Pallas paged attention (the continuous-batching decode step) -----------
#
# The decode engine keeps K/V in one pool of fixed-size blocks,
# (layers, num_blocks, kv_heads, block, head_dim), named per slot by a
# block table.  The kernel reads the pool WHERE IT LIES: for each slot it
# walks only the blocks that hold live positions, fetching them from HBM
# with asynchronous copies (several blocks a chunk, two chunks in flight),
# and serves all `repeats` query heads of a KV group from one read of that
# group's block.  The bytes moved follow the live positions, never the
# table's capacity; no gathered view, no repeat_kv, no float32 copy of the
# cache ever exists in HBM.

_PAGED_CHUNK_POSITIONS = 512   # K/V positions fetched per chunk
_PAGED_CHUNK_BLOCKS_MAX = 16   # copies in flight per chunk and leaf
_PAGED_NARROW = 128            # positions multiplied of a short chunk
_PAGED_MAX_ROW_BYTES = 8192    # heads x window x itemsize one slot brings
_PAGED_LATENT_ROW_BYTES = 2048  # the same for a latent pool's wider rows


def paged_attention_reference(q, pool_k, pool_v, layer, tables, positions,
                              k_scale=None, v_scale=None, sm_scale=None,
                              value_dim=None):
    """Plain-XLA paged attention, same signature as paged_attention:
    gather every slot's WHOLE table from the pool's `layer` into a
    contiguous view, repeat the KV heads and run the masked einsum.
    Window row i of slot s sits at absolute position positions[s] + i
    and sees k_pos <= that.  With k_scale/v_scale (the int8 pool's scale
    leaves) the gathered view is dequantized into the einsum's operand
    load.  With pool_v None the pool is a latent one: a row is the key,
    and its first `value_dim` values the value.  The oracle the kernel's
    tests compare with, and the path of the calls the kernel does not
    take (paged_attention_takes)."""
    slots, heads, window, depth = q.shape
    repeats = heads // pool_k.shape[2]

    def view(leaf):
        # (L, num_blocks, H, bs, d)[layer, tables] -> (S, MB, H, bs, d)
        # -> the slot's contiguous cache view (S, H, MB*bs, d)
        gathered = leaf[layer, tables]
        s, max_blocks, kv_heads, block, d = gathered.shape
        return gathered.transpose(0, 2, 1, 3, 4).reshape(
            s, kv_heads, max_blocks * block, d)

    k_eff = view(pool_k)
    v_eff = k_eff[..., :value_dim] if pool_v is None else view(pool_v)
    if k_scale is not None:
        k_eff = (k_eff.astype(jnp.float32) * view(k_scale)).astype(q.dtype)
        v_eff = (v_eff.astype(jnp.float32) * view(v_scale)).astype(q.dtype)
    # each KV head serves `repeats` consecutive query heads
    k_full = jnp.repeat(k_eff, repeats, axis=1)
    v_full = jnp.repeat(v_eff, repeats, axis=1)
    scale = (1.0 / jnp.sqrt(jnp.float32(depth)) if sm_scale is None
             else sm_scale)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k_full,
                        preferred_element_type=jnp.float32) * scale
    q_pos = positions[:, None] + jnp.arange(window)[None, :]
    k_pos = jnp.arange(k_full.shape[2])[None, None, None, :]
    logits = jnp.where(k_pos <= q_pos[:, None, :, None], logits, _NEG_INF)
    weights = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", weights.astype(v_full.dtype),
                      v_full)


def paged_attention_takes(heads: int, window: int, head_dim: int,
                          pool_dtype, value_dim: int | None = None) -> bool:
    """Whether paged_attention serves a call, decided by what the call
    is: a bf16 or float32 pool whose `heads x window` query rows fit one
    slot's VMEM residency (4096 rows of bf16, 2048 of float32) -- window
    1 always does.  An int8 pool (its scales ride a second leaf) and a
    very large window keep the einsum, and so does, on the chip, a
    head_dim that is not a multiple of the 128 lanes: Mosaic cannot
    slice such a pool for the block copies (jax's own paged kernels
    refuse it too).  A latent pool (`value_dim` given: head_dim is its
    row, 640 wide at DeepSeek-V2's sizes, against one key head) has a
    quarter of the rows' room: 1024 of bf16, a window of 8 at 128 heads."""
    dtype = jnp.dtype(pool_dtype)
    room = (_PAGED_MAX_ROW_BYTES if value_dim is None
            else _PAGED_LATENT_ROW_BYTES)
    return (dtype in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32))
            and heads * window * dtype.itemsize <= room
            and (head_dim % 128 == 0 or _interpret())
            and (value_dim is None or value_dim % 128 == 0
                 or _interpret()))


def paged_attention_writes(window: int) -> bool:
    """Whether paged_attention, where it takes a call, writes the
    window's new rows itself (its `write`): window 1, the decode step.
    A wider window (a verify step, a prefill chunk) has its rows written
    before the call, one update each (transformer._write_window)."""
    return window == 1


def paged_live_blocks(positions, window: int, block: int,
                      max_blocks: int):
    """Blocks the kernel walks for each slot: those holding positions
    0 .. positions + window - 1, at least one (an inactive slot reads
    the trash block) and never more than the table names.  numpy or jax
    integers in, the same out."""
    blocks = (positions + window + block - 1) // block
    return blocks.clip(1, max_blocks)


def _paged_kernel(*refs, window: int, block: int, chunk_blocks: int,
                  max_blocks: int, sm_scale: float,
                  value_dim: int | None = None, writes: bool = False):
    """One slot per grid step.  The slot's live blocks arrive in chunks
    of `chunk_blocks`; while chunk c is multiplied, chunk c + 1 (or the
    next slot's first chunk) is already on its way into the other half
    of k_buf/v_buf.  Softmax state (m, l, acc) lives in VMEM in float32
    across the chunks.  With `value_dim` the pool is a latent one: there
    is no V leaf and no V buffer, a row is fetched once and its first
    `value_dim` values are the value.

    With `writes` (window 1) the kernel also WRITES the slot's new row
    of every leaf at (write_blocks[slot], write_offsets[slot]); the pool
    leaves are then the aliased outputs, read and written through the
    same refs.  One position is not a unit a copy can move in the pool's
    tiled layout, so the aligned tile of `_tile_positions` that holds it
    is read into a scratch of its own, the row put in by an iota-select,
    and the tile copied back where it came from: its other rows return
    as they were.  The fresh row is also put into the chunk buffer at
    the slot's position, so the attention multiplies it like a row read
    from HBM.  The order that keeps this sound:
      - the tile's read is started a slot ahead, with the slot's first
        chunk, and waited for before the patch;
      - the write-back starts only after the slot's LAST chunk, the one
        that holds its position, has been waited for, so no read of that
        block by this slot is in flight;
      - it is waited for before the slot's grid step ends, so the tile
        scratch and its semaphore are free again two slots on;
      - what is in flight meanwhile -- the next slot's first chunk and
        tile -- touches that slot's own pages, or the trash block, which
        several inert slots may write at once: its rows get no weight in
        a live slot, neighbours in a tile are rewritten with the values
        they had, and any winner of the told row is right."""
    refs = iter(refs)
    take = lambda count: [next(refs) for _ in range(count)]  # noqa: E731
    leaf_count = 1 if value_dim is not None else 2
    layer_ref, tables_ref, positions_ref, live_ref = take(4)
    write_blocks_ref, write_offsets_ref = take(2 if writes else 0) or (
        None, None)
    (q_ref,) = take(1)
    new_refs = take(leaf_count if writes else 0)
    hbm = take(leaf_count)
    (o_ref,) = take(1)
    if writes:
        # the pool comes in and goes out as one buffer: the output refs
        # are the ones read and written
        hbm = take(leaf_count)
    bufs = take(leaf_count)
    sems, parity_ref, m_ref, l_ref, acc_ref = take(5)
    tiles, tile_sems = take(leaf_count if writes else 0), next(refs, None)
    leaves = tuple(zip(hbm, bufs))
    k_buf, v_buf = bufs[0], bufs[-1]
    slot = pl.program_id(0)
    slots = pl.num_programs(0)
    kv_heads, rows = q_ref.shape[1], q_ref.shape[2]
    chunk = chunk_blocks * block
    narrow = (_PAGED_NARROW if chunk % _PAGED_NARROW == 0 else chunk)
    layer = layer_ref[0]
    position = positions_ref[slot]
    chunks = (live_ref[slot] + chunk_blocks - 1) // chunk_blocks

    def by_head(body):
        # a K/V head a turn of a loop that is traced ONCE and unrolled
        # when lowered, the head a constant there: the program is the
        # one a Python loop makes, and tracing it -- it is set-up time,
        # under every first request -- does not grow with the heads
        jax.lax.fori_loop(0, kv_heads, body, 0, unroll=True)

    def transfer(of_slot, of_chunk, half, start: bool):
        # one copy per live block and leaf; blocks past the slot's last
        # are neither started nor waited for
        first = of_chunk * chunk_blocks
        count = jnp.minimum(live_ref[of_slot] - first, chunk_blocks)

        def one(j, carry):
            page = tables_ref[of_slot * max_blocks + first + j]
            offset = pl.multiple_of(j * block, block)
            for leaf, (pool, buf) in enumerate(leaves):
                copy = pltpu.make_async_copy(
                    pool.at[layer, page],
                    buf.at[half, :, pl.ds(offset, block), :],
                    sems.at[leaf, half])
                if start:
                    copy.start()
                else:
                    copy.wait()
            return carry

        jax.lax.fori_loop(0, count, one, 0)

    if writes:
        tile = tiles[0].shape[2]

        def tile_copies(of_slot, back: bool):
            # the told row's tile of every leaf, HBM -> scratch or back
            start = pl.multiple_of(
                write_offsets_ref[of_slot] // tile * tile, tile)
            for leaf, (pool, scratch) in enumerate(zip(hbm, tiles)):
                there = pool.at[layer, write_blocks_ref[of_slot], :,
                                pl.ds(start, tile), :]
                here = scratch.at[of_slot % 2]
                yield pltpu.make_async_copy(
                    *((here, there) if back else (there, here)),
                    tile_sems.at[leaf, of_slot % 2])

        def put_row(ref, start, index, new_ref):
            # the `tile` positions of ref (kv_heads, n, d) from `start`,
            # with row `index` of them replaced by the new row, whose
            # heads lie side by side on the lanes
            depth = ref.shape[2]
            row = jax.lax.broadcasted_iota(jnp.int32, (tile, depth), 0)

            def one(head, carry):
                held = ref[head, pl.ds(start, tile), :]
                new = new_ref[0, :, pl.ds(
                    pl.multiple_of(head * depth, depth), depth)]
                ref[head, pl.ds(start, tile), :] = jnp.where(
                    row == index, new, held)
                return carry

            by_head(one)

        def write_row(c, half):
            for copy in tile_copies(slot, back=False):
                copy.wait()
            for scratch, new_ref in zip(tiles, new_refs):
                put_row(scratch.at[slot % 2], 0,
                        write_offsets_ref[slot] % tile, new_ref)
            for copy in tile_copies(slot, back=True):
                copy.start()
            # where the attention looks for it: the slot's position in
            # this chunk (a position past the table patches nothing)
            local = position - c * chunk
            start = pl.multiple_of(
                jnp.clip(local // tile * tile, 0, chunk - tile), tile)
            for buf, new_ref in zip(bufs, new_refs):
                put_row(buf.at[half], start, local - start, new_ref)

    @pl.when(slot == 0)
    def _first():
        # dead columns are masked by position, but 0 x NaN is NaN: what
        # no copy has written yet must at least be finite
        v_buf[...] = jnp.zeros_like(v_buf)
        parity_ref[0] = 0
        transfer(0, 0, 0, start=True)
        if writes:
            for copy in tile_copies(0, back=False):
                copy.start()

    first_half = parity_ref[0]
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def attend(c, half, width: int):
        # the chunk's first `width` positions: a matmul a head for K and
        # one for V, the group's rows served from one read of its block
        row_window = jax.lax.broadcasted_iota(
            jnp.int32, (rows, width), 0) % window
        column = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1)
        visible = c * chunk + column <= position + row_window
        def one(head, carry):
            q = q_ref[0, head]                              # (rows, d)
            k_blk = k_buf[half, head, :width]               # (width, d)
            s = jax.lax.dot_general(
                q, k_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            s = jnp.where(visible, s, _NEG_INF)
            m_prev = m_ref[head, :, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[head] = l_ref[head] * alpha + jnp.broadcast_to(
                jnp.sum(p, axis=-1, keepdims=True), l_ref.shape[1:])
            v_blk = (v_buf[half, head, :width] if value_dim is None
                     else k_blk[:, :value_dim])
            acc_ref[head] = acc_ref[head] * alpha + jax.lax.dot_general(
                p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[head] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            return carry

        by_head(one)

    def chunk_step(c, carry):
        half = (first_half + c) % 2
        last = c + 1 == chunks
        next_slot = jnp.where(last, slot + 1, slot)

        @pl.when(next_slot < slots)
        def _prefetch():
            transfer(next_slot, jnp.where(last, 0, c + 1), 1 - half,
                     start=True)
            if writes:
                @pl.when(last)
                def _next_tile():
                    for copy in tile_copies(slot + 1, back=False):
                        copy.start()

        transfer(slot, c, half, start=False)
        if writes:
            @pl.when(last)
            def _write():
                write_row(c, half)
        if narrow == chunk:
            attend(c, half, chunk)
        else:
            # a chunk is multiplied whole (wide matmuls keep the MXU
            # fed), but one that ends inside its first `narrow`
            # positions -- an idle slot's trash block, a short tail --
            # only that far
            seen = position + window - c * chunk

            @pl.when(seen <= narrow)
            def _short():
                attend(c, half, narrow)

            @pl.when(seen > narrow)
            def _whole():
                attend(c, half, chunk)
        return carry

    jax.lax.fori_loop(0, chunks, chunk_step, 0)
    parity_ref[0] = (first_half + chunks) % 2
    def put_out(head, carry):
        o_ref[0, head] = (acc_ref[head] / l_ref[head, :, :1]).astype(
            o_ref.dtype)
        return carry

    by_head(put_out)
    if writes:
        for copy in tile_copies(slot, back=True):
            copy.wait()


def _sublane_rows(dtype) -> int:
    """Rows in the dtype's sublane tile: 8 of float32, 16 of bfloat16."""
    return 8 * 4 // jnp.dtype(dtype).itemsize


def _tile_positions(dtype, block: int) -> int:
    """Positions in the aligned tile a row write reads and writes back:
    the dtype's sublane tile, cut to what divides the block, so that a
    tile lies inside one (a toy pool's block of 4 on the interpreter;
    Mosaic refuses the block copies of a block under 8)."""
    return math.gcd(block, _sublane_rows(dtype))


def paged_attention(q, pool_k, pool_v, layer, tables, positions,
                    sm_scale=None, value_dim=None, write=None):
    """Paged attention over the pool in place.  q (slots, heads, W, d);
    pool_k/pool_v the WHOLE pool leaves (layers, num_blocks, kv_heads,
    block, d), left in HBM, of which `layer` (an int32 scalar, traced or
    not) is read; tables (slots, max_blocks) int32; positions (slots,)
    int32.  Same mathematics and mask as paged_attention_reference --
    window row i of slot s attends to k_pos <= positions[s] + i, scores
    and accumulation in float32, operands in the pool's dtype -- with
    the softmax taken blockwise, so outputs agree to rounding, not
    bitwise.  Mosaic on the chip, interpreted on CPU (_interpret); named
    `paged_attention` in the device trace.  Returns (out, pool_k,
    pool_v).

    `write` = (new rows, write_blocks, write_offsets) makes the kernel
    write the step's new rows itself (paged_attention_writes: window
    1): the rows, one (slots, kv_heads, 1, d) a leaf, land in the
    pool's dtype at [layer, write_blocks[s], :, write_offsets[s], :]
    before the slot attends, the leaves ride the call aliased in place
    (input_output_aliases: a donated pool stays one buffer) and come
    back written; nothing else in them changes.  A slot's row must lie
    at its position in its own table or in a block no live slot reads
    (the trash block).  Without `write` the leaves come back as given.

    pool_v None is a latent pool (latent attention absorbed: many query
    heads over ONE key head): a row of pool_k is the key and its first
    `value_dim` values the value, so the output is (slots, heads, W,
    value_dim) and a live row is read once; one new row.  That
    kernel is named `mla_paged_attention` in the device trace."""
    slots, heads, window, depth = q.shape
    _, _, kv_heads, block, _ = pool_k.shape
    max_blocks = tables.shape[1]
    repeats = heads // kv_heads
    rows = repeats * window
    # the group's repeats x W query rows are one matmul operand; padded
    # with zero rows to the dtype's sublane tile (sliced off below)
    grouped = _pad_seq(q.reshape(slots, kv_heads, rows, depth),
                       _sublane_rows(q.dtype))
    padded_rows = grouped.shape[2]
    chunk_blocks = max(1, min(_PAGED_CHUNK_BLOCKS_MAX,
                              _PAGED_CHUNK_POSITIONS // block, max_blocks))
    chunk = chunk_blocks * block
    positions = positions.astype(jnp.int32)
    live = paged_live_blocks(positions, window, block, max_blocks)

    pools = (pool_k,) if pool_v is None else (pool_k, pool_v)
    out_depth = depth if pool_v is not None else int(value_dim)
    scalars = [jnp.reshape(layer, (1,)).astype(jnp.int32),
               tables.reshape(-1).astype(jnp.int32), positions, live]
    new_rows, aliased, told_scratch, aliases = (), (), [], {}
    if write is not None:
        assert paged_attention_writes(window), window
        tile = _tile_positions(pool_k.dtype, block)
        new_rows, write_blocks, write_offsets = write
        # (slots, kv_heads, 1, d) -> (slots, 1, kv_heads x d): the heads
        # side by side, as the projection made them
        new_rows = tuple(
            new.astype(pool.dtype).transpose(0, 2, 1, 3).reshape(
                slots, 1, kv_heads * depth)
            for new, pool in zip(new_rows, pools))
        scalars += [write_blocks.reshape(-1).astype(jnp.int32),
                    write_offsets.reshape(-1).astype(jnp.int32)]
        told_scratch = [
            pltpu.VMEM((2, kv_heads, tile, depth), pool.dtype)
            for pool in pools] + [pltpu.SemaphoreType.DMA((len(pools), 2))]
        # operand -> result: the leaves follow the scalars, q and rows
        aliased = pools
        aliases = {len(scalars) + 1 + len(new_rows) + leaf: 1 + leaf
                   for leaf in range(len(pools))}
    kernel = functools.partial(
        _paged_kernel, window=window, block=block,
        chunk_blocks=chunk_blocks, max_blocks=max_blocks,
        sm_scale=(1.0 / math.sqrt(depth) if sm_scale is None
                  else float(sm_scale)),
        value_dim=None if pool_v is not None else out_depth,
        writes=write is not None)
    by_slot = lambda s, *_: (s, 0, 0, 0)                   # noqa: E731
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    o_spec = pl.BlockSpec((1, kv_heads, padded_rows, out_depth), by_slot,
                          memory_space=pltpu.VMEM)
    out, *written = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(slots,),
            in_specs=[pl.BlockSpec((1, kv_heads, padded_rows, depth),
                                   by_slot, memory_space=pltpu.VMEM)] + [
                pl.BlockSpec((1, 1, kv_heads * depth),
                             lambda s, *_: (s, 0, 0),
                             memory_space=pltpu.VMEM)
                for _ in new_rows] + [in_hbm for _ in pools],
            out_specs=[o_spec] + [in_hbm for _ in aliased],
            scratch_shapes=[
                pltpu.VMEM((2, kv_heads, chunk, depth), pool.dtype)
                for pool in pools] + [
                pltpu.SemaphoreType.DMA((len(pools), 2)),
                pltpu.SMEM((1,), jnp.int32),               # buffer parity
                pltpu.VMEM((kv_heads, padded_rows, _STAT_LANES),
                           jnp.float32),                   # m
                pltpu.VMEM((kv_heads, padded_rows, _STAT_LANES),
                           jnp.float32),                   # l
                pltpu.VMEM((kv_heads, padded_rows, out_depth),
                           jnp.float32),                   # acc
            ] + told_scratch),
        out_shape=[jax.ShapeDtypeStruct(
            grouped.shape[:3] + (out_depth,), q.dtype)] + [
            jax.ShapeDtypeStruct(pool.shape, pool.dtype)
            for pool in aliased],
        input_output_aliases=aliases,
        # the buffer parity and the copy in flight cross grid steps
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name=("paged_attention" if pool_v is not None
              else "mla_paged_attention"),
        interpret=_interpret(),
    )(*scalars, grouped, *new_rows, *pools)
    pool_k, pool_v = (tuple(written) or pools) + (None,) * (2 - len(pools))
    return (out[:, :, :rows].reshape(slots, heads, window, out_depth),
            pool_k, pool_v)


# -- Ring attention (sequence parallel) -------------------------------------

# Test hook: when set to a callable, it is invoked (via jax.debug.callback)
# once per EXECUTED ring hop -- hops skipped by the causal lax.cond branch
# never fire it.  Tests use this to assert the masked-hop skip is real.
_RING_HOP_CALLBACK = None


def _merge_softmax_partials(out, lse, out_blk, lse_blk):
    """Associative merge of two normalized attention partials via their
    per-row logsumexp: exact online-softmax combination."""
    lse_new = jnp.logaddexp(lse, lse_blk)
    w_old = jnp.exp(lse - lse_new)[..., None]
    w_blk = jnp.exp(lse_blk - lse_new)[..., None]
    merged = (out.astype(jnp.float32) * w_old
              + out_blk.astype(jnp.float32) * w_blk)
    return merged.astype(out.dtype), lse_new


def ring_attention_sharded(q, k, v, axis_name: str = "seq",
                           causal: bool = True, sm_scale=None,
                           block_q: int = 128, block_k: int = 128):
    """Sequence-parallel attention over mesh axis `axis_name`; call INSIDE
    shard_map with q/k/v seq-sharded as (B, H, L/n, D).

    Q stays resident; K/V shards rotate n-1 hops around the ring via
    ppermute (XLA lowers to ICI collective-permute, overlapping each hop
    with the current block's MXU work).  Each hop runs the Pallas flash
    kernel (O(block) VMEM, never a materialized (L/n)^2 logit tensor) and
    returns (out, lse); hops merge with the associative online-softmax
    combination, so the result is exact.

    Under causal masking the ring ordering sends device i the K/V shard of
    device (i - step) mod n at hop `step`; that shard is entirely in the
    future (fully masked) exactly when step > i, so those hops are skipped
    with lax.cond -- no flash call, no wasted MXU work.  Device i executes
    i + 1 of the n hops; total executed hops are n(n+1)/2 instead of n^2.

    Differentiable: the custom VJP runs a second ring in which dk/dv
    accumulators travel WITH their K/V shards; each executed hop runs the
    blockwise Pallas backward kernels against the forward's GLOBAL
    logsumexp, so backward peak memory stays O(L/n x block) per device.
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    local_len = q.shape[2]
    return _ring(q, k, v, bool(causal), float(sm_scale), str(axis_name),
                 int(min(block_q, local_len)), int(min(block_k, local_len)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _ring(q, k, v, causal, sm_scale, axis_name, block_q, block_k):
    out, _ = _ring_fwd_impl(q, k, v, causal, sm_scale, axis_name, block_q,
                            block_k)
    return out


def _ring_fwd_impl(q, k, v, causal, sm_scale, axis_name, block_q, block_k):
    axis_size = jax.lax.axis_size(axis_name)
    my_index = jax.lax.axis_index(axis_name)
    batch, heads, local_len, _ = q.shape
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def compute_hop(k_blk, v_blk, step):
        # step 0 is the diagonal block (standard causal); every executed
        # later hop holds strictly-past keys, so it runs dense non-causal.
        out_blk, lse_blk = _flash_impl(
            q, k_blk, v_blk, causal and step == 0, sm_scale, block_q,
            block_k, 0)
        if _RING_HOP_CALLBACK is not None:
            jax.debug.callback(_RING_HOP_CALLBACK, step)
        return out_blk, lse_blk

    def skipped_hop(k_blk, v_blk, step):
        return (jnp.zeros_like(q),
                jnp.full((batch, heads, local_len), _NEG_INF, jnp.float32))

    out, lse = compute_hop(k, v, 0)
    out = out.astype(jnp.float32)
    k_blk, v_blk = k, v
    for step in range(1, axis_size):
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        if causal:
            # src shard = (my_index - step) mod n; fully masked iff it
            # wrapped, i.e. my_index < step
            out_blk, lse_blk = jax.lax.cond(
                my_index >= step,
                functools.partial(compute_hop, step=step),
                functools.partial(skipped_hop, step=step),
                k_blk, v_blk)
        else:
            out_blk, lse_blk = compute_hop(k_blk, v_blk, step)
        out, lse = _merge_softmax_partials(out, lse, out_blk, lse_blk)
    return out.astype(q.dtype), lse


def _ring_fwd(q, k, v, causal, sm_scale, axis_name, block_q, block_k):
    out, lse = _ring_fwd_impl(q, k, v, causal, sm_scale, axis_name,
                              block_q, block_k)
    return out, (q, k, v, out, lse)


def _ring_bwd(causal, sm_scale, axis_name, block_q, block_k, residuals,
              dout):
    """Ring backward: a second rotation in which each K/V shard travels
    with its dk/dv accumulator.  Every executed hop recomputes p blockwise
    inside the Pallas backward kernels from the forward's global lse (so
    per-hop partial gradients are exactly the global-attention gradients
    restricted to that shard); a final ppermute delivers each dk/dv
    accumulator back to its home device."""
    q, k, v, out, lse = residuals
    dq_acc = jnp.zeros(q.shape, jnp.float32)

    def compute_hop(k_blk, v_blk, dk_blk, dv_blk, step):
        dq_h, dk_h, dv_h = _flash_bwd_impl(
            q, k_blk, v_blk, out, lse, dout, causal and step == 0,
            sm_scale, block_q, block_k, 0)
        return (dq_h.astype(jnp.float32), dk_blk + dk_h.astype(jnp.float32),
                dv_blk + dv_h.astype(jnp.float32))

    def skipped_hop(k_blk, v_blk, dk_blk, dv_blk, step):
        return jnp.zeros(q.shape, jnp.float32), dk_blk, dv_blk

    axis_size = jax.lax.axis_size(axis_name)
    my_index = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    carry = dq_acc
    kv = (k, v, jnp.zeros(k.shape, jnp.float32),
          jnp.zeros(v.shape, jnp.float32))
    for step in range(axis_size):
        if step > 0:
            kv = tuple(jax.lax.ppermute(x, axis_name, perm) for x in kv)
        if causal and step > 0:
            hop_out = jax.lax.cond(
                my_index >= step,
                functools.partial(compute_hop, step=step),
                functools.partial(skipped_hop, step=step),
                *kv)
        else:
            hop_out = compute_hop(*kv, step=step)
        dq_h, dk_blk, dv_blk = hop_out
        carry = carry + dq_h
        kv = (kv[0], kv[1], dk_blk, dv_blk)
    # shard s sits on device (s + n - 1) mod n after the loop; one more
    # rotation returns every dk/dv accumulator to its home device
    dk = jax.lax.ppermute(kv[2], axis_name, perm)
    dv = jax.lax.ppermute(kv[3], axis_name, perm)
    return (carry.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype))


_ring.defvjp(_ring_fwd, _ring_bwd)


def ring_attention(q, k, v, mesh=None, axis_name: str = "seq",
                   causal: bool = True, sm_scale=None):
    """shard_map entry point: shards (B, H, L, D) on the seq axis and runs
    ring_attention_sharded.  mesh=None uses the ambient mesh (callers
    inside a jax.set_mesh context, e.g. the transformer's
    sequence-parallel prefill)."""
    spec = P(None, None, axis_name, None)
    fn = functools.partial(ring_attention_sharded, axis_name=axis_name,
                           causal=causal, sm_scale=sm_scale)
    kwargs = {} if mesh is None else {"mesh": mesh}
    return jax.shard_map(
        fn, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False, **kwargs)(q, k, v)


# -- Sequence-parallel decode attention --------------------------------------

def sp_decode_attention_sharded(q, cache_k, cache_v, pos,
                                axis_name: str = "seq", sm_scale=None):
    """Sequence-parallel KV-cached decode: call INSIDE shard_map with the
    cache length axis sharded as (B, Hkv, Lc/n, D) and q (B, H, Lq, D)
    replicated over the seq axis (Lq = 1 for single-token decode; Hkv may
    be a divisor of H -- GQA heads expand on the LOCAL shard only).

    Long-context *generation* with the cache spread over the mesh: each
    device attends q over only its local cache shard (masked to positions
    <= pos), producing a normalized partial + logsumexp; partials combine
    exactly with a pmax/psum online-softmax merge over the axis, so
    per-device attention bandwidth is O(Lc/n).  No ring needed -- q is
    tiny, so an all-reduce of the (B, H, Lq, D) partial is cheap.

    Two decode-path optimizations (round-2 weak #6):
      - GQA contracts GROUPED q heads (B, Hkv, G, Lq, D) against the
        un-expanded (B, Hkv, Lc/n, D) cache -- the cache shard, the
        dominant HBM traffic at long context, is streamed once instead
        of being materialized G times;
      - num and den merge in ONE fused psum (payload (B, H, Lq, D+1)).
        With Lq = 1 the payloads are tiny and per-step cost is
        collective LATENCY, so 2 collectives (pmax + psum) beat 3.
        A reduce-to-owner would not beat the all-reduce: every device
        needs the summed output (the following wo/MLP compute is
        replicated over the seq axis), and reduce (n-1)/n + broadcast
        (n-1)/n moves the same bytes as the 2(n-1)/n all-reduce with
        an extra latency hop.
    """
    axis_index = jax.lax.axis_index(axis_name)
    batch, kv_heads, local_len, head_dim = cache_k.shape
    q_len, heads = q.shape[2], q.shape[1]
    groups = heads // kv_heads
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(head_dim)

    q_grouped = q.reshape(batch, kv_heads, groups, q_len, head_dim)
    k_pos = (axis_index * local_len
             + jnp.arange(local_len))[None, None, None, None, :]
    q_pos = (pos + jnp.arange(q_len))[None, None, None, :, None]
    s = jnp.einsum("bhgqd,bhkd->bhgqk", q_grouped, cache_k,
                   preferred_element_type=jnp.float32) * sm_scale
    s = jnp.where(k_pos <= q_pos, s, _NEG_INF)
    m_local = jnp.max(s, axis=-1)                       # (B, Hkv, G, Lq)
    m_global = jax.lax.pmax(m_local, axis_name)
    p = jnp.exp(s - m_global[..., None])
    num = jnp.einsum("bhgqk,bhkd->bhgqd", p,
                     cache_v.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    den = jnp.sum(p, axis=-1, keepdims=True)         # (B, Hkv, G, Lq, 1)
    fused = jax.lax.psum(jnp.concatenate([num, den], axis=-1), axis_name)
    num, den = fused[..., :head_dim], fused[..., head_dim:]
    out = (num / jnp.maximum(den, 1e-30)).astype(q.dtype)
    return out.reshape(batch, heads, q_len, head_dim)


def sp_decode_attention(q, cache_k, cache_v, pos, mesh=None,
                        axis_name: str = "seq", sm_scale=None,
                        batch_axis: str = "data", head_axis: str = "model"):
    """shard_map entry point for sequence-parallel decode: cache length
    sharded over `axis_name`, q sharded only on batch/head axes (when the
    mesh has them -- composes with DP + TP), output sharded like q."""
    if mesh is None:
        axis_names = jax.sharding.get_abstract_mesh().axis_names
    else:
        axis_names = mesh.axis_names
    b_ax = batch_axis if batch_axis in axis_names else None
    h_ax = head_axis if head_axis in axis_names else None
    q_spec = P(b_ax, h_ax, None, None)
    cache_spec = P(b_ax, h_ax, axis_name, None)
    fn = functools.partial(sp_decode_attention_sharded,
                           axis_name=axis_name, sm_scale=sm_scale)
    kwargs = {} if mesh is None else {"mesh": mesh}
    return jax.shard_map(
        fn,
        in_specs=(q_spec, cache_spec, cache_spec, P()),
        out_specs=q_spec,
        check_vma=False, **kwargs)(q, cache_k, cache_v, jnp.asarray(pos))


# -- Ulysses (all-to-all) sequence parallelism ------------------------------

def ulysses_attention_sharded(q, k, v, axis_name: str = "seq",
                              causal: bool = False, sm_scale=None):
    """DeepSpeed-Ulysses style: all-to-all swaps seq-sharding for
    head-sharding, dense local attention (flash kernel) over the full
    sequence, then all-to-all back.  Call INSIDE shard_map with q/k/v
    seq-sharded (B, H, L/n, D); the head count must be divisible by the
    axis size."""
    axis_size = jax.lax.axis_size(axis_name)
    heads = q.shape[1]
    if heads % axis_size != 0:
        raise ValueError(
            f"ulysses_attention: heads ({heads}) must be divisible by "
            f"mesh axis '{axis_name}' size ({axis_size}); use "
            f"ring_attention for head counts smaller than the axis")
    def seq_to_heads(x):   # (B, H, L/n, D) -> (B, H/n, L, D)
        return jax.lax.all_to_all(
            x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    def heads_to_seq(x):   # (B, H/n, L, D) -> (B, H, L/n, D)
        return jax.lax.all_to_all(
            x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    out = flash_attention(
        seq_to_heads(q), seq_to_heads(k), seq_to_heads(v),
        causal=causal, sm_scale=sm_scale)
    return heads_to_seq(out)


def ulysses_attention(q, k, v, mesh=None, axis_name: str = "seq",
                      causal: bool = False, sm_scale=None):
    """mesh=None uses the ambient mesh (callers inside jax.set_mesh,
    e.g. the transformer's sp_mechanism=\"ulysses\" prefill)."""
    spec = P(None, None, axis_name, None)
    fn = functools.partial(ulysses_attention_sharded, axis_name=axis_name,
                           causal=causal, sm_scale=sm_scale)
    kwargs = {} if mesh is None else {"mesh": mesh}
    # check_vma=False: pallas_call inside shard_map can't declare varying
    # mesh axes on its ShapeDtypeStruct outputs yet
    return jax.shard_map(
        fn, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False, **kwargs)(q, k, v)
