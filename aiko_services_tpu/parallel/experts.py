# Routed experts: a grouped matmul over the experts a process holds.
#
# A token chooses k experts; this process holds some of them.  Every
# token-expert pair held is computed (nothing is dropped, whatever the
# batch), and an expert no token chose is never read.  The pairs are
# sorted by expert into a row buffer in which each expert's rows start on
# a tile boundary, so every row tile belongs to ONE expert; the kernel
# walks the tiles that hold rows, runs that expert's whole SwiGLU on the
# tile (gate and up, silu, down, the hidden width in slices so the
# weights stream through VMEM) and skips the rest of the buffer without
# fetching a byte for it.  A decode step's tile is as tall as the batch,
# so each expert hit is one tile and its 3 matrices are read once; a
# prefill's tiles are 256 rows, each expert's tokens a few matmuls.

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import _interpret

__all__ = ["expert_ffn", "expert_ffn_reference", "expert_ffn_takes"]

_ROW_TILE = 256        # rows of a prefill's tile
_ROW_TILE_MIN = 16     # a bf16 tile's sublanes: the shortest tile
_HIDDEN_SLICE = 512    # hidden columns of the weights in VMEM at a time
_VMEM_BYTES = 100 << 20


def expert_ffn_takes(d_model: int, hidden: int, dtype) -> bool:
    """Whether the kernel serves a call, decided by what the call is:
    bf16 or float32 weights whose widths lie on the 128 lanes (the
    interpreter takes any), and no ambient mesh -- a Mosaic kernel cannot
    be partitioned, so under a mesh the einsum oracle runs and XLA
    shards it over the "expert" axis as it does the switch FFN."""
    mesh = jax.sharding.get_abstract_mesh()
    return (jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                 jnp.dtype(jnp.float32))
            and (mesh.empty or mesh.size == 1)
            and ((d_model % 128 == 0 and hidden % 128 == 0)
                 or _interpret()))


def expert_ffn_reference(x, w_gate, w_up, w_down, experts, weights,
                         layer=None):
    """The oracle, and the path of the calls the kernel does not take:
    every expert's SwiGLU over every token, combined by each token's
    weights for the experts it chose.  Same signature and returns as
    expert_ffn; E x the work, for tests and tiny expert counts."""
    if layer is not None:
        w_gate, w_up, w_down = w_gate[layer], w_up[layer], w_down[layer]
    held = w_gate.shape[0]
    # (T, E): a token's weight for each held expert (id `held` = not held)
    combine = jnp.sum(
        jax.nn.one_hot(experts, held + 1, dtype=jnp.float32)[..., :held]
        * weights[..., None], axis=1)
    gate = jnp.einsum("td,edf->tef", x, w_gate,
                      preferred_element_type=jnp.float32)
    up = jnp.einsum("td,edf->tef", x, w_up,
                    preferred_element_type=jnp.float32)
    hidden = (jax.nn.silu(gate) * up).astype(x.dtype)
    out = jnp.einsum("tef,efd->ted", hidden, w_down,
                     preferred_element_type=jnp.float32)
    chosen = jnp.sum(jax.nn.one_hot(experts, held + 1, dtype=jnp.int32
                                    )[..., :held], axis=1)      # (T, E)
    return (jnp.einsum("ted,te->td", out, combine),
            jnp.sum(jnp.any(chosen > 0, axis=0)).astype(jnp.int32),
            jnp.sum(chosen).astype(jnp.int32))


def _kernel(tile_expert_ref, valid_ref, layer_ref, x_ref, gate_ref, up_ref,
            down_ref, o_ref, acc_ref):
    """One (row tile, hidden slice) grid step: the tile's rows through
    that slice of its expert's gate, up and down, accumulated in float32
    over the slices.  Tiles past the last that holds rows do nothing."""
    tile, piece = pl.program_id(0), pl.program_id(1)
    pieces = pl.num_programs(1)

    @pl.when(tile < valid_ref[0])
    def _compute():
        @pl.when(piece == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        x = x_ref[...]
        gate = jnp.dot(x, gate_ref[0, 0],
                       preferred_element_type=jnp.float32)
        up = jnp.dot(x, up_ref[0, 0], preferred_element_type=jnp.float32)
        hidden = (jax.nn.silu(gate) * up).astype(x.dtype)
        acc_ref[...] += jnp.dot(hidden, down_ref[0, 0],
                                preferred_element_type=jnp.float32)

        @pl.when(piece == pieces - 1)
        def _finish():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _grouped_swiglu(rows, w_gate, w_up, w_down, layer, tile_expert, valid,
                    row_tile: int):
    """rows (N, d), every `row_tile` of them one expert's: that expert's
    SwiGLU, by layer `layer` of the stacked weights (layers, E, ., .), of
    each tile among the first `valid`; (N, d) in rows' dtype."""
    count, d_model = rows.shape
    hidden = w_gate.shape[3]
    piece = _HIDDEN_SLICE if hidden % _HIDDEN_SLICE == 0 else hidden
    pieces = hidden // piece

    # a step past the last tile that holds rows names that tile's last
    # blocks again: the pipeline fetches and writes back nothing for it
    def last(tile, valid_ref):
        return jnp.minimum(tile, jnp.maximum(valid_ref[0] - 1, 0))

    def piece_of(tile, index, valid_ref):
        return jnp.where(tile < valid_ref[0], index, pieces - 1)

    def row_index(tile, index, expert_ref, valid_ref, layer_ref):
        return (last(tile, valid_ref), 0)

    def column_index(tile, index, expert_ref, valid_ref, layer_ref):
        return (layer_ref[0], expert_ref[last(tile, valid_ref)], 0,
                piece_of(tile, index, valid_ref))

    def down_index(tile, index, expert_ref, valid_ref, layer_ref):
        return (layer_ref[0], expert_ref[last(tile, valid_ref)],
                piece_of(tile, index, valid_ref), 0)

    row_spec = pl.BlockSpec((row_tile, d_model), row_index)
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(count // row_tile, pieces),
            in_specs=[row_spec,
                      pl.BlockSpec((1, 1, d_model, piece), column_index),
                      pl.BlockSpec((1, 1, d_model, piece), column_index),
                      pl.BlockSpec((1, 1, piece, d_model), down_index)],
            out_specs=row_spec,
            scratch_shapes=[pltpu.VMEM((row_tile, d_model), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(rows.shape, rows.dtype),
        # the accumulator crosses the slices, the repeated block index
        # of the skipped tiles needs the tiles in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES),
        name="moe_expert_ffn",
        interpret=_interpret(),
    )(tile_expert, valid, jnp.reshape(layer, (1,)).astype(jnp.int32), rows,
      w_gate, w_up, w_down)


def _group(experts, held: int, row_tile: int):
    """Lay the token-expert pairs out by expert.  experts (T, k) int32,
    `held` = not held here.  Returns (row_token (N,), the token each row
    of the buffer holds; pair_row (T, k), the row of each pair, 0 for
    one not held; tile_expert (N / row_tile,); valid (1,), the tiles
    that hold rows; sizes (held,), pairs per expert).  N is static:
    every pair held, plus a tile's slack for each expert."""
    tokens, choices = experts.shape
    pairs = tokens * choices
    count = -(-pairs // row_tile) * row_tile + held * row_tile
    flat = experts.reshape(pairs)
    order = jnp.argsort(flat, stable=True)
    sorted_expert = flat[order]
    sizes = jnp.zeros((held + 1,), jnp.int32).at[flat].add(1)
    tiles = -(-sizes[:held] // row_tile)
    tile_end = jnp.cumsum(tiles)
    first_row = (tile_end - tiles) * row_tile                   # (held,)
    first_pair = jnp.cumsum(sizes) - sizes                      # (held+1,)
    is_held = sorted_expert < held
    clipped = jnp.minimum(sorted_expert, held - 1)
    row = jnp.where(
        is_held,
        first_row[clipped] + jnp.arange(pairs) - first_pair[sorted_expert],
        count)                                       # out of range: dropped
    row_token = jnp.zeros((count,), jnp.int32).at[row].set(
        (order // choices).astype(jnp.int32), mode="drop")
    pair_row = jnp.zeros((pairs,), jnp.int32).at[order].set(
        jnp.where(is_held, row, 0).astype(jnp.int32))
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_end, jnp.arange(count // row_tile),
                         side="right"), held - 1).astype(jnp.int32)
    return (row_token, pair_row.reshape(tokens, choices), tile_expert,
            tile_end[-1:].astype(jnp.int32), sizes[:held])


def expert_ffn(x, w_gate, w_up, w_down, experts, weights, layer=None):
    """sum_j weights[t, j] * SwiGLU_{experts[t, j]}(x[t]) over the experts
    held.  x (T, d); w_gate, w_up (E, d, f), w_down (E, f, d) the held
    experts' weights -- or, with `layer` (an int32 scalar, traced or
    not), a stack of layers' (layers, E, ., .), of which the kernel reads
    that layer where it lies: a stack sliced by a layer scan is copied
    whole for a kernel's operand, 1.9 GB a layer at DeepSeek-V2's
    widths.  experts (T, k) int32 indexes the experts, E meaning "not
    held" (that pair adds nothing); weights (T, k) float32.  Returns
    (out (T, d) float32, distinct experts with a pair, pairs computed).
    No pair is dropped; an expert without a pair is not read."""
    held, d_model, hidden = w_gate.shape[-3:]
    if not expert_ffn_takes(d_model, hidden, w_gate.dtype):
        return expert_ffn_reference(x, w_gate, w_up, w_down, experts,
                                    weights, layer)
    if layer is None:
        w_gate, w_up, w_down, layer = w_gate[None], w_up[None], \
            w_down[None], 0
    tokens, choices = experts.shape
    # as tall as the batch while that is short: an expert's rows are
    # then one tile, and its weights are read once
    row_tile = min(_ROW_TILE, -(-tokens // _ROW_TILE_MIN) * _ROW_TILE_MIN)
    row_token, pair_row, tile_expert, valid, sizes = _group(
        experts, held, row_tile)
    computed = _grouped_swiglu(x[row_token], w_gate, w_up, w_down, layer,
                               tile_expert, valid, row_tile)
    out = jnp.zeros((tokens, d_model), jnp.float32)
    for choice in range(choices):
        # a pair not held names row 0, which nothing may have written
        out = out + jnp.where(
            experts[:, choice, None] < held,
            weights[:, choice, None]
            * computed[pair_row[:, choice]].astype(jnp.float32), 0.0)
    return out, jnp.sum(sizes > 0).astype(jnp.int32), jnp.sum(sizes)
