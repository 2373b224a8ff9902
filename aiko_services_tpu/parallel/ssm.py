# The selective scan of a Mamba mixer: a recurrence over rows.
#
# For the rows t of one sequence, per channel d of d_inner and state n of
# d_state (Jamba: 5120 x 16), in float32:
#
#     delta_t = softplus(dt_t + dt_bias)
#     S_t     = exp(delta_t * A) * S_{t-1} + (delta_t * c_t) (x) B_t
#     y_t     = S_t . C_t + D * c_t,      out_t = y_t * silu(z_t)
#
# No matmul computes it (the decay differs by channel AND state), and a
# naive scan writes S_t, 327,680 B a row a layer at Jamba's sizes, to HBM
# several times over.  Two implementations of the same mathematics:
#
#   ssm_scan_reference -- jax.numpy: an associative scan inside a chunk of
#                         rows, a lax.scan over the chunks.  The oracle,
#                         the CPU path, and what a shape the kernel
#                         refuses runs.
#   ssm_chunk_scan     -- Pallas: S held in VMEM across a block of rows and
#                         carried between blocks, blocked over channels;
#                         reads c, dt, z, B, C and writes out, nothing of
#                         S's size but the final state.
#
# The state lies (d_state, d_inner): the channels on the 128 lanes.  Held
# (d_inner, d_state), as the mixer is published, a float32 tile pads 16
# lanes to 128 and the state is eight times its size in HBM and in VMEM.

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import _interpret

__all__ = ["ssm_scan", "ssm_scan_reference", "ssm_scan_takes", "ssm_step",
           "ssm_chunk_scan", "ssm_scan_rows"]

_SCAN_ROWS = 256        # rows of a block: S crosses them in VMEM
_SCAN_CHANNELS = 512    # channels of a block: S is 16 x 512 float32, 8 vregs
_SCAN_GROUP = 8         # rows unrolled together: a float32 tile's sublanes
_SCAN_MIN_ROWS = 128    # below this the oracle: a decode step is ssm_step
_REFERENCE_CHUNK = 64   # rows of the oracle's associative scan


def _softplus(x):
    return jnp.where(x > 20.0, x, jnp.log(1.0 + jnp.exp(jnp.minimum(x, 20.0))))


def ssm_scan_takes(length: int, inner: int, states: int, dtype) -> bool:
    """Whether ssm_chunk_scan serves a scan of `length` rows, decided by
    what the call is: bf16 or float32 rows, at least _SCAN_MIN_ROWS of
    them (a decode step's one row is ssm_step's), a state whose d_state
    fills float32 sublanes, on the chip channels on the 128 lanes, and no
    ambient mesh (a Mosaic kernel cannot be partitioned)."""
    mesh = jax.sharding.get_abstract_mesh()
    return (jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                 jnp.dtype(jnp.float32))
            and length >= _SCAN_MIN_ROWS and states % 8 == 0
            and (mesh.empty or mesh.size == 1)
            and (inner % 128 == 0 or _interpret()))


def ssm_scan_rows(length: int, stop: int, kernel: bool) -> int:
    """Rows of `length` the scan runs for a sequence whose state is taken
    after row stop - 1: the kernel skips the blocks of rows past it, the
    oracle runs them all (with delta 0 there)."""
    if not kernel:
        return length
    return min(length, -(-stop // _SCAN_ROWS) * _SCAN_ROWS)


def ssm_step(c, dt, z, b, cc, a, d, dt_bias, state):
    """One row of the recurrence for every sequence of a batch: the decode
    step's update.  c, dt, z (B, d_inner); b, cc (B, d_state); a (d_state,
    d_inner), d and dt_bias (d_inner,) float32; state (B, d_state,
    d_inner) float32.  Returns (out (B, d_inner) in c's dtype, the new
    state).  Products and sums, no dot: a float32 dot on the chip is
    bf16 passes."""
    f32 = jnp.float32
    delta = _softplus(dt.astype(f32) + dt_bias)
    cf = c.astype(f32)
    state = (jnp.exp(delta[:, None, :] * a) * state
             + (delta * cf)[:, None, :] * b.astype(f32)[:, :, None])
    y = jnp.sum(state * cc.astype(f32)[:, :, None], axis=1) + d * cf
    zf = z.astype(f32)
    return (y * zf * jax.nn.sigmoid(zf)).astype(c.dtype), state


def ssm_scan_reference(c, dt, z, b, cc, a, d, dt_bias, state, stop=None,
                       chunk: int = _REFERENCE_CHUNK):
    """The oracle.  c, dt, z (B, L, d_inner); b, cc (B, L, d_state); a
    (d_state, d_inner), d, dt_bias (d_inner,) float32; state (B, d_state,
    d_inner) float32, S before row 0; stop (traced int32 or None): rows
    at or past it do not advance the state (their delta is 0).  Returns
    (out (B, L, d_inner) in c's dtype, the state after row stop - 1).
    An associative scan over each chunk of rows -- (decay, drive) pairs
    composed as affine maps of S -- and a lax.scan over the chunks, which
    carries S: chunk x d_state x d_inner float32 live at a time."""
    f32 = jnp.float32
    batch, length, inner = c.shape
    delta = _softplus(dt.astype(f32) + dt_bias)
    if stop is not None:
        delta = jnp.where(jnp.arange(length)[None, :, None] < stop, delta,
                          0.0)
    cf = c.astype(f32)
    chunk = min(chunk, length)
    pad = -length % chunk

    def chunks(x):
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        return x.reshape(batch, -1, chunk, x.shape[-1]).swapaxes(0, 1)

    def compose(left, right):
        return (left[0] * right[0], right[0] * left[1] + right[1])

    def step(state, rows):
        delta_c, drive_c, b_c, cc_c = rows
        decay = jnp.exp(delta_c[:, :, None, :] * a)        # (B, T, N, D)
        drive = drive_c[:, :, None, :] * b_c[..., None]
        decayed, driven = jax.lax.associative_scan(
            compose, (decay, drive), axis=1)
        states = decayed * state[:, None] + driven
        return states[:, -1], jnp.sum(states * cc_c[..., None], axis=2)

    state, y = jax.lax.scan(
        step, state.astype(f32),
        (chunks(delta), chunks(delta * cf), chunks(b.astype(f32)),
         chunks(cc.astype(f32))))
    y = y.swapaxes(0, 1).reshape(batch, -1, inner)[:, :length] + d * cf
    zf = z.astype(f32)
    return (y * zf * jax.nn.sigmoid(zf)).astype(c.dtype), state


def _scan_kernel(stop_ref, c_ref, dt_ref, z_ref, b_ref, cc_ref, a_ref,
                 d_ref, bias_ref, state_ref, out_ref, last_ref,
                 state, delta, drive, ys, *, rows: int):
    """One (sequence, channel block, row block) grid step; the row blocks
    of a channel block run in order and S crosses them in `state`.  A row
    block past `stop` fetches nothing new and writes zeros."""
    f32 = jnp.float32
    block = pl.program_id(2)
    first = block * rows
    stop = stop_ref[0]

    @pl.when(block == 0)
    def _start():
        state[...] = state_ref[0]

    @pl.when(first < stop)
    def _run():
        c = c_ref[0].astype(f32)                            # (rows, Dc)
        row = first + jax.lax.broadcasted_iota(jnp.int32, c.shape, 0)
        delta[...] = jnp.where(
            row < stop, _softplus(dt_ref[0].astype(f32) + bias_ref[...]),
            0.0)
        drive[...] = delta[...] * c
        a = a_ref[...]                                      # (N, Dc)
        sublane = jax.lax.broadcasted_iota(
            jnp.int32, (_SCAN_GROUP, c.shape[1]), 0)

        def group(index, s):
            start = pl.multiple_of(index * _SCAN_GROUP, _SCAN_GROUP)
            delta_g = delta[pl.ds(start, _SCAN_GROUP), :]
            drive_g = drive[pl.ds(start, _SCAN_GROUP), :]
            b_g, cc_g = b_ref[0, index], cc_ref[0, index]   # (N, group)
            tile = jnp.zeros(sublane.shape, f32)
            for t in range(_SCAN_GROUP):
                s = (jnp.exp(delta_g[t:t + 1, :] * a) * s
                     + drive_g[t:t + 1, :] * b_g[:, t:t + 1])
                y = jnp.sum(s * cc_g[:, t:t + 1], axis=0, keepdims=True)
                tile = jnp.where(sublane == t, y, tile)
            ys[pl.ds(start, _SCAN_GROUP), :] = tile
            return s

        state[...] = jax.lax.fori_loop(0, rows // _SCAN_GROUP, group,
                                       state[...])
        zf = z_ref[0].astype(f32)
        out_ref[0] = ((ys[...] + d_ref[...] * c)
                      * zf * jax.nn.sigmoid(zf)).astype(out_ref.dtype)

    @pl.when(first >= stop)
    def _skip():
        out_ref[0] = jnp.zeros(out_ref.shape[1:], out_ref.dtype)

    last_ref[0] = state[...]


def _by_groups(x):
    """(B, L, N) -> (B, L / group, N, group) float32: a group of rows'
    B (or C) with the states on the sublanes, as the kernel multiplies
    them into S; the kernel slices a row's column out statically."""
    batch, length, states = x.shape
    return x.astype(jnp.float32).reshape(
        batch, length // _SCAN_GROUP, _SCAN_GROUP, states).swapaxes(2, 3)


def ssm_chunk_scan(c, dt, z, b, cc, a, d, dt_bias, state, stop=None,
                   rows: int = _SCAN_ROWS, channels: int = _SCAN_CHANNELS):
    """ssm_scan_reference's signature and returns, as a Pallas kernel
    named `ssm_chunk_scan` in the device trace.  Blocks of `rows` rows and
    `channels` channels; lengths and widths the blocks do not divide are
    padded (rows with delta 0, channels with zeros) and cut again.  A row
    block past `stop` is not fetched and its rows of `out` are zeros."""
    batch, length, inner = c.shape
    states = b.shape[-1]
    rows = min(rows, -(-length // _SCAN_GROUP) * _SCAN_GROUP)
    channels = min(channels, inner)
    pad_rows, pad_channels = -length % rows, -inner % channels
    stop = jnp.minimum(jnp.asarray(length if stop is None else stop,
                                   jnp.int32), length)
    if pad_rows or pad_channels:
        wide = lambda x: jnp.pad(                           # noqa: E731
            x, [(0, 0)] * (x.ndim - 1) + [(0, pad_channels)])
        long = lambda x: jnp.pad(                           # noqa: E731
            x, ((0, 0), (0, pad_rows), (0, 0)))
        c, dt, z = (long(wide(x)) for x in (c, dt, z))
        b, cc = long(b), long(cc)
        a, d, dt_bias, state = (wide(x) for x in (a, d, dt_bias, state))
    padded_length, padded_inner = c.shape[1:]
    blocks = padded_length // rows

    def live(block, stop_ref):
        # a block past the last that holds a live row names that block
        # again: the pipeline fetches nothing for it
        return jnp.minimum(block, jnp.maximum(stop_ref[0] - 1, 0) // rows)

    row_spec = pl.BlockSpec(
        (1, rows, channels),
        lambda s, i, k, stop_ref: (s, live(k, stop_ref), i))
    group_spec = pl.BlockSpec(
        (1, rows // _SCAN_GROUP, states, _SCAN_GROUP),
        lambda s, i, k, stop_ref: (s, live(k, stop_ref), 0, 0))
    channel_spec = pl.BlockSpec((1, channels),
                                lambda s, i, k, stop_ref: (0, i))
    state_spec = pl.BlockSpec((1, states, channels),
                              lambda s, i, k, stop_ref: (s, 0, i))
    out, last = pl.pallas_call(
        functools.partial(_scan_kernel, rows=rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(batch, padded_inner // channels, blocks),
            in_specs=[row_spec, row_spec, row_spec, group_spec, group_spec,
                      pl.BlockSpec((states, channels),
                                   lambda s, i, k, stop_ref: (0, i)),
                      channel_spec, channel_spec, state_spec],
            out_specs=[pl.BlockSpec((1, rows, channels),
                                    lambda s, i, k, stop_ref: (s, k, i)),
                       state_spec],
            scratch_shapes=[pltpu.VMEM((states, channels), jnp.float32)]
            + [pltpu.VMEM((rows, channels), jnp.float32)] * 3),
        out_shape=[jax.ShapeDtypeStruct(c.shape, c.dtype),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        # S crosses the row blocks of a channel block, in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="ssm_chunk_scan",
        interpret=_interpret(),
    )(stop.reshape(1), c, dt, z, _by_groups(b), _by_groups(cc),
      a.astype(jnp.float32), d.astype(jnp.float32)[None],
      dt_bias.astype(jnp.float32)[None], state.astype(jnp.float32))
    return out[:, :length, :inner], last[..., :inner]


def ssm_scan(c, dt, z, b, cc, a, d, dt_bias, state, stop=None):
    """The selective scan of a sequence's rows: the kernel where
    ssm_scan_takes, else the oracle."""
    takes = ssm_scan_takes(c.shape[1], c.shape[2], b.shape[-1], c.dtype)
    scan = ssm_chunk_scan if takes else ssm_scan_reference
    return scan(c, dt, z, b, cc, a, d, dt_bias, state, stop)


# -- one row of every slot, the state where it lies ---------------------------
#
# A decode step advances every slot's S a row.  Written as ssm_step's two
# expressions over a layer's slice of the stacked leaf, XLA updates the
# slice in place in one fusion and reads what it wrote again for y in a
# second: S crosses HBM three times.  ssm_row_step is the same products and
# sums with S crossing twice, read once and written once where it lies.

__all__ += ["ssm_row_step", "ssm_stack_step", "ssm_step_takes"]

# A block is 32 slots x 512 channels of S, 1 MiB.  Read on the chip (PR 45:
# 26 layers of 32 slots x 16 x 5120, the update alone, us a layer; XLA's two
# fusions 54.2; wider blocks taken 512 channels at a time inside the
# kernel): 32 x 512 37.5, 16 x 512 37.6, 16 x 1024 37.8, 32 x 1024 38.1,
# 16 x 2560 38.0, 32 x 2560 36.6, 16 x 5120 36.2, and with float32 rows (a
# tile of 8 slots) 8 x 1024 39.6, 8 x 2560 39.2, 8 x 5120 39.8 against
# 32 x 512's 39.0.  All within 4 %: the stream of S in and out sets the
# pace, so the block that Mosaic compiles fastest (0.35 s here; 16 x 5120
# 1.7, 32 x 2560 1.5).  In the served step 32.4 us a call, 79 % of HBM.
_STEP_SLOTS = 32        # slots of a block
_STEP_CHANNELS = 512    # channels of a block: a slot's S is 8 vregs
_STEP_GROUP = 8         # slots whose y make one float32 tile
_STEP_VMEM_BYTES = 64 << 20


def ssm_step_takes(states: int, inner: int, dtype) -> bool:
    """Whether the kernel `ssm_row_step` advances a decode step's states,
    decided by what the state is: float32, a d_state that fills float32
    sublanes, on the chip channels on the 128 lanes, and no ambient mesh
    (a Mosaic kernel cannot be partitioned)."""
    mesh = jax.sharding.get_abstract_mesh()
    return (jnp.dtype(dtype) == jnp.dtype(jnp.float32) and states % 8 == 0
            and (mesh.empty or mesh.size == 1)
            and (inner % 128 == 0 or _interpret()))


def _block(size: int, most: int, unit: int) -> int:
    """The largest block of at most `most` that is whole `unit`s and
    divides `size`; `size` itself where there is none."""
    for block in range(min(most, size) // unit * unit, 0, -unit):
        if size % block == 0:
            return block
    return size


def _row_kernel(layer_ref, c_ref, dt_ref, z_ref, b_ref, cc_ref, a_ref,
                d_ref, bias_ref, state_ref, out_ref, new_ref, ys):
    """One (block of channels, block of slots) grid step: each slot's S
    read, advanced a row, written and read out for y, in VMEM."""
    del layer_ref
    f32 = jnp.float32
    slots, channels = c_ref.shape
    lanes = channels // b_ref.shape[-1]
    c = c_ref[...].astype(f32)                              # (slots, Dc)
    delta = _softplus(dt_ref[...].astype(f32) + bias_ref[...])
    drive = delta * c
    a = a_ref[...]                                          # (N, Dc)
    for first in range(0, slots, _STEP_GROUP):
        group = min(_STEP_GROUP, slots - first)
        sublane = jax.lax.broadcasted_iota(jnp.int32, (group, channels), 0)
        tile = jnp.zeros(sublane.shape, f32)
        for t in range(group):
            slot = first + t
            s = (jnp.exp(delta[slot:slot + 1] * a) * state_ref[0, slot]
                 + drive[slot:slot + 1]
                 * pltpu.repeat(b_ref[slot], lanes, axis=1))
            new_ref[0, slot] = s
            y = jnp.sum(s * pltpu.repeat(cc_ref[slot], lanes, axis=1),
                        axis=0, keepdims=True)
            tile = jnp.where(sublane == t, y, tile)
        ys[first:first + group, :] = tile
    zf = z_ref[...].astype(f32)
    out_ref[...] = ((ys[...] + d_ref[...] * c)
                    * zf * jax.nn.sigmoid(zf)).astype(out_ref.dtype)


def ssm_row_step(c, dt, z, b, cc, a, d, dt_bias, states, layer,
                 slots: int = _STEP_SLOTS, channels: int = _STEP_CHANNELS):
    """ssm_step over layer `layer` of a stack of layers' states (layers,
    B, d_state, d_inner) float32, as a Pallas kernel named `ssm_row_step`
    in the device trace: the stack rides the call aliased, the layer's
    blocks (of `slots` slots and `channels` channels, or the whole of an
    axis they do not divide) are read once and written once where they
    lie, and nothing of S's size is staged.  Returns (out (B, d_inner) in
    c's dtype, the stack)."""
    f32 = jnp.float32
    batch, inner = c.shape
    n = b.shape[-1]
    # a tile of the rows' dtype: 8 sublanes of float32, 16 of bf16
    slots = _block(batch, slots, 32 // jnp.dtype(c.dtype).itemsize)
    channels = _block(inner, channels, 128)
    lanes = min(128, channels)

    def rows(x):
        # (B, N) -> (B, N, lanes): a state's scalar along the lanes
        return jnp.broadcast_to(x.astype(f32)[..., None], (batch, n, lanes))

    row_spec = pl.BlockSpec((slots, channels),
                            lambda i, s, layer_ref: (s, i))
    lane_spec = pl.BlockSpec((slots, n, lanes),
                             lambda i, s, layer_ref: (s, 0, 0))
    channel_spec = pl.BlockSpec((1, channels),
                                lambda i, s, layer_ref: (0, i))
    state_spec = pl.BlockSpec(
        (1, slots, n, channels),
        lambda i, s, layer_ref: (layer_ref[0], s, 0, i))
    out, states = pl.pallas_call(
        _row_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(inner // channels, batch // slots),
            in_specs=[row_spec, row_spec, row_spec, lane_spec, lane_spec,
                      pl.BlockSpec((n, channels),
                                   lambda i, s, layer_ref: (0, i)),
                      channel_spec, channel_spec, state_spec],
            out_specs=[row_spec, state_spec],
            scratch_shapes=[pltpu.VMEM((slots, channels), f32)]),
        out_shape=[jax.ShapeDtypeStruct(c.shape, c.dtype),
                   jax.ShapeDtypeStruct(states.shape, f32)],
        # operand 9 (after the prefetched layer): the stack of states
        input_output_aliases={9: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_STEP_VMEM_BYTES),
        name="ssm_row_step",
        interpret=_interpret(),
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), c, dt, z, rows(b),
      rows(cc), a.astype(f32), d.astype(f32)[None],
      dt_bias.astype(f32)[None], states)
    return out, states


def ssm_stack_step(c, dt, z, b, cc, a, d, dt_bias, states, layer):
    """One row of every sequence against layer `layer` of the stack of
    states (layers, B, d_state, d_inner): the kernel where ssm_step_takes,
    else ssm_step on the layer's slice, put back in place.  Returns (out
    (B, d_inner) in c's dtype, the stack)."""
    if ssm_step_takes(b.shape[-1], c.shape[-1], states.dtype):
        return ssm_row_step(c, dt, z, b, cc, a, d, dt_bias, states, layer)
    out, state = ssm_step(c, dt, z, b, cc, a, d, dt_bias, states[layer])
    return out, jax.lax.dynamic_update_index_in_dim(states, state, layer, 0)
