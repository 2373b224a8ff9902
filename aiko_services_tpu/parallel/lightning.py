# Lightning (linear) attention: a recurrence over rows whose state is a
# matrix a head and whose decay is FIXED a head.
#
# For the rows t of one sequence, per head (MiniCPM-SALA: 32 heads, S
# 128 x 128), in float32, with q_t (scaled by d^-1/2), k_t, v_t and the
# head's decay lambda in (0, 1):
#
#     S_t = lambda S_{t-1} + k_t^T v_t           forget, write
#     o_t = q_t S_t                               read
#
# No softmax, no convolution, nothing left behind but S.  The family's
# fixed slopes: lambda_h = exp(-2^(-8 h / H)), h = 1 .. H (decay_rates).
#
# Three implementations of the same mathematics:
#
#   lightning_scan_reference -- jax.numpy: a lax.scan over the rows.  The
#                               oracle, and what a few rows run.
#   lightning_chunk_scan     -- chunkwise: inside a chunk of C rows
#                               (Q K^T * D) V with D[i, j] = lambda^(i-j)
#                               for j <= i, between chunks the carried S.
#                               All of it XLA's batched matmuls, under the
#                               named scope `lightning_chunk_scan`.
#   lightning_step           -- one row of every sequence: the decode
#                               step's update, jax.numpy or the Pallas
#                               kernel `lightning_step`, which reads a
#                               slot's head's S once and writes it once,
#                               where it lies in the stacked leaf.
#
# A row at or past `stop` (a whole prefill's true length) leaves the state
# alone: its decay is 1 and its key zero, so the exponents below are
# running sums of a row's own log-decay, gamma, as in parallel/delta.py,
# and D[i, j] = exp(gamma_i - gamma_j).  Every exponent is of a decay
# (<= 0): nothing is divided by a decay.

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import _interpret
from .delta import _no_mesh

__all__ = ["decay_rates", "lightning_scan", "lightning_scan_reference",
           "lightning_chunk_scan", "lightning_step",
           "lightning_step_reference", "lightning_step_takes",
           "lightning_row_step"]

_CHUNK = 128            # rows of a chunk: D is 128 x 128 a head
_STEP_HEADS = 32        # heads of a decode kernel's block: 32 x 64 KiB of S
_VMEM_BYTES = 64 << 20
# S is float32 and so are the products that read and write it
_S_PRECISION = jax.lax.Precision.HIGHEST


def decay_rates(heads: int):
    """log(lambda_h) (heads,) float32 of the Lightning Attention family's
    fixed slopes: lambda_h = exp(-2^(-8 h / heads)), h = 1 .. heads."""
    h = jnp.arange(1, heads + 1, dtype=jnp.float32)
    return -jnp.exp2(-8.0 * h / heads)


def lightning_step_takes(head_dim: int, heads: int) -> bool:
    """Whether the kernel `lightning_step` advances a decode step's
    states: on the chip the head on the 128 lanes, whole blocks of heads,
    no ambient mesh."""
    return (heads % min(_STEP_HEADS, heads) == 0 and _no_mesh()
            and (head_dim % 128 == 0 or _interpret()))


# -- one row ------------------------------------------------------------------

def lightning_step_reference(q, k, v, log_decay, state):
    """One row of the recurrence for every sequence of a batch.  q, k, v
    (B, H, d) float32 (q scaled); log_decay (H,) float32; state (B, H, d,
    d) float32.  Returns (o (B, H, d) float32, the new state).  Products
    and sums, no dot: a float32 dot on the chip is bf16 passes."""
    state = (state * jnp.exp(log_decay)[:, None, None]
             + k[..., None] * v[..., None, :])
    return jnp.sum(state * q[..., None], axis=-2), state


def _step_kernel(layer_ref, q_ref, k_ref, v_ref, decay_ref, state_ref,
                 out_ref, new_ref, *, heads: int):
    """One (slot, block of heads) grid step: each head's S read, decayed,
    written to and read out, in VMEM."""
    del layer_ref
    for head in range(heads):
        k = k_ref[0, 0, :, head:head + 1]                  # (d, 1)
        q = q_ref[0, 0, :, head:head + 1]
        s = (state_ref[0, 0, head] * decay_ref[0, head:head + 1, :]
             + k * v_ref[0, head:head + 1, :])
        new_ref[0, 0, head] = s
        out_ref[0, head:head + 1, :] = jnp.sum(s * q, axis=0, keepdims=True)


def lightning_row_step(q, k, v, log_decay, states, layer):
    """lightning_step_reference over layer `layer` of a stack of layers'
    states (layers, B, H, d, d) float32, as a Pallas kernel named
    `lightning_step` in the device trace: the stack rides the call
    aliased, the layer's blocks are read once and written once where they
    lie.  Returns (o (B, H, d) float32, the stack)."""
    f32 = jnp.float32
    batch, all_heads, dim = q.shape
    heads = min(_STEP_HEADS, all_heads)
    groups = all_heads // heads

    def columns(x):
        # (B, H, d) -> (B, groups, d, heads): a head's vector down the
        # sublanes, as it multiplies S's rows
        return x.astype(f32).reshape(batch, groups, heads,
                                     dim).swapaxes(2, 3)

    decay = jnp.broadcast_to(jnp.exp(log_decay.astype(f32))[None, :, None],
                             (batch, all_heads, dim))
    column_spec = pl.BlockSpec((1, 1, dim, heads),
                               lambda s, i, layer_ref: (s, i, 0, 0))
    row_spec = pl.BlockSpec((1, heads, dim),
                            lambda s, i, layer_ref: (s, i, 0))
    state_spec = pl.BlockSpec(
        (1, 1, heads, dim, dim),
        lambda s, i, layer_ref: (layer_ref[0], s, i, 0, 0))
    out, states = pl.pallas_call(
        functools.partial(_step_kernel, heads=heads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(batch, groups),
            in_specs=[column_spec, column_spec, row_spec, row_spec,
                      state_spec],
            out_specs=[row_spec, state_spec]),
        out_shape=[jax.ShapeDtypeStruct((batch, all_heads, dim), f32),
                   jax.ShapeDtypeStruct(states.shape, f32)],
        # operand 5 (after the prefetched layer): the stack of states
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_BYTES),
        name="lightning_step",
        interpret=_interpret(),
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), columns(q), columns(k),
      v.astype(f32), decay, states)
    return out, states


def lightning_step(q, k, v, log_decay, states, layer):
    """One row of every sequence against layer `layer` of the stack of
    states (layers, B, H, d, d): the kernel where lightning_step_takes,
    else lightning_step_reference on the layer's slice, put back in
    place.  Returns (o (B, H, d) float32, the stack)."""
    if lightning_step_takes(q.shape[-1], q.shape[1]):
        return lightning_row_step(q, k, v, log_decay, states, layer)
    with jax.named_scope("lightning_step"):
        out, state = lightning_step_reference(q, k, v, log_decay,
                                              states[layer])
        return out, jax.lax.dynamic_update_index_in_dim(states, state,
                                                        layer, 0)


# -- a sequence's rows ----------------------------------------------------------

def _row_decays(log_decay, length: int, stop):
    """(H, L) float32: a row's log-decay, 0 for the rows at or past
    `stop`; and (L,) bool, the rows that write."""
    live = (jnp.ones((length,), bool) if stop is None
            else jnp.arange(length) < stop)
    return jnp.where(live[None, :], log_decay.astype(jnp.float32)[:, None],
                     0.0), live


def lightning_scan_reference(q, k, v, log_decay, state, stop=None):
    """The oracle.  q, k, v (B, H, L, d), q scaled; log_decay (H,)
    float32; state (B, H, d, d) float32, S before row 0; stop (traced
    int32 or None): rows at or past it leave the state alone.  Returns (o
    (B, H, L, d) in v's dtype, the state after row stop - 1).  The
    recurrence as written, a row at a time, float32."""
    f32 = jnp.float32
    g, live = _row_decays(log_decay, q.shape[2], stop)

    def row(state, xs):
        q_t, k_t, v_t, g_t, live_t = xs
        state = (state * jnp.exp(g_t)[:, None, None]
                 + jnp.where(live_t, k_t[..., None] * v_t[..., None, :],
                             0.0))
        return state, jnp.sum(state * q_t[..., None], axis=-2)

    by_rows = lambda x: jnp.moveaxis(x.astype(f32), 2, 0)   # noqa: E731
    state, out = jax.lax.scan(
        row, state.astype(f32),
        (*map(by_rows, (q, k, v)), g.T, live))
    return jnp.moveaxis(out, 0, 2).astype(v.dtype), state


def _dot(equation: str, a, b, precision=None):
    return jnp.einsum(equation, a, b, precision=precision,
                      preferred_element_type=jnp.float32)


def lightning_chunk_scan(q, k, v, log_decay, state, stop=None,
                         chunk: int | None = None):
    """lightning_scan_reference's signature and returns, chunkwise.  The
    length is a multiple of `chunk`.  Inside a chunk the scores Q K^T, of
    operands in the rows' dtype summed in float32, are weighted by D in
    float32 and multiply V in the rows' dtype; what a chunk adds to S, and
    what S gives a chunk's rows, are float32 products at full precision;
    S crosses the chunks in a lax.scan."""
    f32 = jnp.float32
    chunk = chunk or _CHUNK
    batch, heads, length, dim = q.shape
    count = length // chunk
    dtype = v.dtype
    g, live = _row_decays(log_decay, length, stop)
    k = jnp.where(live[:, None], k, jnp.zeros((), k.dtype))

    def split(x):
        return x.reshape(*x.shape[:-2], count, chunk, x.shape[-1])

    qc, kc, vc = split(q), split(k), split(v)
    gamma = jnp.cumsum(g.reshape(heads, count, chunk), axis=-1)  # (H, n, C)
    row = jnp.arange(chunk)[:, None]
    column = jnp.arange(chunk)[None, :]
    # exp of the masked exponent: above the diagonal it would overflow
    weights = jnp.exp(jnp.where(
        row >= column, gamma[..., :, None] - gamma[..., None, :], -jnp.inf))
    scores = (_dot("bhnik,bhnjk->bhnij", qc, kc) * weights).astype(dtype)
    within = _dot("bhnij,bhnjv->bhniv", scores, vc)
    last = gamma[..., -1:]                                       # (H, n, 1)
    # what a chunk adds to S: sum_j lambda^(C - 1 - j) k_j^T v_j
    kept = kc.astype(f32) * jnp.exp(last - gamma)[..., None]
    added = _dot("bhnjk,bhnjv->bhnkv", kept, vc.astype(f32), _S_PRECISION)

    def step(state, xs):
        added_c, decay_c = xs
        return state * decay_c[:, None, None] + added_c, state

    state, before = jax.lax.scan(
        step, state.astype(f32),
        (jnp.moveaxis(added, 2, 0), jnp.moveaxis(jnp.exp(last[..., 0]), 1,
                                                 0)))
    before = jnp.moveaxis(before, 0, 2)                   # (B, H, n, d, d)
    grown = qc.astype(f32) * jnp.exp(gamma)[..., None]
    out = within + _dot("bhnik,bhnkv->bhniv", grown, before, _S_PRECISION)
    return out.reshape(batch, heads, length, dim).astype(dtype), state


def lightning_scan(q, k, v, log_decay, state, stop=None):
    """The recurrence over a sequence's rows: chunkwise for a chunk or
    more (padded to whole chunks with rows that leave the state alone),
    else the oracle."""
    length = q.shape[2]
    with jax.named_scope("lightning_chunk_scan"):
        if length < _CHUNK:
            return lightning_scan_reference(q, k, v, log_decay, state, stop)
        pad = -length % _CHUNK
        if pad:
            stop = length if stop is None else stop
            q, k, v = (jnp.pad(x, [(0, 0), (0, 0), (0, pad), (0, 0)])
                       for x in (q, k, v))
        out, state = lightning_chunk_scan(q, k, v, log_decay, state, stop)
        return out[:, :, :length], state
