# The gated delta rule of a Gated DeltaNet mixer: a recurrence over rows
# whose state is a matrix a head.
#
# For the rows t of one sequence, per value head (Qwen3-Next: 32 heads, S
# 128 x 128), in float32, with q_t and k_t of unit length (q_t scaled by
# d_k^-1/2), v_t, a decay g_t <= 0 and a step beta_t in (0, 1):
#
#     S   <- exp(g_t) S                      forget
#     d_t  = beta_t (v_t - S^T k_t)          what k_t should recall, less
#                                            what it recalls now
#     S   <- S + k_t d_t^T                   write
#     o_t  = S^T q_t                         read
#
# Three implementations of the same mathematics:
#
#   delta_scan_reference -- jax.numpy: a lax.scan over the rows.  The
#                           oracle, and what a few rows run.
#   delta_chunk_scan     -- the chunkwise WY form (arXiv:2412.06464): what
#                           a chunk of 64 rows does to S is solved for
#                           inside the chunk by matmuls, and S crosses the
#                           chunks in a lax.scan.  All of it is XLA's
#                           batched matmuls (a Pallas kernel for the
#                           recurrence over the chunks read slower on the
#                           chip with what lays its operands out: PERF.md
#                           section 6, PR 42).
#   delta_step           -- one row of every sequence: the decode step's
#                           update, jax.numpy or the Pallas kernel
#                           `gdn_step`, which reads a slot's head's S once
#                           and writes it once, where it lies.
#
# Inside a chunk of C rows, gamma the running sum of g from the chunk's
# first row, D[i, j] = exp(gamma_i - gamma_j) for i >= j:
#
#     A = strict_lower(diag(beta) (K K^T * D))       T = (I + A)^-1
#     W = T diag(beta) (K * e^gamma)                 U = T diag(beta) V
#
# and across chunks, S the state before the chunk:
#
#     V' = U - W S                                   the chunk's d_t
#     O  = (Q * e^gamma) S + tril(Q K^T * D) V'
#     S <- e^gamma_C S + (K * e^(gamma_C - gamma))^T V'
#
# A is strictly lower triangular, so T exists and its entries are bounded
# by what the rule itself does to a chunk.  It is solved for by forward
# substitution inside diagonal blocks of 16 rows and the blocks are merged
# pairwise by matmuls (_unit_lower_inverse).  The finite product (I - A)
# (I + A^2)(I + A^4) ... (I + A^(C/2)) is the same T on paper, and is not
# used: where a sequence's keys repeat and the head forgets slowly (beta
# k_i . k_j e^(gamma_i - gamma_j) near 1 far off the diagonal) the powers'
# entries grow as binomial coefficients, to 1e8 and more at C = 64, and
# cancel in float32 (PERF.md section 6, PR 42).  Every exponent above is
# of a decay (<= 0): nothing is divided by a decay.

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import _interpret

__all__ = ["delta_scan", "delta_scan_reference", "delta_chunk_scan",
           "delta_step", "delta_step_reference", "delta_step_takes",
           "gdn_step"]

_CHUNK = 64             # rows solved for together: T is 64 x 64
_SOLVE_ROWS = 16        # rows of a diagonal block of A solved row by row
_STEP_HEADS = 32        # heads of a decode kernel's block: 32 x 64 KiB of S
_VMEM_BYTES = 64 << 20
# T's merging products are float32 x float32, at full precision
_T_PRECISION = jax.lax.Precision.HIGHEST


def _no_mesh() -> bool:
    """A Mosaic kernel cannot be partitioned: under an ambient mesh of
    more than one device XLA's form runs."""
    mesh = jax.sharding.get_abstract_mesh()
    return mesh.empty or mesh.size == 1


def delta_step_takes(key_dim: int, value_dim: int, heads: int) -> bool:
    """Whether the kernel `gdn_step` advances a decode step's states: on
    the chip both head sizes on the 128 lanes, whole blocks of heads, no
    ambient mesh.  (Six layers of 64 slots took it 3.5 ms where XLA's
    three passes took 20.3: PERF.md section 6, PR 42.)"""
    return (heads % min(_STEP_HEADS, heads) == 0 and _no_mesh()
            and ((key_dim % 128 == 0 and value_dim % 128 == 0)
                 or _interpret()))


# -- one row ------------------------------------------------------------------

def delta_step_reference(q, k, v, g, beta, state):
    """One row of the recurrence for every sequence of a batch.  q, k (B,
    H, d_k) and v (B, H, d_v) float32, q and k of unit length (q scaled);
    g, beta (B, H) float32; state (B, H, d_k, d_v) float32.  Returns (o
    (B, H, d_v) float32, the new state).  Products and sums, no dot: a
    float32 dot on the chip is bf16 passes."""
    state = state * jnp.exp(g)[..., None, None]
    recalled = jnp.sum(state * k[..., None], axis=-2)
    delta = beta[..., None] * (v - recalled)
    state = state + k[..., None] * delta[..., None, :]
    return jnp.sum(state * q[..., None], axis=-2), state


def _step_kernel(layer_ref, q_ref, k_ref, v_ref, decay_ref, beta_ref,
                 state_ref, out_ref, new_ref, *, heads: int):
    """One (slot, block of heads) grid step: each head's S read, decayed,
    asked, written and read out, in VMEM."""
    del layer_ref
    for head in range(heads):
        k = k_ref[0, 0, :, head:head + 1]                  # (d_k, 1)
        q = q_ref[0, 0, :, head:head + 1]
        s = state_ref[0, 0, head] * decay_ref[0, head:head + 1, :]
        recalled = jnp.sum(s * k, axis=0, keepdims=True)   # (1, d_v)
        delta = beta_ref[0, head:head + 1, :] * (
            v_ref[0, head:head + 1, :] - recalled)
        s = s + k * delta
        new_ref[0, 0, head] = s
        out_ref[0, head:head + 1, :] = jnp.sum(s * q, axis=0, keepdims=True)


def gdn_step(q, k, v, g, beta, states, layer):
    """delta_step_reference over layer `layer` of a stack of layers'
    states (layers, B, H, d_k, d_v) float32, as a Pallas kernel named
    `gdn_step` in the device trace: the stack rides the call aliased, the
    layer's blocks are read once and written once where they lie, and
    nothing of S's size is staged.  Returns (o (B, H, d_v) float32, the
    stack)."""
    f32 = jnp.float32
    batch, all_heads, key_dim = q.shape
    value_dim = v.shape[-1]
    heads = min(_STEP_HEADS, all_heads)
    groups = all_heads // heads

    def columns(x):
        # (B, H, d_k) -> (B, groups, d_k, heads): a head's vector down the
        # sublanes, as it multiplies S's rows
        return x.astype(f32).reshape(batch, groups, heads,
                                     key_dim).swapaxes(2, 3)

    def rows(x):
        # (B, H) -> (B, H, d_v): a head's scalar along the lanes
        return jnp.broadcast_to(x.astype(f32)[..., None],
                                (batch, all_heads, value_dim))

    column_spec = pl.BlockSpec((1, 1, key_dim, heads),
                               lambda s, i, layer_ref: (s, i, 0, 0))
    row_spec = pl.BlockSpec((1, heads, value_dim),
                            lambda s, i, layer_ref: (s, i, 0))
    state_spec = pl.BlockSpec(
        (1, 1, heads, key_dim, value_dim),
        lambda s, i, layer_ref: (layer_ref[0], s, i, 0, 0))
    out, states = pl.pallas_call(
        functools.partial(_step_kernel, heads=heads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(batch, groups),
            in_specs=[column_spec, column_spec, row_spec, row_spec,
                      row_spec, state_spec],
            out_specs=[row_spec, state_spec]),
        out_shape=[jax.ShapeDtypeStruct((batch, all_heads, value_dim), f32),
                   jax.ShapeDtypeStruct(states.shape, f32)],
        # operand 6 (after the prefetched layer): the stack of states
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_BYTES),
        name="gdn_step",
        interpret=_interpret(),
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), columns(q), columns(k),
      v.astype(f32), rows(jnp.exp(g)), rows(beta), states)
    return out, states


def delta_step(q, k, v, g, beta, states, layer):
    """One row of every sequence against layer `layer` of the stack of
    states (layers, B, H, d_k, d_v): the kernel where delta_step_takes,
    else delta_step_reference on the layer's slice, put back in place.
    Returns (o (B, H, d_v) float32, the stack)."""
    if delta_step_takes(q.shape[-1], v.shape[-1], q.shape[1]):
        return gdn_step(q, k, v, g, beta, states, layer)
    out, state = delta_step_reference(q, k, v, g, beta, states[layer])
    return out, jax.lax.dynamic_update_index_in_dim(states, state, layer, 0)


# -- a sequence's rows ----------------------------------------------------------

def _stopped(g, beta, stop):
    """g and beta with the rows at or past `stop` made to leave the state
    alone: no decay, no write."""
    if stop is None:
        return g, beta
    live = jnp.arange(g.shape[-1]) < stop
    return jnp.where(live, g, 0.0), jnp.where(live, beta, 0.0)


def delta_scan_reference(q, k, v, g, beta, state, stop=None):
    """The oracle.  q, k (B, H, L, d_k), v (B, H, L, d_v); g, beta (B, H,
    L) float32; state (B, H, d_k, d_v) float32, S before row 0; stop
    (traced int32 or None): rows at or past it leave the state alone.
    Returns (o (B, H, L, d_v) in v's dtype, the state after row stop - 1).
    The recurrence as written, a row at a time, float32."""
    f32 = jnp.float32
    g, beta = _stopped(g.astype(f32), beta.astype(f32), stop)

    def row(state, xs):
        out, state = delta_step_reference(*xs, state)
        return state, out

    by_rows = lambda x: jnp.moveaxis(x.astype(f32), 2, 0)   # noqa: E731
    state, out = jax.lax.scan(row, state.astype(f32),
                              tuple(map(by_rows, (q, k, v, g, beta))))
    return jnp.moveaxis(out, 0, 2).astype(v.dtype), state


def _dot(equation: str, a, b):
    return jnp.einsum(equation, a, b, preferred_element_type=jnp.float32)


def _unit_lower_inverse(a):
    """(I + a)^-1 of a strictly lower triangular a (..., C, C) float32, C
    a power of two.  Up to _SOLVE_ROWS rows by forward substitution: row
    i of the inverse is e_i - a[i, :i] times the rows above it.  Larger:
    the two diagonal halves P and Q inverted so, and under them
    -Q^-1 R P^-1 for a's lower left R.  Nothing here is larger than the
    inverse's own entries."""
    size = a.shape[-1]
    if size <= _SOLVE_ROWS:
        eye = jnp.eye(size, dtype=a.dtype)

        def row(index, inverse):
            # the rows from `index` on are still zeros, and a's row
            # `index` is zeros from its diagonal on
            new = eye[index] - jnp.einsum(
                "...j,...jk->...k", a[..., index, :], inverse,
                precision=_T_PRECISION)
            return inverse.at[..., index, :].set(new)

        return jax.lax.fori_loop(0, size, row, jnp.zeros_like(a))
    half = size // 2
    exact = functools.partial(jnp.matmul, precision=_T_PRECISION)
    upper = _unit_lower_inverse(a[..., :half, :half])
    lower = _unit_lower_inverse(a[..., half:, half:])
    under = -exact(exact(lower, a[..., half:, :half]), upper)
    return jnp.concatenate([
        jnp.concatenate([upper, jnp.zeros_like(upper)], axis=-1),
        jnp.concatenate([under, lower], axis=-1)], axis=-2)


def _chunk_terms(q, k, v, g, beta, chunk: int):
    """What each chunk of `chunk` rows does, S apart: (W, U, Q e^gamma,
    tril(Q K^T * D), (K e^(gamma_C - gamma))^T, e^gamma_C), each (B, H,
    chunks, ., .) in the rows' dtype but the last, (B, H, chunks)
    float32.  A and T are float32 (_unit_lower_inverse)."""
    f32 = jnp.float32
    dtype = v.dtype
    batch, heads, length, _ = q.shape
    count = length // chunk

    def split(x):
        return x.reshape(batch, heads, count, chunk, *x.shape[3:])

    qc, kc, vc = split(q), split(k), split(v)
    beta_c = split(beta.astype(f32))
    gamma = jnp.cumsum(split(g.astype(f32)), axis=-1)       # (B, H, n, C)
    row = jnp.arange(chunk)[:, None]
    column = jnp.arange(chunk)[None, :]
    # exp of the masked exponent: above the diagonal it would overflow
    decay = jnp.exp(jnp.where(
        row >= column, gamma[..., :, None] - gamma[..., None, :], -jnp.inf))
    a = jnp.where(row > column, beta_c[..., :, None]
                  * _dot("bhnik,bhnjk->bhnij", kc, kc) * decay, 0.0)
    t = _unit_lower_inverse(a).astype(dtype)
    grown = jnp.exp(gamma)
    w = _dot("bhnij,bhnjk->bhnik", t, (
        kc.astype(f32) * (beta_c * grown)[..., None]).astype(dtype))
    u = _dot("bhnij,bhnjv->bhniv", t, (
        vc.astype(f32) * beta_c[..., None]).astype(dtype))
    scores = jnp.where(
        row >= column, _dot("bhnik,bhnjk->bhnij", qc, kc) * decay, 0.0)
    last = gamma[..., -1:]
    kept = (kc.astype(f32) * jnp.exp(last - gamma)[..., None]).astype(dtype)
    return (w.astype(dtype), u.astype(dtype),
            (qc.astype(f32) * grown[..., None]).astype(dtype),
            scores.astype(dtype), kept.swapaxes(-1, -2),
            jnp.exp(last[..., 0]))


def _chunk_recurrence(w, u, qg, scores, kept_t, decay, state):
    """S across the chunks, a lax.scan.  Returns (O (B, H, chunks, C, d_v)
    float32, the state after the last chunk)."""
    f32 = jnp.float32
    dtype = u.dtype

    def step(state, xs):
        w_c, u_c, qg_c, scores_c, kept_c, decay_c = xs
        held = state.astype(dtype)
        written = (u_c.astype(f32)
                   - _dot("bhck,bhkv->bhcv", w_c, held)).astype(dtype)
        out = (_dot("bhck,bhkv->bhcv", qg_c, held)
               + _dot("bhij,bhjv->bhiv", scores_c, written))
        state = (state * decay_c[..., None, None]
                 + _dot("bhkc,bhcv->bhkv", kept_c, written))
        return state, out

    by_chunks = lambda x: jnp.moveaxis(x, 2, 0)             # noqa: E731
    state, out = jax.lax.scan(
        step, state.astype(f32),
        tuple(map(by_chunks, (w, u, qg, scores, kept_t, decay))))
    return jnp.moveaxis(out, 0, 2), state


def delta_chunk_scan(q, k, v, g, beta, state, stop=None,
                     chunk: int | None = None):
    """delta_scan_reference's signature and returns, chunkwise: the
    chunks' own terms by batched matmuls (_chunk_terms), S across the
    chunks by a lax.scan (_chunk_recurrence).  The length is a multiple
    of `chunk`.  The matmuls' operands are of the rows' dtype, their sums
    float32; S is float32."""
    f32 = jnp.float32
    batch, all_heads, length, _ = q.shape
    g, beta = _stopped(g.astype(f32), beta.astype(f32), stop)
    out, state = _chunk_recurrence(
        *_chunk_terms(q, k, v, g, beta, chunk or _CHUNK), state)
    return out.reshape(batch, all_heads, length, -1).astype(v.dtype), state


def delta_scan(q, k, v, g, beta, state, stop=None):
    """The gated delta rule over a sequence's rows: chunkwise for a chunk
    or more (padded to whole chunks with rows that leave the state
    alone), else the oracle."""
    length = q.shape[2]
    if length < _CHUNK:
        return delta_scan_reference(q, k, v, g, beta, state, stop)
    pad = -length % _CHUNK
    if pad:
        stop = length if stop is None else stop
        long = lambda x: jnp.pad(                           # noqa: E731
            x, [(0, 0), (0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 3))
        q, k, v, g, beta = map(long, (q, k, v, g, beta))
    out, state = delta_chunk_scan(q, k, v, g, beta, state, stop)
    return out[:, :, :length], state
