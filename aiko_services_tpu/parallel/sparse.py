# InfLLM-v2 block selection: attention that SELECTS the K/V blocks it reads.
#
# A position leaves behind its K/V row and, a K/V head, a share in the
# COMPRESSED keys: Kc_j = mean(K[stride j : stride j + kernel]), defined
# once position stride j + kernel - 1 is written.  The query at position t
# of head h scores them, p_h = softmax_j(q_h . Kc_j / sqrt(d)) over the
# defined j; the heads of a K/V group share one choice, r_g[j] = sum_{h in
# g} p_h[j]; a block b (positions block b .. block b + block - 1) scores
# R_g[b] = max r_g[j] over the j whose span overlaps it; chosen are the
# first `init` blocks, the `local` blocks that end with the query's own,
# and the highest R_g of the rest until `topk` are chosen in all (ties to
# the lower block).  The query attends, softmax at 1 / sqrt(d) in float32,
# over the positions u <= t of the chosen blocks.  A query at a position
# under `dense_len` attends over every u <= t.
#
# The selection is XLA's (named scope `sparse_select`): a matmul against
# the compressed keys, a softmax, a max over five neighbours, a top-k.  The
# attention (named scope `sparse_attention`) is
#   - of a decode step, the paged kernel (parallel/attention.py) over the
#     pool seen a K/V head a page, (layers, blocks x heads, 1, block, d),
#     given a table a (slot, K/V head) of the chosen blocks in ascending
#     order: the bytes it moves are the chosen blocks';
#   - of a whole prefill, a query tile at a time over the live tiles past
#     dense_len: the first blocks and the tile's run of local windows read
#     where they lie, the picked blocks gathered, one softmax over all.

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

__all__ = ["SparseSizes", "compress_keys", "block_scores", "choose_blocks",
           "sparse_prefill_attention", "sparse_tile", "decode_tables",
           "due_compressed", "completed_key", "blocks_read"]

_NEG_INF = -jnp.inf
_TILE = 128             # query rows of a whole prefill's selection tile


class SparseSizes(NamedTuple):
    block: int          # positions of a block: the pool's block
    kernel: int         # positions a compressed key averages
    stride: int         # positions between two compressed keys
    topk: int           # blocks a query reads
    init: int           # the first blocks, always read
    local: int          # the last blocks, the query's own among them
    dense_len: int      # positions under it attend over everything

    @property
    def per_block(self) -> int:
        """Compressed keys a block owns (those that start in it)."""
        return self.block // self.stride

    @property
    def overlap(self) -> int:
        """Compressed keys of the block before that reach into a block."""
        return self.kernel // self.stride - 1

    def width(self) -> int:
        """Entries of a decode step's table a (slot, K/V head): the chosen
        blocks, or every block of a context under dense_len."""
        return max(self.topk, -(-self.dense_len // self.block))

    def check(self) -> None:
        if (self.block % self.stride or self.kernel % self.stride
                or self.kernel > self.block
                or self.init + self.local > self.topk
                or self.dense_len < self.topk * self.block
                or self.dense_len % self.block):
            raise ValueError(
                f"block selection needs stride | block, stride | kernel <= "
                f"block, init + local <= topk and dense_len a multiple of "
                f"block >= topk x block, got {self}")


def compress_keys(k, sizes: SparseSizes):
    """k (..., L, d), L a multiple of the stride -> the compressed keys
    (..., L // stride, d) in k's dtype, entry j the mean of rows stride j
    .. stride j + kernel - 1 taken in float32; the last kernel / stride -
    1 entries, whose rows would run past L, average the rows there are
    (they are not defined yet, and no query may read them)."""
    *lead, length, depth = k.shape
    parts = sizes.kernel // sizes.stride
    sums = jnp.sum(k.astype(jnp.float32).reshape(
        *lead, length // sizes.stride, sizes.stride, depth), axis=-2)
    padded = jnp.pad(sums, [(0, 0)] * len(lead) + [(0, parts - 1), (0, 0)])
    count = sums.shape[-2]
    total = sum(padded[..., part:part + count, :] for part in range(parts))
    return (total / sizes.kernel).astype(k.dtype)


def block_scores(q, compressed, positions, sizes: SparseSizes):
    """R of the queries q (B, H, T, d) at `positions` (B, T) against the
    compressed keys (B, G, J, d), J a multiple of per_block: (B, G, T, J
    // per_block) float32, -inf for a block no defined compressed key
    overlaps."""
    f32 = jnp.float32
    batch, heads, rows, depth = q.shape
    groups, count = compressed.shape[1], compressed.shape[2]
    scores = jnp.einsum(
        "bgrtd,bgjd->bgrtj", q.reshape(batch, groups, heads // groups, rows,
                                       depth), compressed,
        preferred_element_type=f32) * (depth ** -0.5)
    defined = (jnp.arange(count) * sizes.stride + sizes.kernel - 1
               <= positions[..., None])                     # (B, T, J)
    scores = jnp.where(defined[:, None, None], scores, _NEG_INF)
    top = jnp.max(scores, axis=-1, keepdims=True)
    weights = jnp.where(defined[:, None, None],
                        jnp.exp(scores - jnp.where(top == _NEG_INF, 0.0,
                                                   top)), 0.0)
    total = jnp.sum(weights, axis=-1, keepdims=True)
    shared = jnp.sum(weights / jnp.where(total == 0.0, 1.0, total), axis=2)
    shared = jnp.where(defined[:, None], shared, _NEG_INF)  # (B, G, T, J)
    per = sizes.per_block
    owned = shared.reshape(batch, groups, rows, count // per, per)
    best = jnp.max(owned, axis=-1)
    if sizes.overlap:
        # the compressed keys that start in the block before and reach in
        reach = jnp.max(owned[..., per - sizes.overlap:], axis=-1)
        before = jnp.concatenate(
            [jnp.full_like(reach[..., :1], _NEG_INF), reach[..., :-1]],
            axis=-1)
        best = jnp.maximum(best, before)
    return best


def rank_blocks(scores, positions, sizes: SparseSizes):
    """The `topk` blocks each query at `positions` (B, T) >= dense_len
    reads, by block_scores' R (B, G, T, blocks): (B, G, T, topk) int32 as
    the ranking has them: the init + local forced blocks first (lax.top_k
    puts equal values in ascending order of index), then the best of the
    rest, best first."""
    blocks = scores.shape[-1]
    index = jnp.arange(blocks)
    own = (positions // sizes.block)[:, None, :, None]      # (B, 1, T, 1)
    forced = (index < sizes.init) | ((index > own - sizes.local)
                                     & (index <= own))
    ranked = jnp.where(forced, jnp.inf, scores)
    ranked = jnp.where(index <= own, ranked, _NEG_INF)
    # (a table of fewer blocks than topk holds no context that selects)
    _, chosen = jax.lax.top_k(ranked, min(sizes.topk, blocks))
    return jnp.pad(chosen.astype(jnp.int32),
                   [(0, 0)] * 3 + [(0, sizes.topk - chosen.shape[-1])])


def choose_blocks(scores, positions, sizes: SparseSizes):
    """rank_blocks' blocks in ascending order, the query's own block
    last: what a decode step's table holds."""
    return jnp.sort(rank_blocks(scores, positions, sizes), axis=-1)


def blocks_read(positions, sizes: SparseSizes):
    """Blocks a K/V head's query at each of `positions` reads (numpy or
    jax integers): topk from dense_len on, else every block up to its
    own."""
    live = positions // sizes.block + 1
    return (positions >= sizes.dense_len) * sizes.topk + (
        positions < sizes.dense_len) * live


def _select(q, compressed, positions, sizes: SparseSizes, ranked=False):
    with jax.named_scope("sparse_select"):
        return (rank_blocks if ranked else choose_blocks)(
            block_scores(q, compressed, positions, sizes), positions, sizes)


# -- a whole prefill ------------------------------------------------------------

def _tile_attention(q, k, v, picked, start, sizes: SparseSizes):
    """The queries q (G, R, T, d) of the tile of rows start .. start + T -
    1 (past dense_len; T within a block or whole blocks) over the blocks
    they chose, in one softmax over three runs of rows of k, v (G, L, d):
    the first `init` blocks, whole; the tile's local windows, which are one
    run of local + T / block - 1 blocks that ends with the tile's own,
    sliced out and masked a row (u <= t, and not before the row's own
    window); and the `picked` blocks (G, T, topk - init - local), the only
    ones gathered, which lie before every window and are seen whole."""
    f32 = jnp.float32
    groups, _, rows, depth = q.shape
    block, scale = sizes.block, depth ** -0.5
    positions = start + jnp.arange(rows)
    first = sizes.init * block
    span = (sizes.local + -(-rows // block) - 1) * block
    window = (start // block - sizes.local + 1) * block
    local_k, local_v = (jax.lax.dynamic_slice_in_dim(x, window, span, 1)
                        for x in (k, v))
    take = jax.vmap(lambda leaf, index: leaf[index])
    blocks = lambda x: x.reshape(groups, -1, block, depth)    # noqa: E731
    far_k, far_v = (take(blocks(x), picked).reshape(groups, rows, -1, depth)
                    for x in (k, v))
    at = window + jnp.arange(span)
    seen = (at[None, :] <= positions[:, None]) & (
        at[None, :] // block > (positions // block)[:, None] - sizes.local)
    scores = jnp.concatenate([
        jnp.einsum("grtd,gud->grtu", q, k[:, :first],
                   preferred_element_type=f32),
        jnp.where(seen, jnp.einsum("grtd,gud->grtu", q, local_k,
                                   preferred_element_type=f32), -jnp.inf),
        jnp.einsum("grtd,gtud->grtu", q, far_k,
                   preferred_element_type=f32)], axis=-1) * scale
    weights = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    ends = (first, first + span)
    return (jnp.einsum("grtu,gud->grtd", weights[..., :ends[0]],
                       v[:, :first])
            + jnp.einsum("grtu,gud->grtd", weights[..., ends[0]:ends[1]],
                         local_v)
            + jnp.einsum("grtu,gtud->grtd", weights[..., ends[1]:], far_v))


def sparse_tile(length: int, sizes: SparseSizes) -> int:
    """Query rows of a selection tile of a prefill of `length` rows: _TILE
    where it divides dense_len and the rows past it."""
    return math.gcd(_TILE, length - sizes.dense_len, sizes.dense_len)


def sparse_prefill_attention(q, k, v, sizes: SparseSizes, live=None):
    """The rows at positions >= dense_len of a causal prefill from
    position 0 of ONE sequence: q (1, H, L, d), k, v (1, G, L, d) ->
    ((1, H, L, d) whose rows under dense_len are zeros (they attend
    densely: the caller's), the compressed keys (1, G, J, d) of the
    sequence padded to whole blocks).  A query tile at a time: selection
    against the compressed keys of the whole sequence (only the defined
    ones count), then one softmax over the first blocks, the tile's run of
    local windows and the picked blocks, gathered (_tile_attention).  `live`
    (traced; None: every row) is the prompt's true length: the tiles past
    it are not run and come back zeros."""
    _, heads, length, depth = q.shape
    groups = k.shape[1]
    pad = -length % sizes.block
    if pad:
        k, v = (jnp.pad(x, [(0, 0), (0, 0), (0, pad), (0, 0)])
                for x in (k, v))
    compressed = compress_keys(k, sizes)                    # (1, G, J, d)
    if length <= sizes.dense_len:
        return jnp.zeros_like(q), compressed
    tile = sparse_tile(length, sizes)
    forced = sizes.init + sizes.local
    grouped = q.reshape(1, groups, heads // groups, length, depth)

    def one(index, out):
        start = index * tile
        positions = start + jnp.arange(tile)
        rows = jax.lax.dynamic_slice_in_dim(grouped, start, tile, 3)
        ranked = _select(rows.reshape(1, heads, tile, depth), compressed,
                         positions[None], sizes, ranked=True)
        with jax.named_scope("sparse_attention"):
            part = _tile_attention(rows[0], k[0], v[0],
                                   ranked[0, ..., forced:], start, sizes)
        return jax.lax.dynamic_update_slice_in_dim(
            out, part[None].astype(out.dtype), start, 3)

    end = length if live is None else jnp.minimum(live, length)
    out = jax.lax.fori_loop(
        sizes.dense_len // tile, -(-end // tile), one,
        jnp.zeros(grouped.shape, q.dtype))
    return out.reshape(q.shape), compressed


# -- a decode step ----------------------------------------------------------------

def due_compressed(positions, sizes: SparseSizes):
    """For the slots whose new row is at `positions` (S,): whether that
    row completes a compressed key, and which (its index; 0 where none)."""
    due = ((positions + 1) % sizes.stride == 0) & (
        positions >= sizes.kernel - 1)
    index = jnp.where(due, (positions - (sizes.kernel - 1)) // sizes.stride,
                      0)
    return due, index


def completed_key(pool_k, layer, tables, positions, sizes: SparseSizes):
    """The compressed key that the rows ending at `positions` (S,) make,
    a K/V head: (S, G, 1, d) in the pool's dtype, the float32 mean of the
    slot's `kernel` newest rows, read from the one or two blocks that
    hold them.  Garbage where the slot's row completes none."""
    block = sizes.block
    first = jnp.maximum(positions - (sizes.kernel - 1), 0)
    held = jnp.stack([first // block, positions // block], axis=1)
    pages = jnp.take_along_axis(tables, held, axis=1)       # (S, 2)
    rows = pool_k[layer, pages]                # (S, 2, G, block, d)
    slots, _, groups, _, depth = rows.shape
    rows = rows.transpose(0, 2, 1, 3, 4).reshape(slots, groups, 2 * block,
                                                 depth)
    # rows offset .. offset + kernel - 1 of [first block ; last block]: the
    # last is the first again where one block holds them all
    offset = first % block
    at = jnp.arange(2 * block)[None, :]
    mean = ((at >= offset[:, None]) & (at < offset[:, None] + sizes.kernel)
            ).astype(jnp.float32) / sizes.kernel
    return jnp.einsum("su,sgud->sgd", mean, rows.astype(jnp.float32)
                      )[:, :, None].astype(pool_k.dtype)


def decode_tables(q, pool_compressed, layer, tables, positions,
                  sizes: SparseSizes):
    """What the paged kernel takes to attend a decode step's queries q (S,
    H, 1, d) at `positions` (S,) over the blocks they choose, the pool
    seen a K/V head a page (page = block x G + head): (tables (S x G,
    width) int32 of pages in ascending order of position, positions (S x
    G,) int32 of the query among its table's rows).  A slot under
    dense_len names every block up to its own."""
    slots, max_blocks = tables.shape
    groups = pool_compressed.shape[2]
    width = sizes.width()
    held = pool_compressed[layer, tables]      # (S, MB, G, per, d)
    compressed = held.transpose(0, 2, 1, 3, 4).reshape(
        slots, groups, max_blocks * sizes.per_block, -1)
    chosen = _select(q, compressed, positions[:, None], sizes)[:, :, 0]
    own = positions // sizes.block
    every = jnp.minimum(jnp.arange(width), max_blocks - 1)
    sparse = (positions >= sizes.dense_len)[:, None, None]
    chosen = jnp.pad(chosen, [(0, 0), (0, 0), (0, width - sizes.topk)])
    logical = jnp.where(sparse, chosen, every[None, None])  # (S, G, width)
    count = jnp.broadcast_to(
        jnp.where(sparse[:, :, 0], sizes.topk, (own + 1)[:, None]),
        (slots, groups))
    pages = jnp.take_along_axis(
        jnp.broadcast_to(tables[:, None], (slots, groups, max_blocks)),
        logical, axis=2) * groups + jnp.arange(groups)[None, :, None]
    at = (count - 1) * sizes.block + (positions % sizes.block)[:, None]
    return (pages.reshape(slots * groups, width).astype(jnp.int32),
            at.reshape(slots * groups).astype(jnp.int32))
