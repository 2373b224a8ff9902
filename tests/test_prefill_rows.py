"""A whole prefill does the work of its prompt, not of its bucket (PR 38).

paged_prefill runs every layer's row-wise work over the row tiles up to
`true_len` and leaves the bucket's other rows undone, in the one program a
bucket has (transformer._row_tiles, a loop with a traced trip count), and
runs the head at the one position whose logits are read.  Held here, on
the CPU with the kernels interpreted and the row tile cut to 8 rows of a
32-row bucket, against the same call traced whole (the tile patched over
the bucket: what every bucket under two tiles still traces):

  - the first token, every row below true_len of every pool leaf, and the
    token a decode step then gives, for a dense, an int8-cache, a latent +
    routed and a looped configuration, and for the two hybrids (ISSUE 44):
    Mamba layers among attention layers, and Gated DeltaNet layers with
    routed experts, whose recurrent layers run by row tiles too, the
    convolution's tail and the state carried from tile to tile -- the
    slot's state is the whole bucket's at lengths on, just past and just
    short of a tile's edge (the convolution's three rows on both sides);
  - rows of dead tiles zero and finite in every leaf;
  - the live rows bit for bit against the SAME program with every tile
    live (true_len = the bucket): a dead tile changes no live row.  Against
    the whole trace they agree to rounding, not bitwise: XLA's CPU dot
    rounds a row's products differently by how many rows it is given;
  - one executable a bucket whatever the length, and no live-rows trace
    where the call is not a whole prefill of at least two tiles.
"""

import dataclasses

import jax
import numpy as np
import pytest

from aiko_services_tpu.models import (
    TransformerConfig, forward, init_cache, init_paged_pool, init_params,
    paged_decode_step, paged_prefill, prefill_attention_rows, prefill_rows,
    transformer)
from aiko_services_tpu.parallel import attention
from test_parallel import pallas_calls

TILE, BUCKET, BLOCK = 8, 32, 4
LENGTHS = (1, TILE - 1, TILE, TILE + 1, BUCKET - 1, BUCKET)
# of a model with a recurrent state: at a tile's edge k, one and three rows
# past it and one row short of it, for k = 1 and 2 (two and one live tiles
# short of the bucket), at the third edge, and the bucket
EDGES = tuple(k * TILE + more for k in (1, 2) for more in (0, 1, 3, -1))
HYBRID_LENGTHS = EDGES + (3 * TILE, BUCKET)

DENSE = dict(vocab_size=97, d_model=32, n_layers=2, n_heads=4,
             n_kv_heads=2, d_ff=64, max_seq_len=64, dtype="float32")
CASES = {
    "dense": {},
    "int8_cache": {"kv_dtype": "int8"},
    # DeepSeek-V2's layer, tests/test_transformer.py's toy: a leading
    # dense layer apart from the scanned stack, two of the four groups'
    # experts held
    "latent_routed": {
        "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 8,
        "qk_rope_head_dim": 4, "v_head_dim": 8, "rope_factor": 40.0,
        "rope_original_max": 8, "rope_mscale": 0.707,
        "rope_mscale_all_dim": 0.707, "top_k": 2, "n_routed_experts": 8,
        "experts_held": (2, 6), "n_shared_experts": 1, "moe_d_ff": 16,
        "n_groups": 4, "topk_groups": 2, "routed_scaling": 4.0,
        "first_dense_layers": 1},
    # Ouro's: the stack run twice, sandwich norms, the exit gate
    "looped": {"ut_steps": 2, "sandwich_norm": True,
               "exit_threshold": 0.6},
    # Jamba's: runs of 1, 1 and 2 layers, no rotary, 4 taps
    "mamba_hybrid": {
        "n_layers": 4, "n_kv_heads": 1, "rotary": False,
        "layer_kinds": ("mamba", "attention", "mamba", "mamba"),
        "ssm_d_inner": 64, "ssm_d_state": 8, "ssm_d_conv": 4,
        "ssm_dt_rank": 4},
    # Qwen3-Next's: runs of 2, 1 and 1 layers, every FFN routed experts
    # (all held) and a gated shared expert, gated attention of 16-wide
    # heads a quarter of which rotates, 2 key heads serving 4 value heads
    "delta_hybrid": {
        "n_layers": 4, "n_kv_heads": 1,
        "layer_kinds": ("delta", "delta", "attention", "delta"),
        "top_k": 2, "n_routed_experts": 8, "n_shared_experts": 1,
        "moe_d_ff": 16, "norm_topk": True, "shared_expert_gate": True,
        "attn_head_dim": 16, "rotary_fraction": 0.25, "qk_norm": True,
        "gated_attention": True, "delta_key_heads": 2,
        "delta_value_heads": 4, "delta_key_dim": 8, "delta_value_dim": 12,
        "delta_conv": 4},
}
HYBRIDS = ("delta_hybrid", "mamba_hybrid")


def _lengths(case: str) -> tuple:
    return HYBRID_LENGTHS if case in HYBRIDS else LENGTHS


def _model(case: str):
    config = TransformerConfig(**{**DENSE, **CASES[case]})
    return config, init_params(config, jax.random.PRNGKey(3))


def _prompt(seed: int = 11):
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed), (1, BUCKET), 0, DENSE["vocab_size"]),
        np.int32)


TABLE = np.array([5, 2, 7, 1, 9, 3, 8, 6, 4, 0], np.int32)
BLOCKS = BUCKET // BLOCK


@pytest.fixture
def tile(monkeypatch):
    """set(rows): the row tile for the programs traced from here on.  The
    jitted programs are cached by config and shape, not by the tile they
    traced at, so every change clears them."""
    def set_tile(rows: int) -> None:
        monkeypatch.setattr(transformer, "_ROW_TILE", rows)
        jax.clear_caches()
    yield set_tile
    jax.clear_caches()


def _prefill(config, params, true_len: int):
    """(the pool's leaves as (caches, H, bucket rows, d) through the
    table, the first token, the token a decode step gives after it)."""
    pool = {**init_paged_pool(config, 10, BLOCK),
            **transformer.init_recurrent_state(config, 1)}
    slot = {"slot": np.int32(0)} if config.recurrent else {}
    pool, first = paged_prefill(params, config, pool, _prompt(), TABLE,
                                np.int32(true_len), **slot)
    rows = {}
    for name, leaf in pool.items():
        if name in transformer._STATE_LEAVES:
            rows[name] = np.asarray(leaf)           # the one slot's state
            continue
        held = np.asarray(leaf)[:, TABLE[:BLOCKS]]  # (caches, blocks, H, B, d)
        rows[name] = held.transpose(0, 2, 1, 3, 4).reshape(
            held.shape[0], held.shape[2], BUCKET, held.shape[-1])
    position = true_len                      # the table names 10 blocks
    _, after, *_ = paged_decode_step(
        params, config, pool, TABLE[None], np.array([position], np.int32),
        np.asarray(first).reshape(1, 1), TABLE[None, position // BLOCK],
        np.array([position % BLOCK], np.int32))
    return rows, int(first), int(np.asarray(after)[0, 0])


_RAN: dict = {}


def _ran(case: str, tile) -> dict:
    """{("whole" | "live", true_len): _prefill's} of `case` at every
    length, each of the two programs traced once."""
    if case not in _RAN:
        config, params = _model(case)
        found = {}
        for what, rows in (("whole", 1 << 20), ("live", TILE)):
            tile(rows)
            for true_len in _lengths(case):
                assert prefill_rows(config, BUCKET, true_len) == (
                    BUCKET if what == "whole"
                    else -(-true_len // TILE) * TILE)
                found[what, true_len] = _prefill(config, params, true_len)
        _RAN[case] = found
    return _RAN[case]


@pytest.mark.parametrize("case,true_len", [
    (case, true_len) for case in sorted(CASES)
    for true_len in _lengths(case)])
def test_live_rows_prefill_is_the_whole_prefill_below_true_len(
        tile, case, true_len):
    ran = _ran(case, tile)
    whole, whole_first, whole_after = ran["whole", true_len]
    live, first, after = ran["live", true_len]
    every, _, _ = ran["live", BUCKET]
    rows = -(-true_len // TILE) * TILE

    assert (first, after) == (whole_first, whole_after)
    assert set(live) == set(whole)
    for name, leaf in live.items():
        if name in transformer._STATE_LEAVES:
            # the state after row true_len - 1, the tail at true_len: what
            # the last live tile left is what the bucket run whole leaves
            np.testing.assert_allclose(leaf, whole[name], atol=2e-5,
                                       rtol=1e-5, err_msg=name)
            assert np.abs(leaf).max() > 1e-3, name
            continue
        below = np.s_[:, :, :true_len]
        if leaf.dtype == np.int8:
            # a code may differ by a step where the row's value did by
            # rounding
            assert np.abs(leaf[below].astype(np.int32)
                          - whole[name][below]).max() <= 1, name
        else:
            np.testing.assert_allclose(leaf[below], whole[name][below],
                                       atol=2e-5, rtol=1e-5, err_msg=name)
        # the same program with every tile live: a live tile's rows do
        # not know how many tiles ran
        if case in ("dense", "int8_cache"):
            np.testing.assert_array_equal(
                leaf[:, :, :rows], every[name][:, :, :rows], err_msg=name)
        else:
            # (the routed experts lay a row out among other rows; the
            # looped stack's later pass reads the first's rounding)
            np.testing.assert_allclose(
                leaf[below].astype(np.float32),
                every[name][below].astype(np.float32), atol=2e-5,
                rtol=1e-5, err_msg=name)
        dead = leaf[:, :, rows:]
        assert np.isfinite(dead.astype(np.float32)).all(), name
        if name.endswith("_scale"):
            # zeros quantise to code 0 at the floor scale (_quantize_kv):
            # the row dequantises to exactly zero
            assert (dead <= 1e-8 / 127 * 1.001).all(), name
        else:
            assert not dead.any(), name


def test_live_rows_prefill_counts_the_live_rows_pairs(tile):
    """stats[1:] of a whole prefill count the live rows only: every pair
    of a row below true_len whose expert is held, none of a row at or
    past it (all experts held here, so the count is known)."""
    config, params = _model("latent_routed")
    config = dataclasses.replace(config, experts_held=())
    params = init_params(config, jax.random.PRNGKey(3))
    expert_layers = config.n_layers - config.first_dense_layers
    hidden = jax.jit(
        lambda tokens, cache, true_len: transformer._hidden(
            params, config, tokens, cache, 0, true_len=true_len)[2])
    tile(TILE)
    for true_len in (1, TILE + 1, BUCKET):
        stats = np.asarray(hidden(_prompt(), init_cache(config, 1, BUCKET),
                                  np.int32(true_len)))
        assert stats[2] == true_len * config.top_k * expert_layers
        assert 1 <= stats[1] <= config.n_routed_experts * expert_layers
    # a bucket under two tiles runs its row-wise work whole, and still
    # sends the rows at or past true_len to no expert
    tile(1 << 20)
    stats = np.asarray(hidden(_prompt(), init_cache(config, 1, BUCKET),
                              np.int32(1)))
    assert stats[2] == 1 * config.top_k * expert_layers


def test_one_executable_a_bucket_whatever_the_length():
    """At the real tile: a 1024-row bucket is two tiles, its program is
    traced and compiled once and serves every length."""
    config = TransformerConfig(**{**DENSE, "max_seq_len": 1024})
    params = init_params(config, jax.random.PRNGKey(0))
    tile_rows = transformer._ROW_TILE
    bucket = 2 * tile_rows
    assert prefill_rows(config, bucket, 1) == tile_rows
    assert prefill_rows(config, bucket, tile_rows + 1) == bucket
    prompt = np.zeros((1, bucket), np.int32)
    prompt[0] = np.arange(bucket) % 97
    table = np.arange(1, bucket // 32 + 1, dtype=np.int32)
    pool = init_paged_pool(config, bucket // 32 + 1, 32)
    before = paged_prefill._cache_size()
    firsts = []
    for true_len in (1, tile_rows - 1, tile_rows, tile_rows + 1, bucket):
        pool, first = paged_prefill(params, config, pool, prompt, table,
                                    np.int32(true_len))
        firsts.append(int(first))
    assert paged_prefill._cache_size() == before + 1
    # and its answers are forward()'s at those positions
    logits = np.asarray(forward(params, config, prompt))[0]
    assert firsts == [int(logits[n - 1].argmax()) for n in (
        1, tile_rows - 1, tile_rows, tile_rows + 1, bucket)]
    # past the first tile's rows nothing was written for a short prompt
    pool = init_paged_pool(config, bucket // 32 + 1, 32)
    pool, _ = paged_prefill(params, config, pool, prompt, table,
                            np.int32(5))
    held = np.asarray(pool["k"])[:, table]
    assert held[:, :tile_rows // 32].any()
    assert not held[:, tile_rows // 32:].any()


@pytest.mark.parametrize("what", [
    "under_two_tiles", "not_whole_tiles", "switch_ffn", "no_true_len",
    "later_position", "no_cache"])
def test_what_is_no_whole_prefill_traces_no_row_loop(tile, what):
    """The live-rows path is decided by what the call is: a bucket under
    two tiles or of no whole number of tiles, a switch FFN (its capacity
    spans the sequence), a call without a true length (forward: training,
    scoring, generate()), from a later position, or without a cache
    lowers to the program it lowered to before, with no loop of a traced
    trip count."""
    tile(TILE)
    fields = {"n_experts": 4} if what == "switch_ffn" else {}
    config = TransformerConfig(**{**DENSE, **fields})
    params = init_params(config, jax.random.PRNGKey(0))
    length = {"under_two_tiles": 2 * TILE - 4,
              "not_whole_tiles": 2 * TILE + 4}.get(what, BUCKET)
    tokens = np.zeros((1, length), np.int32)
    cache = None if what == "no_cache" else init_cache(config, 1, 64)
    true_len = None if what in ("no_true_len", "no_cache") else np.int32(3)
    pos = 8 if what == "later_position" else 0
    assert prefill_rows(config, length, 3) == (
        length if what in ("under_two_tiles", "not_whole_tiles",
                           "switch_ffn") else TILE)

    def loops(true_len) -> int:
        return jax.jit(lambda tokens, cache, true_len: transformer._hidden(
            params, config, tokens, cache, pos, true_len=true_len)[0]
        ).lower(tokens, cache, true_len).as_text().count("stablehlo.while")

    at_the_tile = loops(true_len)
    if what == "no_true_len":
        # the same call, the length given: a loop a row-wise segment
        assert loops(np.int32(3)) > at_the_tile
    tile(1 << 20)
    assert at_the_tile == loops(true_len)


def test_flash_attention_sees_zeros_in_the_dead_tiles(tile, monkeypatch):
    """Through the flash kernel (steered here by its threshold), whose
    whole-bucket attention reads the dead tiles' zero q, k, v: first
    token and live rows as the whole trace's."""
    monkeypatch.setattr(attention, "_FLASH_MIN_SCORE_BYTES", 0)
    config, params = _model("dense")
    config = dataclasses.replace(config, max_seq_len=65)
    tile(1 << 20)
    whole, whole_first, whole_after = _prefill(config, params, TILE + 3)
    tile(TILE)
    live, first, after = _prefill(config, params, TILE + 3)
    assert (first, after) == (whole_first, whole_after)
    for name, leaf in live.items():
        np.testing.assert_allclose(
            leaf[:, :, :TILE + 3], whole[name][:, :, :TILE + 3], atol=2e-5,
            rtol=1e-5, err_msg=name)
        assert not leaf[:, :, 2 * TILE:].any(), name


# -- a hybrid's tiles through its kernel or its chunkwise form (ISSUE 44) ---
#
# The hybrid cases above run a toy tile, under the scan kernel's rows and
# under a chunk of the delta rule: the oracles'.  tests/test_jamba.py and
# tests/test_qwen3_next.py steer the kernel and the chunkwise form to tiles
# of 16 rows of a 64-row bucket and hold them with these two.

EDGE_TILE, EDGE_BUCKET = 16, 64
EDGE_LENGTHS = tuple(k * EDGE_TILE + more for k in (1, 2)
                     for more in (0, 1, 3, -1)) + (3 * EDGE_TILE,)


def hidden_whole_and_tiled(config, params, prompt, set_tile) -> dict:
    """{("whole" | "tiled", true_len): (logits at true_len - 1, h, the
    FFNs' stats, the cache)} of _hidden over `prompt` (1, EDGE_BUCKET) at
    EDGE_LENGTHS, each of the two programs traced once; set_tile(rows)
    sets the row tile and clears the jitted programs."""
    @jax.jit
    def hidden(true_len):
        h, outputs, stats, cache = transformer._hidden(
            params, config, prompt, init_cache(config, 1, EDGE_BUCKET), 0,
            true_len=true_len)
        last = jax.lax.dynamic_slice_in_dim(h, true_len - 1, 1, 1)
        return (transformer._logits(params, config, last, outputs)[0], h,
                stats, cache)

    found = {}
    for what, rows in (("whole", 1 << 20), ("tiled", EDGE_TILE)):
        set_tile(rows)
        for true_len in EDGE_LENGTHS:
            with jax.default_matmul_precision("highest"):
                found[what, true_len] = jax.tree_util.tree_map(
                    np.asarray, hidden(np.int32(true_len)))
    return found


def assert_tiled_is_the_whole_buckets(ran: dict, true_len: int,
                                      atol: float) -> None:
    """Logits at true_len - 1, h and K/V rows below true_len and every
    recurrent layer's tail and state: the tiles' calls, the state carried
    between them, leave what the bucket's one call leaves; h and the K/V
    are zeros past the live tiles."""
    logits, h, _, cache = ran["tiled", true_len]
    whole_logits, whole_h, _, whole_cache = ran["whole", true_len]
    rows = -(-true_len // EDGE_TILE) * EDGE_TILE
    np.testing.assert_allclose(logits, whole_logits, atol=atol, rtol=0)
    np.testing.assert_allclose(h[:, :true_len], whole_h[:, :true_len],
                               atol=atol, rtol=0)
    assert not h[:, rows:].any()
    for name, leaf in cache.items():
        if name in transformer._STATE_LEAVES:
            np.testing.assert_allclose(leaf, whole_cache[name], atol=atol,
                                       rtol=0, err_msg=name)
            assert np.abs(leaf).max() > 1e-3, name
            continue
        np.testing.assert_allclose(
            leaf[:, :, :, :true_len], whole_cache[name][:, :, :, :true_len],
            atol=atol, rtol=0, err_msg=name)
        assert not leaf[:, :, :, rows:].any(), name


# -- the attention does the work of its prompt too (PR 40) -----------------
#
# A whole prefill whose bucket attends through the flash kernel hands the
# kernel its true length (flash_attention's `live`), whether or not the
# bucket runs by row tiles: here it does not (256 rows are under two row
# tiles), so the attention is all that differs from the parent's trace,
# which is the same forward with no length handed in.  The kernel is
# steered to toy sizes by its threshold and its query blocks (two blocks
# of 128 in the 256-row bucket).  tests/test_jamba.py holds the
# hybrid.

LIVE_BUCKET, LIVE_QUERY_BLOCK = 256, 128
LIVE_LENGTHS = (100, 200)               # in the first block, in the last


@pytest.fixture
def live_attention(monkeypatch):
    monkeypatch.setattr(attention, "_FLASH_MIN_SCORE_BYTES", 0)
    # the query block of a call told a live length: of grouped heads,
    # and of heads with K/V of their own (the latent's)
    monkeypatch.setattr(attention, "_FLASH_LIVE_BLOCK", LIVE_QUERY_BLOCK)
    monkeypatch.setattr(attention, "_FLASH_BLOCK", LIVE_QUERY_BLOCK)
    jax.clear_caches()
    yield
    jax.clear_caches()


def attention_of_the_whole_bucket(monkeypatch):
    """From here on the model step hands the kernel no length: the
    parent's attention, through the same entry points."""
    whole = attention.flash_attention
    monkeypatch.setattr(
        transformer, "flash_attention",
        lambda *args, live=None, **keywords: whole(*args, **keywords))
    jax.clear_caches()


def check_live_attention_prefill(config, params, monkeypatch, pool_of,
                                 **slot):
    """paged_prefill and _hidden of a LIVE_BUCKET-row bucket at
    LIVE_LENGTHS, the kernel told the length against the kernel over the
    whole bucket: the first token, the logits at true_len - 1 and every
    pool row below true_len."""
    bucket, block = LIVE_BUCKET, 32
    prompt = np.asarray(jax.random.randint(
        jax.random.PRNGKey(5), (1, bucket), 1, config.vocab_size), np.int32)
    table = np.arange(1, bucket // block + 1, dtype=np.int32)

    def ran(true_len):
        pool, first = paged_prefill(params, config, pool_of(), prompt,
                                    table, np.int32(true_len), **slot)
        h, outputs, _, _ = transformer._hidden(
            params, config, prompt, init_cache(config, 1, bucket), 0,
            true_len=np.int32(true_len))
        at = slice(true_len - 1, true_len)
        logits, _ = transformer._logits(
            params, config, h[:, at], [out[:, at] for out in outputs])
        return pool, int(first), np.asarray(logits)

    def flash_calls():
        traced = jax.make_jaxpr(
            lambda tokens, true_len: transformer._hidden(
                params, config, tokens, init_cache(config, 1, bucket), 0,
                true_len=true_len)[0])(prompt, np.int32(3))
        return [call for call in pallas_calls(traced.jaxpr)
                if call.params["name"] in (None, "mla_flash_attention")]

    def told(call) -> bool:
        return call.params["grid_mapping"].num_index_operands == 1

    for true_len in LIVE_LENGTHS:
        assert prefill_attention_rows(config, bucket, true_len) == (
            -(-true_len // LIVE_QUERY_BLOCK) * LIVE_QUERY_BLOCK)
    live = {true_len: ran(true_len) for true_len in LIVE_LENGTHS}
    # what the trace took: every layer's flash call the length
    assert flash_calls() and all(map(told, flash_calls()))
    attention_of_the_whole_bucket(monkeypatch)
    assert flash_calls() and not any(map(told, flash_calls()))
    for true_len in LIVE_LENGTHS:
        pool, first, logits = live[true_len]
        whole_pool, whole_first, whole_logits = ran(true_len)
        assert first == whole_first
        np.testing.assert_allclose(logits, whole_logits, atol=2e-5, rtol=0)
        for name, leaf in pool.items():
            if name in ("conv", "ssm"):                 # a slot's state
                np.testing.assert_allclose(leaf, whole_pool[name],
                                           atol=2e-5, rtol=0, err_msg=name)
                continue
            # (caches, blocks, H, block, d): whole blocks below true_len
            below = np.s_[:, table[:true_len // block]]
            np.testing.assert_allclose(
                np.asarray(leaf)[below], np.asarray(whole_pool[name])[below],
                atol=2e-5, rtol=0, err_msg=name)


@pytest.mark.parametrize("case", ["dense", "latent_routed"])
def test_live_attention_prefill_is_the_whole_buckets_below_true_len(
        live_attention, monkeypatch, case):
    config, params = _model(case)
    config = dataclasses.replace(config, max_seq_len=LIVE_BUCKET)
    assert not transformer._row_tiles_take(config, LIVE_BUCKET)
    check_live_attention_prefill(
        config, params, monkeypatch,
        lambda: init_paged_pool(config, LIVE_BUCKET // 32 + 1, 32))


@pytest.mark.parametrize("what,rows", [
    ("flash", 128), ("einsum", 256), ("int8_cache", 256),
    ("sequence_parallel", 256)])
def test_prefill_attention_rows_follow_what_the_attention_takes(
        live_attention, monkeypatch, what, rows):
    """The bucket where the kernel is not told the length: the einsum's
    bucket, an int8 cache (attended over as quantised), a
    sequence-parallel prefill (the ring's)."""
    fields = {"int8_cache": {"kv_dtype": "int8"},
              "sequence_parallel": {"sequence_parallel": True}}.get(what, {})
    if what == "einsum":
        monkeypatch.setattr(attention, "_FLASH_MIN_SCORE_BYTES", 64 << 20)
    config = TransformerConfig(**{**DENSE, **fields})
    assert prefill_attention_rows(config, LIVE_BUCKET, 100) == rows
    assert prefill_attention_rows(config, LIVE_BUCKET, 129) == 256
