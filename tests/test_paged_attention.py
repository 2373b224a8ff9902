# The paged-attention kernel (parallel/attention.py paged_attention) against
# its einsum oracle (paged_attention_reference) on random pools, interpreted
# on the CPU: ragged lengths around every block and chunk edge, an idle slot
# on the trash block, garbage past every cursor, GQA ratios, window sizes,
# float32 and bfloat16.  One parametrised test, each case counted.

import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aiko_services_tpu.decode.blocks import TRASH_BLOCK
from aiko_services_tpu.models.transformer import (
    TransformerConfig, init_paged_pool, init_params, paged_decode_step,
    paged_prefill)
from aiko_services_tpu.parallel import attention
from aiko_services_tpu.parallel.attention import (
    paged_attention, paged_attention_reference, paged_attention_takes,
    paged_live_blocks)

BLOCK = 16
MAX_BLOCKS = 40                 # table capacity 640: three chunks of 256
CAPACITY = BLOCK * MAX_BLOCKS
CHUNK = 16 * BLOCK              # the kernel's chunk at this block size
DEPTH = 32
LAYERS = 2
# what a cursor can be: the first position, both sides of a block edge,
# both sides of the short chunk's end (128) and of a chunk edge, the
# table's last position
RAGGED = (0, BLOCK - 2, BLOCK - 1, BLOCK, 127, 128, CHUNK - 1, CHUNK,
          CAPACITY - 1)
GARBAGE = 1e4                   # finite, like stale K/V; would swamp any sum
TOLERANCE = {"float32": 1e-5, "bfloat16": 2e-2}


def _case(kv_heads, repeats, window, dtype, seed):
    """A pool whose every position past a slot's cursor + window holds
    GARBAGE, tables of scattered blocks, and one idle slot: cursor 0,
    every table entry the trash block."""
    rng = np.random.default_rng(seed)
    # the last position the window writes must exist in the table
    positions = np.array(
        [min(p, CAPACITY - window) for p in RAGGED] + [0], np.int32)
    slots = len(positions)
    num_blocks = slots * MAX_BLOCKS + 1
    shape = (LAYERS, num_blocks, kv_heads, BLOCK, DEPTH)
    pool_k = rng.normal(size=shape).astype(np.float32)
    pool_v = rng.normal(size=shape).astype(np.float32)
    tables = rng.permutation(np.arange(1, num_blocks))[
        :slots * MAX_BLOCKS].reshape(slots, MAX_BLOCKS).astype(np.int32)
    tables[-1] = TRASH_BLOCK
    for slot in range(slots - 1):
        live = int(positions[slot]) + window
        for index, block in enumerate(tables[slot]):
            dead_from = min(max(live - index * BLOCK, 0), BLOCK)
            pool_k[:, block, :, dead_from:] = GARBAGE
            pool_v[:, block, :, dead_from:] = -GARBAGE
    # the trash block: one live position, garbage after it
    pool_k[:, TRASH_BLOCK, :, window:] = GARBAGE
    pool_v[:, TRASH_BLOCK, :, window:] = -GARBAGE
    q = rng.normal(size=(slots, kv_heads * repeats, window, DEPTH))
    return (jnp.asarray(q, dtype), jnp.asarray(pool_k, dtype),
            jnp.asarray(pool_v, dtype), jnp.asarray(tables),
            jnp.asarray(positions))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("window", [1, 4, 16])
@pytest.mark.parametrize("kv_heads,repeats", [(2, 1), (2, 4), (1, 8)])
def test_kernel_matches_einsum_oracle(kv_heads, repeats, window, dtype):
    q, pool_k, pool_v, tables, positions = _case(
        kv_heads, repeats, window, jnp.dtype(dtype),
        seed=kv_heads * 100 + repeats * 10 + window)
    assert paged_attention_takes(kv_heads * repeats, window, DEPTH,
                                 pool_k.dtype)
    assert CHUNK == BLOCK * min(attention._PAGED_CHUNK_BLOCKS_MAX,
                                attention._PAGED_CHUNK_POSITIONS // BLOCK)
    assert attention._PAGED_NARROW < CHUNK     # both widths are run
    layer = jnp.int32(1)
    out, same_k, same_v = paged_attention(q, pool_k, pool_v, layer, tables,
                                          positions)
    assert same_k is pool_k and same_v is pool_v     # nothing was written
    oracle = paged_attention_reference(q, pool_k, pool_v, layer, tables,
                                       positions)
    assert out.shape == q.shape and out.dtype == q.dtype
    out = np.asarray(out, np.float32)
    # garbage past a cursor that reached the output would be ~1e4
    assert np.isfinite(out).all() and np.abs(out).max() < 10.0
    np.testing.assert_allclose(out, np.asarray(oracle, np.float32),
                               atol=TOLERANCE[dtype], rtol=0)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("window", [1, 4])
@pytest.mark.parametrize("heads", [4, 16])
def test_latent_kernel_matches_einsum_oracle(heads, window, dtype):
    """A latent pool: no V leaf, one key head for every query head, a
    row's first 24 of 32 values the value, a softmax scale of its own.
    Same ragged cursors, garbage and idle slot as the K/V case."""
    q, pool, _, tables, positions = _case(
        1, heads, window, jnp.dtype(dtype), seed=heads * 10 + window)
    value_dim, scale = 24, 0.31
    assert paged_attention_takes(heads, window, DEPTH, pool.dtype,
                                 value_dim=value_dim)
    layer = jnp.int32(1)
    out, _, no_v = paged_attention(q, pool, None, layer, tables, positions,
                                   sm_scale=scale, value_dim=value_dim)
    assert no_v is None
    oracle = paged_attention_reference(
        q, pool, None, layer, tables, positions, sm_scale=scale,
        value_dim=value_dim)
    assert out.shape == q.shape[:3] + (value_dim,) and out.dtype == q.dtype
    out = np.asarray(out, np.float32)
    assert np.isfinite(out).all() and np.abs(out).max() < 10.0
    np.testing.assert_allclose(out, np.asarray(oracle, np.float32),
                               atol=TOLERANCE[dtype], rtol=0)
    # the scale is read: the default 1/sqrt(depth) gives other numbers
    other = paged_attention(q, pool, None, layer, tables, positions,
                            value_dim=value_dim)[0]
    assert np.abs(np.asarray(other, np.float32) - out).max() > 1e-3


# -- the kernel writes the step's new rows itself (window 1) ----------------

WRITE_BLOCK = 32                # the served block: two bfloat16 tiles of 16
WRITE_MAX_BLOCKS = 20           # capacity 640: a chunk of 512 and a tail
# where a live slot's cursor lies: the first position, both sides of a
# float32 tile's edge (7 | 8), of a bfloat16 tile's (15 | 16) and of a block's
# (31 | 32), odd and even (the packed pair), inside the second chunk, the
# table's last position
WRITE_CURSORS = (0, 7, 8, 15, 16, 31, 32, 200, 519, 639)
PARKED = 100                    # a live table whose told row is the trash's


def _write_case(kv_heads, heads, dtype, seed):
    """Live slots at WRITE_CURSORS, told to write where their table puts
    their position; one parked slot (a live table, as a slot in the
    middle of its prefill has: its row goes to the trash block, offset
    3); two idle slots (every table entry the trash block, row 0)."""
    rng = np.random.default_rng(seed)
    positions = np.array(WRITE_CURSORS + (PARKED, 0, 0), np.int32)
    slots, live = len(positions), len(WRITE_CURSORS)
    num_blocks = slots * WRITE_MAX_BLOCKS + 1
    shape = (LAYERS, num_blocks, kv_heads, WRITE_BLOCK, DEPTH)
    pool_k = rng.normal(size=shape).astype(np.float32)
    pool_v = rng.normal(size=shape).astype(np.float32)
    tables = rng.permutation(np.arange(1, num_blocks))[
        :slots * WRITE_MAX_BLOCKS].reshape(slots, WRITE_MAX_BLOCKS).astype(
        np.int32)
    tables[live + 1:] = TRASH_BLOCK
    write_blocks = tables[np.arange(slots), positions // WRITE_BLOCK]
    write_offsets = positions % WRITE_BLOCK
    write_blocks[live], write_offsets[live] = TRASH_BLOCK, 3
    q = rng.normal(size=(slots, heads, 1, DEPTH))
    new_k, new_v = rng.normal(size=(2, slots, kv_heads, 1, DEPTH))
    as_dtype = lambda *arrays: [jnp.asarray(a, dtype) for a in arrays]
    return (*as_dtype(q, pool_k, pool_v, new_k, new_v),
            jnp.asarray(tables), jnp.asarray(positions),
            jnp.asarray(write_blocks[:, None]),
            jnp.asarray(write_offsets[:, None]), live)


def _assert_written_only_where_told(before, after, new, layer,
                                    write_blocks, write_offsets, live):
    """`after` is `before` with the live slots' new rows at (layer, told
    block, :, told offset); a told row of the trash block holds what it
    held or what any slot was told to put there; every other position
    -- a told row's neighbours in its tile above all -- is bit for bit
    what it was."""
    before, after, new = (np.asarray(a, np.float32)
                          for a in (before, after, new))
    blocks = np.asarray(write_blocks)[:, 0]
    offsets = np.asarray(write_offsets)[:, 0]
    expected = before.copy()
    for slot in range(live):
        expected[layer, blocks[slot], :, offsets[slot]] = new[slot, :, 0]
    told = {(int(b), int(o)) for b, o in zip(blocks[live:], offsets[live:])}
    assert {b for b, _ in told} == {TRASH_BLOCK}
    for block, offset in told:
        row = after[layer, block, :, offset]
        candidates = [before[layer, block, :, offset]] + [
            new[slot, :, 0] for slot in range(live, len(blocks))
            if (blocks[slot], offsets[slot]) == (block, offset)]
        assert any(np.array_equal(row, c) for c in candidates)
        expected[layer, block, :, offset] = row
    np.testing.assert_array_equal(after, expected)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("kv_heads,repeats", [(8, 4), (16, 1), (2, 2)])
def test_kernel_writes_the_new_rows_and_nothing_else(kv_heads, repeats,
                                                     dtype):
    """The kernel with `write` against its oracle after _write_window:
    the same attention over the written pool, and a pool that differs
    from the one that came in only in the told rows."""
    from aiko_services_tpu.models.transformer import _write_window
    (q, pool_k, pool_v, new_k, new_v, tables, positions, write_blocks,
     write_offsets, live) = _write_case(
        kv_heads, kv_heads * repeats, jnp.dtype(dtype),
        seed=kv_heads * 10 + repeats)
    assert attention.paged_attention_writes(1)
    assert not attention.paged_attention_writes(2)
    layer = 1
    out, after_k, after_v = jax.jit(
        lambda *args: paged_attention(
            *args[:3], jnp.int32(layer), tables, positions,
            write=(args[3:], write_blocks, write_offsets)),
        donate_argnums=(1, 2))(q, jnp.copy(pool_k), jnp.copy(pool_v),
                               new_k, new_v)
    for before, after, new in ((pool_k, after_k, new_k),
                               (pool_v, after_v, new_v)):
        assert after.shape == before.shape and after.dtype == before.dtype
        _assert_written_only_where_told(before, after, new, layer,
                                        write_blocks, write_offsets, live)
    oracle = paged_attention_reference(
        q, *(_write_window(pool, new, layer, write_blocks, write_offsets)
             for pool, new in ((pool_k, new_k), (pool_v, new_v))),
        layer, tables, positions)
    out = np.asarray(out, np.float32)
    assert np.isfinite(out).all()
    # the parked and the idle slots' outputs are nobody's
    np.testing.assert_allclose(out[:live], np.asarray(
        oracle, np.float32)[:live], atol=TOLERANCE[dtype], rtol=0)
    # the fresh row is attended: without it the first slot, whose only
    # visible position it is, reads something else
    stale, _, _ = paged_attention(q, pool_k, pool_v, jnp.int32(layer),
                                  tables, positions)
    assert np.abs(np.asarray(stale, np.float32)[0] - out[0]).max() > 1e-2


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_latent_kernel_writes_the_new_row_and_nothing_else(dtype):
    """The latent pool: one leaf, one key head, one new row a slot."""
    from aiko_services_tpu.models.transformer import _write_window
    (q, pool, _, new, _, tables, positions, write_blocks, write_offsets,
     live) = _write_case(1, 8, jnp.dtype(dtype), seed=7)
    value_dim, scale, layer = 24, 0.31, 1
    out, after, no_v = paged_attention(
        q, pool, None, jnp.int32(layer), tables, positions, sm_scale=scale,
        value_dim=value_dim, write=((new,), write_blocks, write_offsets))
    assert no_v is None
    _assert_written_only_where_told(pool, after, new, layer, write_blocks,
                                    write_offsets, live)
    oracle = paged_attention_reference(
        q, _write_window(pool, new, layer, write_blocks, write_offsets),
        None, layer, tables, positions, sm_scale=scale,
        value_dim=value_dim)
    np.testing.assert_allclose(
        np.asarray(out, np.float32)[:live],
        np.asarray(oracle, np.float32)[:live], atol=TOLERANCE[dtype],
        rtol=0)


def test_kernel_reads_the_layer_it_is_given():
    q, pool_k, pool_v, tables, positions = _case(2, 2, 1, jnp.float32, 5)
    outs = [np.asarray(paged_attention(q, pool_k, pool_v, jnp.int32(layer),
                                       tables, positions)[0])
            for layer in range(LAYERS)]
    assert np.abs(outs[0] - outs[1]).max() > 1e-2
    for layer, out in enumerate(outs):
        np.testing.assert_allclose(
            out, np.asarray(paged_attention_reference(
                q, pool_k, pool_v, layer, tables, positions)), atol=1e-5)


def test_live_blocks_follow_the_cursor_not_the_table():
    positions = np.array([0, BLOCK - 1, BLOCK, CAPACITY - 1, CAPACITY + 40])
    np.testing.assert_array_equal(
        paged_live_blocks(positions, 1, BLOCK, MAX_BLOCKS),
        [1, 1, 2, MAX_BLOCKS, MAX_BLOCKS])
    np.testing.assert_array_equal(
        paged_live_blocks(positions, BLOCK + 1, BLOCK, MAX_BLOCKS),
        [2, 2, 3, MAX_BLOCKS, MAX_BLOCKS])


@pytest.mark.parametrize("heads,window,dtype,takes", [
    (32, 1, "bfloat16", True), (32, 1, "float32", True),
    (32, 5, "bfloat16", True), (32, 128, "bfloat16", True),
    (32, 256, "bfloat16", False), (32, 64, "float32", True),
    (32, 128, "float32", False), (32, 1, "int8", False)])
def test_what_takes_the_kernel_is_decided_by_shape_and_dtype(
        heads, window, dtype, takes):
    assert paged_attention_takes(heads, window, 128, dtype) is takes


def test_decode_step_updates_the_pool_in_place_and_only_where_told():
    """The served step: the donated pool comes back with exactly the
    rows at (layer, write_block, write_offset) changed, in every leaf."""
    config = TransformerConfig(vocab_size=64, n_layers=2, n_heads=4,
                               n_kv_heads=2, d_model=32, d_ff=64,
                               max_seq_len=4 * BLOCK, dtype="float32")
    params = init_params(config, jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    slots, max_blocks = 3, 4
    pool = init_paged_pool(config, slots * max_blocks + 1, BLOCK)
    pool = {name: jnp.asarray(rng.normal(size=leaf.shape), leaf.dtype)
            for name, leaf in pool.items()}
    before = {name: np.asarray(leaf) for name, leaf in pool.items()}
    tables = 1 + np.arange(slots * max_blocks, dtype=np.int32).reshape(
        slots, max_blocks)
    positions = np.array([0, BLOCK + 1, 4 * BLOCK - 1], np.int32)
    write_blocks = tables[np.arange(slots), positions // BLOCK]
    write_offsets = positions % BLOCK
    tokens = np.array([[1], [2], [3]], np.int32)
    pool, greedy = paged_decode_step(params, config, pool, tables,
                                     positions, tokens, write_blocks,
                                     write_offsets)
    assert greedy.shape == (slots, 1)
    for name, leaf in pool.items():
        changed = np.argwhere(
            (np.asarray(leaf) != before[name]).any(axis=(2, 4)))
        expected = sorted(
            (layer, int(block), int(offset))
            for layer in range(config.n_layers)
            for block, offset in zip(write_blocks, write_offsets))
        assert sorted(map(tuple, changed)) == expected, name


# -- compiled for the chip, here, without the chip ---------------------------
#
# The TPU's compiler is installed and compiles for a chip that is described,
# not attached: what Mosaic or XLA would refuse or re-lay out on the v5e shows
# at no chip time.  The topology is described inside a fixture (never while a
# module is imported), and these tests stay in this one file.

SERVED = dict(slots=16, kv_heads=8, repeats=4, block=32, max_blocks=128,
              depth=128, layers=16, blocks=1024)     # benchmark/configs


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topology = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as error:
        pytest.skip(f"no v5e:2x2 topology can be described here: {error}")
    return SingleDeviceSharding(topology.devices[0])


@pytest.fixture
def for_the_chip(one_chip, monkeypatch):
    """Shapes placed on the described chip; kernels lowered through
    Mosaic, not the interpreter; nothing written to the compile cache
    (an entry compiled for a described chip cannot be read back)."""
    monkeypatch.setattr(attention, "_interpret", lambda: False)
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, jnp.dtype(dtype), sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", cache)


@pytest.mark.parametrize("window,dtype", [
    (1, "bfloat16"), (5, "bfloat16"), (128, "bfloat16"), (1, "float32"),
    (64, "float32")])
def test_kernel_compiles_for_the_v5e_at_the_served_shape(
        for_the_chip, window, dtype):
    s = SERVED
    pool = for_the_chip((s["layers"], s["blocks"], s["kv_heads"],
                         s["block"], s["depth"]), dtype)
    slots = s["slots"] if window < 64 else 1        # a prefill chunk
    compiled = _compile_kernel(
        for_the_chip, pool, slots, s["kv_heads"] * s["repeats"], window,
        s["max_blocks"])
    assert "tpu_custom_call" in compiled.as_text()
    # the pool is read where it lies: nothing of its size is allocated
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def _compile_kernel(for_the_chip, pool, slots, heads, window, max_blocks,
                    **latent):
    """The kernel compiled for the described chip as the model step calls
    it: at window 1 with the rows to write and the leaves donated, which
    must then come back as the buffers they were; pool_v None with
    `latent`'s value_dim."""
    _, _, kv_heads, _, depth = pool.shape
    dtype = pool.dtype
    leaves = 1 if latent else 2
    int32 = lambda *shape: for_the_chip(shape, "int32")    # noqa: E731
    writes = attention.paged_attention_writes(window)

    def call(q, layer, tables, positions, told, *arrays):
        pools = arrays[:leaves] + (None,) * (2 - leaves)
        write = (arrays[leaves:], *told) if writes else None
        return paged_attention(q, *pools, layer, tables, positions,
                               write=write, **latent)

    new = for_the_chip((slots, kv_heads, 1, depth), dtype)
    compiled = jax.jit(call, donate_argnums=tuple(
        range(5, 5 + leaves))).lower(
        for_the_chip((slots, heads, window, depth), dtype), int32(),
        int32(slots, max_blocks), int32(slots),
        (int32(slots, 1), int32(slots, 1)), *(pool,) * leaves,
        *(new,) * (leaves if writes else 0)).compile()
    if writes:
        leaf_bytes = int(np.prod(pool.shape)) * jnp.dtype(dtype).itemsize
        assert (compiled.memory_analysis().alias_size_in_bytes
                == leaves * leaf_bytes)
    return compiled


# benchmark/configs/ouro_2.6b.json: as many K/V heads as query heads (a
# block brings 128 KiB of keys, not 64), 192 caches, 8 slots of 640
OURO = dict(slots=8, kv_heads=16, block=32, max_blocks=20, depth=128,
            caches=192)


def test_kernel_compiles_for_the_v5e_at_sixteen_kv_heads(for_the_chip):
    s = OURO
    pool = for_the_chip((s["caches"], 168, s["kv_heads"], s["block"],
                         s["depth"]), "bfloat16")
    compiled = _compile_kernel(for_the_chip, pool, s["slots"],
                               s["kv_heads"], 1, s["max_blocks"])
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "paged_attention" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def _made_of_a_leaf(text: str, leaf, operation: str) -> list:
    """The compiled program's lines in which `operation` makes an array
    of a pool leaf's type."""
    kind = {"bfloat16": "bf16", "float32": "f32"}[str(leaf.dtype)]
    leaf_type = kind + "[" + ",".join(map(str, leaf.shape)) + "]"
    return [line for line in text.splitlines()
            if f"= {leaf_type}" in line and operation in line]


def _served_ouro(for_the_chip):
    """(config, params, pool, int32): Ouro-2.6B as ouro.reason serves it,
    placed on the described chip."""
    import json
    import pathlib
    from aiko_services_tpu.models.configs import ouro_config
    published = json.loads((pathlib.Path(__file__).parent.parent
                            / "benchmark/configs/ouro_2.6b.json"
                            ).read_text())
    serve = published["serve"]
    assert (serve["decode_slots"], serve["kv_block_size"],
            serve["max_context"]) == (
        OURO["slots"], OURO["block"], OURO["block"] * OURO["max_blocks"])
    assert 161 <= serve["kv_blocks"] <= 168
    config = ouro_config(published, serve["max_context"])
    assert config.n_caches == OURO["caches"]
    place = lambda tree: jax.tree_util.tree_map(       # noqa: E731
        lambda leaf: for_the_chip(leaf.shape, leaf.dtype), tree)
    params = place(jax.eval_shape(
        lambda: init_params(config, jax.random.PRNGKey(0))))
    pool = place(jax.eval_shape(lambda: init_paged_pool(
        config, serve["kv_blocks"], serve["kv_block_size"])))
    return config, params, pool, lambda *shape: for_the_chip(shape, "int32")


def _served_model(for_the_chip):
    """(config, params, pool, int32): the served model's shapes, placed
    on the described chip."""
    s = SERVED
    config = TransformerConfig(
        vocab_size=32000, d_model=4096, n_layers=s["layers"], n_heads=32,
        n_kv_heads=s["kv_heads"], d_ff=14336, max_seq_len=4096,
        dtype="bfloat16")
    place = lambda tree: jax.tree_util.tree_map(       # noqa: E731
        lambda leaf: for_the_chip(leaf.shape, leaf.dtype), tree)
    params = place(jax.eval_shape(
        lambda: init_params(config, jax.random.PRNGKey(0))))
    pool = place(jax.eval_shape(
        lambda: init_paged_pool(config, s["blocks"], s["block"])))
    return config, params, pool, lambda *shape: for_the_chip(shape, "int32")


def test_served_prefill_keeps_no_scores_in_hbm_on_the_v5e(for_the_chip):
    """The 4096 bucket of lm.longprompt: the einsum's float32 scores
    (2.1 GB a layer) made 4.87 GB of temporaries; through the flash
    kernel the compiler reached 0.79 GB (the float32 logits of 4096
    positions, MLP intermediates)."""
    config, params, pool, int32 = _served_model(for_the_chip)
    compiled = paged_prefill.lower(
        params, config, pool, int32(1, 4096), int32(SERVED["max_blocks"]),
        int32()).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # 0.49 GB since the head runs at one position (its float32 logits at
    # all 4096 were 524 MB of the 0.79) and the row-wise work by row
    # tiles (PR 38).  The row loops slice each weight out of the stack
    # where they read it (_LayerAt): sliced by the layer scan outside
    # them, every layer's weights were copied, 450 MB of temporaries more.
    # Since PR 40 the kernel is told the prompt's length: 487,105,536 B
    # against the parent's 487,040,512 (the scalar operand, 64 KiB)
    assert (compiled.memory_analysis().temp_size_in_bytes
            <= 487_040_512 + (1 << 20))
    assert not _staged_whole(text, {4096 * 14336})


@pytest.mark.parametrize("served", ["mistral7b_l16", "ouro_2.6b"])
def test_served_decode_step_moves_no_pool_leaf_on_the_v5e(for_the_chip,
                                                          served):
    """ouro_2.6b: the pool (8.46 GB at 168 blocks) rides four layer
    loops, one a pass, as carry; a copy of a leaf would not fit."""
    s, model = ((SERVED, _served_model) if served == "mistral7b_l16"
                else (OURO, _served_ouro))
    config, params, pool, int32 = model(for_the_chip)
    slots = s["slots"]
    compiled = paged_decode_step.lower(
        params, config, pool, int32(slots, s["max_blocks"]), int32(slots),
        int32(slots, 1), int32(slots), int32(slots)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    leaf = pool["k"]
    leaf_bytes = int(np.prod(leaf.shape)) * leaf.dtype.itemsize
    memory = compiled.memory_analysis()
    # the donated pool is updated where it lies: no second pool, no
    # gathered view, no copy of a leaf (a scatter made two a layer)
    assert memory.alias_size_in_bytes == 2 * leaf_bytes
    assert memory.temp_size_in_bytes < leaf_bytes // 16
    assert not _made_of_a_leaf(text, leaf, " copy(")
    # and its new rows are the kernel's to write: no update of a leaf, a
    # device operation a slot a leaf a cache (3,072 a step of ouro_2.6b)
    assert not _made_of_a_leaf(text, leaf, " dynamic-update-slice(")
    if served == "ouro_2.6b":
        # weights 5.13 GB + pool: what the chip holds (serve.why)
        assert 13.2e9 < memory.argument_size_in_bytes < 13.7e9
        assert "paged_attention" in text


def test_served_ouro_prefill_fits_the_chip_at_the_256_bucket(for_the_chip):
    """The longest bucket ouro.reason sends: four passes into a
    contiguous cache of 192 x 256 rows (0.40 GB), scattered into the
    pool.  0.81 GB of temporaries when written; with the arguments 14.41
    GB of the chip's 16."""
    config, params, pool, int32 = _served_ouro(for_the_chip)
    compiled = paged_prefill.lower(
        params, config, pool, int32(1, 256), int32(OURO["max_blocks"]),
        int32()).compile()
    memory = compiled.memory_analysis()
    leaf = pool["k"]
    leaf_bytes = int(np.prod(leaf.shape)) * leaf.dtype.itemsize
    assert memory.alias_size_in_bytes == 2 * leaf_bytes
    assert memory.temp_size_in_bytes < 1 << 30
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < 14.6e9)


def test_on_the_chip_a_head_dim_off_the_lanes_keeps_the_einsum(
        monkeypatch):
    assert paged_attention_takes(32, 1, 64, "bfloat16")      # interpreted
    monkeypatch.setattr(attention, "_interpret", lambda: False)
    assert not paged_attention_takes(32, 1, 64, "bfloat16")
    assert paged_attention_takes(32, 1, 128, "bfloat16")
    assert paged_attention_takes(32, 1, 256, "float32")


# -- DeepSeek-V2's share at its served shapes (benchmark/configs/
# deepseek_v2_ep4_l5.json): the three new kernels and the two programs
# the engine runs, compiled for the v5e --------------------------------

DSV2 = dict(slots=16, heads=128, block=32, max_blocks=256, blocks=4096,
            layers=5, row=640, rank=512, d=5120, f=1536, held=40, k=6)


@pytest.mark.parametrize("window", (1, 8))
def test_latent_kernel_compiles_for_the_v5e_at_the_served_shape(
        for_the_chip, window):
    s = DSV2
    assert paged_attention_takes(s["heads"], window, s["row"], "bfloat16",
                                 value_dim=s["rank"])
    assert not paged_attention_takes(s["heads"], 16, s["row"], "bfloat16",
                                     value_dim=s["rank"])
    pool = for_the_chip((s["layers"], s["blocks"], 1, s["block"],
                         s["row"]), "bfloat16")
    compiled = _compile_kernel(
        for_the_chip, pool, s["slots"], s["heads"], window,
        s["max_blocks"], sm_scale=0.1, value_dim=s["rank"])
    assert "tpu_custom_call" in compiled.as_text()
    assert "mla_paged_attention" in compiled.as_text()
    # the one leaf is read where it lies
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("tokens,temporary", [(16, 1 << 24), (8192, 3 << 29)])
def test_expert_kernel_compiles_for_the_v5e_at_the_served_shape(
        for_the_chip, monkeypatch, tokens, temporary):
    """A decode step's 16 tokens and a prefill's 8192: the row buffer
    holds every pair (8192 x 6 rows + a tile an expert: 1.2 GB of rows in
    and out at the prefill), no weight is copied."""
    from aiko_services_tpu.parallel import experts
    monkeypatch.setattr(experts, "_interpret", lambda: False)
    s = DSV2
    weights = [for_the_chip((4, s["held"]) + shape, "bfloat16") for shape in
               ((s["d"], s["f"]), (s["d"], s["f"]), (s["f"], s["d"]))]
    compiled = jax.jit(experts.expert_ffn).lower(
        for_the_chip((tokens, s["d"]), "bfloat16"), *weights,
        for_the_chip((tokens, s["k"]), "int32"),
        for_the_chip((tokens, s["k"]), "float32"),
        for_the_chip((), "int32")).compile()
    assert "moe_expert_ffn" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < temporary


def test_latent_prefill_kernel_compiles_for_the_v5e(for_the_chip):
    """128 heads score over 192 (padded to 256 lanes) and carry 128, at
    the 8192 bucket: no L x L scores (34 GB in float32) anywhere."""
    compiled = jax.jit(lambda q, k, v: attention.flash_attention(
        q, k, v, causal=True, sm_scale=0.1)).lower(
        for_the_chip((1, 128, 8192, 256), "bfloat16"),
        for_the_chip((1, 128, 8192, 256), "bfloat16"),
        for_the_chip((1, 128, 8192, 128), "bfloat16")).compile()
    assert "mla_flash_attention" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def _served_share(for_the_chip, monkeypatch):
    import json
    import pathlib
    from aiko_services_tpu.models.configs import deepseek_v2_config
    from aiko_services_tpu.parallel import experts
    monkeypatch.setattr(experts, "_interpret", lambda: False)
    published = json.loads((pathlib.Path(__file__).parent.parent
                            / "benchmark/configs/deepseek_v2_ep4_l5.json"
                            ).read_text())
    config = deepseek_v2_config(published,
                                published["serve"]["max_context"])
    place = lambda tree: jax.tree_util.tree_map(       # noqa: E731
        lambda leaf: for_the_chip(leaf.shape, leaf.dtype), tree)
    params = place(jax.eval_shape(
        lambda: init_params(config, jax.random.PRNGKey(0))))
    pool = place(jax.eval_shape(lambda: init_paged_pool(
        config, DSV2["blocks"], DSV2["block"])))
    return config, params, pool, lambda *shape: for_the_chip(shape, "int32")


def test_served_share_decode_step_copies_no_pool_and_no_expert(
        for_the_chip, monkeypatch):
    """DeepSeek-V2's share, 16 slots: the latent pool (one leaf, 640
    values a position a layer) is updated where it lies, and the stacked
    expert weights are read where they lie -- sliced by the layer scan
    for the kernel they were copied, 1.9 GB a layer."""
    s = DSV2
    config, params, pool, int32 = _served_share(for_the_chip, monkeypatch)
    assert set(pool) == {"kv"} and pool["kv"].shape[-1] == s["row"] <= 640
    slots = s["slots"]
    compiled = paged_decode_step.lower(
        params, config, pool, int32(slots, s["max_blocks"]), int32(slots),
        int32(slots, 1), int32(slots), int32(slots)).compile()
    text = compiled.as_text()
    assert "mla_paged_attention" in text and "moe_expert_ffn" in text
    leaf = pool["kv"]
    leaf_bytes = int(np.prod(leaf.shape)) * leaf.dtype.itemsize
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == leaf_bytes
    assert memory.temp_size_in_bytes < 1 << 27
    assert not _made_of_a_leaf(text, leaf, " copy(")
    assert not _made_of_a_leaf(text, leaf, " dynamic-update-slice(")
    # weights 10.07 GB + pool 0.84 GB: what the chip holds
    assert 10.8e9 < memory.argument_size_in_bytes < 11.0e9


def test_served_share_prefill_fits_the_chip_at_the_8192_bucket(
        for_the_chip, monkeypatch):
    config, params, pool, int32 = _served_share(for_the_chip, monkeypatch)
    compiled = paged_prefill.lower(
        params, config, pool, int32(1, 8192), int32(DSV2["max_blocks"]),
        int32()).compile()
    text = compiled.as_text()
    assert "mla_flash_attention" in text and "moe_expert_ffn" in text
    memory = compiled.memory_analysis()
    # 2.06 GB when written: q, k, v of 128 heads, the experts' row buffer;
    # 1.88 since the head runs at one position (839 MB of float32 logits
    # at all 8192 were not the peak: 0.26 GB came off it); told the
    # prompt's length (PR 40) 1,876,110,848 B against the parent's
    # 1,876,014,592
    assert memory.temp_size_in_bytes <= 1_876_014_592 + (1 << 20)
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < 15.0e9)


@pytest.mark.parametrize("served", ["mistral7b_l16", "deepseek_v2_ep4_l5"])
def test_served_prefill_runs_the_head_at_one_position(
        for_the_chip, monkeypatch, served):
    """The lowered bucket's program (4096 of lm.longprompt, 8192 of
    dsv2.longgen) holds no float32 logits of the bucket (524 MB, 839 MB:
    one row was read) but the one position's, and a row loop of a traced
    trip count for each row-wise segment of a layer."""
    if served == "mistral7b_l16":
        config, params, pool, int32 = _served_model(for_the_chip)
        bucket, max_blocks, stacks = 4096, SERVED["max_blocks"], 1
    else:
        config, params, pool, int32 = _served_share(for_the_chip,
                                                    monkeypatch)
        bucket, max_blocks, stacks = 8192, DSV2["max_blocks"], 2
    text = paged_prefill.lower(
        params, config, pool, int32(1, bucket), int32(max_blocks),
        int32()).as_text()
    vocab = config.vocab_size
    assert f"{bucket}x{vocab}xf32" not in text
    assert f"1x1x{vocab}xf32" in text
    # a layer scan a stack, and inside each the loops over row tiles
    assert text.count("stablehlo.while") >= 3 * stacks


# -- the decode step multiplies its weights where they lie -------------------
#
# A layer scan's slice of a stacked (layers, in, out) projection whose
# product is then split into heads and rotated was copied transposed before
# its matmul, every layer of every step: Mistral's wq and wk (35.9 + 9.1 us
# a layer on the chip), DeepSeek-V2's wq_b, whose 75.5 MB slice was also
# written to HBM first (77.8 MB of temporaries), and wkv_b.  Stored (out,
# in) the copies go, but the slice is still staged whole through fast
# memory before the matmul reads it from there (wq 45 + 12 us a layer where
# the read alone is 45; wq_b 101 + 101).  So wq and wk are stored (out, in)
# and ride the scan split by head (transformer._by_head), wq_b is stored
# (nope + rope, H, q_rank) and wkv_b as the two operands of the absorbed
# products, heads leading (transformer._init_latent_attention): each
# matmul reads its slice of the stack in place, as wv, wo and the FFN's do.
#
# What the other programs still stage, compiled the same way (PR 33), so
# that nobody need recompile to know.  _generate_compiled (the graph's LM,
# batch 32, 16 + 32 tokens): the whole stack of wv, bf16[16,4096,1024],
# transposed once a call in the entry computation, 134 MB (with wq and wk
# it was 805 MB; temporaries 907 -> 236 MB), and wv's slice through fast
# memory in the token loop.  The 4096-bucket paged_prefill: wv's slice
# copied transposed every layer (bf16[1,4096,1024]), one 4096 x 4096 leaf
# transposed asynchronously a layer and once in the entry computation,
# about 0.05 ms of a 12 ms layer.  The share's 8192-bucket prefill stages
# wq_b's slice (75.5 MB, 0.1 ms of a 70 ms layer) and re-lays it out inside
# the matmul: a prefill is bound by the MXU and the step by bytes, so the
# step decides a leaf's layout.

_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$")
_STAGED = re.compile(
    r"%?([\w.\-]+) = \w+\[([\d,]+)\]\S* (copy|fusion|dynamic-slice)\(")


def _staged_whole(text: str, element_counts: set) -> list:
    """The instructions of a compiled program that give a layer's weights
    a buffer of their own: a `copy`, or a slice of the stack (alone or as
    a `...dynamic-slice_fusion`), whose result has one of `element_counts`
    elements, as "computation: instruction".  Outside fusions only: what
    is fused into a matmul's operand load moves nothing, and the
    temporaries' size says so."""
    found, computation = [], ""
    for line in text.splitlines():
        header = _COMPUTATION.match(line)
        if header:
            computation = header.group(1)
        staged = _STAGED.search(line)
        if (staged and not computation.startswith("fused_computation")
                and (staged.group(3) != "fusion"
                     or "dynamic-slice" in staged.group(1))
                and math.prod(map(int, staged.group(2).split(",")))
                in element_counts):
            found.append(f"{computation}: {line.strip()[:120]}")
    return found


@pytest.mark.parametrize("served", ["mistral7b_l16", "deepseek_v2_ep4_l5",
                                    "ouro_2.6b"])
def test_served_decode_step_stages_no_projection_on_the_v5e(
        for_the_chip, monkeypatch, served):
    if served == "mistral7b_l16":
        s = SERVED
        config, params, pool, int32 = _served_model(for_the_chip)
    elif served == "ouro_2.6b":
        s = OURO
        config, params, pool, int32 = _served_ouro(for_the_chip)
    else:
        s = DSV2
        config, params, pool, int32 = _served_share(for_the_chip,
                                                    monkeypatch)
    slots = s["slots"]
    compiled = paged_decode_step.lower(
        params, config, pool, int32(slots, s["max_blocks"]), int32(slots),
        int32(slots, 1), int32(slots), int32(slots)).compile()
    # a layer's share of every stacked weight, in the scanned stack and
    # among the leading dense layers (staged, if at all, in the entry
    # computation: one layer is no loop)
    per_layer = {math.prod(leaf.shape[1:])
                 for name in ("layers", "dense_layers") if name in params
                 for leaf in jax.tree_util.tree_leaves(params[name])
                 if leaf.ndim > 2}
    assert per_layer & {4096 * 4096, 24576 * 1536, 2048 * 5632}
    staged = _staged_whole(compiled.as_text(), per_layer)
    assert not staged, staged
    # 1.45 MB and 2.3 MB when written; the share's was 77.8 MB, wq_b's
    # slice of the stack materialised in HBM before its transposed copy
    assert compiled.memory_analysis().temp_size_in_bytes < 8 << 20


# benchmark/configs/jamba2_3b.json: 26 Mamba layers whose state is a slot's
# beside 2 attention layers of 20 query heads to ONE K/V head, 32 slots of
# 176 blocks
JAMBA = dict(slots=32, block=32, max_blocks=176, states=26, caches=2)


def _served_jamba(for_the_chip, monkeypatch):
    """(config, params, pool, int32): Jamba2-3B as jamba.think serves it,
    the slots' recurrent state among the pool's leaves, placed on the
    described chip; the scan kernel lowered through Mosaic."""
    import json
    import pathlib
    from aiko_services_tpu.models.configs import jamba_config
    from aiko_services_tpu.models.transformer import init_recurrent_state
    from aiko_services_tpu.parallel import ssm
    monkeypatch.setattr(ssm, "_interpret", lambda: False)
    published = json.loads((pathlib.Path(__file__).parent.parent
                            / "benchmark/configs/jamba2_3b.json"
                            ).read_text())
    serve = published["serve"]
    s = JAMBA
    assert (serve["decode_slots"], serve["kv_block_size"],
            serve["max_context"]) == (
        s["slots"], s["block"], s["block"] * s["max_blocks"])
    assert serve["kv_blocks"] == s["slots"] * s["max_blocks"] + 1
    config = jamba_config(published, serve["max_context"])
    assert (config.n_states, config.n_caches) == (s["states"], s["caches"])
    place = lambda tree: jax.tree_util.tree_map(       # noqa: E731
        lambda leaf: for_the_chip(leaf.shape, leaf.dtype), tree)
    params = place(jax.eval_shape(
        lambda: init_params(config, jax.random.PRNGKey(0))))
    pool = place(jax.eval_shape(lambda: {
        **init_paged_pool(config, serve["kv_blocks"], s["block"]),
        **init_recurrent_state(config, s["slots"])}))
    return config, params, pool, lambda *shape: for_the_chip(shape, "int32")


def test_served_jamba_decode_step_copies_no_state_leaf_on_the_v5e(
        for_the_chip, monkeypatch):
    """The step advances every slot's convolution tail and SSM state a
    row, in place: the donated leaves (0.30 GB of state, 0.18 GB of K/V)
    come back as the buffers they were, no copy of either state leaf, as
    none of a pool leaf; the SSM state through `ssm_row_step`, which
    takes the whole leaf aliased and writes a layer's blocks where they
    lie (ISSUE 45: no dynamic-update-slice of the leaf is left); the 20
    query heads attend over their one K/V head through the paged kernel,
    which writes the step's new rows."""
    s = JAMBA
    config, params, pool, int32 = _served_jamba(for_the_chip, monkeypatch)
    assert pool["conv"].shape == (26, 3, 32, 5120)
    assert pool["ssm"].shape == (26, 32, 16, 5120)
    assert pool["k"].shape == (2, 5633, 1, 32, 128)
    slots = s["slots"]
    compiled = paged_decode_step.lower(
        params, config, pool, int32(slots, s["max_blocks"]), int32(slots),
        int32(slots, 1), int32(slots), int32(slots)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "paged_attention" in text
    assert "ssm_row_step" in text
    memory = compiled.memory_analysis()
    held = sum(int(np.prod(leaf.shape)) * leaf.dtype.itemsize
               for leaf in pool.values())
    assert memory.alias_size_in_bytes == held
    assert 32 * 26 * 358_400 < held < 32 * 26 * 358_400 + 190e6
    assert memory.temp_size_in_bytes < 8 << 20
    for name in ("conv", "ssm", "k", "v"):
        assert not _made_of_a_leaf(text, pool[name], " copy("), name
    for name in ("ssm", "k"):
        assert not _made_of_a_leaf(text, pool[name],
                                   " dynamic-update-slice("), name
    # weights 6.06 GB + state 0.30 + K/V 0.18: what the chip holds
    assert 6.5e9 < memory.argument_size_in_bytes < 6.6e9


def test_served_jamba_prefill_scans_through_the_kernel_on_the_v5e(
        for_the_chip, monkeypatch):
    """The 4096 bucket: 26 selective scans through `ssm_chunk_scan` (S in
    VMEM: nothing of rows x d_inner x d_state in HBM, 1.3 GB a tensor a
    layer), the two attention layers through the flash kernel, the slot's
    state written into the donated leaves."""
    s = JAMBA
    config, params, pool, int32 = _served_jamba(for_the_chip, monkeypatch)
    compiled = paged_prefill.lower(
        params, config, pool, int32(1, 4096), int32(s["max_blocks"]),
        int32(), int32()).compile()
    text = compiled.as_text()
    assert "ssm_chunk_scan" in text
    memory = compiled.memory_analysis()
    # 146 MB with the bucket run whole; 67 MB by row tiles (ISSUE 44)
    assert memory.temp_size_in_bytes < 1 << 29
    for name in ("conv", "ssm"):
        assert not _made_of_a_leaf(text, pool[name], " copy("), name
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < 7.2e9)
    # the tile loop inside a Mamba layer reads w_in where it lies in the
    # run's stack (52 MB a layer: _LayerAt's slice, tied to the loop)
    assert not _staged_whole(text, {2560 * 2 * 5120})


def _traced(monkeypatch, kind: str) -> list:
    """A list that grows by one each time the mixer of recurrent layers
    of `kind` is traced from here on."""
    from aiko_services_tpu.models import transformer
    mixer, leaf = transformer._MIXERS[kind]
    calls = []

    def counted(*args):
        calls.append(kind)
        return mixer(*args)

    monkeypatch.setitem(transformer._MIXERS, kind, (counted, leaf))
    return calls


def test_served_jamba_prefill_traces_a_mamba_layer_and_each_kernel_once(
        for_the_chip, monkeypatch):
    """What start-up pays for the 4096 bucket (ISSUE 44; PR 43 was
    refused on setup_s, +1.45 s a program of tracing and lowering): by
    row tiles each of the five runs of layers scans a body of its own,
    and still the Mamba layer is traced once (the three runs share one
    jitted tile, the 26 layers one body as on the parent) and each Pallas
    kernel lowered once (`ssm_chunk_scan` and the flash kernel: two
    custom calls, as on the parent)."""
    s = JAMBA
    config, params, pool, int32 = _served_jamba(for_the_chip, monkeypatch)
    calls = _traced(monkeypatch, "mamba")
    jax.clear_caches()
    text = paged_prefill.lower(
        params, config, pool, int32(1, 4096), int32(s["max_blocks"]),
        int32(), int32()).as_text()
    jax.clear_caches()
    assert len(calls) == 1
    assert text.count("stablehlo.custom_call @tpu_custom_call") == 2
    assert text.count("func.func private @_recurrent_rows") == 1
    # the runs' layer scans, a tile loop a Mamba run, two an attention run
    assert text.count("stablehlo.while") == 5 + 3 + 2 * 2


# One chip's share of Qwen3-Next as qnext.assist serves it
# (benchmark/configs/qwen3_next_ep4_l8.json): 6 Gated DeltaNet layers whose
# state is a slot's beside 2 gated attention layers of 16 query heads of
# 256 over 2 K/V heads, 128 of 512 experts held a layer; 64 slots of 280
# blocks
QNEXT = dict(slots=64, block=32, max_blocks=280, states=6, caches=2)


def _served_qnext(for_the_chip, monkeypatch):
    """(config, params, pool, int32): the share as qnext.assist serves it,
    the slots' recurrent state among the pool's leaves, placed on the
    described chip; the delta and expert kernels lowered through Mosaic."""
    import json
    import pathlib
    from aiko_services_tpu.models.configs import qwen3_next_config
    from aiko_services_tpu.models.transformer import init_recurrent_state
    from aiko_services_tpu.parallel import delta, experts
    monkeypatch.setattr(delta, "_interpret", lambda: False)
    monkeypatch.setattr(experts, "_interpret", lambda: False)
    published = json.loads((pathlib.Path(__file__).parent.parent
                            / "benchmark/configs/qwen3_next_ep4_l8.json"
                            ).read_text())
    serve = published["serve"]
    s = QNEXT
    assert (serve["decode_slots"], serve["kv_block_size"],
            serve["max_context"]) == (
        s["slots"], s["block"], s["block"] * s["max_blocks"])
    assert serve["kv_blocks"] == s["slots"] * s["max_blocks"] + 1
    config = qwen3_next_config(
        {key: value for key, value in published.items()
         if key not in ("serve", "deployment", "assumed", "reduced")},
        serve["max_context"])
    assert (config.n_states, config.n_caches) == (s["states"], s["caches"])
    place = lambda tree: jax.tree_util.tree_map(       # noqa: E731
        lambda leaf: for_the_chip(leaf.shape, leaf.dtype), tree)
    params = place(jax.eval_shape(
        lambda: init_params(config, jax.random.PRNGKey(0))))
    pool = place(jax.eval_shape(lambda: {
        **init_paged_pool(config, serve["kv_blocks"], s["block"]),
        **init_recurrent_state(config, s["slots"])}))
    return config, params, pool, lambda *shape: for_the_chip(shape, "int32")


def test_served_qnext_decode_step_copies_no_state_leaf_on_the_v5e(
        for_the_chip, monkeypatch):
    """The step advances every slot's convolution tail and S a row, in
    place: the donated leaves (0.82 GB of state, 2.35 GB of K/V) come
    back as the buffers they were, no copy of either state leaf, as none
    of a pool leaf; S through `gdn_step`, which reads a slot's heads
    where they lie; the 16 query heads of 256 attend over their 2 K/V
    heads through the paged kernel, which writes the step's new rows;
    the experts through the grouped matmul."""
    s = QNEXT
    config, params, pool, int32 = _served_qnext(for_the_chip, monkeypatch)
    assert pool["conv"].shape == (6, 3, 64, 8192)
    assert pool["delta"].shape == (6, 64, 32, 128, 128)
    assert pool["k"].shape == (2, 17921, 2, 32, 256)
    slots = s["slots"]
    compiled = paged_decode_step.lower(
        params, config, pool, int32(slots, s["max_blocks"]), int32(slots),
        int32(slots, 1), int32(slots), int32(slots)).compile()
    text = compiled.as_text()
    for kernel in ("gdn_step", "paged_attention", "moe_expert_ffn"):
        assert kernel in text, kernel
    memory = compiled.memory_analysis()
    held = sum(int(np.prod(leaf.shape)) * leaf.dtype.itemsize
               for leaf in pool.values())
    assert memory.alias_size_in_bytes == held
    assert held == 64 * 6 * 2_146_304 + 2 * 2 * 17921 * 2 * 32 * 256 * 2
    # the experts' row buffer, twice: 8,832 rows of 2048
    assert memory.temp_size_in_bytes < 96 << 20
    for name in ("conv", "delta", "k", "v"):
        assert not _made_of_a_leaf(text, pool[name], " copy("), name
    assert not _made_of_a_leaf(text, pool["k"], " dynamic-update-slice(")
    assert not _made_of_a_leaf(text, pool["delta"],
                               " dynamic-update-slice(")
    # weights 7.18 GB + state 0.82 + K/V 2.35: what the chip holds
    assert 10.3e9 < memory.argument_size_in_bytes < 10.4e9


@pytest.mark.parametrize("bucket", [4096, 8192])
def test_served_qnext_prefill_fits_the_chip_at_its_warm_buckets(
        for_the_chip, monkeypatch, bucket):
    """The buckets qnext.assist sends: six chunkwise delta rules whose S
    crosses the chunks in XLA's scan, two attention layers of 256-wide
    heads through the flash kernel, the experts' row buffer (114,688 rows
    at 8192), the slot's state written into the donated leaves; with the
    arguments under 12 GB of the chip's 16."""
    s = QNEXT
    config, params, pool, int32 = _served_qnext(for_the_chip, monkeypatch)
    compiled = paged_prefill.lower(
        params, config, pool, int32(1, bucket), int32(s["max_blocks"]),
        int32(), int32()).compile()
    text = compiled.as_text()
    for kernel in ("flash_attention", "moe_expert_ffn"):
        assert kernel in text, kernel
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 1.5e9
    for name in ("conv", "delta"):
        assert not _made_of_a_leaf(text, pool[name], " copy("), name
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < 12e9)
    # the tile loop inside a delta layer reads w_qkvz where it lies in the
    # run's stack (50 MB a layer)
    assert not _staged_whole(text, {2048 * (8192 + 4096)})


def test_served_qnext_prefill_traces_a_delta_layer_once(for_the_chip,
                                                        monkeypatch):
    """The 8192 bucket's start-up cost, as Jamba's: the two runs of delta
    layers share one traced and one lowered tile, chunkwise rule and all,
    and no kernel is lowered more often than on the parent (the flash
    kernel once, the experts' once a run's body: five custom calls)."""
    s = QNEXT
    config, params, pool, int32 = _served_qnext(for_the_chip, monkeypatch)
    calls = _traced(monkeypatch, "delta")
    jax.clear_caches()
    text = paged_prefill.lower(
        params, config, pool, int32(1, 8192), int32(s["max_blocks"]),
        int32(), int32()).as_text()
    jax.clear_caches()
    assert len(calls) == 1
    assert text.count("stablehlo.custom_call @tpu_custom_call") == 5
    assert text.count("func.func private @_recurrent_rows") == 1


# One pipeline stage of MiniCPM-SALA as sala.longdoc serves it
# (benchmark/configs/minicpm_sala_pp4_l8.json): 6 lightning-attention
# layers whose state is a slot's beside 2 attention layers that select 64
# of a context's blocks of 64 by the compressed keys stored beside K/V; 16
# slots of 560 blocks
SALA = dict(slots=16, block=64, max_blocks=560, states=6, caches=2)


def _served_sala(for_the_chip, monkeypatch):
    """(config, params, pool, int32): the stage as sala.longdoc serves it,
    the slots' states and the compressed keys among the pool's leaves,
    placed on the described chip; the lightning step lowered through
    Mosaic."""
    import json
    import pathlib
    from aiko_services_tpu.models.configs import minicpm_sala_config
    from aiko_services_tpu.models.transformer import init_recurrent_state
    from aiko_services_tpu.parallel import lightning
    monkeypatch.setattr(lightning, "_interpret", lambda: False)
    published = json.loads((pathlib.Path(__file__).parent.parent
                            / "benchmark/configs/minicpm_sala_pp4_l8.json"
                            ).read_text())
    serve = published["serve"]
    s = SALA
    assert (serve["decode_slots"], serve["kv_block_size"],
            serve["max_context"]) == (
        s["slots"], s["block"], s["block"] * s["max_blocks"])
    assert serve["kv_blocks"] == s["slots"] * s["max_blocks"] + 1
    config = minicpm_sala_config(
        {key: value for key, value in published.items()
         if key not in ("serve", "deployment", "assumed", "reduced")},
        serve["max_context"])
    assert (config.n_states, config.n_caches) == (s["states"], s["caches"])
    place = lambda tree: jax.tree_util.tree_map(       # noqa: E731
        lambda leaf: for_the_chip(leaf.shape, leaf.dtype), tree)
    params = place(jax.eval_shape(
        lambda: init_params(config, jax.random.PRNGKey(0))))
    pool = place(jax.eval_shape(lambda: {
        **init_paged_pool(config, serve["kv_blocks"], s["block"]),
        **init_recurrent_state(config, s["slots"])}))
    return config, params, pool, lambda *shape: for_the_chip(shape, "int32")


def test_served_sala_decode_step_stages_nothing_on_the_v5e(for_the_chip,
                                                           monkeypatch):
    """The step advances every slot's S a row in place through
    `lightning_step` and attends over the chosen blocks through the paged
    kernel, the pool seen a K/V head a page: the donated leaves (0.20 GB
    of state, 1.17 GB of K/V, 37 MB of compressed keys) come back as the
    buffers they were, no copy of any of them and no weight staged (a
    5-D split of [q | k | v | g]'s product once made XLA copy 134 MB of
    W_qkvg a layer a step)."""
    s = SALA
    config, params, pool, int32 = _served_sala(for_the_chip, monkeypatch)
    assert pool["lightning"].shape == (6, 16, 32, 128, 128)
    assert pool["k"].shape == (2, 8961, 2, 64, 128)
    assert pool["kc"].shape == (2, 8961, 2, 4, 128)
    slots = s["slots"]
    compiled = paged_decode_step.lower(
        params, config, pool, int32(slots, s["max_blocks"]), int32(slots),
        int32(slots, 1), int32(slots), int32(slots)).compile()
    text = compiled.as_text()
    for kernel in ("lightning_step", "paged_attention"):
        assert kernel in text, kernel
    memory = compiled.memory_analysis()
    held = sum(int(np.prod(leaf.shape)) * leaf.dtype.itemsize
               for leaf in pool.values())
    assert memory.alias_size_in_bytes == held
    assert held == (16 * 6 * (2 << 20) + 2 * 2 * 8961 * 2 * 64 * 128 * 2
                    + 2 * 8961 * 2 * 4 * 128 * 2)
    assert memory.temp_size_in_bytes < 32 << 20
    for name in ("lightning", "k", "v", "kc"):
        assert not _made_of_a_leaf(text, pool[name], " copy("), name
    assert not _made_of_a_leaf(text, pool["lightning"],
                               " dynamic-update-slice(")
    # weights 5.64 GB + state 0.20 + K/V 1.17 + compressed keys 0.04
    assert 7.0e9 < memory.argument_size_in_bytes < 7.1e9


@pytest.mark.parametrize("bucket", [16384, 32768])
def test_served_sala_prefill_fits_the_chip_at_its_warm_buckets(
        for_the_chip, monkeypatch, bucket):
    """The buckets sala.longdoc sends: six chunkwise lightning rules by
    row tiles, two sparse layers whose first 8192 rows attend through the
    flash kernel and whose other rows gather the blocks they choose, a
    tile of 128 rows at a time; the slot's state written into the donated
    leaf; with the arguments under 10 GB of the chip's 16."""
    s = SALA
    config, params, pool, int32 = _served_sala(for_the_chip, monkeypatch)
    compiled = paged_prefill.lower(
        params, config, pool, int32(1, bucket), int32(s["max_blocks"]),
        int32(), int32()).compile()
    text = compiled.as_text()
    assert "flash_attention" in text
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 2.5e9
    assert not _made_of_a_leaf(text, pool["lightning"], " copy(")
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < 10e9)
    # the tile loop inside a lightning layer reads W_qkvg where it lies in
    # the run's stack (134 MB a layer; at 16384 rows the hidden rows have
    # as many elements, so the larger bucket is asked)
    if bucket == 32768:
        assert not _staged_whole(text, {4096 * 16384})
