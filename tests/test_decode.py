# Continuous batching with paged KV (aiko_services_tpu/decode/): the
# block manager's pool invariants, the engine's bit-compatibility with
# the closed-batch generate() path, the zero-recompile shape-stability
# guarantee across admission/eviction storms, exhaustion behavior
# (deferral + preemption, no deadlock), and the LMGenerate
# `continuous: true` pipeline integration.

import queue

from functools import partial

import numpy as np
import pytest

import jax

from aiko_services_tpu.decode import BlockManager, DecodeEngine, TRASH_BLOCK
from aiko_services_tpu.models import TransformerConfig, generate, init_params
from aiko_services_tpu.pipeline import create_pipeline
from aiko_services_tpu.runtime import Process
from aiko_services_tpu.transport import reset_brokers

from helpers import wait_for

ELEMENTS = "aiko_services_tpu.elements"

TINY = dict(vocab_size=64, n_layers=2, n_heads=2, n_kv_heads=2,
            d_model=32, d_ff=64, max_seq_len=64, dtype="float32")


@pytest.fixture(autouse=True)
def clean_brokers():
    reset_brokers()
    yield
    reset_brokers()


@pytest.fixture(scope="module")
def tiny_model():
    config = TransformerConfig(**TINY)
    return init_params(config, jax.random.PRNGKey(0)), config


def reference(params, config, prompt, max_new):
    """Closed-batch greedy completion for ONE exact-length prompt --
    the bit-compatibility oracle for every engine test."""
    out, _ = generate(params, config, np.asarray(prompt)[None],
                      max_new_tokens=max_new)
    return np.asarray(out)[0]


def drain(engine, limit=2000):
    """Step the engine until idle; returns {request_id: Completion}."""
    done = {}
    steps = 0
    while engine.has_work():
        report = engine.step()
        for completion in report.completions:
            done[completion.request_id] = completion
        steps += 1
        assert steps < limit, "engine failed to drain (deadlock?)"
    return done


# -- BlockManager ------------------------------------------------------------

class TestBlockManager:
    def test_capacity_excludes_trash_block(self):
        manager = BlockManager(8, 4)
        assert manager.capacity == 7
        assert manager.free_count == 7

    def test_allocate_is_all_or_nothing(self):
        manager = BlockManager(4, 4)  # capacity 3
        assert manager.allocate(4) is None
        assert manager.free_count == 3  # nothing partially taken
        granted = manager.allocate(3)
        assert len(granted) == 3
        assert TRASH_BLOCK not in granted
        assert manager.allocate(1) is None

    def test_free_returns_blocks_and_rejects_double_free(self):
        manager = BlockManager(4, 4)
        granted = manager.allocate(2)
        manager.free(granted)
        assert manager.free_count == 3
        with pytest.raises(ValueError, match="double free"):
            manager.free([granted[0], granted[0]])
        with pytest.raises(ValueError, match="trash"):
            manager.free([TRASH_BLOCK])

    def test_blocks_for_rounds_up(self):
        manager = BlockManager(8, 4)
        assert manager.blocks_for(1) == 1
        assert manager.blocks_for(4) == 1
        assert manager.blocks_for(5) == 2

    def test_rejects_degenerate_pools(self):
        with pytest.raises(ValueError):
            BlockManager(1, 4)  # no room for trash + one real block
        with pytest.raises(ValueError):
            BlockManager(4, 0)


# -- engine vs closed batch: bit-identical ----------------------------------

def test_engine_matches_generate_bitwise(tiny_model):
    """The acceptance invariant: continuous-mode completions are
    bit-identical to the closed-batch generate() for the same prompts,
    across ragged lengths decoded interleaved in shared slots."""
    params, config = tiny_model
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, 64, size=n).astype(np.int32)
               for n in (5, 9, 3, 12, 7, 4)]
    max_new = 8
    engine = DecodeEngine(params, config, decode_slots=3, kv_block_size=8)
    for index, prompt in enumerate(prompts):
        engine.submit(index, prompt, max_new)
    done = drain(engine)
    assert len(done) == len(prompts)
    for index, prompt in enumerate(prompts):
        expected = reference(params, config, prompt, max_new)
        np.testing.assert_array_equal(done[index].tokens, expected)
    stats = engine.stats()
    assert stats["completed"] == len(prompts)
    assert stats["active_slots"] == 0
    assert stats["free_blocks"] == engine.blocks.capacity  # all returned


def test_engine_counts_the_attention_each_whole_prefill_takes(
        tiny_model, monkeypatch):
    """stats() counts whole prefills by what their bucket's attention
    takes, asking the model (cache_attention_kind, by which the step
    itself decides): the engine holds no predicate of its own.  Where it
    is the flash kernel -- steered here by the threshold, on a config of
    its own so that no einsum program compiled earlier is reused -- the
    engine's tokens still equal the closed batch's."""
    from aiko_services_tpu.decode import engine as engine_module
    from aiko_services_tpu.parallel import attention
    assert "flash_attention_takes" not in vars(engine_module)
    params, config = tiny_model
    prompts = [np.arange(1, n, dtype=np.int32) for n in (6, 10, 4)]
    engine = DecodeEngine(params, config, decode_slots=3, kv_block_size=8)
    for index, prompt in enumerate(prompts):
        engine.submit(index, prompt, 4)
    drain(engine)
    stats = engine.stats()
    assert (stats["prefill_einsum"], stats["prefill_flash"]) == (3, 0)

    monkeypatch.setattr(attention, "_FLASH_MIN_SCORE_BYTES", 0)
    grouped = TransformerConfig(**{**TINY, "n_heads": 4, "n_kv_heads": 2})
    params = init_params(grouped, jax.random.PRNGKey(1))
    engine = DecodeEngine(params, grouped, decode_slots=3, kv_block_size=8)
    for index, prompt in enumerate(prompts):
        engine.submit(index, prompt, 4)
    done = drain(engine)
    stats = engine.stats()
    assert (stats["prefill_einsum"], stats["prefill_flash"]) == (0, 3)
    for index, prompt in enumerate(prompts):
        np.testing.assert_array_equal(
            done[index].tokens, reference(params, grouped, prompt, 4))


def _whole_prefills(engine_kind: str, params, config, prompts,
                    max_new: int = 4):
    """Serve `prompts` through a decode engine or a prefill engine, their
    tokens (the prefill engine's first tokens) the closed batch's;
    returns (the engine, the fields of its `engine.prefill` spans in
    order)."""
    from aiko_services_tpu.decode import PrefillEngine
    recorded = _Order()      # the spans seam, defined below
    if engine_kind == "decode":
        engine = DecodeEngine(params, config, decode_slots=len(prompts),
                              kv_block_size=8, spans=recorded)
        for index, prompt in enumerate(prompts):
            engine.submit(index, prompt, max_new)
        done = drain(engine)
        for index, prompt in enumerate(prompts):
            np.testing.assert_array_equal(
                done[index].tokens,
                reference(params, config, prompt, max_new))
    else:
        engine = PrefillEngine(params, config, kv_block_size=8,
                               spans=recorded)
        for index, prompt in enumerate(prompts):
            engine.submit(index, prompt, max_new)
        firsts = {}
        while engine.has_work():
            for handoff in engine.step():
                firsts[handoff["request_id"]] = handoff["first_token"]
        for index, prompt in enumerate(prompts):
            assert firsts[index] == reference(params, config, prompt, 1)[0]
    return engine, [fields for _, fields in
                    recorded.named("engine.prefill")]


@pytest.mark.parametrize("engine_kind", ["decode", "prefill"])
@pytest.mark.parametrize("tile", [None, 8])
def test_engine_says_the_rows_each_whole_prefill_runs(
        tiny_model, monkeypatch, engine_kind, tile):
    """Every whole `engine.prefill` span of the decode engine and of the
    prefill engine carries `rows`, what models.prefill_rows says of its
    bucket and length (the model step decides by the same predicate: the
    engines hold none), and stats() sums them beside the buckets.  At the
    real tile these buckets are under two tiles and run whole; with the
    tile cut to 8 rows a 32-row bucket runs 24 rows for a prompt of 20 --
    and the tokens are the closed batch's either way."""
    from aiko_services_tpu.decode import disagg, engine as engine_module
    from aiko_services_tpu.models import prefill_rows, transformer
    assert "_ROW_TILE" not in vars(engine_module)
    assert "_ROW_TILE" not in vars(disagg)
    params, config = tiny_model
    if tile is not None:
        monkeypatch.setattr(transformer, "_ROW_TILE", tile)
        jax.clear_caches()
    lengths = (5, 20, 30)
    prompts = [np.arange(1, n + 1, dtype=np.int32) for n in lengths]
    try:
        engine, prefills = _whole_prefills(engine_kind, params, config,
                                           prompts)
    finally:
        if tile is not None:
            jax.clear_caches()
    assert [args["true_len"] for args in prefills] == list(lengths)
    assert [args["bucket"] for args in prefills] == [8, 32, 32]
    assert [args["rows"] for args in prefills] == (
        [8, 32, 32] if tile is None else [8, 24, 32])
    for args in prefills:
        assert args["rows"] == prefill_rows(config, args["bucket"],
                                            args["true_len"])
    stats = engine.stats()
    assert stats["prefill_rows_bucket"] == 72
    assert stats["prefill_rows_run"] == sum(
        args["rows"] for args in prefills)


@pytest.mark.parametrize("engine_kind", ["decode", "prefill"])
def test_engine_says_the_rows_each_whole_prefills_attention_runs(
        monkeypatch, engine_kind):
    """PR 40: every whole `engine.prefill` span of both engines carries
    `attn_rows` beside `rows`, what models.prefill_attention_rows says of
    its bucket and length (the engines hold no predicate of their own),
    never over the bucket, and stats() sums them.  The flash kernel is
    steered to toy sizes (its threshold; query blocks of 128 for a call
    told a live length): a 512-row bucket attends 384 rows for a prompt
    of 300, and the tokens are the closed batch's."""
    from aiko_services_tpu.decode import disagg, engine as engine_module
    from aiko_services_tpu.models import prefill_attention_rows
    from aiko_services_tpu.parallel import attention
    assert "flash_query_block" not in vars(engine_module)
    assert "flash_query_block" not in vars(disagg)
    monkeypatch.setattr(attention, "_FLASH_MIN_SCORE_BYTES", 0)
    monkeypatch.setattr(attention, "_FLASH_LIVE_BLOCK", 128)
    jax.clear_caches()
    config = TransformerConfig(**{**TINY, "n_heads": 4, "n_kv_heads": 2,
                                  "max_seq_len": 520})
    params = init_params(config, jax.random.PRNGKey(2))
    lengths = (5, 100, 300)
    prompts = [1 + np.arange(n, dtype=np.int32) % 60 for n in lengths]
    try:
        engine, prefills = _whole_prefills(engine_kind, params, config,
                                           prompts, max_new=3)
        assert [args["bucket"] for args in prefills] == [8, 128, 512]
        assert [args["attn_rows"] for args in prefills] == [8, 128, 384]
        for args in prefills:
            assert args["attn_rows"] == prefill_attention_rows(
                config, args["bucket"], args["true_len"]) <= args["bucket"]
        stats = engine.stats()
        assert stats["prefill_attn_rows"] == 8 + 128 + 384
        assert stats["prefill_rows_bucket"] == 8 + 128 + 512
    finally:
        jax.clear_caches()


@pytest.mark.parametrize("case", ["plain", "chunked", "int8"])
def test_engine_counts_who_writes_each_windows_rows(tiny_model, case):
    """stats() counts the paged window calls by who puts their new rows
    into the pool, asking the model (pool_write_kind, by which the step
    itself decides): every plain decode step is the kernel's, a prefill
    chunk's window and an int8 pool keep the unrolled updates -- and the
    tokens are the closed batch's either way."""
    from aiko_services_tpu.decode import engine as engine_module
    assert "paged_attention_writes" not in vars(engine_module)
    params, config = tiny_model
    if case == "int8":
        config = TransformerConfig(**{**TINY, "kv_dtype": "int8"})
    prompts = [np.arange(1, n, dtype=np.int32) for n in (6, 10, 4)]
    engine = DecodeEngine(
        params, config, decode_slots=3, kv_block_size=8,
        prefill_chunk_size=4 if case == "chunked" else None)
    for index, prompt in enumerate(prompts):
        engine.submit(index, prompt, 4)
    done = drain(engine)
    stats = engine.stats()
    assert stats["decode_steps"] > 0
    steps, chunks = stats["decode_steps"], stats["prefill_chunks"]
    assert (chunks > 0) == (case == "chunked")
    assert (stats["writes_kernel"], stats["writes_updates"]) == (
        (0, steps) if case == "int8" else (steps, chunks))
    for index, prompt in enumerate(prompts):
        np.testing.assert_array_equal(
            done[index].tokens, reference(params, config, prompt, 4))


# -- the seam: the model says what its programs did, the engines write it --
# down (ISSUE 46).  What a span's model fields and stats()'s model
# counters are is models.prefill_record / window_record / step_counts'
# business; the tests above pin today's names and values, these the seam.

PREDICATES = ("cache_attention_kind", "pool_write_kind", "prefill_rows",
              "prefill_attention_rows", "scan_kind", "scan_rows",
              "state_step_kind")
# every key stats() held before the records (PR 45), by engine
ENGINE_KEYS = {
    "decode": (
        "active_slots", "free_blocks", "waiting", "slots", "blocks",
        "block_size", "admitted", "completed", "preempted",
        "deferred_admissions", "cancelled", "compiles", "prefill_chunks",
        "chunk_interleaves", "spec_windows", "spec_drafted",
        "spec_accepted", "adopted", "adopt_fallbacks", "kv_migrated_bytes",
        "restores", "restore_fallbacks", "restore_replayed_tokens",
        "prefix_hits", "prefix_partial_hits", "prefix_blocks_shared",
        "prefix_evictions", "live_blocks", "table_blocks", "prefill_flash",
        "prefill_einsum", "prefill_rows_run", "prefill_rows_bucket",
        "prefill_attn_rows", "writes_kernel", "writes_updates",
        "decode_steps", "steps_ahead", "overrun_tokens", "experts_read",
        "expert_pairs", "latent_positions", "ut_passes", "cache_rows",
        "exit_expected_step", "state_slots", "state_bytes", "scan_rows",
        "scan_kernel", "scan_jnp", "state_step_kernel", "state_step_jnp"),
    "prefill": (
        "waiting", "active", "block_size", "free_blocks", "submitted",
        "exported", "chunks", "compiles", "exported_bytes",
        "prefill_rows_run", "prefill_rows_bucket", "prefill_attn_rows")}
# what a dense model with one pass, plain K/V and no state never counts
NOT_A_DENSE_MODELS = (
    "experts_read", "expert_pairs", "latent_positions", "ut_passes",
    "cache_rows", "exit_expected_step", "state_slots", "state_bytes",
    "scan_rows", "scan_kernel", "scan_jnp", "state_step_kernel",
    "state_step_jnp")


def _saying_more(record, field, count):
    """`record` with one more field and one more counted name."""
    def wrapped(*args, **kwargs):
        fields, counts = record(*args, **kwargs)
        return {**fields, field: 7}, {**counts, count: 3}
    return wrapped


@pytest.mark.parametrize("engine_kind", ["decode", "prefill"])
def test_engines_write_down_whatever_the_record_says(
        tiny_model, monkeypatch, engine_kind):
    """A record that says one more field and counts one more name shows
    both, on the call's span and in stats(), with no edit to decode/: the
    engines know no name a record returns."""
    from aiko_services_tpu.decode import disagg, engine as engine_module
    module = engine_module if engine_kind == "decode" else disagg
    monkeypatch.setattr(module, "prefill_record", _saying_more(
        module.prefill_record, "said", "prefills_said"))
    if engine_kind == "decode":
        monkeypatch.setattr(module, "window_record", _saying_more(
            module.window_record, "walked", "windows_said"))
        monkeypatch.setattr(module, "step_counts", _saying_more(
            module.step_counts, "device_said", "steps_said"))
    params, config = tiny_model
    prompts = [np.arange(1, n, dtype=np.int32) for n in (6, 10, 4)]
    engine, prefills = _whole_prefills(engine_kind, params, config, prompts)
    stats = engine.stats()
    assert [fields["said"] for fields in prefills] == [7, 7, 7]
    assert stats["prefills_said"] == 9
    # and what the record said before is all there
    assert {"attention", "rows", "attn_rows"} <= set(prefills[0])
    assert stats["prefill_einsum"] == 3
    if engine_kind == "prefill":
        return
    decodes = [fields for _, fields in engine._spans.named("engine.decode")]
    assert decodes and all(fields["walked"] == 7 for fields in decodes)
    assert stats["windows_said"] == 3 * len(decodes)
    assert stats["steps_said"] == 3 * stats["decode_steps"]
    # what the device counted rides the spans opened after its readback
    assert "device_said" not in decodes[0]
    assert decodes[-1]["device_said"] == 7


@pytest.mark.parametrize("module_name", ["engine", "disagg"])
def test_engines_hold_no_predicate_and_read_no_model_field(module_name):
    """decode/engine.py and decode/disagg.py import the programs and the
    records, none of the predicates the records answer by, and read none
    of the config's fields that say which model has which field
    (`state_bytes` only where refuse_recurrent words its message)."""
    import importlib
    import inspect
    module = importlib.import_module(
        f"aiko_services_tpu.decode.{module_name}")
    assert not set(PREDICATES) & set(vars(module))
    source = inspect.getsource(module)
    for field in ("top_k", "ut_steps", "n_caches"):
        assert f"config.{field}" not in source
    assert source.count("config.state_bytes") == (module_name == "engine")
    assert "config.state_bytes" in inspect.getsource(
        importlib.import_module("aiko_services_tpu.decode.engine"
                                ).refuse_recurrent)
    for gone in ("_looped", "_stateful"):
        assert f"def {gone}(" not in source


@pytest.mark.parametrize("engine_kind", ["decode", "prefill"])
def test_stats_hold_every_key_for_every_model(tiny_model, engine_kind):
    """stats() of a dense model holds every key it held before the
    records, at zero where the model has no such field: the engine's own
    literal and, from the model, RECORD_COUNTERS."""
    from aiko_services_tpu.decode import PrefillEngine
    from aiko_services_tpu.models import RECORD_COUNTERS
    params, config = tiny_model
    make = (partial(DecodeEngine, decode_slots=2)
            if engine_kind == "decode" else PrefillEngine)
    fresh = make(params, config, kv_block_size=8).stats()
    assert set(ENGINE_KEYS[engine_kind]) | set(RECORD_COUNTERS) <= set(fresh)
    assert set(NOT_A_DENSE_MODELS) < set(RECORD_COUNTERS)
    assert all(fresh[name] == 0 for name in RECORD_COUNTERS)
    engine, _ = _whole_prefills(
        engine_kind, params, config,
        [np.arange(1, n, dtype=np.int32) for n in (6, 10)])
    stats = engine.stats()
    assert set(fresh) <= set(stats)
    assert stats["prefill_rows_bucket"] == 8 + 16
    assert all(stats[name] == 0 for name in NOT_A_DENSE_MODELS)
    assert isinstance(stats["exit_expected_step"], float)


def test_engine_eos_frees_slot_early(tiny_model):
    """A sequence hitting eos_id completes before max_new; its tokens
    are EOS-padded to the fixed width and its slot frees immediately."""
    params, config = tiny_model
    prompt = np.arange(1, 6, dtype=np.int32)
    probe = DecodeEngine(params, config, decode_slots=1, kv_block_size=8)
    probe.submit(0, prompt, 12)
    tokens = drain(probe)[0].tokens
    # pretend some mid-sequence token is EOS: pick one whose FIRST
    # occurrence is past position 0, so the cut point is unambiguous
    cut = next(k for k in range(1, 12) if tokens[k] not in tokens[:k])
    eos = int(tokens[cut])
    engine = DecodeEngine(params, config, decode_slots=1, kv_block_size=8,
                          eos_id=eos)
    engine.submit(0, prompt, 12)
    completion = drain(engine)[0]
    assert completion.stats["tokens"] == cut + 1
    np.testing.assert_array_equal(completion.tokens[:cut + 1],
                                  tokens[:cut + 1])
    assert (completion.tokens[cut + 1:] == eos).all()


def test_engine_rejects_oversized_request(tiny_model):
    params, config = tiny_model
    engine = DecodeEngine(params, config, decode_slots=1, kv_block_size=8,
                          max_context=32)
    with pytest.raises(ValueError, match="max_context"):
        engine.submit(0, np.arange(1, 30, dtype=np.int32), 16)
    with pytest.raises(ValueError, match="empty"):
        engine.submit(1, np.zeros((0,), np.int32), 4)


def test_engine_admits_prompt_whose_pow2_bucket_overshoots(tiny_model):
    """A non-power-of-two (block-multiple) max_context must admit any
    request with prompt + max_new <= max_context, even when the
    power-of-two prefill bucket rounds past max_context — the bucket is
    clamped, prefill runs at the block-multiple length, and the output
    still matches the closed-batch reference."""
    params, config = tiny_model
    engine = DecodeEngine(params, config, decode_slots=1, kv_block_size=8,
                          max_context=24)
    prompt = np.arange(1, 21, dtype=np.int32)    # bucket(20) pow2 = 32 > 24
    engine.submit(0, prompt, 4)                  # 20 + 4 == 24: fits
    done = drain(engine)
    np.testing.assert_array_equal(done[0].tokens,
                                  reference(params, config, prompt, 4))
    with pytest.raises(ValueError, match="max_context"):
        engine.submit(1, prompt, 5)              # 20 + 5 > 24: real reject


# -- shape stability: the zero-recompile acceptance assertion ---------------

def test_zero_recompiles_across_admission_eviction_storm(tiny_model):
    """After warmup, a seeded sequence of >= 20 admissions/evictions at
    varying prompt lengths triggers ZERO new compiles (ISSUE 6
    acceptance criterion) -- the trash-block masking keeps every
    paged_decode_step / per-bucket paged_prefill shape identical."""
    params, config = tiny_model
    engine = DecodeEngine(params, config, decode_slots=3, kv_block_size=8)
    # warmup: one prompt per prefill bucket reachable under max_context,
    # plus the decode step itself
    for index, length in enumerate((3, 9, 17)):  # buckets 8, 16, 24
        engine.submit(("warmup", index),
                      np.arange(1, length + 1, dtype=np.int32), 3)
    drain(engine)
    warm = engine.compile_count
    assert warm > 0
    rng = np.random.default_rng(42)
    submitted = 0
    completed = 0
    while submitted < 24:
        # ragged arrival: keep the slot array churning (partial
        # occupancy, admissions mid-decode, evictions at EOS)
        for _ in range(int(rng.integers(1, 4))):
            length = int(rng.integers(1, 21))
            engine.submit(("storm", submitted),
                          rng.integers(1, 64, size=length).astype(np.int32),
                          int(rng.integers(1, 8)))
            submitted += 1
        for _ in range(int(rng.integers(1, 5))):
            completed += len(engine.step().completions)
    completed += len(drain(engine))
    assert completed == submitted >= 20
    assert engine.compile_count == warm, (
        f"admission/eviction storm recompiled "
        f"{engine.compile_count - warm} signatures")


# -- pool exhaustion: deferral and preemption -------------------------------

def test_exhausted_pool_defers_admission_without_deadlock(tiny_model):
    """With free slots but no free blocks, admission DEFERS (counter
    incremented, FIFO order kept) and resumes as completions free
    blocks -- the queue always drains."""
    params, config = tiny_model
    # capacity 3 blocks of 8; each request needs 2 prompt blocks, so the
    # second admission must wait for the first completion
    engine = DecodeEngine(params, config, decode_slots=2, kv_block_size=8,
                          kv_blocks=4)
    prompts = {index: np.arange(1, 10, dtype=np.int32) + index
               for index in range(3)}
    for index, prompt in prompts.items():
        engine.submit(index, prompt, 3)
    done = drain(engine)
    assert len(done) == 3
    # counted per deferred REQUEST (not per blocked engine tick): many
    # ticks pass while request 1 waits, but at most requests 1 and 2
    # can defer
    assert 1 <= engine.counters["deferred_admissions"] <= 2
    assert engine.counters["preempted"] == 0
    for index, prompt in prompts.items():
        np.testing.assert_array_equal(
            done[index].tokens, reference(params, config, prompt, 3))


def test_preemption_evicts_youngest_and_stays_deterministic(tiny_model):
    """Mid-decode block growth on an exhausted pool preempts the
    YOUNGEST slot (the oldest always progresses -- no livelock); greedy
    decode makes the re-prefilled victim's output bit-identical."""
    params, config = tiny_model
    # two slots, capacity 5: both admit with 1 block (prompt 4 -> bucket
    # 4), then growth toward 4 blocks each (4 + 12 = 16 positions)
    # exhausts the pool mid-decode
    engine = DecodeEngine(params, config, decode_slots=2, kv_block_size=4,
                          kv_blocks=6)
    prompts = {0: np.arange(1, 5, dtype=np.int32),
               1: np.arange(11, 15, dtype=np.int32)}
    for index, prompt in prompts.items():
        engine.submit(index, prompt, 12)
    done = drain(engine)
    assert engine.counters["preempted"] >= 1
    assert done[1].stats["preemptions"] >= 1  # youngest was the victim
    for index, prompt in prompts.items():
        np.testing.assert_array_equal(
            done[index].tokens, reference(params, config, prompt, 12))


def test_preempted_request_does_not_reemit_streamed_tokens(tiny_model):
    """emitted_upto survives preemption: the regenerated prefix is NOT
    re-surfaced, so a token-streaming consumer sees gapless offsets."""
    params, config = tiny_model
    engine = DecodeEngine(params, config, decode_slots=2, kv_block_size=4,
                          kv_blocks=6)
    for index in range(2):
        engine.submit(index, np.arange(1, 5, dtype=np.int32) + index, 12)
    emitted = {}
    steps = 0
    while engine.has_work():
        report = engine.step()
        for request_id, offset, token in report.emitted:
            emitted.setdefault(request_id, []).append((offset, token))
        steps += 1
        assert steps < 2000
    assert engine.counters["preempted"] >= 1
    for request_id, pairs in emitted.items():
        offsets = [offset for offset, _ in pairs]
        assert offsets == list(range(len(offsets))), (
            f"{request_id}: duplicated/gapped stream offsets {offsets}")
        assert len(pairs) == 12


def test_preemption_mid_chunked_prefill_frees_partial_blocks(tiny_model):
    """A slot preempted BETWEEN prefill chunks discards its partially
    written KV blocks back to the free list (no leak), and the
    re-admitted request still completes bit-identical -- the
    youngest-first policy extended to mid-prefill victims."""
    params, config = tiny_model
    # 2-position blocks: slot 0 (2-token prompt, 18 new) grows a block
    # every other step while slot 1 chunks a 16-token prompt 2 tokens
    # per tick (8 chunks, 8 blocks granted up front).  Capacity 11
    # exhausts on slot 0's growth around tick 6 -- mid-way through
    # slot 1's chunk sequence -- so the youngest (mid-prefill) slot is
    # preempted with blocks partially written.
    engine = DecodeEngine(params, config, decode_slots=2, kv_block_size=2,
                          kv_blocks=12, prefill_chunk_size=2)
    prompts = {0: np.arange(1, 3, dtype=np.int32),
               1: np.arange(11, 27, dtype=np.int32)}
    engine.submit(0, prompts[0], 18)
    engine.step()  # admit + prefill slot 0 (monolithic: bucket == chunk)
    engine.submit(1, prompts[1], 4)
    mid_prefill_preempted = False
    done = {}
    steps = 0
    while engine.has_work():
        slot1 = next((slot for slot in engine.slots
                      if slot is not None
                      and slot.request.request_id == 1), None)
        before = engine.counters["preempted"]
        report = engine.step()
        if (slot1 is not None and slot1.prefilling
                and engine.counters["preempted"] > before):
            mid_prefill_preempted = True
        for completion in report.completions:
            done[completion.request_id] = completion
        steps += 1
        assert steps < 4000
    assert engine.counters["preempted"] >= 1
    assert mid_prefill_preempted, (
        "scenario no longer preempts a mid-prefill slot; retune pool")
    # every block returned: a leaked partial grant would show here
    assert engine.stats()["free_blocks"] == engine.blocks.capacity
    for index, prompt in prompts.items():
        np.testing.assert_array_equal(
            done[index].tokens,
            reference(params, config, prompt, done[index].tokens.size))


def test_cancel_frees_slots_and_waiting(tiny_model):
    params, config = tiny_model
    engine = DecodeEngine(params, config, decode_slots=1, kv_block_size=8)
    for index in range(3):
        engine.submit(("s", index), np.arange(1, 6, dtype=np.int32), 8)
    engine.step()  # admit request 0 into the single slot
    assert engine.cancel(lambda rid: rid[1] != 1) == 2
    assert engine.counters["cancelled"] == 2
    done = drain(engine)
    assert list(done) == [("s", 1)]
    assert engine.stats()["free_blocks"] == engine.blocks.capacity


def test_engine_int8_kv_matches_quantized_generate():
    """The paged pool carries the int8 KV layout (codes + scales);
    pool-backed decode must match the contiguous int8 cache bitwise."""
    config = TransformerConfig(**{**TINY, "kv_dtype": "int8"})
    params = init_params(config, jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 64, size=n).astype(np.int32)
               for n in (6, 11)]
    engine = DecodeEngine(params, config, decode_slots=2, kv_block_size=8)
    for index, prompt in enumerate(prompts):
        engine.submit(index, prompt, 6)
    done = drain(engine)
    for index, prompt in enumerate(prompts):
        np.testing.assert_array_equal(
            done[index].tokens, reference(params, config, prompt, 6))


# -- one step ahead of the readback --------------------------------------------

class _Order:
    """The engine's spans seam, recording (name, fields) in the order
    the spans open: which of dispatch and readback came first."""

    enabled = True

    class _Span:
        def __init__(self, fields):
            self.fields = fields

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

        def set(self, **fields):
            self.fields.update(fields)

    def __init__(self):
        self.events = []

    def span(self, name, request_id=None, **fields):
        self.events.append((name, fields))
        return self._Span(fields)

    def mark(self, name, waited_s=None, request_id=None, **fields):
        pass

    def record_engine_submit(self, request_id):
        pass

    def named(self, *names):
        return [event for event in self.events if event[0] in names]

    def unread(self) -> bool:
        """A step was dispatched and has not been read back."""
        return (len(self.named("engine.decode"))
                > len(self.named("engine.readback")))


def _serve(engine, arrivals, synchronous=False, each_tick=None, emit=None):
    """Drive `engine` over `arrivals` ({tick: [(id, prompt, max_new)]})
    until idle.  `synchronous` reads every step back in its own tick:
    the engine as it was before it ran ahead, the twin whose decisions
    (admissions, preemptions) the run-ahead engine has to repeat.
    `emit` is handed to every step(), and the reports then hold no
    token.  Returns ({id: Completion}, {id: [(offset, token)]})."""
    done, emitted = {}, {}
    tick = 0
    while engine.has_work() or any(at >= tick for at in arrivals):
        for request_id, prompt, max_new in arrivals.get(tick, ()):
            engine.submit(request_id, prompt, max_new)
        report = engine.step(emit)
        if synchronous:
            engine.settle(report)
        for request_id, offset, token in report.emitted:
            emitted.setdefault(request_id, []).append((offset, token))
        for completion in report.completions:
            assert completion.request_id not in done
            done[completion.request_id] = completion
        if each_tick is not None:
            each_tick(tick)
        tick += 1
        assert tick < 2000, "engine failed to drain (deadlock?)"
    return done, emitted


def _expected(params, config, prompt, max_new, eos=None):
    """The closed batch's tokens, cut and padded at `eos` as a
    Completion's are."""
    tokens = np.array(reference(params, config, prompt, max_new))
    if eos is not None and eos in tokens:
        tokens[int(np.argmax(tokens == eos)) + 1:] = eos
    return tokens


def _assert_served(params, config, engine, requests, done, emitted,
                   eos=None):
    for request_id, prompt, max_new in requests:
        expected = _expected(params, config, prompt, max_new, eos)
        np.testing.assert_array_equal(done[request_id].tokens, expected)
        count = done[request_id].stats["tokens"]
        assert emitted[request_id] == [
            (offset, int(expected[offset])) for offset in range(count)]
    stats = engine.stats()
    assert (stats["free_blocks"] + stats.get("prefix_cached_blocks", 0)
            == engine.blocks.capacity)
    assert stats["active_slots"] == 0


def _twins(params, config, arrivals, **options):
    """The same traffic through a run-ahead engine and its synchronous
    twin; returns (engine, twin, done, emitted), the twin's results
    already held equal."""
    engine = DecodeEngine(params, config, **options)
    twin = DecodeEngine(params, config, **options)
    done, emitted = _serve(engine, arrivals)
    twin_done, twin_emitted = _serve(twin, arrivals, synchronous=True)
    assert twin.counters["steps_ahead"] == 0
    assert emitted == twin_emitted
    assert sorted(done, key=str) == sorted(twin_done, key=str)
    for name in ("admitted", "completed", "preempted",
                 "deferred_admissions"):
        assert engine.counters[name] == twin.counters[name], name
    return engine, twin, done, emitted


def _run_ahead_storm(params, config, _monkeypatch):
    """(a) Admissions and evictions over more requests than slots."""
    rng = np.random.default_rng(11)
    arrivals, requests = {}, []
    for index in range(14):
        request = (index, rng.integers(1, 64, size=int(
            rng.integers(1, 21))).astype(np.int32),
            int(rng.integers(1, 10)))
        requests.append(request)
        arrivals.setdefault(int(rng.integers(0, 24)), []).append(request)
    engine, _twin, done, emitted = _twins(
        params, config, arrivals, decode_slots=3, kv_block_size=8)
    _assert_served(params, config, engine, requests, done, emitted)
    stats = engine.stats()
    assert stats["preempted"] == 0 and stats["overrun_tokens"] == 0
    assert 0 < stats["steps_ahead"] < stats["decode_steps"]


def _run_ahead_eos(params, config, _monkeypatch):
    """(b) An EOS arrives with the next step already dispatched for its
    slot: tokens cut where the synchronous engine cuts them, the overrun
    dropped and counted, nobody preempted for it, the slot taken by the
    waiting request."""
    prompts = [np.arange(1, 6, dtype=np.int32),
               np.arange(20, 29, dtype=np.int32)]
    tokens = reference(params, config, prompts[0], 12)
    cut = next(k for k in range(1, 11) if tokens[k] not in tokens[:k])
    eos = int(tokens[cut])
    requests = [(index, prompt, 12)
                for index, prompt in enumerate(prompts)]
    # a pool of four blocks beside the trash block: the prompts take
    # one and two, so one is free when the overrun step may want it
    engine, _twin, done, emitted = _twins(
        params, config, {0: requests}, decode_slots=1, kv_block_size=8,
        kv_blocks=5, eos_id=eos)
    _assert_served(params, config, engine, requests, done, emitted,
                   eos=eos)
    assert done[0].stats["tokens"] == cut + 1
    # every request that an EOS ended before its count ran one step over
    overruns = sum(
        1 for _id, prompt, max_new in requests
        if eos in reference(params, config, prompt, max_new)[:-1])
    assert overruns >= 1
    assert engine.counters["overrun_tokens"] == overruns
    assert engine.counters["preempted"] == 0
    assert engine.counters["admitted"] == 2


def _run_ahead_preemption(params, config, _monkeypatch):
    """(c) The pool runs out with a step in flight: it is read before a
    victim is chosen, so the victims are the synchronous engine's."""
    requests = [(0, np.arange(1, 5, dtype=np.int32), 12),
                (1, np.arange(11, 15, dtype=np.int32), 12)]
    engine, _twin, done, emitted = _twins(
        params, config, {0: requests}, decode_slots=2, kv_block_size=4,
        kv_blocks=6)
    assert engine.counters["preempted"] >= 1
    assert done[1].stats["preemptions"] >= 1
    assert engine.counters["steps_ahead"] > 0
    _assert_served(params, config, engine, requests, done, emitted)


def _run_ahead_cancel(params, config, _monkeypatch):
    """(d) A cancel while the victim's token is in flight."""
    order = _Order()
    engine = DecodeEngine(params, config, decode_slots=2, kv_block_size=8,
                          spans=order)
    requests = [(index, np.arange(1, 7, dtype=np.int32) + 3 * index, 10)
                for index in range(3)]
    cancelled = []

    def cancel_the_first(tick):
        if tick == 3:
            assert order.unread()
            cancelled.append(engine.cancel(lambda rid: rid == 0))
            assert not order.unread()

    done, emitted = _serve(engine, {0: requests},
                           each_tick=cancel_the_first)
    assert cancelled == [1] and engine.counters["cancelled"] == 1
    assert sorted(done) == [1, 2]
    # the victim's token in flight was read by the cancel: what it had
    # surfaced by then is the closed batch's, and nothing after it
    assert emitted[0] == [
        (offset, int(token)) for offset, token in enumerate(
            reference(params, config, requests[0][1], 10)[:5])]
    _assert_served(params, config, engine, requests[1:], done, emitted)


def _run_ahead_unread_state(params, config, _monkeypatch):
    """(e) With a step unread: has_work() holds, stats() reads nothing
    back, a prefix export and a checkpoint see positions = prompt +
    generated - 1, and the last tokens still come out."""
    from aiko_services_tpu.decode import (
        CheckpointKeeper, CheckpointPolicy, DecodeCheckpointer)
    order = _Order()
    engine = DecodeEngine(params, config, decode_slots=2, kv_block_size=8,
                          prefix_policy="prefix_cache=on", spans=order)
    keeper = CheckpointKeeper("run-ahead")
    snapshots, store = [], keeper.store

    def spy(snapshot):
        snapshots.append(snapshot)
        store(snapshot)

    keeper.store = spy
    checkpointer = DecodeCheckpointer(
        engine, CheckpointPolicy.parse(
            "checkpoint_every=3;max_checkpoint_lag=32;keeper=run-ahead"),
        keeper=keeper)
    prompt = np.arange(1, 18, dtype=np.int32)  # two whole blocks
    request = (0, prompt, 12)
    unread_ticks = []

    def look(tick):
        if order.unread():
            unread_ticks.append(tick)
            assert engine.has_work()
            reads = len(order.named("engine.readback"))
            stats = engine.stats()
            assert stats["active_slots"] == 1
            exported = engine.export_prefix_snapshot(prompt)
            assert exported["position"] == exported["true_len"] == 16
            assert exported["prompt"] == [int(t) for t in prompt[:16]]
            assert len(order.named("engine.readback")) == reads
        checkpointer.tick()
        slot = engine.slots[0]
        if slot is not None and snapshots and not order.unread():
            assert int(engine.positions[0]) == (
                slot.true_len + len(slot.request.generated) - 1)

    done, emitted = _serve(engine, {0: [request]}, each_tick=look)
    assert len(unread_ticks) >= 6
    assert len(snapshots) >= 3
    for snapshot in snapshots:
        assert snapshot["position"] == (
            snapshot["true_len"] + len(snapshot["generated"]) - 1)
        assert snapshot["blocks_total"] == engine.blocks.blocks_for(
            snapshot["position"])
    assert keeper.flush()
    keeper.stop()
    _assert_served(params, config, engine, [request], done, emitted)


def _run_ahead_order(params, config, monkeypatch):
    """(f) The order itself, for a lone request: step n + 1 is
    dispatched, from step n's tokens as the device array they are,
    before step n is read."""
    from aiko_services_tpu.decode import engine as engine_module
    order = _Order()
    calls = []
    step = engine_module.paged_decode_step

    def recording_step(params, config, pool, tables, positions, tokens,
                       *rest):
        result = step(params, config, pool, tables, positions, tokens,
                      *rest)
        calls.append((tokens, result[1], np.array(positions)))
        return result

    recording_step._cache_size = step._cache_size
    monkeypatch.setattr(engine_module, "paged_decode_step",
                        recording_step)
    engine = DecodeEngine(params, config, decode_slots=2, kv_block_size=8,
                          spans=order)
    request = (0, np.arange(1, 6, dtype=np.int32), 9)
    done, emitted = _serve(engine, {0: [request]})
    _assert_served(params, config, engine, [request], done, emitted)
    stats = engine.stats()
    assert stats["decode_steps"] == len(calls) == 8
    assert stats["steps_ahead"] == stats["decode_steps"] - 1
    assert stats["overrun_tokens"] == 0
    names = [name for name, _ in order.named("engine.decode",
                                             "engine.readback")]
    assert names == (["engine.decode"]
                     + ["engine.decode", "engine.readback"] * 7
                     + ["engine.readback"])
    assert [fields["ahead"] for _, fields in order.named(
        "engine.decode")] == [0] + [1] * 7
    for index, (tokens, _out, positions) in enumerate(calls):
        assert isinstance(tokens, jax.Array)
        assert positions[0] == 5 + index  # advanced at the dispatch
        if index:
            assert tokens is calls[index - 1][1]


RUN_AHEAD_CASES = {
    "storm": _run_ahead_storm, "eos": _run_ahead_eos,
    "preemption": _run_ahead_preemption, "cancel": _run_ahead_cancel,
    "unread_state": _run_ahead_unread_state, "order": _run_ahead_order}


@pytest.mark.parametrize("case", sorted(RUN_AHEAD_CASES))
def test_run_ahead(tiny_model, monkeypatch, case):
    """The plain decode step runs one ahead of its readback, and every
    request's tokens stay the closed batch's to the bit."""
    RUN_AHEAD_CASES[case](*tiny_model, monkeypatch)


# -- a token leaves when the host holds it -------------------------------------

def _self_draft(params, config):
    return dict(draft_params=params, draft_config=config, spec_k=3)


EMIT_CASES = {
    "plain": lambda params, config: {},
    "carried": lambda params, config: {},
    "chunked": lambda params, config: {"prefill_chunk_size": 8},
    "prefix": lambda params, config: {
        "prefix_policy": "prefix_cache=on;min_prefix_blocks=1"},
    "speculative": _self_draft,
}


@pytest.mark.parametrize("case", sorted(EMIT_CASES))
def test_emit_moves_when_a_token_leaves_and_nothing_else(tiny_model, case):
    """An engine stepped with an `emit` hands out the tokens the reports
    would have held, a request's in their order, and gives the device
    the same programs in the same order: only the moment of the
    hand-over moves.  Two requests admitted in one tick: the first's token 0 is
    out before the second's prefill is dispatched."""
    params, config = tiny_model
    options = dict(decode_slots=3, kv_block_size=8,
                   **EMIT_CASES[case](params, config))
    rng = np.random.default_rng(17)
    shared = rng.integers(1, 64, size=16).astype(np.int32)
    requests = [(index, np.concatenate([shared, rng.integers(
        1, 64, size=int(rng.integers(1, 9))).astype(np.int32)]),
        int(rng.integers(2, 10))) for index in range(6)]
    arrivals = {0: requests[:2], 3: requests[2:5], 9: requests[5:]}
    settle_at = (2, 5) if case == "carried" else ()

    order, twin_order = _Order(), _Order()
    engine = DecodeEngine(params, config, spans=order, **options)
    twin = DecodeEngine(params, config, spans=twin_order, **options)
    carried = {engine: 0, twin: 0}

    def settling(engine):
        # the step in flight read between two steps: its tokens ride
        # the carry into the next step
        def each_tick(tick):
            if tick in settle_at:
                engine.settle()
                carried[engine] += len(engine._carry.emitted)
        return each_tick

    done, in_reports = _serve(
        engine, arrivals, each_tick=settling(engine),
        emit=lambda triple: order.events.append(("emit", triple)))
    twin_done, twin_emitted = _serve(twin, arrivals,
                                     each_tick=settling(twin))
    assert in_reports == {}
    emitted: dict = {}
    for _, (request_id, offset, token) in order.named("emit"):
        emitted.setdefault(request_id, []).append((offset, token))
    assert emitted == twin_emitted
    assert carried[engine] == carried[twin]
    assert bool(carried[engine]) == bool(settle_at)
    for request_id, prompt, max_new in requests:
        expected = reference(params, config, prompt, max_new)
        np.testing.assert_array_equal(done[request_id].tokens, expected)
        np.testing.assert_array_equal(twin_done[request_id].tokens,
                                      expected)
        # gapless from 0, whatever surfaced them (a speculative round
        # several at once, a settle between steps through the carry)
        assert emitted[request_id] == [
            (offset, int(expected[offset])) for offset in range(max_new)]
    # what the device was given, and in what order
    device = ("engine.prefill", "engine.decode", "engine.readback")
    assert ([(name, fields.get("bucket"), fields.get("decoding"))
             for name, fields in order.named(*device)]
            == [(name, fields.get("bucket"), fields.get("decoding"))
                for name, fields in twin_order.named(*device)])
    assert engine.stats()["decode_steps"] == twin.stats()["decode_steps"]
    # the first tick admits requests 0 and 1: 0's first token is out
    # before 1's prefill opens (a chunked prefill runs a chunk a tick,
    # the oldest slot's first: 0's three chunks, then its token)
    opened = [name if name != "emit" else fields[:2]
              for name, fields in order.events
              if name in ("emit", "engine.prefill")]
    assert opened[:opened.index((1, 0))].count("engine.prefill") == (
        6 if case == "chunked" else 2)
    assert opened[:opened.index((0, 0))].count("engine.prefill") == (
        3 if case == "chunked" else 1)


def test_step_without_emit_fills_the_report(tiny_model):
    """No `emit`: a tick's tokens leave in `report.emitted` when it
    ends, two admissions' first tokens side by side, as before."""
    params, config = tiny_model
    engine = DecodeEngine(params, config, decode_slots=2, kv_block_size=8)
    prompts = [np.arange(1, 6, dtype=np.int32),
               np.arange(3, 12, dtype=np.int32)]
    for index, prompt in enumerate(prompts):
        engine.submit(index, prompt, 4)
    report = engine.step()
    assert report.emit is None and report.admitted == 2
    assert report.emitted == [
        (index, 0, int(reference(params, config, prompt, 4)[0]))
        for index, prompt in enumerate(prompts)]
    # and a later step handed an `emit` leaves the list alone
    seen = []
    while engine.has_work():
        assert engine.step(seen.append).emitted == []
    assert sorted(seen) == sorted(
        (index, offset, int(token))
        for index, prompt in enumerate(prompts)
        for offset, token in enumerate(
            reference(params, config, prompt, 4)) if offset)


# -- chunked prefill (paged_prefill_chunk) ----------------------------------


class TestChunkedPrefill:
    """ISSUE 11 tentpole (a): chunked prefill must be bit-identical to
    the monolithic paged_prefill path at every chunk size, and must
    actually interleave prefill progress with decode steps."""

    PROMPT_LENGTHS = (5, 21, 3, 33, 7, 12)

    def _run(self, params, config, chunk, max_new=8, **kwargs):
        rng = np.random.default_rng(13)
        prompts = [rng.integers(1, 64, size=n).astype(np.int32)
                   for n in self.PROMPT_LENGTHS]
        engine = DecodeEngine(params, config, decode_slots=3,
                              kv_block_size=8,
                              prefill_chunk_size=chunk, **kwargs)
        for index, prompt in enumerate(prompts):
            engine.submit(index, prompt, max_new)
        return prompts, engine, drain(engine)

    @pytest.mark.parametrize("chunk", (8, 16, 64))
    def test_chunked_matches_monolithic_bitwise(self, tiny_model, chunk):
        """Chunk sizes {1 block, 1 bucket, full prompt}: completions
        equal the closed-batch reference (and therefore the monolithic
        engine, which the other tests pin to the same oracle)."""
        params, config = tiny_model
        prompts, engine, done = self._run(params, config, chunk)
        for index, prompt in enumerate(prompts):
            np.testing.assert_array_equal(
                done[index].tokens, reference(params, config, prompt, 8))
        if chunk < 64:
            assert engine.counters["prefill_chunks"] > 0

    def test_chunked_int8_kv_matches_monolithic(self):
        config = TransformerConfig(**{**TINY, "kv_dtype": "int8"})
        params = init_params(config, jax.random.PRNGKey(0))
        prompts, engine, done = self._run(params, config, 8)
        for index, prompt in enumerate(prompts):
            np.testing.assert_array_equal(
                done[index].tokens, reference(params, config, prompt, 8))

    def test_prefill_interleaves_with_decode(self, tiny_model):
        """The convoy-breaking property itself: while a long prompt is
        mid-prefill, co-scheduled decode slots keep emitting tokens --
        counted by decode.chunk_interleaves."""
        params, config = tiny_model
        engine = DecodeEngine(params, config, decode_slots=2,
                              kv_block_size=8, prefill_chunk_size=8)
        engine.submit("short", np.arange(1, 4, dtype=np.int32), 24)
        engine.step()  # short prompt admitted and decoding
        engine.submit("long", np.arange(1, 34, dtype=np.int32), 4)
        interleaved_tokens = 0
        steps = 0
        while engine.has_work():
            long_slot = next(
                (slot for slot in engine.slots if slot is not None
                 and slot.request.request_id == "long"), None)
            mid_prefill = long_slot is not None and long_slot.prefilling
            report = engine.step()
            if mid_prefill:
                interleaved_tokens += sum(
                    1 for rid, _, _ in report.emitted if rid == "short")
            steps += 1
            assert steps < 2000
        assert interleaved_tokens > 0, (
            "no short-request tokens decoded during the long prefill")
        assert engine.counters["chunk_interleaves"] > 0

    def test_chunk_size_coerced_to_block_multiple(self, tiny_model):
        params, config = tiny_model
        engine = DecodeEngine(params, config, decode_slots=1,
                              kv_block_size=8, prefill_chunk_size=3)
        assert engine.prefill_chunk == 8  # pow2 floored at block size


# -- speculative decoding (paged_verify_step) -------------------------------


class TestSpeculativeDecoding:
    """ISSUE 11 tentpole (b): greedy-exact speculative decoding --
    draft proposes k, target verifies k+1 positions in one window,
    emitted tokens bit-identical to plain greedy decode."""

    def _models(self):
        config = TransformerConfig(**TINY)
        params = init_params(config, jax.random.PRNGKey(0))
        draft_config = TransformerConfig(**{**TINY, "n_layers": 1})
        draft_params = init_params(draft_config, jax.random.PRNGKey(3))
        return params, config, draft_params, draft_config

    def test_spec_decode_storm_bit_identical(self):
        """The satellite suite: a seeded 20-request engine storm with
        ragged prompt/completion lengths under speculation matches
        plain greedy bit-for-bit, with zero recompiles after warmup."""
        params, config, draft_params, draft_config = self._models()
        engine = DecodeEngine(params, config, decode_slots=3,
                              kv_block_size=8, draft_params=draft_params,
                              draft_config=draft_config, spec_k=3)
        rng = np.random.default_rng(42)
        # warmup: every prefill bucket + the spec-round executables
        for index, length in enumerate((3, 9, 17)):
            engine.submit(("warm", index),
                          np.arange(1, length + 1, dtype=np.int32), 5)
        drain(engine)
        warm = engine.compile_count
        workload = {}
        done = {}
        submitted = 0
        while submitted < 20:
            for _ in range(int(rng.integers(1, 4))):
                length = int(rng.integers(1, 21))
                prompt = rng.integers(1, 64, size=length).astype(np.int32)
                max_new = int(rng.integers(1, 10))
                workload[submitted] = (prompt, max_new)
                engine.submit(submitted, prompt, max_new)
                submitted += 1
            for _ in range(int(rng.integers(1, 5))):
                for completion in engine.step().completions:
                    done[completion.request_id] = completion
        done.update(drain(engine))
        assert len(done) >= 20
        for index, (prompt, max_new) in workload.items():
            np.testing.assert_array_equal(
                done[index].tokens,
                reference(params, config, prompt, max_new))
        assert engine.compile_count == warm, (
            f"speculative storm recompiled "
            f"{engine.compile_count - warm} signatures")
        assert engine.counters["spec_windows"] > 0

    def test_self_draft_accepts_full_window(self):
        """draft == target: every proposal matches, so each verify
        window emits k+1 tokens (modulo the final clipped window) --
        the acceptance accounting sanity check."""
        params, config, _, _ = self._models()
        engine = DecodeEngine(params, config, decode_slots=1,
                              kv_block_size=8, draft_params=params,
                              draft_config=config, spec_k=3)
        engine.submit(0, np.arange(1, 6, dtype=np.int32), 16)
        done = drain(engine)
        np.testing.assert_array_equal(
            done[0].tokens, reference(params, config,
                                      np.arange(1, 6), 16))
        stats = engine.stats()
        assert stats["accepted_len_mean"] > 3.0  # ceiling k+1 = 4
        assert 0.0 < stats["draft_overhead_frac"] < 1.0

    def test_spec_int8_kv_matches_plain(self):
        config = TransformerConfig(**{**TINY, "kv_dtype": "int8"})
        params = init_params(config, jax.random.PRNGKey(0))
        draft_config = TransformerConfig(
            **{**TINY, "kv_dtype": "int8", "n_layers": 1})
        draft_params = init_params(draft_config, jax.random.PRNGKey(3))
        rng = np.random.default_rng(3)
        prompts = [rng.integers(1, 64, size=n).astype(np.int32)
                   for n in (6, 11)]
        engine = DecodeEngine(params, config, decode_slots=2,
                              kv_block_size=8, draft_params=draft_params,
                              draft_config=draft_config, spec_k=2)
        for index, prompt in enumerate(prompts):
            engine.submit(index, prompt, 6)
        done = drain(engine)
        for index, prompt in enumerate(prompts):
            np.testing.assert_array_equal(
                done[index].tokens, reference(params, config, prompt, 6))

    def test_spec_eos_truncates_accepted_run(self, tiny_model):
        """An EOS inside an accepted window stops the run exactly where
        plain greedy decode would."""
        params, config = tiny_model
        prompt = np.arange(1, 6, dtype=np.int32)
        plain = reference(params, config, prompt, 12)
        cut = next(k for k in range(1, 12)
                   if plain[k] not in plain[:k])
        eos = int(plain[cut])
        engine = DecodeEngine(params, config, decode_slots=1,
                              kv_block_size=8, eos_id=eos,
                              draft_params=params, draft_config=config,
                              spec_k=4)
        engine.submit(0, prompt, 12)
        completion = drain(engine)[0]
        assert completion.stats["tokens"] == cut + 1
        np.testing.assert_array_equal(completion.tokens[:cut + 1],
                                      plain[:cut + 1])
        assert (completion.tokens[cut + 1:] == eos).all()

    def test_spec_with_chunked_prefill_storm(self):
        """Acceptance criterion: BOTH features on, a seeded admission
        storm decodes bit-identically with zero engine recompiles
        after warmup."""
        params, config, draft_params, draft_config = self._models()
        engine = DecodeEngine(params, config, decode_slots=3,
                              kv_block_size=8, prefill_chunk_size=8,
                              draft_params=draft_params,
                              draft_config=draft_config, spec_k=3)
        rng = np.random.default_rng(7)
        for index, length in enumerate((3, 9, 17, 33)):
            engine.submit(("warm", index),
                          np.arange(1, length + 1, dtype=np.int32), 3)
        drain(engine)
        warm = engine.compile_count
        workload = {}
        done = {}
        submitted = 0
        while submitted < 20:
            for _ in range(int(rng.integers(1, 4))):
                length = int(rng.integers(1, 40))
                prompt = rng.integers(1, 64, size=length).astype(np.int32)
                max_new = int(rng.integers(1, 8))
                workload[submitted] = (prompt, max_new)
                engine.submit(submitted, prompt, max_new)
                submitted += 1
            for _ in range(int(rng.integers(1, 5))):
                for completion in engine.step().completions:
                    done[completion.request_id] = completion
        done.update(drain(engine))
        for index, (prompt, max_new) in workload.items():
            np.testing.assert_array_equal(
                done[index].tokens,
                reference(params, config, prompt, max_new))
        assert engine.compile_count == warm
        assert engine.counters["prefill_chunks"] > 0
        assert engine.counters["spec_windows"] > 0

    def test_spec_rejects_mismatched_vocab_and_partial_config(self):
        params, config, draft_params, draft_config = self._models()
        from dataclasses import replace
        bad = replace(draft_config, vocab_size=32)
        with pytest.raises(ValueError, match="vocab"):
            DecodeEngine(params, config, draft_params=draft_params,
                         draft_config=bad)
        with pytest.raises(ValueError, match="BOTH"):
            DecodeEngine(params, config, draft_params=draft_params)
        with pytest.raises(ValueError, match="draft model"):
            DecodeEngine(params, config, spec_k=3)


# -- LMGenerate `continuous: true` pipeline integration ---------------------

LM_PARAMS = {"vocab_size": 300, "d_model": 32, "n_layers": 1,
             "n_heads": 2, "n_kv_heads": 1, "d_ff": 64,
             "max_seq_len": 128, "dtype": "float32", "max_new_tokens": 6}


def lm_definition(extra_parameters):
    return {
        "name": "lm_pipe",
        "graph": ["(lm)"],
        "elements": [
            {"name": "lm", "input": [{"name": "tokens"}],
             "output": [{"name": "generated"}],
             "parameters": {**LM_PARAMS, **extra_parameters},
             "deploy": {"local": {"module": ELEMENTS,
                                  "class_name": "LMGenerate"}}},
        ],
    }


def run_lm_frames(extra_parameters, frames, wait_out=0):
    """Run frames through a one-element LMGenerate pipeline; with
    `wait_out`, also wait for that many `/out` publishes BEFORE
    terminating (the response queue bypasses the broker, so the
    response can land while /out messages are still in flight)."""
    process = Process(transport_kind="loopback")
    pipeline = create_pipeline(process, lm_definition(extra_parameters))
    streamed = []
    if wait_out:
        process.add_message_handler(
            lambda topic, payload: streamed.append(payload),
            f"{pipeline.elements['lm'].topic_path}/out")
    process.run(in_thread=True)
    responses = queue.Queue()
    stream = pipeline.create_stream("s", queue_response=responses,
                                    grace_time=300)
    for frame in frames:
        pipeline.create_frame(stream, {"tokens": frame})
    results = [responses.get(timeout=120) for _ in range(len(frames))]
    if wait_out:
        wait_for(lambda: len(streamed) >= wait_out, timeout=30)
    lm_element = pipeline.elements["lm"]
    process.terminate()
    return results, streamed, lm_element


def test_continuous_pipeline_bit_identical_to_closed_batch():
    """ISSUE 6 acceptance: the SAME frames through `continuous: true`
    and the closed-batch path produce bit-identical completions -- and
    responses arrive per-frame, in frame order, from interleaved
    decoding."""
    rng = np.random.default_rng(0)
    frames = [rng.integers(1, 300, size=(2, 7)).astype(np.int32)
              for _ in range(3)]
    closed, _, _ = run_lm_frames({}, frames)
    continuous, _, lm_element = run_lm_frames(
        {"continuous": True, "decode_slots": 3, "kv_block_size": 8},
        frames)
    for (_, closed_frame, closed_out), (_, cont_frame, cont_out) in zip(
            closed, continuous):
        assert closed_frame.frame_id == cont_frame.frame_id
        np.testing.assert_array_equal(
            np.asarray(closed_out["generated"]),
            np.asarray(cont_out["generated"]))
    stats = lm_element.engine_stats()
    assert stats["completed"] == sum(frame.shape[0] for frame in frames)
    assert stats["active_slots"] == 0 and stats["waiting"] == 0


def test_continuous_pipeline_with_kernel_floor_features_bit_identical():
    """The AIKO405 surface end-to-end: `prefill_chunk_size` +
    `speculative: draft=self;k=3;layers=...` through LMGenerate produce
    completions bit-identical to the plain closed-batch path, and the
    engine telemetry (accepted-length mean, chunk counters) reaches
    engine_stats()."""
    rng = np.random.default_rng(21)
    frames = [rng.integers(1, 300, size=(2, 17)).astype(np.int32)
              for _ in range(2)]
    closed, _, _ = run_lm_frames({}, frames)
    continuous, _, lm_element = run_lm_frames(
        {"continuous": True, "decode_slots": 3, "kv_block_size": 8,
         "prefill_chunk_size": 8,
         "speculative": "draft=self;k=3;layers=1;seed=9"},
        frames)
    for (_, closed_frame, closed_out), (_, _, cont_out) in zip(
            closed, continuous):
        np.testing.assert_array_equal(
            np.asarray(closed_out["generated"]),
            np.asarray(cont_out["generated"]))
    stats = lm_element.engine_stats()
    assert stats["prefill_chunks"] > 0
    assert stats["spec_windows"] > 0
    assert stats["accepted_len_mean"] >= 1.0
    assert 0.0 <= stats["draft_overhead_frac"] <= 1.0
    assert stats["prefill_chunk_size"] == 8 and stats["spec_k"] == 3


def test_speculative_parameter_rejects_bad_spec():
    """A malformed `speculative` spec fails the first continuous frame
    with the same GrammarError message offline lint reports (AIKO405),
    not a cryptic engine crash."""
    from aiko_services_tpu.analyze.policies import (
        check_decode_parameters, parse_speculative_spec)

    with pytest.raises(ValueError, match="speculative"):
        parse_speculative_spec("draft=self")          # missing k
    with pytest.raises(ValueError, match="unknown"):
        parse_speculative_spec("draft=self;k=2;warp=9")
    with pytest.raises(ValueError, match="draft=self"):
        parse_speculative_spec("draft=toy;k=2;layers=1")
    problems = check_decode_parameters(
        {"continuous": True, "speculative": "draft=self;k=0"})
    assert any(code == "AIKO405" for code, _ in problems)
    # both features demand the continuous engine
    problems = check_decode_parameters(
        {"speculative": "draft=self;k=2", "prefill_chunk_size": 16})
    codes = [code for code, _ in problems]
    assert codes.count("AIKO405") == 2


def test_continuous_pipeline_zero_recompiles_after_warmup():
    """Same-shape traffic after the first frame re-uses the warmed
    executables: the engine's compile counter is flat across frames
    2..N even though every frame is a fresh admission/eviction cycle."""
    rng = np.random.default_rng(1)
    frames = [rng.integers(1, 300, size=(1, 9)).astype(np.int32)
              for _ in range(4)]
    process = Process(transport_kind="loopback")
    pipeline = create_pipeline(process, lm_definition(
        {"continuous": True, "decode_slots": 2, "kv_block_size": 8}))
    process.run(in_thread=True)
    responses = queue.Queue()
    stream = pipeline.create_stream("s", queue_response=responses,
                                    grace_time=300)
    pipeline.create_frame(stream, {"tokens": frames[0]})
    responses.get(timeout=120)
    warm = pipeline.elements["lm"].engine_stats()["compiles"]
    for frame in frames[1:]:
        pipeline.create_frame(stream, {"tokens": frame})
    for _ in frames[1:]:
        responses.get(timeout=120)
    assert pipeline.elements["lm"].engine_stats()["compiles"] == warm
    process.terminate()


def _row_chunks(streamed):
    """{(frame_id, row): [(offset, [tokens])]} of the `(token_chunk
    stream_id frame_id row offset payload)` publishes, in the order
    they came."""
    from aiko_services_tpu.utils import parse
    rows: dict = {}
    for payload in streamed:
        command, parameters = parse(payload)
        if command == "token_chunk":
            rows.setdefault(
                (int(parameters[1]), int(parameters[2])), []).append(
                (int(parameters[3]),
                 [int(token) for token in parameters[4][0]]))
    return rows


# stream_chunk, the element's other parameters, the count of
# engine_stats() that says the case ran what it names
STREAMING_CASES = {
    "chunk1": (1, {}, "decode_steps"), "chunk2": (2, {}, "decode_steps"),
    "chunk8": (8, {}, "decode_steps"),
    "chunk3_chunked_prefill": (3, {"prefill_chunk_size": 8},
                               "prefill_chunks"),
    "chunk3_prefix_hit": (3, {"prefix_policy":
                              "prefix_cache=on;min_prefix_blocks=1"},
                          "prefix_hits"),
    "chunk3_speculative": (3, {"speculative": "draft=self;k=3;layers=1"},
                           "spec_windows"),
}


@pytest.mark.parametrize("case", sorted(STREAMING_CASES))
def test_continuous_token_streaming_chunks(case):
    """`stream_tokens` under the engine publishes per-ROW chunks
    `(token_chunk stream_id frame_id row offset payload)` -- a DISTINCT
    command from the closed-batch `(tokens stream_id offset payload)`
    schema.  A row's first token is a chunk of its own at offset 0,
    out the moment the host holds it; chunks of `stream_chunk` follow,
    the last what is left; offsets are gapless and the chunks joined
    are the answer, whoever made the first token (a whole prefill, a
    chunked one's last chunk, a prefix hit's tail) and however many a
    round surfaces (a speculative one several)."""
    stream_chunk, extra, ran = STREAMING_CASES[case]
    rng = np.random.default_rng(2)
    shared = rng.integers(1, 300, size=(1, 16)).astype(np.int32)
    frames = [np.concatenate([np.repeat(shared, 2, axis=0), rng.integers(
        1, 300, size=(2, 5)).astype(np.int32)], axis=1) for _ in range(2)]
    new_tokens = 12
    publishes = 2 * 2 * (1 + -(-(new_tokens - 1) // stream_chunk))
    results, streamed, lm_element = run_lm_frames(
        {"continuous": True, "decode_slots": 2, "kv_block_size": 8,
         "max_new_tokens": new_tokens, "stream_tokens": True,
         "stream_chunk": stream_chunk, **extra},
        frames, wait_out=publishes)
    assert len(streamed) == publishes
    assert lm_element.engine_stats()[ran] > 0
    chunks_of = _row_chunks(streamed)
    assert len(chunks_of) == 2 * 2
    for (_, frame, outputs) in results:
        generated = np.asarray(outputs["generated"])
        assert generated.shape == (2, new_tokens)
        for row in range(2):
            chunks = chunks_of[frame.frame_id, row]
            assert chunks[0][0] == 0 and len(chunks[0][1]) == 1
            assert [len(tokens) for _, tokens in chunks[1:-1]] == [
                stream_chunk] * (len(chunks) - 2)
            assert 1 <= len(chunks[-1][1]) <= stream_chunk
            # gapless: a chunk starts where the one before ended
            assert [offset for offset, _ in chunks] == [
                sum(len(tokens) for _, tokens in chunks[:index])
                for index in range(len(chunks))]
            np.testing.assert_array_equal(
                np.concatenate([tokens for _, tokens in chunks]),
                generated[row])


def test_first_token_is_published_before_the_next_prefill_is_dispatched(
        monkeypatch):
    """Two rows of one frame are admitted in one tick: row 0's chunk at
    offset 0 is on /out before row 1's prefill is handed to the device,
    and the device is still given prefill, prefill, then the steps."""
    from aiko_services_tpu.decode import engine as engine_module
    events = []
    for name in ("paged_prefill", "paged_decode_step"):
        program = getattr(engine_module, name)

        def recording(*args, _name=name, _program=program, **kwargs):
            events.append(_name)
            return _program(*args, **kwargs)

        monkeypatch.setattr(engine_module, name, recording)
    process = Process(transport_kind="loopback")
    pipeline = create_pipeline(process, lm_definition(
        {"continuous": True, "decode_slots": 2, "kv_block_size": 8,
         "stream_tokens": True, "stream_chunk": 8}))
    lm_element = pipeline.elements["lm"]
    publish = lm_element.publish_out

    def recording_publish(command, parameters):
        events.append((command, parameters[2], parameters[3]))
        return publish(command, parameters)

    lm_element.publish_out = recording_publish
    process.run(in_thread=True)
    responses = queue.Queue()
    stream = pipeline.create_stream("s", queue_response=responses,
                                    grace_time=300)
    pipeline.create_frame(stream, {"tokens": np.arange(
        1, 11, dtype=np.int32).reshape(2, 5)})
    _, _, outputs = responses.get(timeout=120)
    process.terminate()
    assert np.asarray(outputs["generated"]).shape == (2, 6)
    assert events[:5] == [
        "paged_prefill", ("token_chunk", 0, 0),
        "paged_prefill", ("token_chunk", 1, 0), "paged_decode_step"]
    # the rest of each answer leaves when it ends: five tokens, under 8
    assert [event for event in events[5:] if event != "paged_decode_step"
            ] == [("token_chunk", 0, 1), ("token_chunk", 1, 1)]


def test_continuous_stop_stream_cancels_inflight():
    """Destroying a stream mid-decode cancels its engine requests:
    slots and blocks free, no completion is delivered for the dead
    stream, and a following stream decodes normally."""
    process = Process(transport_kind="loopback")
    pipeline = create_pipeline(process, lm_definition(
        {"continuous": True, "decode_slots": 2, "kv_block_size": 8,
         "max_new_tokens": 64}))
    process.run(in_thread=True)
    responses = queue.Queue()
    stream = pipeline.create_stream("s1", queue_response=responses,
                                    grace_time=300)
    tokens = np.arange(1, 8, dtype=np.int32)[None]
    pipeline.create_frame(stream, {"tokens": tokens})
    lm_element = pipeline.elements["lm"]
    wait_for(lambda: lm_element.engine_stats() is not None
             and lm_element.engine_stats()["admitted"] >= 1, timeout=60)
    pipeline.destroy_stream("s1")
    wait_for(lambda: lm_element.engine_stats()["cancelled"] >= 1
             or lm_element.engine_stats()["completed"] >= 1, timeout=60)
    # a second stream is unaffected by the cancellation
    responses2 = queue.Queue()
    stream2 = pipeline.create_stream("s2", queue_response=responses2,
                                     grace_time=300)
    pipeline.create_frame(stream2, {"tokens": tokens})
    _, _, outputs = responses2.get(timeout=120)
    assert np.asarray(outputs["generated"]).shape == (1, 64)
    wait_for(lambda: lm_element.engine_stats()["active_slots"] == 0,
             timeout=60)
    process.terminate()


def test_engine_metrics_reach_summary_and_dashboard():
    """The decode.* gauges ride the pipeline telemetry: the EC-share
    summary grows a `decode` sub-dict (per-replica slot occupancy for
    the gateway / services page) and the dashboard pipeline plugin
    renders it."""
    rng = np.random.default_rng(5)
    frames = [rng.integers(1, 300, size=(2, 6)).astype(np.int32)]
    process = Process(transport_kind="loopback")
    pipeline = create_pipeline(process, lm_definition(
        {"continuous": True, "decode_slots": 2, "kv_block_size": 8}))
    process.run(in_thread=True)
    responses = queue.Queue()
    stream = pipeline.create_stream("s", queue_response=responses,
                                    grace_time=300)
    pipeline.create_frame(stream, {"tokens": frames[0]})
    responses.get(timeout=120)
    summary = pipeline.telemetry.summary()
    decode = summary["decode"]
    assert decode["completed"] == 2
    assert decode["active_slots"] == 0 and decode["waiting"] == 0
    assert decode["free_blocks"] > 0

    from aiko_services_tpu.dashboard import _pipeline_plugin

    class Model:
        selected_share = {"stream_count": 1, "frame_count": 1,
                          "element_count": 1, "metrics": summary}

    lines = _pipeline_plugin(Model())
    decode_lines = [line for line in lines if line.startswith("decode:")]
    assert decode_lines and "completed 2" in decode_lines[0]

    # over the real EC wire every value arrives as a STRING -- the
    # plugin must render those too, not only in-process numbers
    class WireModel:
        selected_share = {"metrics": dict(
            summary, decode={key: str(value)
                             for key, value in decode.items()})}

    wire_lines = _pipeline_plugin(WireModel())
    assert any(line.startswith("decode:") for line in wire_lines)
    process.terminate()
    # a pipeline without an engine keeps the old summary shape
    reset_brokers()
    plain_process = Process(transport_kind="loopback")
    plain = create_pipeline(plain_process, lm_definition({}))
    plain_process.run(in_thread=True)
    assert "decode" not in plain.telemetry.summary()
    plain_process.terminate()


def test_engine_failure_releases_pending_frames():
    """A crash inside the mailbox pump (device error mid-step) must not
    strand parked PENDING frames: in-flight frames get an error
    response, the broken engine is dropped, and the next continuous
    frame rebuilds a working one."""
    process = Process(transport_kind="loopback")
    pipeline = create_pipeline(process, lm_definition(
        {"continuous": True, "decode_slots": 2, "kv_block_size": 8}))
    process.run(in_thread=True)
    lm_element = pipeline.elements["lm"]
    responses = queue.Queue()
    stream = pipeline.create_stream("ok1", queue_response=responses,
                                    grace_time=300,
                                    parameters={"max_new_tokens": 4})
    tokens = np.arange(1, 9, dtype=np.int32)[None]
    pipeline.create_frame(stream, {"tokens": tokens})
    expected = np.asarray(responses.get(timeout=120)[2]["generated"])

    def explode(emit=None):
        raise RuntimeError("injected device failure")

    lm_element._engine.step = explode
    doomed = pipeline.create_stream("doomed", grace_time=300)
    pipeline.create_frame(doomed, {"tokens": tokens})
    wait_for(lambda: lm_element._engine is None
             and not lm_element._engine_frames, timeout=60)

    responses2 = queue.Queue()
    stream2 = pipeline.create_stream("ok2", queue_response=responses2,
                                     grace_time=300,
                                     parameters={"max_new_tokens": 4})
    pipeline.create_frame(stream2, {"tokens": tokens})
    out = np.asarray(responses2.get(timeout=120)[2]["generated"])
    np.testing.assert_array_equal(out, expected)

    # crash AFTER a completion (telemetry hook) but BEFORE the response
    # is posted: the frame entry must still be registered so the
    # release path can error it out -- then the engine rebuilds again
    telemetry = pipeline.telemetry
    original = telemetry.record_engine_frame

    def boom(*args, **kwargs):
        raise RuntimeError("injected telemetry crash")

    telemetry.record_engine_frame = boom
    doomed2 = pipeline.create_stream("doomed2", grace_time=300)
    pipeline.create_frame(doomed2, {"tokens": tokens})
    wait_for(lambda: lm_element._engine is None
             and not lm_element._engine_frames, timeout=60)
    telemetry.record_engine_frame = original
    responses3 = queue.Queue()
    stream3 = pipeline.create_stream("ok3", queue_response=responses3,
                                     grace_time=300,
                                     parameters={"max_new_tokens": 4})
    pipeline.create_frame(stream3, {"tokens": tokens})
    out = np.asarray(responses3.get(timeout=120)[2]["generated"])
    np.testing.assert_array_equal(out, expected)
    process.terminate()


def test_rejected_submit_does_not_leak_frame_entry():
    """A frame whose rows the engine rejects (prompt + max_new over
    max_context) must not strand an _engine_frames entry or queued
    sibling rows; a following stream decodes normally."""
    process = Process(transport_kind="loopback")
    pipeline = create_pipeline(process, lm_definition(
        {"continuous": True, "decode_slots": 2, "kv_block_size": 8,
         "max_context": 32, "max_new_tokens": 20}))
    process.run(in_thread=True)
    lm_element = pipeline.elements["lm"]
    stream = pipeline.create_stream("bad", grace_time=300)
    # ragged rows left-padded to width 16: EVERY row's true width is 16
    # after padding, so 16 + 20 > max_context=32 -> submit raises after
    # row 0 queued... use an explicit 2-row (8, 16) unpadded pair
    # instead: row 0 (8 + 20 = 28) queues, row 1 (16 + 20 = 36) raises,
    # and the cleanup must also cancel the queued row 0
    bad = np.zeros((2, 16), np.int32)
    bad[0, :8] = np.arange(1, 9)
    bad[1, :] = np.arange(1, 17)
    pipeline.create_frame(stream, {"tokens": bad})
    wait_for(lambda: lm_element._engine is not None
             and not lm_element._engine_frames
             and not lm_element._engine.has_work(), timeout=60)
    # a fresh stream with admissible sizes is unaffected
    responses = queue.Queue()
    stream2 = pipeline.create_stream("ok", queue_response=responses,
                                     grace_time=300,
                                     parameters={"max_new_tokens": 4})
    pipeline.create_frame(
        stream2, {"tokens": np.arange(1, 9, dtype=np.int32)[None]})
    _, _, outputs = responses.get(timeout=120)
    assert np.asarray(outputs["generated"]).shape == (1, 4)
    process.terminate()


def test_gateway_routes_to_continuous_replicas_bit_identical():
    """The serving-tier composition the ISSUE names: a Gateway fronting
    LMGenerate replicas running `continuous: true` serves the same
    completions as a direct closed-batch pipeline -- frames route, the
    engine decodes them interleaved, and responses ride the gateway's
    exactly-once delivery."""
    from aiko_services_tpu.serve import Gateway

    rng = np.random.default_rng(9)
    frames = [rng.integers(1, 300, size=(1, 6)).astype(np.int32)
              for _ in range(4)]
    closed, _, _ = run_lm_frames({}, frames)
    expected = [np.asarray(outputs["generated"])
                for _, _, outputs in closed]
    reset_brokers()

    processes = []
    replicas = []
    for index in range(2):
        process = Process(transport_kind="loopback")
        processes.append(process)
        definition = lm_definition(
            {"continuous": True, "decode_slots": 2, "kv_block_size": 8})
        definition["name"] = f"replica{index}"
        replicas.append(create_pipeline(process, definition))
    gateway_process = Process(transport_kind="loopback")
    processes.append(gateway_process)
    gateway = Gateway(gateway_process, policy="max_inflight=8;queue=32")
    for replica in replicas:
        gateway.attach_replica(replica)
    for process in processes:
        process.run(in_thread=True)
    try:
        responses = queue.Queue()
        gateway.submit_stream("s1", {}, queue_response=responses)
        for frame_id, frame in enumerate(frames):
            gateway.submit_frame("s1", {"tokens": frame},
                                 frame_id=frame_id)
        got = {}
        for _ in frames:
            stream_id, frame_id, outputs, status = responses.get(
                timeout=120)
            assert status == "ok", (frame_id, outputs)
            got[frame_id] = np.asarray(outputs["generated"])
        for frame_id, reference_out in enumerate(expected):
            np.testing.assert_array_equal(got[frame_id], reference_out)
        # the stream pinned to ONE replica and its engine did the work
        engines = [replica.elements["lm"].engine_stats()
                   for replica in replicas]
        completed = [stats["completed"] if stats else 0
                     for stats in engines]
        assert sorted(completed) == [0, len(frames)]
    finally:
        for process in processes:
            process.terminate()


def test_continuous_interleaves_new_frames_mid_decode():
    """The open-batch property itself: a frame submitted while another
    is mid-decode is admitted into the RUNNING loop (admissions overlap
    decode progress) rather than convoying behind a closed batch."""
    process = Process(transport_kind="loopback")
    pipeline = create_pipeline(process, lm_definition(
        {"continuous": True, "decode_slots": 4, "kv_block_size": 8,
         "max_new_tokens": 48}))
    process.run(in_thread=True)
    responses = queue.Queue()
    stream = pipeline.create_stream("s", queue_response=responses,
                                    grace_time=300)
    lm_element = pipeline.elements["lm"]
    pipeline.create_frame(
        stream, {"tokens": np.arange(1, 8, dtype=np.int32)[None]})
    wait_for(lambda: lm_element.engine_stats() is not None
             and lm_element.engine_stats()["admitted"] >= 1, timeout=60)
    pipeline.create_frame(
        stream, {"tokens": np.arange(11, 18, dtype=np.int32)[None]})
    # both frames decode concurrently at some point
    wait_for(lambda: lm_element.engine_stats()["active_slots"] == 2,
             timeout=60)
    first = responses.get(timeout=120)
    second = responses.get(timeout=120)
    assert first[1].frame_id != second[1].frame_id
    process.terminate()
