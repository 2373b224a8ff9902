# Ouro's looped stack on the normal path: the whole stack of layers run
# total_ut_steps times a token, K/V of its own for every pass, sublayer
# outputs normed, the exit gate -- each held to benchmark/reference/ouro.py,
# the float32 reference that imports nothing of the program.
#
# Everything here is float32 at toy widths (hidden 64, 4 heads of 16, FFN
# 96, 2 layers x 3 passes = 6 caches), the gate's bias moved off zero so
# that tokens leave after different passes.  A tolerance is float32 rounding
# through a few matmuls of other shapes and a blockwise softmax: 2e-5 on
# logits of size ~1 (tests/test_transformer.py's STORE_CASES hold the dense
# model to the same).

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from aiko_services_tpu.decode import (
    CheckpointKeeper, CheckpointPolicy, DecodeCheckpointer, DecodeEngine)
from aiko_services_tpu.models import configs, transformer
from aiko_services_tpu.models.configs import ouro_config
from aiko_services_tpu.models.transformer import (
    TransformerConfig, decode_step, forward, generate, init_cache,
    init_paged_pool, init_params, make_train_step, paged_prefill,
    param_specs)
from benchmark.reference import ouro as reference

PUBLISHED = {
    "model_type": "ouro", "vocab_size": 256, "hidden_size": 64,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 16, "intermediate_size": 96,
    "hidden_act": "silu", "layer_types": ["full_attention"] * 2,
    "total_ut_steps": 3, "early_exit_threshold": 1, "rope_theta": 10000,
    "rope_scaling": None, "sliding_window": None,
    "use_sliding_window": False, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": False, "max_position_embeddings": 4096,
    "torch_dtype": "float32"}
SEED = 7
GATE_BIAS = 0.3
TOLERANCE = 2e-5
LAYERS, PASSES = 2, 3


def model_at(threshold: float):
    """(config, params, the reference's shape) with the exit rule held to
    `threshold` and the gate's bias at GATE_BIAS on both sides."""
    published = dict(PUBLISHED, early_exit_threshold=threshold)
    config = ouro_config(published, max_seq_len=128)
    params = init_params(config, jax.random.PRNGKey(SEED))
    params["exit_gate"]["b"] = jnp.float32(GATE_BIAS)
    shape = dataclasses.replace(reference.shape_of(published),
                                gate_bias=GATE_BIAS)
    return config, params, shape


@pytest.fixture(scope="module")
def model():
    return model_at(0.5)


def reference_logits(shape, tokens) -> np.ndarray:
    tokens = np.asarray(tokens)
    positions = np.tile(np.arange(tokens.shape[1])[None],
                        (tokens.shape[0], 1))
    return np.asarray(reference.logits_at(shape, SEED, tokens, positions))


def some_tokens(rows: int, length: int, seed: int = 5):
    return jax.random.randint(jax.random.PRNGKey(seed), (rows, length), 1,
                              PUBLISHED["vocab_size"]).astype(jnp.int32)


def assert_served_is_the_references(shape, prompt, served, what=""):
    """Every served token's reference logit is the reference's best to
    TOLERANCE, in one full pass over prompt + served tokens."""
    sequence = np.concatenate([prompt, served])[None]
    logits = reference_logits(shape, sequence)[0]
    at = len(prompt) - 1 + np.arange(len(served))
    gaps = logits[at].max(axis=-1) - logits[at, served]
    assert gaps.max() <= TOLERANCE, (what, gaps.max())


def drain(engine, done=None, emitted=None):
    done = {} if done is None else done
    steps = 0
    while engine.has_work():
        report = engine.step()
        if emitted is not None:
            emitted.extend((offset, token) for _rid, offset, token
                           in report.emitted)
        for completion in report.completions:
            done[completion.request_id] = completion
        steps += 1
        assert steps < 4000
    return done


# -- (a) the cache-less forward is the reference ------------------------------

@pytest.mark.parametrize("threshold", (0.5, 1.0))
def test_forward_is_the_reference(threshold):
    config, params, shape = model_at(threshold)
    tokens = some_tokens(2, 24)
    np.testing.assert_allclose(
        np.asarray(forward(params, config, tokens)),
        reference_logits(shape, tokens), atol=TOLERANCE, rtol=0)


def test_the_seeded_gate_is_the_references_draw():
    """The reference makes the gate's weight itself (bias 0), so the
    seeded model with nothing moved is the reference too."""
    config = ouro_config(dict(PUBLISHED, early_exit_threshold=0.5), 128)
    params = init_params(config, jax.random.PRNGKey(SEED))
    assert float(params["exit_gate"]["b"]) == 0.0
    assert params["exit_gate"]["w"].shape == (64,)
    tokens = some_tokens(1, 16)
    shape = reference.shape_of(dict(PUBLISHED, early_exit_threshold=0.5))
    np.testing.assert_allclose(
        np.asarray(forward(params, config, tokens)),
        reference_logits(shape, tokens), atol=TOLERANCE, rtol=0)


# -- (b) every KV store gives the reference's logits --------------------------

def _tables(rows: int, max_blocks: int) -> np.ndarray:
    return 1 + np.arange(rows * max_blocks, dtype=np.int32).reshape(
        rows, max_blocks)                    # block 0 is the trash block


def _window_logits(params, config, pool, tables, start: int, tokens, block):
    """transformer._paged_logits over `tokens` (rows, W) from position
    `start`: (pool, logits (rows, W, V), exit_steps (rows, W))."""
    rows, window = tokens.shape
    at = start + np.arange(window)
    write_blocks = tables[:, at // block]
    write_offsets = np.tile(at % block, (rows, 1)).astype(np.int32)
    pool, logits, _, exit_steps = transformer._paged_logits(
        params, config, pool, tables, np.full((rows,), start, np.int32),
        tokens, write_blocks, write_offsets)
    return pool, np.asarray(logits), np.asarray(exit_steps)


def _through_the_cache(params, config, tokens, prompt_len):
    """Prefill at position 0 into the contiguous cache, then decode_step
    by decode_step: generate()'s path, by logits."""
    rows, length = tokens.shape
    cache = init_cache(config, rows, max_len=32)
    logits, cache = forward(params, config, tokens[:, :prompt_len],
                            cache=cache, pos=0)
    out = [np.asarray(logits)]
    for position in range(prompt_len, length):
        _, logits, cache = decode_step(
            params, config, cache, tokens[:, position:position + 1],
            jnp.int32(position))
        out.append(np.asarray(logits))
    return np.concatenate(out, axis=1), None


def _through_the_pool(params, config, tokens, prompt_len, block=4):
    """paged_prefill, then one-token windows through the pool.  The
    prefill hands out a token, no logits: at every prompt position it is
    the reference's choice (the caller checks the gap); the decoded
    positions give logits."""
    rows, length = tokens.shape
    tables = _tables(rows, 32 // block)
    pool = init_paged_pool(config, 1 + tables.size, block)
    firsts = np.zeros((rows, prompt_len), np.int32)
    for row in range(rows):
        for true_len in range(1, prompt_len + 1):
            pool, first = paged_prefill(
                params, config, pool, tokens[row:row + 1, :prompt_len],
                tables[row], np.int32(true_len))
            firsts[row, true_len - 1] = int(first)
    out = []
    for position in range(prompt_len, length):
        pool, logits, _ = _window_logits(
            params, config, pool, tables, position,
            tokens[:, position:position + 1], block)
        out.append(logits)
    return np.concatenate(out, axis=1), firsts


def _through_chunks(params, config, tokens, prompt_len, block=4):
    """A chunked prefill: windows of 8 through the pool from position 0,
    each attending over the chunks before it."""
    rows, length = tokens.shape
    tables = _tables(rows, 32 // block)
    pool = init_paged_pool(config, 1 + tables.size, block)
    out = []
    for start in range(0, length, 8):
        pool, logits, _ = _window_logits(
            params, config, pool, tables, start,
            tokens[:, start:start + 8], block)
        out.append(logits)
    return np.concatenate(out, axis=1), None


STORES = {"contiguous_cache": _through_the_cache,
          "paged_pool": _through_the_pool,
          "chunked_prefill": _through_chunks}


@pytest.mark.parametrize("store", sorted(STORES))
def test_every_store_gives_the_references_logits(model, store):
    config, params, shape = model
    prompt_len, length = 8, 24
    tokens = some_tokens(2, length)
    expected = reference_logits(shape, tokens)
    logits, firsts = STORES[store](params, config, tokens, prompt_len)
    covered = logits.shape[1]
    np.testing.assert_allclose(logits, expected[:, length - covered:],
                               atol=TOLERANCE, rtol=0)
    if firsts is not None:
        prompt = expected[:, :prompt_len]
        gaps = prompt.max(axis=-1) - np.take_along_axis(
            prompt, firsts[..., None], axis=-1)[..., 0]
        assert gaps.max() <= TOLERANCE


def test_generate_serves_the_references_tokens(model):
    config, params, shape = model
    prompt = np.asarray(some_tokens(1, 11, seed=9))
    served, cache = generate(params, config, prompt, max_new_tokens=9)
    assert cache["k"].shape[0] == LAYERS * PASSES
    assert_served_is_the_references(shape, prompt[0], np.asarray(served)[0])


# -- (c) a cache for every pass -----------------------------------------------

def test_caches_are_numbered_by_pass_then_layer(model):
    config, _, _ = model
    assert config.n_caches == 6 and config.ut_steps == 3
    index = transformer._cache_index
    assert [index(config, step, layer) for step in range(PASSES)
            for layer in range(LAYERS)] == list(range(6))
    assert init_cache(config, 2, 16)["k"].shape == (6, 2, 4, 16, 16)
    assert init_paged_pool(config, 9, 4)["v"].shape == (6, 9, 4, 4, 16)
    plain = dataclasses.replace(config, ut_steps=1)
    assert plain.n_caches == 2 and index(plain, 0, 1) == 1


def test_a_pass_reads_only_its_own_cache():
    """Zero pass 1's rows in the pool and decode one token.  Pass 0 is
    untouched: its new rows and, where every token leaves after pass 0,
    the logits, bit for bit.  Pass 1's first layer still writes what it
    wrote (its K/V are projections of pass 0's output); everything after
    it attends over the zeroed rows and changes, the last pass's logits
    with it."""
    block, prompt_len = 4, 12
    tokens = some_tokens(1, prompt_len + 1)
    tables = _tables(1, 8)
    results = {}
    for threshold in (0.0, 1.0):
        config, params, _ = model_at(threshold)
        pool = init_paged_pool(config, 9, block)
        pool, _ = paged_prefill(params, config, pool,
                                tokens[:, :prompt_len], tables[0],
                                np.int32(prompt_len))
        zeroed = {name: leaf.at[LAYERS:2 * LAYERS].set(0.0)
                  for name, leaf in pool.items()}
        for what, start in (("sound", pool), ("zeroed", zeroed)):
            after, logits, _ = _window_logits(
                params, config, start, tables, prompt_len,
                tokens[:, prompt_len:], block)
            row = np.asarray(after["k"])[:, tables[0, prompt_len // block],
                                         :, prompt_len % block]
            results[threshold, what] = (logits, row)
    np.testing.assert_array_equal(results[0.0, "sound"][0],
                                  results[0.0, "zeroed"][0])
    assert np.abs(results[1.0, "sound"][0]
                  - results[1.0, "zeroed"][0]).max() > 1e-3
    sound, zeroed = results[1.0, "sound"][1], results[1.0, "zeroed"][1]
    changed = [bool(np.any(sound[cache] != zeroed[cache]))
               for cache in range(LAYERS * PASSES)]
    #        pass 0      | pass 1: layer 0, 1 | pass 2
    assert changed == [False, False, False, True, True, True]


def test_passes_sharing_one_cache_is_another_model(model, monkeypatch):
    """The control the benchmark's cell runs on the chip: every pass
    writing and reading pass 0's caches serves other tokens."""
    config, params, shape = model
    tokens = some_tokens(1, 20)
    expected = reference_logits(shape, tokens)
    monkeypatch.setattr(transformer, "_cache_index",
                        lambda config, step, layer: layer)
    logits, _ = _through_chunks(params, config, tokens, 8)
    assert np.abs(logits - expected).max() > 1e-2


# -- (d) the gate and the exit rule -------------------------------------------

@pytest.mark.parametrize("threshold", (0.5, 1.0))
def test_gate_pdf_and_exit_pass_are_the_references(threshold):
    config, params, shape = model_at(threshold)
    tokens = some_tokens(2, 20)
    with jax.default_matmul_precision("highest"):
        outputs, gate_logits, _ = reference._forward(shape, SEED, tokens,
                                                     "stated")
    expected_pdf = np.asarray(reference.exit_pdf(gate_logits))
    pdf = transformer._exit_pdf(params, list(outputs))
    np.testing.assert_allclose(np.asarray(pdf), expected_pdf, atol=1e-6,
                               rtol=0)
    np.testing.assert_allclose(np.asarray(pdf).sum(axis=0), 1.0, atol=1e-6)
    chosen = np.asarray(transformer._exit_pass(pdf, threshold))
    np.testing.assert_array_equal(
        chosen, np.asarray(reference.exit_pass(expected_pdf, threshold)))
    if threshold == 1.0:
        assert (chosen == PASSES - 1).all()
    else:
        assert len(set(chosen.ravel())) > 1      # exits differ
    # the step's own account of it: sum_t (t + 1) p[t] a position
    tables = _tables(2, 8)
    pool = init_paged_pool(config, 17, 4)
    _, _, exit_steps = _window_logits(params, config, pool, tables, 0,
                                      tokens[:, :8], 4)
    np.testing.assert_allclose(
        exit_steps, np.tensordot(np.arange(1, PASSES + 1), expected_pdf,
                                 axes=1)[:, :8], atol=1e-5, rtol=0)


# -- (e) one pass is the dense model, and the loop trains ---------------------

def test_one_pass_without_output_norms_is_the_dense_model(model):
    """The looped model's seeded layers are the dense model's, leaf for
    leaf; with one pass and no output norms it computes the dense model
    bit for bit, its two extra norms and the gate unread, and lowers to
    one layer loop with no gate."""
    config, params, _ = model
    dense = TransformerConfig(
        vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=4,
        d_ff=96, max_seq_len=128, rope_theta=10000.0, dtype="float32")
    assert dataclasses.replace(config, ut_steps=1, sandwich_norm=False,
                               exit_threshold=1.0) == dense
    dense_params = init_params(dense, jax.random.PRNGKey(SEED))
    assert set(params) - set(dense_params) == {"exit_gate"}
    assert set(params["layers"]) - set(dense_params["layers"]) == {
        "attn_out_norm", "mlp_out_norm"}
    for name, leaf in jax.tree_util.tree_leaves_with_path(dense_params):
        looped = params
        for key in name:
            looped = looped[key.key]
        np.testing.assert_array_equal(np.asarray(leaf), np.asarray(looped))
    tokens = some_tokens(2, 16)
    np.testing.assert_array_equal(
        np.asarray(forward(params, dense, tokens)),
        np.asarray(forward(dense_params, dense, tokens)))
    lowered = jax.jit(lambda p, t: forward(p, dense, t)).lower(
        dense_params, tokens).as_text()
    # (the interpreted attention kernel brings a loop of its own, in a
    # function the passes share)
    loops = lowered.count("stablehlo.while")
    assert loops >= 1 and "cumprod" not in lowered
    looped = jax.jit(lambda p, t: forward(p, config, t)).lower(
        params, tokens).as_text()
    assert looped.count("stablehlo.while") == loops + PASSES - 1
    assert "cumprod" in looped          # the gate


def test_train_step_differentiates_through_the_passes():
    config, params, shape = model_at(1.0)
    tokens = some_tokens(2, 17)
    logits = reference_logits(shape, tokens[:, :-1])
    log_probs = jax.nn.log_softmax(jnp.asarray(logits), axis=-1)
    expected = -float(jnp.mean(jnp.take_along_axis(
        log_probs, tokens[:, 1:, None], axis=-1)))
    optimizer = optax.sgd(0.05)
    before = jax.tree_util.tree_map(np.asarray, params)
    step = make_train_step(config, optimizer)
    trained, _, loss = step(params, optimizer.init(params), tokens)
    assert abs(float(loss) - expected) < 1e-4
    moved = {name: float(np.abs(np.asarray(trained["layers"][name][key])
                                - before["layers"][name][key]).max())
             for name, key in (("wq", "w"), ("w_down", "w"),
                               ("attn_out_norm", "scale"),
                               ("mlp_out_norm", "scale"))}
    assert all(change > 0 for change in moved.values()), moved
    assert float(np.abs(np.asarray(trained["norm_out"]["scale"])
                        - before["norm_out"]["scale"]).max()) > 0
    again = float(step(trained, optimizer.init(trained), tokens)[2])
    assert again < float(loss)


def test_param_specs_cover_the_looped_models_leaves(model):
    config, params, _ = model
    specs = param_specs(config)
    assert (jax.tree_util.tree_structure(specs)
            == jax.tree_util.tree_structure(params))


# -- (f) the engine over the looped pool --------------------------------------

def test_engine_serves_the_references_tokens_across_block_boundaries(model):
    """Prefill then decode through DecodeEngine (run-ahead on, two slots
    for three requests, blocks of 8 crossed) against the reference's one
    full pass over prompt + served tokens, by logits; the spans' counts
    are the loop's."""
    config, params, shape = model
    engine = DecodeEngine(params, config, decode_slots=2, kv_block_size=8,
                          max_context=64)
    assert engine.pool["k"].shape[0] == LAYERS * PASSES
    rng = np.random.default_rng(3)
    prompts = {name: rng.integers(1, 256, size=length).astype(np.int32)
               for name, length in (("a", 13), ("b", 21), ("c", 7))}
    for name, prompt in prompts.items():
        engine.submit(name, prompt, 19)
    done = drain(engine)
    stats = engine.stats()
    assert stats["preempted"] == 0 and stats["steps_ahead"] > 0
    # three prefills and every decode step ran three passes
    assert stats["ut_passes"] == PASSES * (3 + stats["decode_steps"])
    # the prompts' rows in every cache, and over them the steps' rows
    assert stats["cache_rows"] > 6 * (13 + 21 + 7)
    assert stats["cache_rows"] % 6 == 0
    mean = stats["exit_expected_step"] / stats["decode_steps"]
    assert 1.0 < mean < PASSES
    for name, prompt in prompts.items():
        assert_served_is_the_references(shape, prompt, done[name].tokens,
                                        name)


def test_engine_counts_no_pass_for_a_model_of_one_pass():
    config = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=2, n_kv_heads=2,
        d_ff=64, max_seq_len=64, dtype="float32")
    engine = DecodeEngine(init_params(config, jax.random.PRNGKey(0)),
                          config, decode_slots=1, kv_block_size=8)
    engine.submit("r", np.arange(1, 6, dtype=np.int32), 6)
    drain(engine)
    stats = engine.stats()
    assert stats["decode_steps"] > 0
    assert (stats["ut_passes"], stats["cache_rows"],
            stats["exit_expected_step"]) == (0, 0, 0.0)


def test_engine_serves_the_references_tokens_through_a_preemption(model):
    """Two slots grow on a pool too small for both: the youngest is
    preempted, re-prefilled into all six caches and resumed."""
    config, params, shape = model
    engine = DecodeEngine(params, config, decode_slots=2, kv_block_size=4,
                          kv_blocks=6)
    prompts = {0: np.arange(1, 5, dtype=np.int32),
               1: np.arange(11, 15, dtype=np.int32)}
    for index, prompt in prompts.items():
        engine.submit(index, prompt, 12)
    done = drain(engine)
    assert engine.counters["preempted"] >= 1
    assert done[1].stats["preemptions"] >= 1
    for index, prompt in prompts.items():
        assert_served_is_the_references(shape, prompt, done[index].tokens,
                                        index)


def test_engine_restores_a_checkpoint_of_the_looped_pool(model):
    """A mid-decode crash restored from the keeper (blocks of all six
    caches shipped and adopted) finishes with the reference's tokens,
    every offset emitted once."""
    config, params, shape = model
    prompt = np.random.default_rng(5).integers(1, 256, size=11).astype(
        np.int32)
    max_new = 14
    keeper = CheckpointKeeper("ouro-keeper")
    engine = DecodeEngine(params, config, decode_slots=2, kv_block_size=8)
    checkpointer = DecodeCheckpointer(
        engine, CheckpointPolicy.parse(
            "checkpoint_every=2;max_checkpoint_lag=4;keeper=ouro-keeper"),
        keeper=keeper)
    engine.submit("r", prompt, max_new)
    emitted = []
    for _ in range(7):
        emitted.extend((offset, token) for _rid, offset, token
                       in engine.step().emitted)
        checkpointer.tick()
    assert keeper.flush()
    assert 0 < len(emitted) < max_new, "the crash must be mid-decode"
    survivor = DecodeEngine(params, config, decode_slots=1, kv_block_size=8)
    report = survivor.restore_request("r", keeper.restore("r"))
    emitted = [(offset, token) for _rid, offset, token in report.emitted]
    done = {c.request_id: c for c in report.completions}
    drain(survivor, done, emitted)
    assert survivor.counters["restores"] == 1
    assert survivor.counters["restore_fallbacks"] == 0
    # 11 + a few positions of 6 caches x (k, v) x 4 heads x 16 floats
    assert survivor.counters["kv_migrated_bytes"] >= 11 * 6 * 2 * 4 * 16 * 4
    assert sorted(dict(emitted)) == list(range(max_new))
    assert_served_is_the_references(shape, prompt, done["r"].tokens)


def test_engine_prefix_hit_borrows_blocks_of_every_pass(model):
    """A repeated prompt borrows its two full blocks (of all six caches:
    a block is a position range, not a cache) and prefills the tail
    through the pool; cold and warm serve the reference's tokens."""
    config, params, shape = model
    prompt = np.arange(1, 21, dtype=np.int32)     # 2 full blocks of 8
    engine = DecodeEngine(params, config, decode_slots=2, kv_block_size=8,
                          prefix_policy="prefix_cache=on")
    engine.submit(0, prompt, 6)
    done = drain(engine)
    assert engine.counters["prefix_hits"] == 0
    engine.submit(1, prompt, 6)
    done = drain(engine, done)
    assert engine.counters["prefix_hits"] == 1
    assert engine.counters["prefix_blocks_shared"] == 2
    np.testing.assert_array_equal(done[0].tokens, done[1].tokens)
    assert_served_is_the_references(shape, prompt, done[1].tokens)


# -- (g) the published keys ---------------------------------------------------

@pytest.mark.parametrize("key,value", [
    ("sliding_window", 4096), ("rope_scaling", {"type": "yarn"}),
    ("use_sliding_window", True), ("hidden_act", "gelu"),
    ("layer_types", ["full_attention", "sliding_attention"]),
    ("head_dim", 32)])
def test_published_keys_that_are_not_implemented_are_refused(key, value):
    with pytest.raises(ValueError, match=key):
        ouro_config(dict(PUBLISHED, **{key: value}))


def test_published_keys_reach_the_program_under_their_own_names():
    config = ouro_config(PUBLISHED, max_seq_len=640)
    assert (config.ut_steps, config.exit_threshold, config.sandwich_norm,
            config.n_layers, config.n_kv_heads, config.d_ff,
            config.max_seq_len, config.rope_theta, config.norm_eps) == (
        3, 1.0, True, 2, 4, 96, 640, 10000.0, 1e-6)
    assert ouro_config(PUBLISHED).max_seq_len == 4096
    assert configs.PUBLISHED_READERS["ouro"] is ouro_config
    with pytest.raises(ValueError, match="ut_steps"):
        TransformerConfig(ut_steps=0)
    with pytest.raises(ValueError, match="sequence_parallel"):
        TransformerConfig(ut_steps=2, sequence_parallel=True)


def test_the_element_reads_a_model_by_its_model_type():
    """LMGenerate's `model` parameter goes to the reader its model_type
    names; one without a reader is refused, naming those there are."""
    from aiko_services_tpu.elements import ml

    class Element:
        def __init__(self, model):
            self.parameters = {"model": model, "max_seq_len": 96}

        def get_parameter(self, name, default=None):
            return self.parameters.get(name, default)

    config = ml._transformer_config(Element(PUBLISHED))
    assert (config.ut_steps, config.max_seq_len, config.dtype) == (
        3, 96, "float32")
    with pytest.raises(ValueError, match="deepseek_v2.*ouro"):
        ml._transformer_config(Element(dict(PUBLISHED, model_type="olmo")))
