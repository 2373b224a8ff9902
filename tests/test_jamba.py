# Jamba's hybrid stack on the normal path: Mamba layers whose recurrent
# state is a slot's (a convolution tail and an SSM state a layer), beside
# attention layers without rotary in the paged pool -- each held to
# benchmark/reference/jamba.py, the float32 reference that imports nothing
# of the program and scans a whole sequence from zero, row by row.
#
# Everything here is float32 at toy widths: hidden 64, 4 query heads of 16
# over ONE K/V head, FFN 96, 8 layers in 2 periods of 4 with the attention
# layer off-centre (layers 1 and 5: runs of 1, 1, 3, 1, 2), d_inner 128,
# d_state 16, 4 taps, dt_rank 8.  A tolerance is float32 rounding through a
# few matmuls of other shapes, a blockwise softmax and a chunked scan: 2e-5
# on logits of size ~1.

import collections
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from aiko_services_tpu.decode import (
    CheckpointPolicy, DecodeCheckpointer, DecodeEngine, PrefillEngine)
from aiko_services_tpu.models import configs, transformer
from aiko_services_tpu.models.configs import jamba_config
from aiko_services_tpu.models.transformer import (
    TransformerConfig, forward, generate, init_cache, init_paged_pool,
    init_params, make_train_step, paged_decode_step, paged_prefill,
    param_specs, quantize_weights_int8)
from aiko_services_tpu.parallel import ssm
from benchmark.reference import jamba as reference
from test_decode import _Order
from test_prefill_rows import (                         # noqa: F401
    EDGE_BUCKET, EDGE_LENGTHS, EDGE_TILE, LIVE_BUCKET,
    assert_tiled_is_the_whole_buckets, check_live_attention_prefill,
    hidden_whole_and_tiled, live_attention)

PUBLISHED = {
    "model_type": "jamba", "vocab_size": 256, "hidden_size": 64,
    "num_hidden_layers": 8, "num_attention_heads": 4,
    "num_key_value_heads": 1, "intermediate_size": 96, "hidden_act": "silu",
    "attn_layer_period": 4, "attn_layer_offset": 1,
    "expert_layer_period": 2, "expert_layer_offset": 1, "num_experts": 1,
    "num_experts_per_tok": 1, "mamba_expand": 2, "mamba_d_state": 16,
    "mamba_d_conv": 4, "mamba_dt_rank": 8, "mamba_conv_bias": True,
    "mamba_proj_bias": False, "rms_norm_eps": 1e-6, "sliding_window": None,
    "tie_word_embeddings": True, "max_position_embeddings": 4096,
    "torch_dtype": "float32"}
SEED = 7
TOLERANCE = 2e-5
MAMBA, ATTENTION = 6, 2
STATE_BYTES = MAMBA * 128 * (4 * 16 + 3 * 4)     # a slot's, float32 rows


@pytest.fixture(scope="module")
def model():
    config = jamba_config(PUBLISHED, max_seq_len=128)
    return (config, init_params(config, jax.random.PRNGKey(SEED)),
            reference.shape_of(PUBLISHED))


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
    TOLERANCE, in one full pass over prompt + served tokens: by logits,
    not by tokens."""
    sequence = np.concatenate([prompt, served])[None]
    logits = reference_logits(shape, sequence)[0]
    at = len(prompt) - 1 + np.arange(len(served))
    gaps = logits[at].max(axis=-1) - logits[at, served]
    assert gaps.max() <= TOLERANCE, (what, gaps.max())


def drain(engine, done=None):
    done = {} if done is None else done
    steps = 0
    while engine.has_work():
        for completion in engine.step().completions:
            done[completion.request_id] = completion
        steps += 1
        assert steps < 4000
    return done


# -- (a) the layers are the reference's ---------------------------------------

def test_layer_kinds_come_from_the_two_published_keys(model):
    config, params, _ = model
    kinds = ["mamba"] * 8
    kinds[1] = kinds[5] = "attention"
    assert list(config.layer_kinds) == kinds and not config.rotary
    assert (config.n_caches, config.n_states, config.recurrent) == (
        ATTENTION, MAMBA, True)
    assert config.state_bytes == STATE_BYTES
    assert transformer._kind_runs(config) == [
        ("mamba", 0, 1), ("attention", 0, 1), ("mamba", 1, 3),
        ("attention", 1, 1), ("mamba", 4, 2)]
    assert [jax.tree_util.tree_leaves(run)[0].shape[0]
            for run in params["runs"]] == [1, 1, 3, 1, 2]
    assert configs.PUBLISHED_READERS["jamba"] is jamba_config
    assert jamba_config(PUBLISHED).max_seq_len == 4096
    # the published sizes: 26 + 2 layers, 358,400 B a Mamba layer a slot
    published = jamba_config(dict(
        PUBLISHED, hidden_size=2560, num_hidden_layers=28,
        num_attention_heads=20, attn_layer_period=14, attn_layer_offset=7,
        mamba_dt_rank=160, torch_dtype="bfloat16"))
    assert (published.n_states, published.n_caches) == (26, 2)
    assert published.state_bytes == 26 * 358_400
    assert [index for index, kind in enumerate(published.layer_kinds)
            if kind == "attention"] == [7, 21]


def test_forward_is_the_reference(model):
    config, params, shape = model
    tokens = some_tokens(2, 45)
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(forward(params, config, tokens))
    np.testing.assert_allclose(logits, reference_logits(shape, tokens),
                               atol=TOLERANCE, rtol=0)


def test_the_seeded_weights_are_the_references_draws(model):
    """Leaf by leaf, a Mamba layer (2, the first of a run of three) and
    an attention layer (5): the reference makes the program's numbers
    from the seed itself."""
    config, params, shape = model
    _, layers = reference._weights(shape, SEED, "stated")
    mamba = jax.tree_util.tree_map(lambda leaf: leaf[0], params["runs"][2])
    ours = {"w_in": mamba["w_in"]["w"], "conv_w": mamba["conv"]["w"],
            "conv_b": mamba["conv"]["b"], "w_x": mamba["w_x"]["w"],
            "w_dt": mamba["w_dt"]["w"], "dt_bias": mamba["dt_bias"],
            "a_log": mamba["a_log"].T, "d": mamba["d"],
            "w_out": mamba["w_out"]["w"], "w_gate": mamba["w_gate"]["w"],
            "w_up": mamba["w_up"]["w"], "w_down": mamba["w_down"]["w"]}
    assert set(ours) == set(layers[2])
    for name, leaf in ours.items():
        np.testing.assert_array_equal(leaf, layers[2][name], err_msg=name)
    attention = jax.tree_util.tree_map(lambda leaf: leaf[0],
                                       params["runs"][3])
    for name in ("wq", "wk"):                        # held (out, in)
        np.testing.assert_array_equal(attention[name]["w"].T,
                                      layers[5][name], err_msg=name)
    for name in ("wv", "wo", "w_gate", "w_up", "w_down"):
        np.testing.assert_array_equal(attention[name]["w"], layers[5][name],
                                      err_msg=name)
    # softplus(dt_bias) lies in the published initialiser's range
    step = np.asarray(jax.nn.softplus(mamba["dt_bias"]))
    assert 1e-3 * 0.999 <= step.min() and step.max() <= 1e-1 * 1.001


# -- (b) the scan: oracle against the plain recurrence, kernel against oracle -

def _plain_scan(c, dt, z, b, cc, a, d, dt_bias, state, stop):
    """The recurrence as written, a row at a time."""
    def row(state, xs):
        index, c_t, dt_t, z_t, b_t, cc_t = xs
        step = jnp.where(index < stop, jax.nn.softplus(dt_t + dt_bias), 0.0)
        state = (jnp.exp(step[:, None, :] * a) * state
                 + (step * c_t)[:, None, :] * b_t[:, :, None])
        y = jnp.sum(state * cc_t[:, :, None], axis=1) + d * c_t
        return state, y * jax.nn.silu(z_t)

    rows = lambda x: x.swapaxes(0, 1)                      # noqa: E731
    state, y = jax.lax.scan(row, state, (
        jnp.arange(c.shape[1]), rows(c), rows(dt), rows(z), rows(b),
        rows(cc)))
    return rows(y), state


def _scan_case(batch, length, inner, states=16, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    normal = jax.random.normal
    return (normal(keys[0], (batch, length, inner)),
            normal(keys[1], (batch, length, inner)) - 2.0,
            normal(keys[2], (batch, length, inner)),
            normal(keys[3], (batch, length, states)),
            normal(keys[4], (batch, length, states)),
            -jnp.broadcast_to(jnp.arange(1.0, states + 1)[:, None],
                              (states, inner)),
            jnp.ones((inner,)), normal(keys[5], (inner,)) * 0.1,
            normal(keys[6], (batch, states, inner)))


@pytest.mark.parametrize("length,stop", [(150, 150), (150, 97), (64, 1)])
def test_scan_oracle_is_the_plain_recurrence(length, stop):
    case = _scan_case(2, length, 72)
    want_y, want_state = _plain_scan(*case, stop)
    y, state = ssm.ssm_scan_reference(*case, jnp.int32(stop))
    np.testing.assert_allclose(y[:, :stop], want_y[:, :stop], atol=1e-5)
    np.testing.assert_allclose(state, want_state, atol=1e-5)


@pytest.mark.parametrize("length,inner,rows,channels,stop", [
    (150, 200, 64, 128, 150),     # neither block divides its length
    (150, 200, 64, 128, 70),      # a block of rows past the stop: skipped
    (128, 256, 64, 128, 128),     # both divide
    (40, 72, 16, 72, 33)])
def test_scan_kernel_is_the_oracle(length, inner, rows, channels, stop):
    case = _scan_case(2, length, inner, seed=3)
    want_y, want_state = ssm.ssm_scan_reference(*case, jnp.int32(stop))
    y, state = ssm.ssm_chunk_scan(*case, jnp.int32(stop), rows=rows,
                                  channels=channels)
    assert y.shape == want_y.shape and state.shape == want_state.shape
    np.testing.assert_allclose(y[:, :stop], want_y[:, :stop], atol=1e-5)
    np.testing.assert_allclose(state, want_state, atol=1e-5)
    # a row block wholly past the stop is zeros, not what lay there
    skipped = -(-stop // rows) * rows
    assert not np.asarray(y[:, skipped:]).any()


def test_what_takes_the_scan_kernel_is_decided_by_shape_and_dtype():
    assert ssm.ssm_scan_takes(2048, 5120, 16, "bfloat16")
    assert ssm.ssm_scan_takes(128, 5120, 16, "float32")
    assert not ssm.ssm_scan_takes(64, 5120, 16, "bfloat16")   # a short row
    assert not ssm.ssm_scan_takes(2048, 5120, 12, "bfloat16")
    assert not ssm.ssm_scan_takes(2048, 5120, 16, "int8")
    assert ssm.ssm_scan_rows(4096, 1153, True) == 1280
    assert ssm.ssm_scan_rows(4096, 1153, False) == 4096
    assert ssm.ssm_scan_rows(2048, 2048, True) == 2048


# -- (b') a decode row: the kernel against ssm_step, on a layer of a stack ----

def _step_case(layers, slots, inner, states=16, seed=11):
    """(a row's operands as ssm_step takes them, a stack of `layers`
    layers' states)."""
    c, dt, z, b, cc, a, d, dt_bias, _ = _scan_case(slots, 1, inner, states,
                                                   seed)
    stack = jax.random.normal(jax.random.PRNGKey(seed + 1),
                              (layers, slots, states, inner))
    return (c[:, 0], dt[:, 0], z[:, 0], b[:, 0], cc[:, 0], a, d,
            dt_bias), stack


@pytest.mark.parametrize("layer", [0, 2, 4])
@pytest.mark.parametrize("inner", [256, 5120])
@pytest.mark.parametrize("slots", [1, 8, 32])
def test_row_step_kernel_is_the_oracle_on_its_layer_of_the_stack(
        slots, inner, layer):
    """`ssm_row_step` (interpreted) against ssm_step on the layer's
    slice: the same out and the same new state to float32 rounding (the
    16-term sum in another order), and every other layer of the stack
    back bit for bit."""
    row, stack = _step_case(5, slots, inner)
    want_out, want_state = ssm.ssm_step(*row, stack[layer])
    out, new = ssm.ssm_row_step(*row, stack, jnp.int32(layer))
    assert out.shape == want_out.shape and out.dtype == want_out.dtype
    np.testing.assert_allclose(out, want_out, atol=1e-5)
    np.testing.assert_allclose(new[layer], want_state, atol=1e-6)
    for other in range(5):
        if other != layer:
            np.testing.assert_array_equal(new[other], stack[other])


@pytest.mark.parametrize("slots,channels", [(16, 128), (32, 256), (8, 512)])
def test_row_step_kernel_by_blocks_of_slots_and_channels(slots, channels):
    """A grid of several blocks either way (48 slots in blocks of 16,
    768 channels in blocks of 128 or 256), bf16 rows as the served model
    hands them; a block size that does not divide is the whole axis."""
    row, stack = _step_case(2, 48, 768, seed=17)
    row = tuple(x.astype(jnp.bfloat16) for x in row[:5]) + row[5:]
    want_out, want_state = ssm.ssm_step(*row, stack[1])
    out, new = ssm.ssm_row_step(*row, stack, jnp.int32(1), slots=slots,
                                channels=channels)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(out.astype(jnp.float32),
                               want_out.astype(jnp.float32), atol=0.05)
    np.testing.assert_allclose(new[1], want_state, atol=1e-6)
    np.testing.assert_array_equal(new[0], stack[0])


def test_what_takes_the_step_kernel_is_decided_by_shape_dtype_and_mesh(
        monkeypatch):
    assert ssm.ssm_step_takes(16, 5120, "float32")
    assert ssm.ssm_step_takes(16, 72, jnp.float32)      # interpreted here
    assert not ssm.ssm_step_takes(16, 5120, "bfloat16")  # S is float32
    assert not ssm.ssm_step_takes(12, 5120, "float32")   # sublanes
    with jax.sharding.set_mesh(jax.make_mesh((2,), ("x",))):
        assert not ssm.ssm_step_takes(16, 5120, "float32")
    monkeypatch.setattr(ssm, "_interpret", lambda: False)
    assert ssm.ssm_step_takes(16, 5120, "float32")
    assert not ssm.ssm_step_takes(16, 72, "float32")     # lanes


@pytest.mark.parametrize("kernel", [False, True])
def test_a_step_on_a_stack_is_ssm_step_either_way(kernel, monkeypatch):
    """ssm_stack_step, what the paged step's Mamba layer calls: the
    kernel where ssm_step_takes, else ssm_step on the layer's slice put
    back in place (channels off the 128 lanes where nothing is
    interpreted); layer 1 written, layers 0 and 2 as they were."""
    monkeypatch.setattr(ssm, "_interpret", lambda: kernel)
    row, stack = _step_case(3, 4, 72)
    assert ssm.ssm_step_takes(16, 72, stack.dtype) == kernel
    want_out, want_state = ssm.ssm_step(*row, stack[1])
    out, new = ssm.ssm_stack_step(*row, stack, jnp.int32(1))
    np.testing.assert_allclose(out, want_out, atol=1e-5)
    np.testing.assert_allclose(new[1], want_state, atol=1e-6)
    np.testing.assert_array_equal(new[0], stack[0])
    np.testing.assert_array_equal(new[2], stack[2])


# -- (c) the stores: cache, pool, engine ---------------------------------------

def test_cached_prefill_and_decode_are_the_reference(model):
    """generate()'s path by logits: a prefill into the contiguous cache
    (K/V of two layers, the state of six), then a token at a time."""
    config, params, shape = model
    tokens = some_tokens(2, 30)
    want = reference_logits(shape, tokens)
    cache = init_cache(config, 2, max_len=32)
    assert cache["k"].shape[0] == ATTENTION
    assert cache["conv"].shape == (MAMBA, 3, 2, 128)
    assert cache["ssm"].shape == (MAMBA, 2, 16, 128)
    with jax.default_matmul_precision("highest"):
        logits, cache = forward(params, config, tokens[:, :19], cache=cache,
                                pos=0)
        out = [np.asarray(logits)]
        for position in range(19, 30):
            logits, cache = forward(
                params, config, tokens[:, position:position + 1],
                cache=cache, pos=jnp.int32(position))
            out.append(np.asarray(logits))
    np.testing.assert_allclose(np.concatenate(out, axis=1), want,
                               atol=TOLERANCE, rtol=0)


def test_the_same_prompt_in_two_buckets_gives_the_same_state_and_logits(
        model):
    """Right padding advances nothing: the state after row true_len - 1
    and the logits there, whatever the bucket and whatever the padding
    holds."""
    config, params, _ = model
    prompt = np.asarray(some_tokens(1, 11, seed=9))
    found = []
    for bucket, fill in ((16, 0), (32, 0), (32, 77)):
        padded = np.full((1, bucket), fill, np.int32)
        padded[:, :11] = prompt
        with jax.default_matmul_precision("highest"):
            h, outputs, _, cache = transformer._hidden(
                params, config, padded, init_cache(config, 1, bucket), 0,
                true_len=jnp.int32(11))
            logits, _ = transformer._logits(params, config, h[:, 10:11],
                                            outputs)
        found.append((np.asarray(logits), np.asarray(cache["conv"]),
                      np.asarray(cache["ssm"])))
    for other in found[1:]:
        for ours, theirs in zip(found[0], other):
            np.testing.assert_allclose(ours, theirs, atol=1e-5, rtol=0)
    # and the tail is the three inputs before row 11, not the bucket's last
    with jax.default_matmul_precision("highest"):
        whole = transformer._hidden(params, config, np.asarray(prompt),
                                    init_cache(config, 1, 11), 0)[3]
    np.testing.assert_allclose(found[0][1], whole["conv"], atol=1e-5)


def test_engine_serves_the_references_tokens(model):
    """Prefill then decode through DecodeEngine (run-ahead on, two slots
    for three requests, so a slot changes hands; blocks of 8 crossed)
    against the reference's one full pass, by logits; the spans' counts
    are the state's."""
    config, params, shape = model
    engine = DecodeEngine(params, config, decode_slots=2, kv_block_size=8,
                          max_context=64)
    assert engine.pool["k"].shape[0] == ATTENTION
    assert engine.pool["conv"].shape == (MAMBA, 3, 2, 128)
    assert engine.pool["ssm"].shape == (MAMBA, 2, 16, 128)
    rng = np.random.default_rng(3)
    prompts = {name: rng.integers(1, 256, size=length).astype(np.int32)
               for name, length in (("a", 13), ("b", 21), ("c", 7))}
    for name, prompt in prompts.items():
        engine.submit(name, prompt, 19)
    done = drain(engine)
    stats = engine.stats()
    assert stats["preempted"] == 0 and stats["steps_ahead"] > 0
    assert stats["writes_kernel"] == stats["decode_steps"]
    # every step advanced its decoding slots' state: read and written
    assert stats["state_slots"] >= 3 * 18
    assert stats["state_bytes"] == 2 * STATE_BYTES * stats["state_slots"]
    assert stats["cache_rows"] % ATTENTION == 0
    # three whole prefills, each bucket run whole by the oracle
    assert (stats["scan_jnp"], stats["scan_kernel"]) == (3, 0)
    assert stats["scan_rows"] == 16 + 32 + 8
    assert stats["prefill_rows_run"] == stats["prefill_rows_bucket"]
    for name, prompt in prompts.items():
        assert_served_is_the_references(shape, prompt, done[name].tokens,
                                        name)


def test_engine_is_generate(model):
    config, params, _ = model
    prompts = np.asarray(some_tokens(2, 12, seed=21))
    want, _ = generate(params, config, jnp.asarray(prompts), 15)
    engine = DecodeEngine(params, config, decode_slots=2, kv_block_size=8,
                          max_context=64)
    for row, prompt in enumerate(prompts):
        engine.submit(row, prompt, 15)
    done = drain(engine)
    for row in range(2):
        np.testing.assert_array_equal(done[row].tokens, np.asarray(want)[row])


def test_a_slot_reused_after_a_longer_request_keeps_nothing_of_it(model):
    """One slot: a long request, then a short one into the same slot.
    The prefill overwrites the whole of the slot's state; nobody zeroes
    it in between."""
    config, params, shape = model
    engine = DecodeEngine(params, config, decode_slots=1, kv_block_size=8,
                          max_context=64)
    long = np.asarray(some_tokens(1, 29, seed=31))[0]
    short = np.asarray(some_tokens(1, 5, seed=32))[0]
    engine.submit("long", long, 17)
    engine.submit("short", short, 9)
    done = drain(engine)
    assert np.asarray(engine.pool["ssm"]).any()
    assert_served_is_the_references(shape, long, done["long"].tokens)
    assert_served_is_the_references(shape, short, done["short"].tokens)


def test_a_preempted_request_regenerates_its_tokens(model):
    """Two slots grow on a pool too small for both: the youngest is
    preempted, its prompt prefilled again into whichever slot is free
    (state and all), and it finishes with the reference's tokens."""
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


def test_engine_serves_the_reference_through_the_scan_kernel(
        model, monkeypatch):
    """The same through the Pallas scan (interpreted), taken at toy
    lengths: the prefill span says `kernel` and the rows it stopped at."""
    config, params, shape = model
    monkeypatch.setattr(ssm, "_SCAN_MIN_ROWS", 8)
    monkeypatch.setattr(ssm, "_SCAN_ROWS", 16)
    jax.clear_caches()
    try:
        engine = DecodeEngine(params, config, decode_slots=1,
                              kv_block_size=8, max_context=64)
        prompt = np.asarray(some_tokens(1, 37, seed=41))[0]   # bucket 64
        engine.submit("r", prompt, 11)
        done = drain(engine)
        stats = engine.stats()
        assert (stats["scan_kernel"], stats["scan_jnp"]) == (1, 0)
        assert stats["scan_rows"] == 48           # 37 up to a block of 16
        assert_served_is_the_references(shape, prompt, done["r"].tokens)
    finally:
        jax.clear_caches()


@pytest.mark.parametrize("kernel", [True, False])
def test_engine_serves_the_reference_through_the_step_kernel(
        model, monkeypatch, kernel):
    """ISSUE 45: the paged step hands a Mamba layer the whole `ssm` leaf
    and its index, and the state is advanced by `ssm_row_step`
    (interpreted) -- or, where ssm_step_takes says no, by ssm_step on the
    layer's slice: every decode span says which, as
    models.state_step_kind does, the counts are the steps' and the
    tokens the reference's either way."""
    config, params, shape = model
    if not kernel:
        # d_inner is 128 here and S float32: nothing of the toy model
        # makes the predicate say no, so the test does
        monkeypatch.setattr(ssm, "ssm_step_takes",
                            lambda states, inner, dtype: False)
        monkeypatch.setattr(transformer, "ssm_step_takes",
                            lambda states, inner, dtype: False)
    kind = "kernel" if kernel else "jnp"
    jax.clear_caches()
    try:
        assert transformer.state_step_kind(config) == kind
        spans = _Order()
        engine = DecodeEngine(params, config, decode_slots=2,
                              kv_block_size=8, max_context=64, spans=spans)
        rng = np.random.default_rng(5)
        prompts = {name: rng.integers(1, 256, size=length).astype(np.int32)
                   for name, length in (("a", 9), ("b", 17), ("c", 5))}
        for name, prompt in prompts.items():
            engine.submit(name, prompt, 13)
        done = drain(engine)
        stats = engine.stats()
        decodes = [fields for _, fields in spans.named("engine.decode")]
        assert decodes and {fields["state_step"] for fields in decodes} == {
            kind}
        other = "jnp" if kernel else "kernel"
        assert stats["state_step_" + kind] == stats["decode_steps"] == len(
            decodes)
        assert stats["state_step_" + other] == 0
        for name, prompt in prompts.items():
            assert_served_is_the_references(shape, prompt, done[name].tokens,
                                            name)
    finally:
        jax.clear_caches()


def test_a_model_without_a_recurrent_state_names_no_state_step():
    """The span field and the counts are a recurrent model's: a dense
    model's decode spans carry no `state_step` and its counts stay 0."""
    config = TransformerConfig(vocab_size=64, d_model=32, n_layers=2,
                               n_heads=2, n_kv_heads=1, d_ff=48,
                               max_seq_len=32, dtype="float32")
    spans = _Order()
    engine = DecodeEngine(init_params(config, jax.random.PRNGKey(0)), config,
                          decode_slots=1, kv_block_size=8, spans=spans)
    engine.submit("r", np.arange(1, 6, dtype=np.int32), 5)
    drain(engine)
    decodes = [fields for _, fields in spans.named("engine.decode")]
    assert decodes and not any("state_step" in fields for fields in decodes)
    stats = engine.stats()
    assert (stats["state_step_kernel"], stats["state_step_jnp"]) == (0, 0)


# -- (c') a whole prefill by row tiles, the state carried (ISSUE 44) ------------
#
# tests/test_prefill_rows.py holds the hybrid's tiled prefill to the whole
# bucket's at every length around a tile's edge, its scan the oracle's (a
# toy tile is under the kernel's rows).  Here the same through the Pallas
# scan, interpreted: tiles of 16 rows of a 64-row bucket, each tile one
# kernel call, the state handed from call to call.


@pytest.fixture
def scan_kernel_at_toy_sizes(monkeypatch):
    """set(row tile): the scan kernel taken from 8 rows, the row tile as
    told, for the programs traced from here on."""
    monkeypatch.setattr(ssm, "_SCAN_MIN_ROWS", 8)
    monkeypatch.setattr(ssm, "_SCAN_ROWS", EDGE_TILE)

    def set_tile(rows: int) -> None:
        monkeypatch.setattr(transformer, "_ROW_TILE", rows)
        jax.clear_caches()
    yield set_tile
    jax.clear_caches()


_THROUGH_THE_KERNEL: dict = {}


@pytest.mark.parametrize("true_len", EDGE_LENGTHS)
def test_a_tiled_prefill_through_the_scan_kernel_is_the_whole_buckets(
        model, scan_kernel_at_toy_sizes, true_len):
    """Every Mamba layer's tail and state, and what reads them: the
    tiles' kernel calls, the state carried between them, leave what the
    bucket's one call leaves (assert_tiled_is_the_whole_buckets)."""
    if not _THROUGH_THE_KERNEL:
        config, params, _ = model
        assert transformer.scan_kind(config, EDGE_BUCKET) == "kernel"
        _THROUGH_THE_KERNEL.update(hidden_whole_and_tiled(
            config, params, np.asarray(some_tokens(1, EDGE_BUCKET, seed=13)),
            scan_kernel_at_toy_sizes))
    assert_tiled_is_the_whole_buckets(_THROUGH_THE_KERNEL, true_len,
                                      TOLERANCE)


def test_engine_serves_the_reference_by_row_tiles(
        model, scan_kernel_at_toy_sizes):
    """The engine over a bucket of four row tiles of which three hold a
    live row: the counters say what ran, the scan's rows with the
    layer's, and the tokens are the reference's."""
    config, params, shape = model
    scan_kernel_at_toy_sizes(EDGE_TILE)
    assert transformer._row_tiles_take(config, EDGE_BUCKET)
    engine = DecodeEngine(params, config, decode_slots=1, kv_block_size=8,
                          max_context=EDGE_BUCKET + 16)
    prompt = np.asarray(some_tokens(1, 37, seed=41))[0]   # bucket 64
    engine.submit("r", prompt, 11)
    done = drain(engine)
    stats = engine.stats()
    assert (stats["prefill_rows_run"], stats["prefill_rows_bucket"]) == (
        48, EDGE_BUCKET)
    assert (stats["scan_kernel"], stats["scan_jnp"]) == (1, 0)
    assert stats["scan_rows"] == 48               # 37 up to a block of 16
    assert_served_is_the_references(shape, prompt, done["r"].tokens)


def test_the_attention_layers_are_told_the_prompts_length(
        model, live_attention, monkeypatch):
    """PR 40: the attention layers' condition is the kernel's own, whether
    or not the bucket runs by row tiles (this one, under two tiles, does
    not) -- a whole prefill whose bucket attends through the flash kernel
    tells it the true length, and first token, logits, K/V rows below
    true_len and the slot's state are the whole bucket's attention's
    (tests/test_prefill_rows.py's check: `wo` and the Mamba layers after
    it see zeros in the dead blocks' rows, which nothing below true_len
    reads)."""
    config, params, _ = model
    config = dataclasses.replace(config, max_seq_len=LIVE_BUCKET)
    assert transformer._row_tiles_take(config, 2048)
    assert not transformer._row_tiles_take(config, LIVE_BUCKET)
    check_live_attention_prefill(
        config, params, monkeypatch,
        lambda: {**init_paged_pool(config, LIVE_BUCKET // 32 + 1, 32),
                 **transformer.init_recurrent_state(config, 2)},
        slot=np.int32(1))


# -- (d) what is refused by name ------------------------------------------------

def _engine(model, **keywords):
    config, params, _ = model
    return DecodeEngine(params, config, decode_slots=1, kv_block_size=8,
                        max_context=32, **keywords)


def _dense_target(model, **keywords):
    config, params, _ = model
    dense = TransformerConfig(
        vocab_size=256, d_model=32, n_layers=1, n_heads=2, n_kv_heads=1,
        d_ff=64, max_seq_len=32, dtype="float32")
    return DecodeEngine(init_params(dense, jax.random.PRNGKey(0)), dense,
                        decode_slots=1, kv_block_size=8,
                        draft_params=params, draft_config=config, spec_k=2)


REFUSED = {
    "prefix_policy": lambda model: _engine(
        model, prefix_policy="prefix_cache=on"),
    "prefill_chunk_size": lambda model: _engine(model, prefill_chunk_size=8),
    "speculation_as_target": lambda model: _engine(
        model, draft_params=model[1], draft_config=model[0], spec_k=2),
    "speculation_as_draft": _dense_target,
    "checkpoint_export": lambda model: DecodeCheckpointer(
        _engine(model), CheckpointPolicy.parse("checkpoint_every=1")),
    "restore_request": lambda model: _engine(model).restore_request(
        "r", None, np.arange(1, 5), 4),
    "disagg_handoff": lambda model: PrefillEngine(
        model[1], model[0], kv_block_size=8),
    "adopt_request": lambda model: _engine(model).adopt_request(
        "r", {"prompt": [1, 2, 3], "max_new": 2}),
    "sequence_parallel": lambda model: dataclasses.replace(
        model[0], sequence_parallel=True),
    "kv_dtype_int8": lambda model: dataclasses.replace(
        model[0], kv_dtype="int8"),
    "quantize_weights_int8": lambda model: quantize_weights_int8(
        model[1], model[0]),
    "make_train_step": lambda model: make_train_step(
        model[0], optax.sgd(0.1)),
    "a_window_over_one": lambda model: transformer.paged_verify_step(
        model[1], model[0],
        {**init_paged_pool(model[0], 5, 8),
         **transformer.init_recurrent_state(model[0], 1)},
        np.ones((1, 4), np.int32), np.zeros((1,), np.int32),
        np.ones((1, 3), np.int32), np.ones((1, 3), np.int32),
        np.zeros((1, 3), np.int32)),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_what_the_state_is_not_carried_through_is_refused_by_name(model,
                                                                  what):
    with pytest.raises(ValueError, match="recurrent state"):
        REFUSED[what](model)


@pytest.mark.parametrize("key,value", [
    ("num_experts", 16), ("sliding_window", 4096), ("hidden_act", "gelu"),
    ("mamba_proj_bias", True), ("mamba_conv_bias", False),
    ("tie_word_embeddings", False)])
def test_published_keys_that_are_not_implemented_are_refused(key, value):
    with pytest.raises(ValueError, match=key):
        jamba_config(dict(PUBLISHED, **{key: value}))


def test_layer_kinds_are_checked():
    plain = dict(vocab_size=64, d_model=32, n_layers=2, n_heads=2,
                 n_kv_heads=1, d_ff=64)
    with pytest.raises(ValueError, match="layer_kinds"):
        TransformerConfig(**plain, layer_kinds=("mamba",))
    with pytest.raises(ValueError, match="layer_kinds"):
        TransformerConfig(**plain, layer_kinds=("mamba", "conv"))
    with pytest.raises(ValueError, match="ssm_d_inner"):
        TransformerConfig(**plain, layer_kinds=("mamba", "attention"))
    with pytest.raises(ValueError, match="ut_steps"):
        TransformerConfig(**plain, layer_kinds=("mamba", "attention"),
                          ssm_d_inner=64, ssm_d_state=16, ssm_d_conv=4,
                          ssm_dt_rank=4, ut_steps=2)


# -- (e) what the other models lower to ------------------------------------------

def _operations(lowered) -> collections.Counter:
    return collections.Counter(re.findall(r"stablehlo\.\w+",
                                          lowered.as_text()))


def test_a_model_of_one_kind_lowers_to_the_program_it_lowered_to():
    """Layer kinds that are all attention are one run, one scan: the same
    operations, one for one, as the model without kinds (whose programs
    the tests of PRs 34 and 38 hold), in forward, in the paged prefill
    and in the decode step; and no model without Mamba layers carries a
    state leaf."""
    plain = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=3, n_heads=2, n_kv_heads=1,
        d_ff=64, max_seq_len=64, dtype="float32")
    kinds = dataclasses.replace(plain, layer_kinds=("attention",) * 3)
    assert kinds.n_caches == plain.n_caches == 3 and not kinds.recurrent
    tokens = np.ones((1, 16), np.int32)
    table, one = np.arange(1, 9, dtype=np.int32), np.int32(1)
    idle = np.zeros((2,), np.int32)
    found = []
    for config in (plain, kinds):
        params = init_params(config, jax.random.PRNGKey(0))
        assert ("runs" in params) == bool(config.layer_kinds)
        pool = init_paged_pool(config, 9, 8)
        assert set(pool) == set(init_cache(config, 1, 8)) == {"k", "v"}
        found.append([
            _operations(jax.jit(lambda p, t, c=config: forward(p, c, t)
                                ).lower(params, tokens)),
            _operations(paged_prefill.lower(params, config, pool, tokens,
                                            table, one)),
            _operations(paged_decode_step.lower(
                params, config, pool, np.ones((2, 8), np.int32), idle,
                np.ones((2, 1), np.int32), idle, idle))])
    assert found[0] == found[1]


def test_param_specs_cover_the_hybrid_models_leaves(model):
    config, params, _ = model
    specs = param_specs(config)
    assert (jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda leaf: 0, params))
        == jax.tree_util.tree_structure(jax.tree_util.tree_map(
            lambda spec: 0, specs,
            is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))))
    for leaf, spec in zip(
            jax.tree_util.tree_leaves(params),
            jax.tree_util.tree_leaves(
                specs, is_leaf=lambda x: isinstance(
                    x, jax.sharding.PartitionSpec))):
        assert len(spec) <= leaf.ndim


def test_the_element_reads_jamba_by_its_model_type():
    from aiko_services_tpu.elements import ml

    class Element:
        parameters = {"model": PUBLISHED, "max_seq_len": 96}

        def get_parameter(self, name, default=None):
            return self.parameters.get(name, default)

    config = ml._transformer_config(Element())
    assert (config.n_states, config.max_seq_len, config.dtype) == (
        MAMBA, 96, "float32")
