# Qwen3-Next's hybrid stack on the normal path: Gated DeltaNet layers whose
# recurrent state is a slot's (a convolution tail and a matrix a value head
# a layer), beside gated attention layers of an explicit head size in the
# paged pool, every layer's FFN routed experts with a gated shared expert --
# each held to benchmark/reference/qwen3_next.py, the float32 reference that
# imports nothing of the program and scans a whole sequence from zero, row
# by row.
#
# Everything here is float32 at toy widths: hidden 64, 4 query heads of 32
# (not 64 / 4) over ONE K/V head, 8 of a head's columns rotating; 8 layers
# in 2 periods of 3 delta layers and 1 attention layer; 2 key heads of 16
# serving 4 value heads of 24, 4 taps; 16 routed experts of width 48, top
# 3, one shared expert of 48.  The norm gains are random (not 1), so that a
# gain stored as 1 + w is told from one stored as w.  A tolerance is float32
# rounding through a few matmuls of other shapes, a blockwise softmax and a
# chunked scan, times what this model does to it: a delta head's output is
# normed a head, and a head that forgets at once (A up to 16) gives o =
# (k . q) d, whose direction turns on the sign of a dot product of two
# random unit vectors; where that is near 0 what is left of the older rows
# decides, and a rounding is amplified.  Measured on the reference itself:
# one norm's gains moved by 1e-7 of themselves move its logits by 1.4e-4,
# by 3e-7 7.7e-4.  So 1e-3 on logits of size ~1; a gain misplaced, a state
# not reset or a stale tail are 10 to 1000 times that.

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from aiko_services_tpu.decode import (
    CheckpointPolicy, DecodeCheckpointer, DecodeEngine, PrefillEngine)
from aiko_services_tpu.models import configs, transformer
from aiko_services_tpu.models.configs import qwen3_next_config
from aiko_services_tpu.models.layers import dense, swiglu
from aiko_services_tpu.models.transformer import (
    TransformerConfig, forward, generate, init_cache, init_paged_pool,
    init_params, make_train_step, param_specs, quantize_weights_int8)
from aiko_services_tpu.parallel import delta
from benchmark.reference import qwen3_next as reference
from test_prefill_rows import (
    EDGE_BUCKET, EDGE_LENGTHS, EDGE_TILE, assert_tiled_is_the_whole_buckets,
    hidden_whole_and_tiled)

PUBLISHED = {
    "model_type": "qwen3_next", "vocab_size": 256, "hidden_size": 64,
    "num_hidden_layers": 8, "num_attention_heads": 4,
    "num_key_value_heads": 1, "head_dim": 32, "intermediate_size": 96,
    "hidden_act": "silu", "full_attention_interval": 4,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 16,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_value_head_dim": 24, "moe_intermediate_size": 48,
    "shared_expert_intermediate_size": 48, "num_experts": 16,
    "num_experts_per_tok": 3, "norm_topk_prob": True,
    "decoder_sparse_step": 1, "mlp_only_layers": [],
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-6,
    "rope_scaling": None, "rope_theta": 10000000,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "max_position_embeddings": 4096, "torch_dtype": "float32"}
SEED = 7
TOLERANCE = 1e-3
DELTA, ATTENTION = 6, 2
CHANNELS = 2 * 2 * 16 + 4 * 24                      # [q | k | v]
STATE_BYTES = DELTA * (4 * 4 * 16 * 24 + 3 * CHANNELS * 4)   # float32 rows


def _random_gains(params: dict) -> dict:
    """The program's seeded weights with every norm gain drawn anew."""
    counter = iter(range(10_000))

    def visit(tree):
        if isinstance(tree, dict):
            if set(tree) == {"scale"}:
                key = jax.random.PRNGKey(1000 + next(counter))
                return {"scale": 1.0 + 0.3 * jax.random.normal(
                    key, tree["scale"].shape, tree["scale"].dtype)}
            return {name: visit(leaf) for name, leaf in tree.items()}
        if isinstance(tree, list):
            return [visit(leaf) for leaf in tree]
        return tree

    return visit(params)


def _published_gains(config, params: dict) -> dict:
    """The program's gains g as the reference takes them: w = g - 1 where
    the published gain is 1 + w, g itself for the delta mixer's own."""
    layers = []
    for stack, (kind, _, count) in zip(params["runs"],
                                       transformer._kind_runs(config)):
        for index in range(count):
            at = lambda name: np.asarray(             # noqa: E731
                stack[name]["scale"][index])
            if kind == "delta":
                layers.append({"norm_1": at("mixer_norm") - 1.0,
                               "norm_2": at("mlp_norm") - 1.0,
                               "gate_norm": at("delta_norm")})
            else:
                layers.append({"norm_1": at("attn_norm") - 1.0,
                               "norm_2": at("mlp_norm") - 1.0,
                               "q_norm": at("q_norm") - 1.0,
                               "k_norm": at("k_norm") - 1.0})
    return {"layers": layers,
            "final": np.asarray(params["norm_out"]["scale"]) - 1.0}


@pytest.fixture(scope="module")
def model():
    config = qwen3_next_config(PUBLISHED, max_seq_len=128)
    params = _random_gains(init_params(config, jax.random.PRNGKey(SEED)))
    return (config, params, reference.shape_of(PUBLISHED),
            _published_gains(config, params))


def reference_logits(model, tokens) -> np.ndarray:
    _, _, shape, norms = model
    tokens = np.asarray(tokens)
    positions = np.tile(np.arange(tokens.shape[1])[None],
                        (tokens.shape[0], 1))
    return np.asarray(reference.logits_at(shape, SEED, tokens, positions,
                                          norms=norms))


def some_tokens(rows: int, length: int, seed: int = 5):
    return jax.random.randint(jax.random.PRNGKey(seed), (rows, length), 1,
                              PUBLISHED["vocab_size"]).astype(jnp.int32)


def assert_served_is_the_references(model, prompt, served, what=""):
    """Every served token's reference logit is the reference's best to
    TOLERANCE, in one full pass over prompt + served tokens: by logits,
    not by tokens."""
    sequence = np.concatenate([prompt, served])[None]
    logits = reference_logits(model, sequence)[0]
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

def test_layer_kinds_and_sizes_come_from_the_published_keys(model):
    config, params, _, _ = model
    kinds = ["delta", "delta", "delta", "attention"] * 2
    assert list(config.layer_kinds) == kinds
    assert (config.n_caches, config.n_states, config.recurrent_kind) == (
        ATTENTION, DELTA, "delta")
    assert (config.head_dim, config.rotary_dim) == (32, 8)
    assert config.state_bytes == STATE_BYTES
    assert transformer._kind_runs(config) == [
        ("delta", 0, 3), ("attention", 0, 1), ("delta", 3, 3),
        ("attention", 1, 1)]
    assert [jax.tree_util.tree_leaves(run)[0].shape[0]
            for run in params["runs"]] == [3, 1, 3, 1]
    # a gated attention's wq is twice as wide, held (out, in)
    assert params["runs"][1]["wq"]["w"].shape == (1, 4 * 2 * 32, 64)
    assert configs.PUBLISHED_READERS["qwen3_next"] is qwen3_next_config
    assert qwen3_next_config(PUBLISHED).max_seq_len == 4096
    # the published sizes: 2,146,304 B a delta layer a slot
    published = qwen3_next_config(dict(
        PUBLISHED, hidden_size=2048, num_hidden_layers=8,
        num_attention_heads=16, num_key_value_heads=2, head_dim=256,
        linear_key_head_dim=128, linear_value_head_dim=128,
        linear_num_key_heads=16, linear_num_value_heads=32,
        moe_intermediate_size=512, shared_expert_intermediate_size=512,
        num_experts=128, router_experts=512, experts_held=[0, 128],
        num_experts_per_tok=10, torch_dtype="bfloat16"))
    assert published.state_bytes == 6 * 2_146_304
    assert (published.rotary_dim, published.held, published.top_k) == (
        64, (0, 128), 10)
    assert published.n_routed_experts == 512 and published.norm_topk


def test_forward_is_the_reference(model):
    config, params, _, _ = model
    tokens = some_tokens(2, 45)
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(forward(params, config, tokens))
    np.testing.assert_allclose(logits, reference_logits(model, tokens),
                               atol=TOLERANCE, rtol=0)


def test_a_gain_stored_as_w_is_told_from_one_stored_as_one_plus_w(model):
    """The random gains do what they are for: the reference fed the
    program's gains as if they were the published w is far off."""
    config, params, shape, norms = model
    tokens = some_tokens(1, 20)
    positions = np.arange(20)[None]
    wrong = {"layers": [{name: gain + 1.0 if name != "gate_norm"
                         else gain - 1.0 for name, gain in layer.items()}
                        for layer in norms["layers"]],
             "final": norms["final"] + 1.0}
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(forward(params, config, tokens))
    off = np.asarray(reference.logits_at(shape, SEED, np.asarray(tokens),
                                         positions, norms=wrong))
    assert np.abs(off - logits).max() > 100 * TOLERANCE, np.abs(
        off - logits).max()


def test_the_seeded_weights_are_the_references_draws(model):
    """Leaf by leaf, a delta layer (1, the second of a run of three) and
    an attention layer (7), an expert of each: the reference makes the
    program's numbers from the seed itself."""
    config, params, shape, _ = model
    keys = jax.random.split(jax.random.PRNGKey(SEED), shape.layers + 1)[1:]
    theirs, layer_keys = reference._layer_weights(keys[1], 1, shape)
    ours = jax.tree_util.tree_map(lambda leaf: leaf[1], params["runs"][0])
    pairs = {"w_qkvz": ours["w_qkvz"]["w"], "conv": ours["conv"]["w"],
             "w_ba": ours["w_ba"]["w"], "a_log": ours["a_log"],
             "dt_bias": ours["dt_bias"], "w_out": ours["w_out"]["w"],
             "router": ours["router"]["w"],
             "shared_gate": ours["shared_gate"]["w"],
             "shared_up": ours["shared_up"]["w"],
             "shared_down": ours["shared_down"]["w"],
             "shared_mix": ours["shared_mix"]["w"]}
    for name, leaf in pairs.items():
        np.testing.assert_array_equal(leaf, theirs[name], err_msg=name)
    expert = reference._expert_weights(layer_keys[5:], 11, shape,
                                       jnp.float32)
    for name, leaf in zip(("w_gate", "w_up", "w_down"), expert):
        np.testing.assert_array_equal(ours[name]["w"][11], leaf,
                                      err_msg=name)
    # A and the step size lie in the training initialiser's ranges
    assert 0 < np.exp(np.asarray(ours["a_log"])).max() <= 16.0
    step = np.asarray(jax.nn.softplus(ours["dt_bias"]))
    assert 1e-3 <= step.min() and step.max() <= 1e-1 + 1e-6
    theirs, _ = reference._layer_weights(keys[7], 7, shape)
    ours = jax.tree_util.tree_map(lambda leaf: leaf[0], params["runs"][3])
    for name in ("wq", "wk"):                        # held (out, in)
        np.testing.assert_array_equal(ours[name]["w"].T, theirs[name],
                                      err_msg=name)
    for name in ("wv", "wo", "router", "shared_mix"):
        np.testing.assert_array_equal(ours[name]["w"], theirs[name],
                                      err_msg=name)


# -- (b) the gated delta rule: chunkwise against the rows, a step ------------

def _delta_case(batch, heads, length, key_dim, value_dim, seed=0,
                slow=False):
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    normal = jax.random.normal

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    rate = jax.random.uniform(keys[3], (heads,)) * (1.0 if slow else 16.0)
    return (unit(normal(keys[0], (batch, heads, length, key_dim)))
            / np.sqrt(key_dim),
            unit(normal(keys[1], (batch, heads, length, key_dim))),
            normal(keys[2], (batch, heads, length, value_dim)),
            -rate[None, :, None] * jax.nn.softplus(
                normal(keys[4], (batch, heads, length)) + 1.0),
            jax.nn.sigmoid(normal(keys[5], (batch, heads, length))),
            normal(keys[6], (batch, heads, key_dim, value_dim)))


def _plain_rule(q, k, v, g, beta, state, stop):
    """The recurrence as written, a row and a head at a time, in numpy."""
    q, k, v, g, beta = (np.asarray(x, np.float64) for x in (q, k, v, g, beta))
    state = np.array(state, np.float64)
    out = np.zeros(v.shape)
    for b in range(q.shape[0]):
        for h in range(q.shape[1]):
            s = state[b, h]
            for t in range(q.shape[2]):
                if t < stop:
                    s = np.exp(g[b, h, t]) * s
                    d = beta[b, h, t] * (v[b, h, t] - s.T @ k[b, h, t])
                    s = s + np.outer(k[b, h, t], d)
                out[b, h, t] = s.T @ q[b, h, t]
            state[b, h] = s
    return out, state


@pytest.mark.parametrize("length,stop", [(70, 70), (70, 33), (16, 1)])
def test_scan_oracle_is_the_plain_recurrence(length, stop):
    case = _delta_case(2, 3, length, 8, 12, seed=length, slow=True)
    want_out, want_state = _plain_rule(*case, stop)
    out, state = delta.delta_scan_reference(*case, jnp.int32(stop))
    np.testing.assert_allclose(out[:, :, :stop], want_out[:, :, :stop],
                               atol=1e-5)
    np.testing.assert_allclose(state, want_state, atol=1e-5)


@pytest.mark.parametrize("chunk", [64, 32])
@pytest.mark.parametrize("length,stop,slow", [
    (192, 192, False),            # three chunks of 64, S across them
    (192, 97, True),              # the stop inside a chunk: 64 + 33
    (256, 70, True),              # whole chunks past the stop
    (128, 1, False)])
def test_chunk_scan_is_the_oracle(length, stop, slow, chunk):
    case = _delta_case(2, 4, length, 16, 24, seed=length + stop, slow=slow)
    want_out, want_state = delta.delta_scan_reference(*case,
                                                      jnp.int32(stop))
    out, state = delta.delta_chunk_scan(*case, jnp.int32(stop), chunk=chunk)
    assert out.shape == want_out.shape and state.shape == want_state.shape
    np.testing.assert_allclose(out[:, :, :stop], want_out[:, :, :stop],
                               atol=1e-5)
    np.testing.assert_allclose(state, want_state, atol=1e-5)


@pytest.mark.parametrize("size", [8, 16, 64])
def test_the_chunks_inverse_holds_where_the_product_form_cancels(size):
    """(I + A)^-1 where every entry under the diagonal is near 1 (one key
    repeated, beta near 1, no decay): the inverse's entries are small,
    the powers of A that the finite product (I - A)(I + A^2)... sums are
    binomial coefficients, 1e17 at 64 rows."""
    rng = np.random.default_rng(size)
    a = np.tril(0.9 + 0.1 * rng.random((3, size, size)), -1)
    want = np.linalg.inv(np.eye(size) + a)
    found = delta._unit_lower_inverse(jnp.asarray(a, jnp.float32))
    np.testing.assert_allclose(found, want, atol=1e-5)


@pytest.mark.parametrize("stop", [256, 150])
def test_chunk_scan_is_the_oracle_where_keys_repeat_and_heads_remember(
        stop):
    """Keys that share most of their direction, decays of a thousandth a
    row: what a trained model's slow heads see.  S crosses four chunks
    carrying nearly all it was."""
    q, k, v, g, beta, state = _delta_case(2, 4, 256, 16, 24, seed=5)
    common = k[:, :, :1]
    k = k * 0.3 + common
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    case = (q, k, v, g * 1e-3, beta, state)
    want_out, want_state = delta.delta_scan_reference(*case,
                                                      jnp.int32(stop))
    out, state = delta.delta_chunk_scan(*case, jnp.int32(stop))
    np.testing.assert_allclose(out[:, :, :stop], want_out[:, :, :stop],
                               atol=2e-5)
    np.testing.assert_allclose(state, want_state, atol=2e-5)


def test_a_length_off_the_chunks_is_padded_with_rows_that_do_nothing():
    case = _delta_case(2, 4, 70, 16, 24, seed=3, slow=True)
    want_out, want_state = delta.delta_scan_reference(*case)
    out, state = delta.delta_scan(*case)
    np.testing.assert_allclose(out, want_out, atol=1e-5)
    np.testing.assert_allclose(state, want_state, atol=1e-5)


@pytest.mark.parametrize("kernel", [False, True])
def test_a_step_is_a_row_of_the_oracle_on_its_layer_of_the_stack(
        kernel, monkeypatch):
    """delta_step against one row of delta_scan_reference; the kernel
    `gdn_step` (interpreted) and XLA's form (what heads off the 128 lanes
    take where nothing is interpreted) write layer 1 of the stack and
    leave layers 0 and 2 as they were."""
    monkeypatch.setattr(delta, "_interpret", lambda: kernel)
    q, k, v, g, beta, state = _delta_case(3, 8, 1, 16, 24, seed=9)
    want_out, want_state = delta.delta_scan_reference(q, k, v, g, beta,
                                                      state)
    stack = jnp.stack([state * 0.5, state, state * 2.0])
    assert delta.delta_step_takes(16, 24, 8) == kernel
    out, new = delta.delta_step(q[:, :, 0], k[:, :, 0], v[:, :, 0],
                                g[:, :, 0], beta[:, :, 0], stack,
                                jnp.int32(1))
    np.testing.assert_allclose(out, want_out[:, :, 0], atol=1e-6)
    np.testing.assert_allclose(new[1], want_state, atol=1e-6)
    np.testing.assert_array_equal(new[0], stack[0])
    np.testing.assert_array_equal(new[2], stack[2])


def test_what_takes_the_step_kernel_is_decided_by_shape_and_mesh(
        monkeypatch):
    assert delta.delta_step_takes(128, 128, 32)
    assert delta.delta_step_takes(16, 24, 8)           # interpreted here
    assert not delta.delta_step_takes(128, 128, 48)    # blocks of 32 heads
    with jax.sharding.set_mesh(jax.make_mesh((2,), ("x",))):
        assert not delta.delta_step_takes(128, 128, 32)
    monkeypatch.setattr(delta, "_interpret", lambda: False)
    assert delta.delta_step_takes(128, 128, 32)
    assert not delta.delta_step_takes(128, 96, 32)     # lanes
    assert not delta.delta_step_takes(96, 128, 32)


# -- (c) the shares add up to the layer ---------------------------------------

def test_the_four_shares_and_the_shared_expert_once_are_the_uncut_layer(
        model):
    """Four chips hold experts 0-3, 4-7, 8-11, 12-15 of one layer: the
    routed parts the four compute, plus what every chip computes alike
    (the gated shared expert) counted once, are the reference's layer
    with every expert held."""
    config, _, shape, _ = model
    key = jax.random.PRNGKey(SEED)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 23, 64)) * 0.7
    routed = 0.0
    for low in range(0, 16, 4):
        share = dataclasses.replace(config, experts_held=(low, low + 4))
        stack = init_params(share, key)["runs"][0]
        assert stack["w_gate"]["w"].shape[:2] == (3, 4)
        layer = {name: jax.tree_util.tree_map(lambda leaf: leaf[1], leaf)
                 for name, leaf in stack.items()
                 if name not in transformer._EXPERT_LEAVES}
        layer["experts"] = ({name: stack[name]
                             for name in transformer._EXPERT_LEAVES}, 1)
        shared = swiglu(layer["shared_gate"], layer["shared_up"],
                        layer["shared_down"], x) * jax.nn.sigmoid(
            dense(layer["shared_mix"], x))
        with jax.default_matmul_precision("highest"):
            out, stats = transformer._routed_moe(share, layer, x)
        assert 0 < stats[2] <= 23 * 3
        routed = routed + (out - shared)
    keys = jax.random.split(key, shape.layers + 1)[1:]
    stored, layer_keys = reference._layer_weights(keys[1], 1, shape)
    with jax.default_matmul_precision("highest"):
        want = reference.moe(x[0], reference._widened(stored, "stated"),
                             layer_keys, shape, "stated")
    np.testing.assert_allclose((routed + shared)[0], want, atol=TOLERANCE,
                               rtol=0)


# -- (d) the stores: cache, pool, engine ----------------------------------------

def test_cached_prefill_and_decode_are_the_reference(model):
    """generate()'s path by logits: a prefill into the contiguous cache
    (K/V of two layers, the state of six), then a token at a time."""
    config, params, _, _ = model
    tokens = some_tokens(2, 30)
    want = reference_logits(model, tokens)
    cache = init_cache(config, 2, max_len=32)
    assert cache["k"].shape == (ATTENTION, 2, 1, 32, 32)
    assert cache["conv"].shape == (DELTA, 3, 2, CHANNELS)
    assert cache["delta"].shape == (DELTA, 2, 4, 16, 24)
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
    holds (the padding's rows go to no expert either)."""
    config, params, _, _ = model
    prompt = np.asarray(some_tokens(1, 11, seed=9))
    found = []
    for bucket, fill in ((16, 0), (32, 0), (32, 77)):
        padded = np.full((1, bucket), fill, np.int32)
        padded[:, :11] = prompt
        with jax.default_matmul_precision("highest"):
            h, outputs, stats, cache = transformer._hidden(
                params, config, padded, init_cache(config, 1, bucket), 0,
                true_len=jnp.int32(11))
            logits, _ = transformer._logits(params, config, h[:, 10:11],
                                            outputs)
        assert stats[2] == 11 * 3 * 8         # every live row's pairs
        found.append((np.asarray(logits), np.asarray(cache["conv"]),
                      np.asarray(cache["delta"])))
    for other in found[1:]:
        for ours, theirs in zip(found[0], other):
            np.testing.assert_allclose(ours, theirs, atol=1e-5, rtol=0)
    # and the tail is the three inputs before row 11, not the bucket's last
    with jax.default_matmul_precision("highest"):
        whole = transformer._hidden(params, config, np.asarray(prompt),
                                    init_cache(config, 1, 11), 0)[3]
    np.testing.assert_allclose(found[0][1], whole["conv"], atol=1e-5)
    np.testing.assert_allclose(found[0][2], whole["delta"], atol=1e-5)


@pytest.mark.parametrize("step_kernel", [True, False])
def test_engine_serves_the_references_tokens(model, step_kernel,
                                             monkeypatch):
    """Prefill then decode through DecodeEngine (run-ahead on, two slots
    for three requests, so a slot changes hands; blocks of 8 crossed)
    against the reference's one full pass, by logits; the spans' counts
    are the state's and the experts'.  The step's S through `gdn_step`
    (interpreted) and through XLA's form (heads of 16 x 24 where nothing
    of delta's is interpreted)."""
    config, params, _, _ = model
    monkeypatch.setattr(delta, "_interpret", lambda: step_kernel)
    jax.clear_caches()
    try:
        engine = DecodeEngine(params, config, decode_slots=2,
                              kv_block_size=8, max_context=64)
        assert engine.pool["k"].shape[0] == ATTENTION
        assert engine.pool["conv"].shape == (DELTA, 3, 2, CHANNELS)
        assert engine.pool["delta"].shape == (DELTA, 2, 4, 16, 24)
        rng = np.random.default_rng(3)
        prompts = {name: rng.integers(1, 256, size=length).astype(np.int32)
                   for name, length in (("a", 13), ("b", 21), ("c", 7))}
        for name, prompt in prompts.items():
            engine.submit(name, prompt, 19)
        done = drain(engine)
    finally:
        jax.clear_caches()
    stats = engine.stats()
    assert stats["preempted"] == 0 and stats["steps_ahead"] > 0
    assert stats["writes_kernel"] == stats["decode_steps"]
    # every step advanced its decoding slots' state: read and written
    assert stats["state_slots"] >= 3 * 18
    assert stats["state_bytes"] == 2 * STATE_BYTES * stats["state_slots"]
    assert stats["cache_rows"] % ATTENTION == 0
    # every layer's experts: at most top_k pairs a token a layer
    assert 0 < stats["expert_pairs"] <= stats["state_slots"] * 3 * 8 * 2
    # three whole prefills, each bucket run whole by the oracle
    assert (stats["scan_jnp"], stats["scan_kernel"]) == (3, 0)
    assert stats["scan_rows"] == 16 + 32 + 8
    for name, prompt in prompts.items():
        assert_served_is_the_references(model, prompt, done[name].tokens,
                                        name)


def test_engine_is_generate(model):
    config, params, _, _ = model
    prompts = np.asarray(some_tokens(2, 12, seed=21))
    want, _ = generate(params, config, jnp.asarray(prompts), 15)
    engine = DecodeEngine(params, config, decode_slots=2, kv_block_size=8,
                          max_context=64)
    for row, prompt in enumerate(prompts):
        engine.submit(row, prompt, 15)
    done = drain(engine)
    for row in range(2):
        np.testing.assert_array_equal(done[row].tokens, np.asarray(want)[row])


@pytest.mark.parametrize("first,second", [(5, 29), (29, 5)])
def test_a_reused_slot_starts_from_zero_state(model, first, second):
    """One slot: a request, then another into the same slot, shorter or
    longer.  The prefill computes from a zero state and overwrites the
    whole of the slot's; nobody zeroes it in between."""
    config, params, _, _ = model
    engine = DecodeEngine(params, config, decode_slots=1, kv_block_size=8,
                          max_context=64)
    one = np.asarray(some_tokens(1, first, seed=31))[0]
    two = np.asarray(some_tokens(1, second, seed=32))[0]
    engine.submit("one", one, 13)
    engine.submit("two", two, 9)
    done = drain(engine)
    assert np.asarray(engine.pool["delta"]).any()
    assert_served_is_the_references(model, one, done["one"].tokens)
    assert_served_is_the_references(model, two, done["two"].tokens)


def test_a_preempted_request_regenerates_its_tokens(model):
    config, params, _, _ = model
    engine = DecodeEngine(params, config, decode_slots=2, kv_block_size=4,
                          kv_blocks=6)
    prompts = {0: np.arange(1, 5, dtype=np.int32),
               1: np.arange(11, 15, dtype=np.int32)}
    for index, prompt in prompts.items():
        engine.submit(index, prompt, 12)
    done = drain(engine)
    assert engine.counters["preempted"] >= 1
    for index, prompt in prompts.items():
        assert_served_is_the_references(model, prompt, done[index].tokens,
                                        index)


@pytest.mark.parametrize("length,bucket", [(37, 64), (19, 32)])
def test_engine_serves_the_reference_through_the_chunkwise_scan(
        model, length, bucket, monkeypatch):
    """The same through the chunkwise form at toy lengths (chunks of 8):
    S crosses the bucket's chunks in XLA's scan, the rows past the prompt
    leaving it alone, and the prefill span says so."""
    config, params, _, _ = model
    monkeypatch.setattr(delta, "_CHUNK", 8)
    jax.clear_caches()
    try:
        engine = DecodeEngine(params, config, decode_slots=1,
                              kv_block_size=8, max_context=64)
        prompt = np.asarray(some_tokens(1, length, seed=41))[0]
        engine.submit("r", prompt, 11)
        done = drain(engine)
        stats = engine.stats()
        assert (stats["scan_jnp"], stats["scan_kernel"]) == (1, 0)
        assert stats["scan_rows"] == bucket
        assert_served_is_the_references(model, prompt, done["r"].tokens)
    finally:
        jax.clear_caches()


# -- (d') a whole prefill by row tiles, the state carried (ISSUE 44) ------------
#
# tests/test_prefill_rows.py holds a delta hybrid's tiled prefill to the
# whole bucket's at every length around a tile's edge, its rule the oracle's
# (a toy tile is under a chunk).  Here the same through the chunkwise form:
# tiles of 16 rows of a 64-row bucket, each tile two chunks of 8 rows, S and
# the convolution's tail handed from tile to tile, the routed experts
# between the layer's loop and its residual.


@pytest.fixture
def chunks_at_toy_sizes(monkeypatch):
    """set(row tile): chunks of 8 rows, the row tile as told, for the
    programs traced from here on."""
    monkeypatch.setattr(delta, "_CHUNK", 8)

    def set_tile(rows: int) -> None:
        monkeypatch.setattr(transformer, "_ROW_TILE", rows)
        jax.clear_caches()
    yield set_tile
    jax.clear_caches()


_THROUGH_THE_CHUNKS: dict = {}


@pytest.mark.parametrize("true_len", EDGE_LENGTHS)
def test_a_tiled_prefill_through_the_chunkwise_rule_is_the_whole_buckets(
        model, chunks_at_toy_sizes, true_len):
    """Every delta layer's tail and S, what reads them, and every live
    row's expert pairs: the tiles' chunkwise calls, S carried between
    them, leave what the bucket's one call leaves
    (assert_tiled_is_the_whole_buckets)."""
    if not _THROUGH_THE_CHUNKS:
        config, params, _, _ = model
        _THROUGH_THE_CHUNKS.update(hidden_whole_and_tiled(
            config, params, np.asarray(some_tokens(1, EDGE_BUCKET, seed=13)),
            chunks_at_toy_sizes))
    for what in ("tiled", "whole"):
        assert _THROUGH_THE_CHUNKS[what, true_len][2][2] == true_len * 3 * 8
    assert_tiled_is_the_whole_buckets(_THROUGH_THE_CHUNKS, true_len, 1e-4)


def test_engine_serves_the_reference_by_row_tiles(model,
                                                  chunks_at_toy_sizes):
    """The engine over a bucket of four row tiles of which three hold a
    live row: the counters say what ran, the rule's rows with the
    layer's, and the tokens are the reference's."""
    config, params, _, _ = model
    chunks_at_toy_sizes(EDGE_TILE)
    assert transformer._row_tiles_take(config, EDGE_BUCKET)
    engine = DecodeEngine(params, config, decode_slots=1, kv_block_size=8,
                          max_context=EDGE_BUCKET + 16)
    prompt = np.asarray(some_tokens(1, 37, seed=41))[0]     # bucket 64
    engine.submit("r", prompt, 11)
    done = drain(engine)
    stats = engine.stats()
    assert (stats["prefill_rows_run"], stats["prefill_rows_bucket"]) == (
        48, EDGE_BUCKET)
    assert (stats["scan_jnp"], stats["scan_kernel"]) == (1, 0)
    assert stats["scan_rows"] == 48
    assert_served_is_the_references(model, prompt, done["r"].tokens)


# -- (e) what is refused by name ------------------------------------------------

def _engine(model, **keywords):
    config, params = model[:2]
    return DecodeEngine(params, config, decode_slots=1, kv_block_size=8,
                        max_context=32, **keywords)


def _dense_target(model, **keywords):
    config, params = model[:2]
    plain = TransformerConfig(
        vocab_size=256, d_model=32, n_layers=1, n_heads=2, n_kv_heads=1,
        d_ff=64, max_seq_len=32, dtype="float32")
    return DecodeEngine(init_params(plain, jax.random.PRNGKey(0)), plain,
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


def test_the_refusal_names_the_kind_and_the_bytes_of_the_model_at_hand(
        model):
    with pytest.raises(ValueError) as raised:
        _engine(model, prefill_chunk_size=8)
    assert "delta layers" in str(raised.value)
    assert f"6 states of {STATE_BYTES // 6} B a slot" in str(raised.value)


@pytest.mark.parametrize("key,value", [
    ("sliding_window", 4096), ("use_sliding_window", True),
    ("mlp_only_layers", [0]), ("decoder_sparse_step", 2),
    ("rope_scaling", {"type": "yarn", "factor": 4.0}),
    ("norm_topk_prob", False), ("hidden_act", "gelu"),
    ("attention_bias", True), ("shared_expert_intermediate_size", 40)])
def test_published_keys_that_are_not_implemented_are_refused(key, value):
    with pytest.raises(ValueError, match=key):
        qwen3_next_config(dict(PUBLISHED, **{key: value}))


def test_experts_held_is_what_num_experts_says():
    with pytest.raises(ValueError, match="experts_held"):
        qwen3_next_config(dict(PUBLISHED, router_experts=64,
                               experts_held=[0, 8]))
    share = qwen3_next_config(dict(PUBLISHED, router_experts=64,
                                   experts_held=[16, 32]))
    assert (share.n_routed_experts, share.held) == (64, (16, 32))


def test_layer_kinds_take_one_recurrent_kind():
    plain = dict(vocab_size=64, d_model=32, n_layers=2, n_heads=2,
                 n_kv_heads=1, d_ff=64)
    with pytest.raises(ValueError, match="layer_kinds"):
        TransformerConfig(**plain, layer_kinds=("mamba", "delta"))
    with pytest.raises(ValueError, match="delta_key_dim"):
        TransformerConfig(**plain, layer_kinds=("delta", "attention"))
    with pytest.raises(ValueError, match="delta_value_heads"):
        TransformerConfig(**plain, layer_kinds=("delta", "attention"),
                          delta_key_heads=2, delta_value_heads=3,
                          delta_key_dim=8, delta_value_dim=8, delta_conv=4)


# -- (f) specs and the element ----------------------------------------------------

def test_param_specs_cover_the_hybrid_models_leaves(model):
    config, params = model[:2]
    specs = param_specs(config)
    is_spec = lambda x: isinstance(x, jax.sharding.PartitionSpec)  # noqa
    assert (jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda leaf: 0, params))
        == jax.tree_util.tree_structure(jax.tree_util.tree_map(
            lambda spec: 0, specs, is_leaf=is_spec)))
    for leaf, spec in zip(jax.tree_util.tree_leaves(params),
                          jax.tree_util.tree_leaves(specs, is_leaf=is_spec)):
        assert len(spec) <= leaf.ndim


def test_the_element_reads_qwen3_next_by_its_model_type():
    from aiko_services_tpu.elements import ml

    class Element:
        parameters = {"model": PUBLISHED, "max_seq_len": 96}

        def get_parameter(self, name, default=None):
            return self.parameters.get(name, default)

    config = ml._transformer_config(Element())
    assert (config.n_states, config.max_seq_len, config.dtype) == (
        DELTA, 96, "float32")
