# DeepSeek-V2 on the normal path: latent (MLA) attention over the latent
# cache and pool, group-limited routed experts with shared experts, a
# leading dense layer -- each held to benchmark/reference/deepseek_v2.py,
# the float32 reference that imports nothing of the program.
#
# Everything here is float32 at toy widths (hidden 64, 4 heads, nope 16 /
# rope 8 / v 16, latent 32, q-rank 48, 16 experts in 4 groups, 2 groups
# kept, top 3, 1 dense + 2 expert layers), so a tolerance is float32
# rounding through a few matmuls of other shapes and a blockwise softmax:
# 2e-5 on logits of size ~1 (tests/test_transformer.py's STORE_CASES hold
# the dense model to the same).

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aiko_services_tpu.decode import DecodeEngine
from aiko_services_tpu.models import transformer
from aiko_services_tpu.models.configs import deepseek_v2_config
from aiko_services_tpu.models.layers import yarn_frequencies, yarn_mscale
from aiko_services_tpu.models.transformer import (
    forward, init_paged_pool, init_params, param_specs)
from aiko_services_tpu.parallel import (
    create_mesh, filter_specs, shard_pytree)
from aiko_services_tpu.parallel.experts import (
    expert_ffn, expert_ffn_reference)
from benchmark.reference import deepseek_v2 as reference

PUBLISHED = {
    "model_type": "deepseek_v2", "vocab_size": 256, "hidden_size": 64,
    "num_hidden_layers": 3, "num_attention_heads": 4, "q_lora_rank": 48,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "intermediate_size": 128,
    "moe_intermediate_size": 32, "n_routed_experts": 16,
    "n_shared_experts": 2, "num_experts_per_tok": 3, "n_group": 4,
    "topk_group": 2, "routed_scaling_factor": 16,
    "first_k_dense_replace": 1, "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"type": "yarn", "factor": 40,
                     "original_max_position_embeddings": 32,
                     "beta_fast": 32, "beta_slow": 1, "mscale": 0.707,
                     "mscale_all_dim": 0.707},
    "max_position_embeddings": 4096, "torch_dtype": "float32"}
SEED = 7
TOLERANCE = 2e-5


def share(low: int, high: int) -> dict:
    """The published keys of the share that holds experts [low, high)."""
    return dict(PUBLISHED, n_routed_experts=high - low, router_experts=16,
                experts_held=[low, high])


@pytest.fixture(scope="module")
def model():
    config = deepseek_v2_config(PUBLISHED, max_seq_len=128)
    return config, init_params(config, jax.random.PRNGKey(SEED))


def first_expert_layer(params: dict) -> dict:
    """The first routed layer as the layer scan hands it to the layer
    body: its own leaves, and the stacked expert weights with its index
    among them (transformer._scan_layers)."""
    layer = jax.tree_util.tree_map(lambda leaf: leaf[0], params["layers"])
    layer["experts"] = ({name: params["layers"][name] for name in
                         transformer._EXPERT_LEAVES}, 0)
    return layer


def reference_logits(published: dict, tokens) -> np.ndarray:
    tokens = np.asarray(tokens)
    positions = np.tile(np.arange(tokens.shape[1])[None],
                        (tokens.shape[0], 1))
    return np.asarray(reference.logits_at(
        reference.shape_of(published), SEED, tokens, positions))


# -- (a) the engine's latent pool against the reference's full pass ----------

def test_engine_over_the_latent_pool_serves_the_references_tokens(model):
    """Prefill then decode through DecodeEngine (run-ahead on, two slots
    for three requests, block boundaries crossed) against the
    reference's one full pass over prompt + served tokens, by logits:
    every served token's reference logit is the reference's best to
    TOLERANCE, and nothing is preempted."""
    config, params = model
    engine = DecodeEngine(params, config, decode_slots=2, kv_block_size=8,
                          max_context=64)
    rng = np.random.default_rng(3)
    prompts = {name: rng.integers(1, 256, size=length).astype(np.int32)
               for name, length in (("a", 13), ("b", 21), ("c", 7))}
    for name, prompt in prompts.items():
        engine.submit(name, prompt, 19)
    done = {}
    while engine.has_work():
        for completion in engine.step().completions:
            done[completion.request_id] = completion.tokens
    stats = engine.stats()
    assert stats["preempted"] == 0 and stats["steps_ahead"] > 0
    # the device counted the experts, the host the rows
    assert stats["latent_positions"] > 0
    assert 0 < stats["experts_read"] <= stats["expert_pairs"]
    for name, prompt in prompts.items():
        sequence = np.concatenate([prompt, done[name]])[None]
        logits = reference_logits(PUBLISHED, sequence)[0]
        at = len(prompt) - 1 + np.arange(len(done[name]))
        gaps = logits[at].max(axis=-1) - logits[at, done[name]]
        assert gaps.max() <= TOLERANCE, (name, gaps.max())


def test_forward_is_the_reference(model):
    config, params = model
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0, 256)
    np.testing.assert_allclose(
        np.asarray(forward(params, config, tokens.astype(jnp.int32))),
        reference_logits(PUBLISHED, tokens), atol=TOLERANCE, rtol=0)


# -- (b) absorbed against decompressed ---------------------------------------

def test_absorbed_attention_is_decompressed_attention(model):
    """One layer's attention over the same latent rows, through the pool
    absorbed (the keys' up-projection in the query, the values' after
    the weighted rows) and over a contiguous cache decompressed (every
    head's keys and values made): the same numbers to float32 rounding
    of two orders of the same matmuls, 1e-5 on outputs of size ~1."""
    config, params = model
    layer = jax.tree_util.tree_map(lambda leaf: leaf[0],
                                   params["dense_layers"])
    rows, length, block = 2, 11, 4
    x = jax.random.normal(jax.random.PRNGKey(2), (rows, length, 64))
    cos, sin = transformer._rotary_tables(config, jnp.arange(length))
    q, latent, _ = transformer._project_latent(
        config, layer, x, cos[None, None], sin[None, None])
    cache = {"kv": jnp.zeros((rows, 1, 16, config.latent_row))}
    decompressed, cache = transformer._attend_cache_latent(
        config, cache, 0, layer, q, latent)
    # the same rows, laid into a pool block by block
    max_blocks = 16 // block
    tables = 1 + np.arange(rows * max_blocks, dtype=np.int32).reshape(
        rows, max_blocks)
    pool = init_paged_pool(config, 1 + rows * max_blocks, block)
    held = np.asarray(cache["kv"]).reshape(
        rows, 1, max_blocks, block, -1).transpose(0, 2, 1, 3, 4)
    pool["kv"] = pool["kv"].at[0, tables.reshape(-1)].set(
        held.reshape(rows * max_blocks, 1, block, -1))
    last = length - 1
    absorbed, _ = transformer._attend_pool_latent(
        config, pool, 0, jnp.asarray(tables),
        jnp.full((rows,), last, jnp.int32),
        jnp.asarray(tables[:, last // block, None]),
        jnp.full((rows, 1), last % block, jnp.int32), layer,
        q[:, :, last:], latent[:, :, last:])
    np.testing.assert_allclose(np.asarray(absorbed)[:, :, 0],
                               np.asarray(decompressed)[:, :, last],
                               atol=1e-5, rtol=0)


# -- (c) the shares add up ----------------------------------------------------

def test_the_four_shares_of_a_layer_add_up_to_the_uncut_layer():
    """The routed parts of the four shares of an expert layer, the shared
    experts counted once, are the uncut reference layer's FFN: what a
    share leaves out is exactly what the other three add."""
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 40, 64))
    shape = reference.shape_of(PUBLISHED)
    layer_key = jax.random.split(jax.random.PRNGKey(SEED), 4)[2]
    keys = jax.random.split(layer_key, 12)
    uncut = np.asarray(reference._expert_layer_ffn(
        x[0], keys[5:12], shape, "stated"))
    total, shared = 0.0, None
    for low in range(0, 16, 4):
        config = deepseek_v2_config(share(low, low + 4), max_seq_len=128)
        params = init_params(config, jax.random.PRNGKey(SEED))
        layer = first_expert_layer(params)
        out, stats = transformer._mlp_block(config, layer, x)
        shared = np.asarray(transformer.swiglu(
            layer["shared_gate"], layer["shared_up"], layer["shared_down"],
            x))
        total = total + np.asarray(out) - shared
        assert 0 < float(stats[1]) <= 4 and float(stats[2]) <= 40 * 3
    np.testing.assert_allclose((total + shared)[0], uncut, atol=TOLERANCE,
                               rtol=0)


# -- (d) the router -----------------------------------------------------------

def test_router_chooses_as_the_reference_and_as_published(model):
    config, params = model
    router = {"w": params["layers"]["router"]["w"][0]}
    x = jax.random.normal(jax.random.PRNGKey(6), (50, 64))
    weights, ids = transformer._route(config, router, x)
    want_weights, want_ids = reference.route(
        x, router["w"], reference.shape_of(PUBLISHED))
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(want_ids))
    np.testing.assert_allclose(np.asarray(weights),
                               np.asarray(want_weights), atol=1e-6, rtol=0)
    # as published: the chosen lie in the two groups whose best score is
    # largest, and weigh 16 x their softmax score, not renormalised
    scores = np.asarray(jax.nn.softmax(x @ router["w"], axis=-1))
    best_groups = np.argsort(-scores.reshape(50, 4, 4).max(-1),
                             axis=1)[:, :2]
    ids, weights = np.asarray(ids), np.asarray(weights)
    for token in range(50):
        assert set(ids[token] // 4) <= set(best_groups[token])
    np.testing.assert_allclose(
        weights, 16 * np.take_along_axis(scores, ids, axis=1), atol=1e-5)
    assert not np.allclose(weights.sum(-1), 16.0)


def test_router_ties_go_to_the_lower_index_as_the_references_do(model):
    """All scores equal: groups 0 and 1 are kept and experts 0, 1, 2
    chosen, by the program and by the reference."""
    config, _ = model
    x = jnp.ones((3, 64))
    zeros = jnp.zeros((64, 16))
    _, ids = transformer._route(config, {"w": zeros}, x)
    _, want = reference.route(x, zeros, reference.shape_of(PUBLISHED))
    np.testing.assert_array_equal(np.asarray(ids), [[0, 1, 2]] * 3)
    np.testing.assert_array_equal(np.asarray(want), [[0, 1, 2]] * 3)


def test_a_token_whose_kept_groups_are_not_held_gets_the_shared_experts():
    config = deepseek_v2_config(share(8, 16), max_seq_len=128)
    params = init_params(config, jax.random.PRNGKey(SEED))
    layer = first_expert_layer(params)
    # every token's best experts lie in groups 0 and 1 (experts 0-7)
    router = np.zeros((64, 16), np.float32)
    router[:, :8] = 0.05
    layer["router"] = {"w": jnp.asarray(router)}
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(8), (1, 9, 64)))
    out, stats = transformer._mlp_block(config, layer, x)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(transformer.swiglu(
            layer["shared_gate"], layer["shared_up"], layer["shared_down"],
            x)))
    assert float(stats[1]) == 0 and float(stats[2]) == 0


# -- (e) the grouped matmul ---------------------------------------------------

@pytest.mark.parametrize("tokens", (5, 16, 300))
def test_grouped_matmul_is_a_loop_over_the_experts(tokens):
    """The kernel (interpreted) against every expert's SwiGLU over every
    token: experts that get no token, pairs not held (id 6), a batch
    under one tile, one tile, and an expert with more than a tile's
    rows.  float32: 1e-4 on sums of up to 3 outputs of size ~10."""
    held, d, f, k = 6, 64, 32, 3
    keys = jax.random.split(jax.random.PRNGKey(tokens), 6)
    x = jax.random.normal(keys[0], (tokens, d))
    w_gate = jax.random.normal(keys[1], (held, d, f)) / 8
    w_up = jax.random.normal(keys[2], (held, d, f)) / 8
    w_down = jax.random.normal(keys[3], (held, f, d)) / 6
    # experts 1 and 4 get no token; 6 means "held elsewhere"
    experts = jnp.asarray(np.random.default_rng(tokens).choice(
        [0, 2, 3, 5, 6], size=(tokens, k)), jnp.int32)
    weights = jnp.where(experts < held,
                        jax.random.uniform(keys[4], (tokens, k)), 0.0)
    out, read, pairs = expert_ffn(x, w_gate, w_up, w_down, experts,
                                  weights)
    want, want_read, want_pairs = expert_ffn_reference(
        x, w_gate, w_up, w_down, experts, weights)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-4, rtol=0)
    assert int(read) == int(want_read) <= 4
    assert int(pairs) == int(want_pairs) == int((experts < held).sum())
    # the stacked form reads the layer it is given
    stacked = [jnp.stack([jnp.zeros_like(w), w])
               for w in (w_gate, w_up, w_down)]
    again, _, _ = expert_ffn(x, *stacked, experts, weights, layer=1)
    np.testing.assert_array_equal(np.asarray(again), np.asarray(out))


def test_routed_layer_shards_over_the_expert_axis(model):
    """Under a mesh with an `expert` axis the routed layer shards as
    param_specs says (the einsum form: a Mosaic kernel cannot be
    partitioned) and gives the unsharded logits."""
    config, params = model
    tokens = jax.random.randint(jax.random.PRNGKey(9), (2, 12), 0, 256
                                ).astype(jnp.int32)
    want = np.asarray(forward(params, config, tokens))
    mesh = create_mesh({"data": 2, "expert": 4})
    with jax.set_mesh(mesh):
        sharded = shard_pytree(params, mesh,
                               filter_specs(param_specs(config), mesh))
        gate = sharded["layers"]["w_gate"]["w"]
        assert not gate.sharding.is_fully_replicated
        got = np.asarray(forward(sharded, config, tokens))
    np.testing.assert_allclose(got, want, atol=TOLERANCE, rtol=0)


# -- (f) YaRN -----------------------------------------------------------------

def test_yarn_frequencies_and_mscale_are_the_published_numbers():
    """DeepSeek-V2's own keys, by hand: of the 32 rotary frequencies the
    first 11 (dimensions that turn over 32 times in 4096 positions: up to
    floor(64 ln(4096 / 64 pi) / 2 ln 10000) = 10) keep theta's own,
    from ceil(64 ln(4096 / 2 pi) / 2 ln 10000) = 23 on they are divided
    by 40, and between them a ramp of (i - 10) / 13 blends the two."""
    got = yarn_frequencies(64, 10000.0, 40.0, 4096, 32.0, 1.0)
    plain = 10000.0 ** (-np.arange(32) / 32.0)
    np.testing.assert_allclose(got[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(got[23:], plain[23:] / 40.0, rtol=1e-6)
    ramp = (16 - 10) / 13
    np.testing.assert_allclose(
        got[16], plain[16] / 40 * ramp + plain[16] * (1 - ramp), rtol=1e-6)
    np.testing.assert_allclose(
        got, reference.rotary_frequencies(reference.shape_of(dict(
            PUBLISHED, qk_rope_head_dim=64, rope_scaling=dict(
                PUBLISHED["rope_scaling"],
                original_max_position_embeddings=4096)))), rtol=1e-6)
    # m = 0.1 x 0.707 x ln 40 + 1 = 1.26081; scale = m^2 / sqrt(192)
    assert yarn_mscale(40.0, 0.707) == pytest.approx(1.2608, abs=1e-4)
    assert yarn_mscale(1.0, 0.707) == 1.0
    config = deepseek_v2_config(dict(
        PUBLISHED, qk_nope_head_dim=128, qk_rope_head_dim=64))
    assert config.attention_scale == pytest.approx(
        1.2608 ** 2 / math.sqrt(192), rel=1e-4)
    assert config.attention_scale == pytest.approx(
        reference.attention_scale(reference.shape_of(dict(
            PUBLISHED, qk_nope_head_dim=128, qk_rope_head_dim=64))))


# -- (g) growth across blocks -------------------------------------------------

def test_a_latent_slot_grows_across_block_boundaries_without_preemption(
        model):
    """A pool with exactly the blocks two slots need at their ends: each
    slot takes a new block every 4 tokens, nothing is preempted, the pool
    has one leaf of latent_row values a position, and the blocks come
    back."""
    config, params = model
    engine = DecodeEngine(params, config, decode_slots=2, kv_block_size=4,
                          max_context=32, kv_blocks=2 * 8 + 1)
    assert set(engine.pool) == {"kv"}
    assert engine.pool["kv"].shape == (3, 17, 1, 4, config.latent_row)
    for name in ("a", "b"):
        engine.submit(name, np.arange(1, 6, dtype=np.int32), 25)
    seen = set()
    while engine.has_work():
        engine.step()
        seen.add(engine.stats()["free_blocks"])
    stats = engine.stats()
    assert stats["preempted"] == 0 and stats["completed"] == 2
    assert len(seen) > 4 and min(seen) <= 2
    assert stats["free_blocks"] == engine.blocks.capacity


def test_published_keys_that_are_not_implemented_are_refused():
    with pytest.raises(ValueError, match="scoring_func"):
        deepseek_v2_config(dict(PUBLISHED, scoring_func="sigmoid"))
    with pytest.raises(ValueError, match="experts_held"):
        deepseek_v2_config(dict(PUBLISHED, experts_held=[0, 4]))
    config = deepseek_v2_config(share(4, 8))
    assert config.held == (4, 8) and config.n_routed_experts == 16
    assert dataclasses.replace(config, experts_held=()).held == (0, 16)
