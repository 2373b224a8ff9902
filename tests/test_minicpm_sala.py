# MiniCPM-SALA's hybrid stack on the normal path: lightning (linear)
# attention layers whose recurrent state is a slot's (a matrix a head a
# layer, nothing else) beside attention layers without positional encoding
# that SELECT the K/V blocks they read (InfLLM-v2: a store of compressed
# keys beside K/V in the paged pool), muP's three scalars and an untied
# head -- each held to benchmark/reference/minicpm_sala.py, the float32
# reference that imports nothing of the program, scans a whole sequence
# from zero row by row and masks a full softmax by the chosen blocks.
#
# Everything here is float32 at toy widths: hidden 64, 4 query heads of 16
# over 2 K/V heads, 4 lightning heads of 16; 4 layers (sparse, lightning,
# lightning, sparse); blocks of 8 positions, compressed keys of 4 rows
# every 2, top 4 blocks (1 first, 2 local), dense under 64 positions.
#
# Tolerances, each with its reason.  TOLERANCE 2e-4 on logits of size ~1:
# float32 rounding through a few matmuls of other shapes, a blockwise
# softmax and a chunked scan reads 2e-6 here (measured: forward against
# the reference 1.5e-6, the paged steps 3e-6); the sparse layers' seeded q
# gain (3 sqrt(kernel)) makes their softmax sharp, and a query whose two
# best keys tie amplifies a rounding some tens of times.  A wrong choice of
# blocks, a stale compressed key, a stranger's S or a gain misplaced move
# logits by 1e-2 to 1 (the controls below).  STATE_TOLERANCE 1e-5 on a
# state or an output of one scan, of its size where that is over 1 (a
# state sums tens of rows): S rounded to bfloat16 moves
# it by 1e-3 (test_a_state_rounded_to_bfloat16_is_told), scores rounded to
# bfloat16 move the choice of blocks (test_scores_rounded_to_bfloat16...).

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from aiko_services_tpu.decode import (
    CheckpointPolicy, DecodeCheckpointer, DecodeEngine, PrefillEngine)
from aiko_services_tpu.models import configs, transformer
from aiko_services_tpu.models.configs import minicpm_sala_config
from aiko_services_tpu.models.transformer import (
    TransformerConfig, forward, generate, init_cache, init_paged_pool,
    init_params, make_train_step, param_specs, quantize_weights_int8)
from aiko_services_tpu.parallel import lightning, sparse
from benchmark.reference import minicpm_sala as reference
from test_decode import _Order

SPARSE = {"kernel_size": 4, "kernel_stride": 2, "block_size": 8, "topk": 4,
          "init_blocks": 1, "window_size": 16, "dense_len": 64}
PUBLISHED = {
    "model_type": "minicpm_sala", "vocab_size": 257, "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 4,
    "mixer_types": ["minicpm4", "lightning-attn", "lightning-attn",
                    "minicpm4"],
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "lightning_nh": 4, "lightning_nkv": 4, "lightning_head_dim": 16,
    "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
    "attn_use_rope": False, "qk_norm": True, "use_output_gate": True,
    "use_output_norm": True, "attn_use_output_gate": True,
    "attention_bias": False, "hidden_act": "silu", "rope_theta": 10000,
    "rms_norm_eps": 1e-6, "scale_emb": 12, "scale_depth": 1.4,
    "mup_denominator": 32, "dim_model_base": 16,
    "max_position_embeddings": 256, "tie_word_embeddings": False,
    "torch_dtype": "float32", "sparse_config": SPARSE}
# the catalog row's `config` as it stands (the model-configs guide's
# architectures.jsonl, MiniCPM-SALA)
CATALOG = {
    "attention_bias": False, "attn_use_rope": False, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 4096, "intermediate_size": 16384,
    "lightning_head_dim": 128, "lightning_nh": 32, "lightning_nkv": 32,
    "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
    "max_position_embeddings": 524288, "model_type": "minicpm_sala",
    "mixer_types": ["minicpm4"] + ["lightning-attn"] * 8 + ["minicpm4"]
    + ["lightning-attn"] * 6 + ["minicpm4"] * 2 + ["lightning-attn"] * 4
    + ["minicpm4"] + ["lightning-attn"] * 6 + ["minicpm4"] * 3,
    "num_attention_heads": 32, "num_hidden_layers": 32,
    "num_key_value_heads": 2, "qk_norm": True, "rand_init": False,
    "rms_norm_eps": 1e-06, "vocab_size": 73448, "rope_theta": 10000,
    "scale_emb": 12, "scale_depth": 1.4, "mup_denominator": 32,
    "dim_model_base": 256, "tie_word_embeddings": False,
    "use_output_gate": True, "use_output_norm": True,
    "attn_use_output_gate": True}
SEED = 5
TOLERANCE = 2e-4
STATE_TOLERANCE = 1e-5
LIGHTNING, ATTENTION = 2, 2
STATE_BYTES = LIGHTNING * 4 * 4 * 16 * 16
SIZES = sparse.SparseSizes(block=8, kernel=4, stride=2, topk=4, init=1,
                           local=2, dense_len=64)


def _random_gains(params: dict) -> dict:
    """The program's seeded weights with every norm gain drawn anew
    around what it was."""
    counter = iter(range(10_000))

    def visit(tree):
        if isinstance(tree, dict):
            if set(tree) == {"scale"}:
                key = jax.random.PRNGKey(1000 + next(counter))
                return {"scale": tree["scale"] * (
                    1.0 + 0.3 * jax.random.normal(
                        key, tree["scale"].shape, tree["scale"].dtype))}
            return {name: visit(leaf) for name, leaf in tree.items()}
        if isinstance(tree, list):
            return [visit(leaf) for leaf in tree]
        return tree

    return visit(params)


def _reference_gains(config, params: dict) -> dict:
    """The program's gains as the reference takes them: stored as w."""
    layers = []
    for stack, (kind, _, count) in zip(params["runs"],
                                       transformer._kind_runs(config)):
        for index in range(count):
            at = lambda name: np.asarray(             # noqa: E731
                stack[name]["scale"][index])
            gains = {"norm_2": at("mlp_norm"), "q_norm": at("q_norm"),
                     "k_norm": at("k_norm")}
            if kind == "lightning":
                gains.update(norm_1=at("mixer_norm"),
                             out_norm=at("out_norm"))
            else:
                gains.update(norm_1=at("attn_norm"))
            layers.append(gains)
    return {"layers": layers,
            "final": np.asarray(params["norm_out"]["scale"])}


@pytest.fixture(scope="module", params=["seeded", "drawn"])
def model(request):
    """(config, params, the reference's shape, the gains handed to it):
    once with the seeded gains (none handed over: the reference makes
    them itself), once with every gain drawn anew."""
    config = minicpm_sala_config(PUBLISHED, max_seq_len=256)
    params = init_params(config, jax.random.PRNGKey(SEED))
    norms = None
    if request.param == "drawn":
        params = _random_gains(params)
        norms = _reference_gains(config, params)
    return config, params, reference.shape_of(PUBLISHED), norms


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


# -- (a) the reader and the layers --------------------------------------------

def test_the_reader_takes_the_catalog_rows_config_as_it_stands():
    config = minicpm_sala_config(CATALOG)
    kinds = config.layer_kinds
    assert (len(kinds), kinds.count("lightning"), kinds.count("attention")
            ) == (32, 24, 8)
    assert kinds[0] == "attention" and kinds[1:9] == ("lightning",) * 8
    assert (config.d_model, config.d_ff, config.vocab_size,
            config.max_seq_len) == (4096, 16384, 73448, 524288)
    assert (config.n_heads, config.n_kv_heads, config.head_dim) == (
        32, 2, 128)
    assert (config.lightning_heads, config.lightning_head_dim) == (32, 128)
    assert not config.rotary and config.qk_norm and config.gated_attention
    assert config.untied_head
    # muP: 12 on the embedding, 1.4 / sqrt(32) a branch, / 16 the logits
    assert config.embed_scale == 12.0 and config.logit_divisor == 16.0
    assert config.residual_scale == pytest.approx(1.4 / 32 ** 0.5)
    # the selection's sizes are the MiniCPM4 family's
    assert config.sparse_sizes == sparse.SparseSizes(
        block=64, kernel=32, stride=16, topk=64, init=1, local=32,
        dense_len=8192)
    # 2 MiB a layer a slot whatever the context
    assert config.state_bytes == 24 * 32 * 128 * 128 * 4
    assert config.n_caches == 8 and config.n_states == 24


def test_a_cut_keeps_the_published_depth_under_the_branches():
    cut = dict(CATALOG, num_hidden_layers=8,
               mixer_types=CATALOG["mixer_types"][::4])
    config = minicpm_sala_config(cut, max_seq_len=35840)
    assert config.layer_kinds == ("attention",) + ("lightning",) * 3 + (
        "attention",) + ("lightning",) * 3
    assert config.residual_scale == pytest.approx(1.4 / 32 ** 0.5)
    assert config.max_seq_len == 35840


def test_layer_kinds_and_sizes_come_from_the_published_keys(model):
    config, params, _, _ = model
    assert config.layer_kinds == ("attention", "lightning", "lightning",
                                  "attention")
    assert (config.n_states, config.n_caches) == (LIGHTNING, ATTENTION)
    assert config.state_bytes == STATE_BYTES
    assert config.sparse_sizes == SIZES
    assert [transformer._layer_kind(run) for run in params["runs"]] == [
        "attention", "lightning", "attention"]
    assert params["lm_head"]["w"].shape == (257, 64)
    assert params["runs"][1]["w_qkvg"]["w"].shape == (2, 64, 4 * 64)
    # a sparse layer's q gain is seeded 3 sqrt(kernel), wq a query and a
    # gate a head
    assert params["runs"][0]["wq"]["w"].shape == (1, 4 * 2 * 16, 64)


def test_forward_is_the_reference(model):
    """A whole causal pass of 120 rows: 64 dense, 56 that select."""
    config, params, _, _ = model
    tokens = some_tokens(2, 120)
    with jax.default_matmul_precision("highest"):
        logits = forward(params, config, tokens)
    np.testing.assert_allclose(np.asarray(logits),
                               reference_logits(model, tokens),
                               atol=TOLERANCE, rtol=0)


def test_the_seeded_weights_are_the_references_draws():
    config = minicpm_sala_config(PUBLISHED, max_seq_len=256)
    params = init_params(config, jax.random.PRNGKey(SEED))
    shape = reference.shape_of(PUBLISHED)
    keys = jax.random.split(jax.random.PRNGKey(SEED), 5)[1:]
    sparse_layer = reference._layer_weights(keys[0], 0, shape)
    np.testing.assert_array_equal(
        np.asarray(params["runs"][0]["wq"]["w"][0]).T, sparse_layer["wq"])
    np.testing.assert_array_equal(
        np.asarray(params["runs"][0]["q_norm"]["scale"][0]),
        sparse_layer["q_norm"])
    assert float(sparse_layer["q_norm"][0]) == 6.0        # 3 sqrt(4)
    lightning_layer = reference._layer_weights(keys[2], 2, shape)
    for ours, theirs in (("w_qkvg", "w_qkvg"), ("w_out", "w_out"),
                         ("w_down", "w_down")):
        np.testing.assert_array_equal(
            np.asarray(params["runs"][1][ours]["w"][1]),
            lightning_layer[theirs])
    np.testing.assert_array_equal(np.asarray(params["lm_head"]["w"]),
                                  reference.head_of(shape, SEED))


def test_the_three_scalars_are_applied(model):
    """Each of muP's scalars moves the logits: a model without one is not
    the reference's."""
    config, params, _, _ = model
    tokens = some_tokens(1, 24)
    want = reference_logits(model, tokens)
    for change in ({"embed_scale": 1.0}, {"residual_scale": 1.0},
                   {"logit_divisor": 1.0}):
        other = dataclasses.replace(config, **change)
        with jax.default_matmul_precision("highest"):
            logits = forward(params, other, tokens)
        assert np.abs(np.asarray(logits) - want).max() > 100 * TOLERANCE


# -- (b) the lightning rule: chunkwise = the row scan = the step --------------

def _lightning_case(batch, heads, length, dim, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, k, v = (jax.random.normal(key, (batch, heads, length, dim),
                                 jnp.float32) for key in keys[:3])
    state = jax.random.normal(keys[3], (batch, heads, dim, dim), jnp.float32)
    return q * dim ** -0.5, k, v, lightning.decay_rates(heads), state


def _plain_rule(q, k, v, log_decay, state, stop):
    """S_t = lambda S_{t-1} + k_t^T v_t, o_t = q_t S_t in numpy float64."""
    q, k, v, state = (np.asarray(x, np.float64) for x in (q, k, v, state))
    decay = np.exp(np.asarray(log_decay, np.float64))[None, :, None, None]
    out = np.zeros(v.shape)
    final = state
    for t in range(q.shape[2]):
        state = decay * state + k[:, :, t, :, None] * v[:, :, t, None, :]
        out[:, :, t] = np.einsum("bhk,bhkv->bhv", q[:, :, t], state)
        if t == stop - 1:
            final = state
    return out, final


def test_the_decays_are_the_familys_fixed_slopes():
    rates = np.exp(np.asarray(lightning.decay_rates(32), np.float64))
    want = np.exp(-(2.0 ** (-8.0 * np.arange(1, 33) / 32)))
    np.testing.assert_allclose(rates, want, rtol=1e-6)
    np.testing.assert_allclose(rates, np.asarray(reference.decays(32)),
                               rtol=1e-6)
    assert 0.43 < rates[0] < 0.44 and 0.996 < rates[-1] < 0.9962


@pytest.mark.parametrize("length,stop", [(70, 70), (70, 33), (16, 1)])
def test_scan_oracle_is_the_plain_recurrence(length, stop):
    case = _lightning_case(2, 4, length, 16)
    out, state = lightning.lightning_scan_reference(*case, stop=stop)
    want_out, want_state = _plain_rule(*case, stop)
    np.testing.assert_allclose(out[:, :, :stop], want_out[:, :, :stop],
                               atol=STATE_TOLERANCE,
                               rtol=STATE_TOLERANCE)
    np.testing.assert_allclose(state, want_state, atol=STATE_TOLERANCE,
                               rtol=STATE_TOLERANCE)


@pytest.mark.parametrize("chunk", [8, 32])
@pytest.mark.parametrize("length,stop", [(64, None), (64, 64), (64, 37),
                                         (96, 1), (32, 31)])
def test_chunk_scan_is_the_oracle(length, stop, chunk):
    """The chunkwise form from a state that is not zero, stopped inside a
    chunk, at a chunk's edge and at the first row."""
    case = _lightning_case(2, 4, length, 16, seed=3)
    with jax.default_matmul_precision("highest"):
        out, state = lightning.lightning_chunk_scan(*case, stop=stop,
                                                    chunk=chunk)
    want_out, want_state = lightning.lightning_scan_reference(*case,
                                                              stop=stop)
    live = length if stop is None else stop
    np.testing.assert_allclose(out[:, :, :live], want_out[:, :, :live],
                               atol=STATE_TOLERANCE,
                               rtol=STATE_TOLERANCE)
    np.testing.assert_allclose(state, want_state, atol=STATE_TOLERANCE,
                               rtol=STATE_TOLERANCE)


def test_a_length_off_the_chunks_is_padded_with_rows_that_do_nothing(
        monkeypatch):
    monkeypatch.setattr(lightning, "_CHUNK", 16)
    case = _lightning_case(1, 4, 53, 16, seed=4)
    with jax.default_matmul_precision("highest"):
        out, state = lightning.lightning_scan(*case)
    want_out, want_state = lightning.lightning_scan_reference(*case)
    np.testing.assert_allclose(out, want_out, atol=STATE_TOLERANCE,
                               rtol=STATE_TOLERANCE)
    np.testing.assert_allclose(state, want_state, atol=STATE_TOLERANCE,
                               rtol=STATE_TOLERANCE)


@pytest.mark.parametrize("kernel", [False, True])
def test_a_step_is_a_row_of_the_oracle_on_its_layer_of_the_stack(
        kernel, monkeypatch):
    """The step on layer 1 of a stack of three: the kernel (interpreted)
    and XLA's form give the oracle's row and leave the other layers'."""
    monkeypatch.setattr(lightning, "_interpret", lambda: kernel)
    q, k, v, decay, state = _lightning_case(3, 4, 1, 16, seed=6)
    stack = jnp.stack([state * 0.5, state, state * 2.0])
    assert lightning.lightning_step_takes(16, 4) == kernel
    if kernel:
        # the interpreter runs the kernel whatever the predicate's answer
        out, new = lightning.lightning_row_step(
            q[:, :, 0], k[:, :, 0], v[:, :, 0], decay, stack, jnp.int32(1))
    else:
        out, new = lightning.lightning_step(
            q[:, :, 0], k[:, :, 0], v[:, :, 0], decay, stack, jnp.int32(1))
    want_out, want_state = lightning.lightning_scan_reference(
        q, k, v, decay, state)
    np.testing.assert_allclose(out, want_out[:, :, 0], atol=STATE_TOLERANCE,
                               rtol=STATE_TOLERANCE)
    np.testing.assert_allclose(new[1], want_state, atol=STATE_TOLERANCE,
                               rtol=STATE_TOLERANCE)
    np.testing.assert_array_equal(new[0], stack[0])
    np.testing.assert_array_equal(new[2], stack[2])


def test_a_state_rounded_to_bfloat16_is_told():
    """What STATE_TOLERANCE is for: S kept in bfloat16 between two halves
    of a sequence moves the second half's outputs a hundred times it."""
    q, k, v, decay, state = _lightning_case(1, 4, 64, 16, seed=8)
    want, _ = lightning.lightning_scan_reference(q, k, v, decay, state)
    half = lambda x: (x[:, :, :32], x[:, :, 32:])           # noqa: E731
    (q1, q2), (k1, k2), (v1, v2) = half(q), half(k), half(v)
    _, middle = lightning.lightning_scan_reference(q1, k1, v1, decay, state)
    rounded = middle.astype(jnp.bfloat16).astype(jnp.float32)
    second, _ = lightning.lightning_scan_reference(q2, k2, v2, decay,
                                                   rounded)
    exact, _ = lightning.lightning_scan_reference(q2, k2, v2, decay, middle)
    np.testing.assert_allclose(exact, want[:, :, 32:], atol=STATE_TOLERANCE,
                               rtol=STATE_TOLERANCE)
    assert np.abs(np.asarray(second - want[:, :, 32:])).max() \
        > 100 * STATE_TOLERANCE


# -- (c) the selection ------------------------------------------------------------

def _selection_case(length: int, seed: int):
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    q = 6.0 * jax.random.normal(keys[0], (1, 4, length, 16), jnp.float32)
    k = jax.random.normal(keys[1], (1, 2, length, 16), jnp.float32)
    return q, k


def _reference_choice(q, k, positions):
    shape = reference.shape_of(PUBLISHED)
    with jax.default_matmul_precision("highest"):
        return np.asarray(reference.block_choice(
            q[0][:, positions], k[0], jnp.asarray(positions), shape))


def _as_sets(chosen, blocks: int):
    """(G, T, topk) block numbers -> (G, T, blocks) bool."""
    mask = np.zeros(chosen.shape[:2] + (blocks,), bool)
    np.put_along_axis(mask, np.asarray(chosen), True, axis=-1)
    return mask


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_chosen_block_sets_are_the_references(seed):
    """Every row from dense_len on of a 160-row sequence: the first block,
    the two local ones and the best of the rest, four in all, as the
    reference's ranking has them."""
    q, k = _selection_case(160, seed)
    positions = np.arange(64, 160)
    with jax.default_matmul_precision("highest"):
        compressed = sparse.compress_keys(k, SIZES)
        chosen = sparse._select(q[:, :, 64:], compressed,
                                jnp.asarray(positions)[None], SIZES)[0]
    ours = _as_sets(chosen, 20)
    np.testing.assert_array_equal(ours, _reference_choice(q, k, positions))
    own = positions // 8
    assert (np.asarray(chosen)[..., -1] == own).all()        # own block last
    assert ours[:, :, 0].all()                               # the first
    assert ours[:, np.arange(96), own - 1].all()             # the local two
    assert (ours.sum(-1) == 4).all()


def test_scores_rounded_to_bfloat16_choose_other_blocks():
    """What holds the selection's scores to float32: rounded to bfloat16
    (the queries and the compressed keys) some row's choice changes."""
    q, k = _selection_case(160, 0)
    positions = jnp.arange(64, 160)[None]
    compressed = sparse.compress_keys(k, SIZES)
    exact = sparse._select(q[:, :, 64:], compressed, positions, SIZES)
    low = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    rounded = sparse._select(low(q[:, :, 64:]), low(compressed), positions,
                             SIZES)
    assert (np.asarray(exact) != np.asarray(rounded)).any()


def test_compressed_keys_are_means_of_a_kernel_every_stride():
    k = jax.random.normal(jax.random.PRNGKey(2), (1, 2, 40, 16), jnp.float32)
    compressed = np.asarray(sparse.compress_keys(k, SIZES))
    assert compressed.shape == (1, 2, 20, 16)
    for j in (0, 1, 7, 18):
        np.testing.assert_allclose(
            compressed[:, :, j], np.asarray(k)[:, :, 2 * j:2 * j + 4].mean(2),
            atol=1e-6)


@pytest.mark.parametrize("position", [70, 79, 64, 159])
def test_a_decode_steps_table_names_the_prefills_choice(position):
    """The same query over the same keys chooses the same blocks whether
    a prefill's tile or a decode step asks: the step's table holds them as
    pages (block x G + head) through the slot's block table, in order, and
    its position is the query's among their rows."""
    q, k = _selection_case(160, 1)
    compressed = sparse.compress_keys(k, SIZES)
    want = np.asarray(sparse._select(
        q[:, :, position:position + 1], compressed,
        jnp.asarray([[position]]), SIZES))[0, :, 0]           # (G, topk)
    # a pool of one layer whose table maps logical block b to 2 b + 1
    table = (2 * np.arange(20) + 1).astype(np.int32)[None]
    pool = np.zeros((1, 41, 2, 4, 16), np.float32)
    pool[0, table[0]] = np.asarray(compressed)[0].reshape(
        2, 20, 4, 16).transpose(1, 0, 2, 3)
    pages, at = sparse.decode_tables(
        q[:, :, position:position + 1], jnp.asarray(pool), 0,
        jnp.asarray(table), jnp.asarray([position]), SIZES)
    assert pages.shape == (2, 8) and at.shape == (2,)
    for group in range(2):
        np.testing.assert_array_equal(
            np.asarray(pages)[group, :4], table[0][want[group]] * 2 + group)
    assert (np.asarray(at) == 3 * 8 + position % 8).all()


def test_a_slot_under_dense_len_names_every_block_up_to_its_own():
    q, _ = _selection_case(8, 0)
    table = (np.arange(20) + 3).astype(np.int32)[None]
    pages, at = sparse.decode_tables(
        q[:, :, :1], jnp.zeros((1, 41, 2, 4, 16)), 0, jnp.asarray(table),
        jnp.asarray([21]), SIZES)
    np.testing.assert_array_equal(np.asarray(pages)[1, :3],
                                  table[0, :3] * 2 + 1)
    assert (np.asarray(at) == 21).all()
    np.testing.assert_array_equal(
        sparse.blocks_read(np.array([21, 63, 64, 159]), SIZES), [3, 8, 4, 4])


def test_sizes_that_do_not_fit_are_refused():
    for change in ({"sparse_stride": 3}, {"sparse_kernel": 16},
                   {"sparse_init": 3}, {"sparse_dense_len": 24},
                   {"kv_dtype": "int8"}):
        with pytest.raises(ValueError):
            dataclasses.replace(minicpm_sala_config(PUBLISHED), **change)
    config = minicpm_sala_config(PUBLISHED)
    with pytest.raises(ValueError, match="pool of such blocks"):
        init_paged_pool(config, 5, 16)


# -- (d) the stores: cache, pool, engine ----------------------------------------

def _paged_logits(model, prompt, follow, block=8, max_blocks=24):
    """Logits after each of `follow`'s tokens, teacher-forced, through
    paged_prefill into a pool and then _paged_logits a step at a time:
    (len(follow), vocab)."""
    config, params, _, _ = model
    bucket = 8
    while bucket < len(prompt):
        bucket *= 2
    pool = {**init_paged_pool(config, max_blocks + 1, block),
            **transformer.init_recurrent_state(config, 2)}
    # slot 1 of two; the blocks in reverse so that a table is not a range
    table = np.arange(max_blocks, 0, -1).astype(np.int32)
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :len(prompt)] = prompt
    pool, _ = transformer.paged_prefill(
        params, config, pool, jnp.asarray(padded), jnp.asarray(table),
        jnp.int32(len(prompt)), slot=jnp.int32(1))
    tables = np.zeros((2, max_blocks), np.int32)
    tables[1] = table
    step = jax.jit(lambda pool, positions, tokens, blocks, offsets:
                   transformer._paged_logits(
                       params, config, pool, jnp.asarray(tables), positions,
                       tokens, blocks, offsets)[:2])
    out = []
    for index, token in enumerate(follow):
        position = len(prompt) + index
        pool, logits = step(
            pool, jnp.asarray([0, position], jnp.int32),
            jnp.asarray([[0], [token]], jnp.int32),
            jnp.asarray([[0], [table[position // block]]], jnp.int32),
            jnp.asarray([[0], [position % block]], jnp.int32))
        out.append(np.asarray(logits[1, 0]))
    return np.stack(out), pool


@pytest.mark.parametrize("prompt_len,steps", [(50, 30), (75, 24), (9, 12)])
def test_paged_prefill_then_steps_are_the_reference_by_logits(
        model, prompt_len, steps):
    """Prefill then decode through the pool, the state and the compressed
    store, every step's LOGITS against the reference's one full pass: a
    context that crosses dense_len mid-answer (50 + 30: the steps from
    position 64 on select, from compressed keys the prefill left and the
    steps completed, one of them -- rows 62..65 -- straddling two pool
    blocks), one that selects from its prefill on (75, a bucket of 128),
    one that never does."""
    tokens = np.asarray(some_tokens(1, prompt_len + steps, seed=prompt_len))
    want = reference_logits(model, tokens)[0]
    with jax.default_matmul_precision("highest"):
        got, _ = _paged_logits(model, tokens[0, :prompt_len],
                               tokens[0, prompt_len:])
    np.testing.assert_allclose(got, want[prompt_len:], atol=TOLERANCE,
                               rtol=0)


def test_a_straddling_compressed_key_is_written_when_its_last_row_is(model):
    """Block b's fourth compressed key averages rows 8 b + 6 .. 8 b + 9:
    it is written into block b's entry when row 8 b + 9 is, by the step at
    that position, from rows of two pool blocks."""
    config, params, _, _ = model
    tokens = np.asarray(some_tokens(1, 60, seed=77))
    with jax.default_matmul_precision("highest"):
        _, pool = _paged_logits(model, tokens[0, :20], tokens[0, 20:])
    table = np.arange(24, 0, -1)
    keys = np.asarray(pool["k"])[:, table]        # (caches, blocks, G, 8, d)
    rows = keys.transpose(0, 2, 1, 3, 4).reshape(ATTENTION, 2, -1, 16)
    stored = np.asarray(pool["kc"])[:, table].transpose(
        0, 2, 1, 3, 4).reshape(ATTENTION, 2, -1, 16)
    for j in (3, 7, 11, 12, 27):                  # 3, 7, 11, 27 straddle
        np.testing.assert_allclose(
            stored[:, :, j], rows[:, :, 2 * j:2 * j + 4].mean(2), atol=1e-6)
    # the prefill's (j <= 8: rows up to 19) and the steps' alike


def test_selection_from_stale_compressed_keys_is_told(model, monkeypatch):
    """The control of the compressed store: steps that never write a
    completed key select from what the prefill left, and their logits
    leave the reference's by far more than TOLERANCE."""
    tokens = np.asarray(some_tokens(1, 110, seed=50))
    want = reference_logits(model, tokens)[0]
    monkeypatch.setattr(
        transformer, "due_compressed",
        lambda positions, sizes: (positions < 0, positions * 0))
    jax.clear_caches()
    try:
        with jax.default_matmul_precision("highest"):
            got, _ = _paged_logits(model, tokens[0, :50], tokens[0, 50:])
    finally:
        jax.clear_caches()
    assert np.abs(got - want[50:]).max() > 20 * TOLERANCE


def test_the_same_prompt_in_two_buckets_gives_the_same_state_and_logits(
        model):
    """Right padding advances nothing: S after row true_len - 1 and the
    logits there, whatever the bucket and whatever the padding holds."""
    config, params, _, _ = model
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
        found.append((np.asarray(logits), np.asarray(cache["lightning"])))
    for other in found[1:]:
        for ours, theirs in zip(found[0], other):
            np.testing.assert_allclose(ours, theirs, atol=1e-5, rtol=0)


@pytest.mark.parametrize("step_kernel", [True, False])
def test_engine_serves_the_references_tokens(model, step_kernel,
                                             monkeypatch):
    """Prefill then decode through DecodeEngine (run-ahead on, two slots
    for three requests, so a slot changes hands) against the reference's
    one full pass; the spans' counts are the state's and the selection's.
    The step's S through `lightning_step` (interpreted) and through XLA's
    form."""
    config, params, _, _ = model
    monkeypatch.setattr(lightning, "_interpret", lambda: step_kernel)
    jax.clear_caches()
    try:
        spans = _Order()
        engine = DecodeEngine(params, config, decode_slots=2,
                              kv_block_size=8, max_context=160, spans=spans)
        assert engine.pool["k"].shape == (ATTENTION, 41, 2, 8, 16)
        assert engine.pool["kc"].shape == (ATTENTION, 41, 2, 4, 16)
        assert engine.pool["lightning"].shape == (LIGHTNING, 2, 4, 16, 16)
        rng = np.random.default_rng(3)
        prompts = {name: rng.integers(1, 257, size=length).astype(np.int32)
                   for name, length in (("a", 40), ("b", 70), ("c", 9))}
        for name, prompt in prompts.items():
            engine.submit(name, prompt, 40)
        done = drain(engine)
    finally:
        jax.clear_caches()
    stats = engine.stats()
    kind = "kernel" if step_kernel else "jnp"
    assert stats["preempted"] == 0 and stats["steps_ahead"] > 0
    assert stats["state_step_" + kind] == stats["decode_steps"]
    assert stats["state_bytes"] == 2 * STATE_BYTES * stats["state_slots"]
    # three whole prefills: buckets 64 (dense), 128 (6 rows select), 16
    assert (stats["prefill_sparse"], stats["prefill_einsum"]) == (1, 2)
    assert stats["scan_lightning_chunk"] == 3
    assert stats["select_rows"] == 70 - 64
    # a K/V head of a sparse layer reads at most topk blocks once it
    # selects, and fewer than it chose from
    each = 2 * ATTENTION
    assert 0 < stats["sparse_blocks_read"] < stats["sparse_blocks_live"]
    assert stats["sparse_blocks_read"] % each == 0
    prefills = [fields for _, fields in spans.named("engine.prefill")]
    assert [fields["attention"] for fields in prefills] == [
        "einsum", "sparse", "einsum"]
    assert {fields["scan"] for fields in prefills} == {"lightning_chunk"}
    assert [fields["select_rows"] for fields in prefills] == [0, 6, 0]
    decodes = [fields for _, fields in spans.named("engine.decode")]
    assert {fields["state_step"] for fields in decodes} == {kind}
    for fields in decodes:
        assert {"sparse_blocks_read", "sparse_blocks_live",
                "compressed_rows", "state_bytes"} <= set(fields)
        assert fields["sparse_blocks_read"] <= fields["sparse_blocks_live"]
    for name, prompt in prompts.items():
        assert_served_is_the_references(model, prompt, done[name].tokens,
                                        name)


@pytest.mark.parametrize("first,second", [(30, 75), (75, 30)])
def test_a_reused_slot_sees_nothing_of_its_previous_occupant(model, first,
                                                             second):
    """One slot: a request, then another into the same slot and the same
    blocks, shorter or longer.  The prefill overwrites the whole of the
    slot's S and of its blocks' compressed keys; a compressed key past the
    prompt is written by the step that completes it before any query may
    read it."""
    config, params, _, _ = model
    engine = DecodeEngine(params, config, decode_slots=1, kv_block_size=8,
                          max_context=160)
    one = np.asarray(some_tokens(1, first, seed=31))[0]
    two = np.asarray(some_tokens(1, second, seed=32))[0]
    engine.submit("one", one, 50)
    engine.submit("two", two, 50)
    done = drain(engine)
    assert np.asarray(engine.pool["lightning"]).any()
    assert_served_is_the_references(model, one, done["one"].tokens)
    assert_served_is_the_references(model, two, done["two"].tokens)


def test_a_strangers_state_left_in_a_slot_is_told(model, monkeypatch):
    """The control of the state by slot: a prefill that writes slot 0's
    state whatever its slot leaves the other slot decoding from zeros, and
    the served tokens' gaps leave TOLERANCE."""
    config, params, _, _ = model
    original = transformer.paged_prefill

    def misplaced(params, config, pool, prompt, table_row, true_len,
                  slot=None):
        return original(params, config, pool, prompt, table_row, true_len,
                        slot=jnp.int32(0))

    from aiko_services_tpu.decode import engine as engine_module
    monkeypatch.setattr(engine_module, "paged_prefill", misplaced)
    engine = DecodeEngine(params, config, decode_slots=2, kv_block_size=8,
                          max_context=160)
    prompts = [np.asarray(some_tokens(1, 40, seed=seed))[0]
               for seed in (61, 62)]
    for index, prompt in enumerate(prompts):
        engine.submit(index, prompt, 24)
    done = drain(engine)
    with pytest.raises(AssertionError):
        assert_served_is_the_references(model, prompts[1], done[1].tokens)


# -- (d') a whole prefill by row tiles, the state carried ------------------------

@pytest.fixture
def tiles_at_toy_sizes(monkeypatch):
    """Row tiles of 16 rows, lightning chunks of 8, selection tiles of 8."""
    monkeypatch.setattr(lightning, "_CHUNK", 8)
    monkeypatch.setattr(sparse, "_TILE", 8)
    monkeypatch.setattr(transformer, "_ROW_TILE", 16)
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("true_len", [17, 64, 65, 79, 80, 81, 100, 128])
def test_a_tiled_prefill_is_the_whole_buckets(model, tiles_at_toy_sizes,
                                              true_len):
    """A 128-row bucket by row tiles of 16 -- S handed from tile to tile
    and taken at row true_len - 1, the rotary tables riding the tiles,
    the selection run over the live tiles past dense_len -- leaves the
    logits at the last row, the state, and the live rows' K/V and
    compressed keys that the whole sequence's own pass leaves."""
    config, params, _, _ = model
    prompt = np.asarray(some_tokens(1, 128, seed=13))
    assert transformer._row_tiles_take(config, 128)
    with jax.default_matmul_precision("highest"):
        h, outputs, _, cache = transformer._hidden(
            params, config, prompt, init_cache(config, 1, 128), 0,
            true_len=jnp.int32(true_len))
        got, _ = transformer._logits(
            params, config, h[:, true_len - 1:true_len], outputs)
        want = forward(params, config, prompt[:, :true_len])[:, -1:]
        plain = transformer._hidden(
            params, config, prompt[:, :true_len],
            init_cache(config, 1, true_len + (-true_len % 8)), 0)[3] \
            if true_len % 8 == 0 else None
    np.testing.assert_allclose(got, want, atol=TOLERANCE, rtol=0)
    if plain is not None:
        np.testing.assert_allclose(cache["lightning"], plain["lightning"],
                                   atol=1e-4)
        np.testing.assert_allclose(cache["k"][..., :true_len, :],
                                   plain["k"], atol=1e-4)
        defined = (true_len - 4) // 2 + 1
        np.testing.assert_allclose(cache["kc"][..., :defined, :],
                                   plain["kc"][..., :defined, :], atol=1e-4)


def test_engine_serves_the_reference_by_row_tiles(model,
                                                  tiles_at_toy_sizes):
    config, params, _, _ = model
    engine = DecodeEngine(params, config, decode_slots=1, kv_block_size=8,
                          max_context=160)
    prompt = np.asarray(some_tokens(1, 83, seed=41))[0]      # bucket 128
    engine.submit("r", prompt, 30)
    done = drain(engine)
    stats = engine.stats()
    assert (stats["prefill_rows_run"], stats["prefill_rows_bucket"]) == (
        96, 128)
    assert stats["prefill_attn_rows"] == 88          # selection tiles of 8
    assert stats["scan_rows"] == 96
    assert_served_is_the_references(model, prompt, done["r"].tokens)


# -- (e) what is refused by name ------------------------------------------------

def _engine(model, **keywords):
    config, params = model[:2]
    return DecodeEngine(params, config, decode_slots=1, kv_block_size=8,
                        max_context=32, **keywords)


def _dense_target(model, **keywords):
    config, params = model[:2]
    plain = TransformerConfig(
        vocab_size=257, d_model=32, n_layers=1, n_heads=2, n_kv_heads=1,
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


@pytest.mark.parametrize("call", ["generate", "decode_step"])
def test_the_closed_batch_cache_is_refused_by_name(model, call):
    """generate()'s contiguous cache carries the state but not the
    compressed keys past its prefill: a step into it is refused, naming
    them."""
    config, params, _, _ = model
    with pytest.raises(ValueError, match="compressed"):
        if call == "generate":
            generate(params, config, some_tokens(1, 12), 4)
        else:
            forward(params, config, some_tokens(1, 1),
                    cache=init_cache(config, 1, 32), pos=jnp.int32(5))


def test_the_refusal_names_the_kind_the_bytes_and_the_compressed_store(
        model):
    with pytest.raises(ValueError) as raised:
        _engine(model, prefill_chunk_size=8)
    assert "lightning layers" in str(raised.value)
    assert f"2 states of {STATE_BYTES // 2} B a slot" in str(raised.value)
    assert "compressed keys" in str(raised.value)


@pytest.mark.parametrize("key,value", [
    ("attn_use_rope", True), ("lightning_use_rope", False),
    ("qk_norm", False), ("use_output_gate", False),
    ("use_output_norm", False), ("attn_use_output_gate", False),
    ("tie_word_embeddings", True), ("hidden_act", "gelu"),
    ("attention_bias", True), ("lightning_nkv", 2),
    ("lightning_scale", "1"), ("rope_scaling", {"type": "yarn"}),
    ("mixer_types", ["minicpm4"] * 3 + ["mamba"])])
def test_published_keys_that_are_not_implemented_are_refused(key, value):
    with pytest.raises(ValueError, match=key):
        minicpm_sala_config(dict(PUBLISHED, **{key: value}))


def test_layer_kinds_take_one_recurrent_kind():
    with pytest.raises(ValueError, match="layer_kinds"):
        dataclasses.replace(
            minicpm_sala_config(PUBLISHED),
            layer_kinds=("lightning", "delta", "attention", "attention"))


# -- (f) specs and the element ----------------------------------------------------

def test_param_specs_cover_the_models_leaves(model):
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


def test_the_element_reads_minicpm_sala_by_its_model_type():
    from aiko_services_tpu.elements import ml

    class Element:
        parameters = {"model": PUBLISHED, "max_seq_len": 96}

        def get_parameter(self, name, default=None):
            return self.parameters.get(name, default)

    config = ml._transformer_config(Element())
    assert (config.n_states, config.max_seq_len, config.dtype) == (
        LIGHTNING, 96, "float32")
    assert "minicpm_sala" in configs.PUBLISHED_READERS
