"""The plain reference against the program at a toy size, and its control:
the reference makes the program's seeded weights itself, agrees with the
program's forward pass, and its int8 control fails the comparison."""
import numpy as np
import pytest

from benchmark.tests import toy
from benchmark.harness import checks
from benchmark.reference import transformer as reference

SEED = 2 ** 31 + 77


def _program_config():
    from aiko_services_tpu.models.transformer import TransformerConfig
    lm = toy.TOY_LM
    return TransformerConfig(
        vocab_size=lm["vocab_size"], d_model=lm["hidden_size"],
        n_layers=lm["num_hidden_layers"],
        n_heads=lm["num_attention_heads"],
        n_kv_heads=lm["num_key_value_heads"],
        d_ff=lm["intermediate_size"], max_seq_len=256, dtype="float32")


def test_the_reference_makes_the_programs_seeded_weights():
    import jax
    from aiko_services_tpu.models import init_params
    params = init_params(_program_config(), jax.random.PRNGKey(SEED))
    shape = reference.shape_of(toy.TOY_LM)
    embed_key, layer_keys = reference._keys(shape, SEED)
    assert np.array_equal(np.asarray(reference._embedding(embed_key, shape)),
                          np.asarray(params["embed"]["w"]))
    for index, key in enumerate(layer_keys):
        made = reference._layer_weights(key, shape)
        for name, value in made.items():
            assert np.array_equal(
                np.asarray(value),
                np.asarray(params["layers"][name]["w"][index])), name


def test_reference_logits_agree_with_the_programs_forward():
    import jax
    from aiko_services_tpu.models import forward, init_params
    config = _program_config()
    params = init_params(config, jax.random.PRNGKey(SEED))
    tokens = np.random.default_rng(3).integers(
        1, config.vocab_size, (2, 40)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        expected = np.asarray(forward(params, config, tokens))
    positions = np.tile(np.arange(40), (2, 1))
    got = np.asarray(reference.logits_at(
        reference.shape_of(toy.TOY_LM), SEED, tokens, positions))
    # float32 both sides, different operation order: 1e-4 of the
    # logits' scale is rounding, a wrong mask or rotation is 1e-1
    assert np.abs(got - expected).max() <= 1e-4 * np.abs(expected).max()


def _greedy_samples(count: int = 3, prompt: int = 24, new: int = 12):
    """Prompts and what the program's own greedy generation serves."""
    import jax
    from aiko_services_tpu.models import generate, init_params
    config = _program_config()
    params = init_params(config, jax.random.PRNGKey(SEED))
    prompts = np.random.default_rng(5).integers(
        1, config.vocab_size, (count, prompt)).astype(np.int32)
    served, _ = generate(params, config, prompts, new)
    return [(prompts[row], np.asarray(served[row]))
            for row in range(count)]


def test_served_tokens_pass_and_the_int8_control_fails():
    samples = _greedy_samples()
    honest = checks.served_gaps(toy.TOY_LM, SEED, samples, pad_to=64)
    assert honest["tokens_compared"] == 36
    correct, lines = checks.judge(honest, toy.TOY_LIMITS)
    assert correct and all("ok" in line for line in lines)
    control = checks.served_gaps(toy.TOY_LM, SEED, samples, pad_to=64,
                                 control="int8")
    correct, lines = checks.judge(control, toy.TOY_LIMITS)
    assert not correct and any("OVER" in line for line in lines)
    assert control["gap_mean"] > 3 * max(honest["gap_mean"], 1e-6)


def test_an_altered_token_fails():
    samples = _greedy_samples()
    prompt, served = samples[0]
    altered = served.copy()
    altered[5] = (altered[5] + 1) % toy.TOY_LM["vocab_size"]
    measured = checks.served_gaps(
        toy.TOY_LM, SEED, [(prompt, altered)] + samples[1:], pad_to=64)
    assert not checks.judge(measured, toy.TOY_LIMITS)[0]


def test_a_sample_longer_than_the_reference_is_refused():
    with pytest.raises(ValueError):
        checks.served_gaps(toy.TOY_LM, SEED, _greedy_samples(1), pad_to=32)
