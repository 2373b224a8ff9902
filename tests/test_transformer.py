# Transformer LM tests: forward shape/sanity, prefill-vs-decode parity
# (the KV-cache path must reproduce the flash prefill path), generation
# determinism, sharded train step on the virtual 8-device mesh.

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from aiko_services_tpu.models import (
    TransformerConfig, cache_specs, count_params, decode_step, forward,
    generate, init_cache, init_params, make_train_step, param_specs)
from aiko_services_tpu.models.transformer import (
    init_paged_pool, paged_decode_step, paged_prefill)
from aiko_services_tpu.parallel import (
    attention, create_mesh, shard_pytree)

CONFIG = TransformerConfig(
    vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
    d_ff=128, max_seq_len=64, dtype="float32")


def _params():
    return init_params(CONFIG, jax.random.PRNGKey(0))


def test_param_count_and_specs_match_structure():
    params = _params()
    specs = param_specs(CONFIG)
    # same tree structure: tree_map must not raise
    jax.tree_util.tree_map(lambda leaf, spec: None, params, specs)
    assert count_params(params) > CONFIG.vocab_size * CONFIG.d_model


def test_forward_shapes_and_finite():
    params = _params()
    tokens = jnp.arange(24, dtype=jnp.int32).reshape(2, 12) % 256
    logits = forward(params, CONFIG, tokens)
    assert logits.shape == (2, 12, 256)
    assert bool(jnp.isfinite(logits).all())


def test_prefill_and_cached_decode_agree():
    """Scoring token t via full prefill must equal scoring it incrementally
    through the KV cache."""
    params = _params()
    tokens = (jax.random.randint(jax.random.PRNGKey(1), (1, 10), 0, 256)
              .astype(jnp.int32))
    full_logits = forward(params, CONFIG, tokens)

    cache = init_cache(CONFIG, batch=1, max_len=16)
    step_logits = []
    for position in range(10):
        logits, cache = forward(
            params, CONFIG, tokens[:, position:position + 1],
            cache=cache, pos=position)
        step_logits.append(logits[:, 0])
    stacked = jnp.stack(step_logits, axis=1)
    np.testing.assert_allclose(np.asarray(stacked),
                               np.asarray(full_logits),
                               atol=2e-3, rtol=2e-3)


def test_generate_greedy_deterministic():
    params = _params()
    prompt = jnp.array([[1, 2, 3, 4]], dtype=jnp.int32)
    out1, _ = generate(params, CONFIG, prompt, max_new_tokens=8)
    out2, _ = generate(params, CONFIG, prompt, max_new_tokens=8)
    assert out1.shape == (1, 8)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    assert int(out1.min()) >= 0 and int(out1.max()) < 256


def test_train_step_reduces_loss():
    params = _params()
    optimizer = optax.adam(1e-2)
    opt_state = optimizer.init(params)
    train_step = make_train_step(CONFIG, optimizer)
    tokens = (jax.random.randint(jax.random.PRNGKey(2), (4, 16), 0, 256)
              .astype(jnp.int32))
    losses = []
    for _ in range(5):
        params, opt_state, loss = train_step(params, opt_state, tokens)
        losses.append(float(loss))
    assert losses[-1] < losses[0]
    assert np.isfinite(losses).all()


class TestRematPolicySweep:
    """ROADMAP #3b groundwork: make_train_step(remat_policy=) accepts
    named jax.checkpoint_policies entries.  Remat only changes WHEN
    activations are (re)computed, never WHAT is computed, so every
    policy must produce bit-identical losses -- the sweep is purely a
    step-time/HBM frontier the bench `remat` knob walks."""

    POLICIES = ("none", "nothing_saveable", "dots_saveable",
                "dots_with_no_batch_dims_saveable")

    def test_policies_produce_bit_identical_losses(self):
        tokens = (jax.random.randint(jax.random.PRNGKey(5), (2, 17),
                                     0, 256).astype(jnp.int32))
        optimizer = optax.adamw(1e-3)
        losses = {}
        for policy in self.POLICIES:
            params = _params()
            opt_state = optimizer.init(params)
            step = make_train_step(CONFIG, optimizer,
                                   remat_policy=policy)
            trail = []
            for _ in range(3):
                params, opt_state, loss = step(params, opt_state, tokens)
                trail.append(np.asarray(loss))
            losses[policy] = trail
        baseline = losses["none"]
        for policy in self.POLICIES[1:]:
            np.testing.assert_array_equal(
                np.asarray(losses[policy]), np.asarray(baseline),
                err_msg=f"remat_policy={policy} drifted from baseline")

    def test_unknown_policy_fails_fast(self):
        from aiko_services_tpu.models import REMAT_POLICIES
        with pytest.raises(ValueError, match="remat_policy"):
            make_train_step(CONFIG, optax.adam(1e-3),
                            remat_policy="dots_savable")  # typo
        assert "nothing_saveable" in REMAT_POLICIES

    def test_remat_rejected_on_decode_path(self):
        params = _params()
        cache = init_cache(CONFIG, 1, max_len=8)
        tokens = jnp.ones((1, 1), jnp.int32)
        with pytest.raises(ValueError, match="cache-less"):
            forward(params, CONFIG, tokens, cache=cache, pos=0,
                    remat_policy="nothing_saveable")


def test_sharded_train_step_on_mesh():
    """Full TP+FSDP+DP+SP train step over the 8-device mesh: params sharded
    by param_specs, batch sharded on data, runs and stays finite."""
    mesh = create_mesh({"data": 2, "fsdp": 1, "seq": 2, "model": 2})
    config = TransformerConfig(
        vocab_size=128, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, max_seq_len=32, dtype="float32")
    with jax.set_mesh(mesh):
        params = init_params(config, jax.random.PRNGKey(0))
        params = shard_pytree(params, mesh, param_specs(config))
        optimizer = optax.adam(1e-2)
        opt_state = optimizer.init(params)
        train_step = make_train_step(config, optimizer, sharded=True)
        tokens = jax.device_put(
            jax.random.randint(jax.random.PRNGKey(3), (4, 16), 0, 128)
            .astype(jnp.int32),
            NamedSharding(mesh, P("data", None)))
        params, opt_state, loss = train_step(params, opt_state, tokens)
        assert np.isfinite(float(loss))
        # TP sharding preserved through the update
        wq = params["layers"]["wq"]["w"]
        assert not wq.sharding.is_fully_replicated


def test_sequence_parallel_matches_dense():
    """Ring-attention prefill over the seq axis must reproduce the dense
    flash prefill (the long-context path is exact, not approximate)."""
    import dataclasses
    mesh = create_mesh({"seq": 8})
    config = TransformerConfig(
        vocab_size=128, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, max_seq_len=64, dtype="float32")
    sp_config = dataclasses.replace(config, sequence_parallel=True)
    params = init_params(config, jax.random.PRNGKey(0))
    tokens = (jax.random.randint(jax.random.PRNGKey(5), (2, 32), 0, 128)
              .astype(jnp.int32))
    dense = forward(params, config, tokens)
    with jax.set_mesh(mesh):
        ringed = forward(params, sp_config, tokens)
    np.testing.assert_allclose(np.asarray(ringed), np.asarray(dense),
                               atol=2e-3, rtol=2e-3)


def test_sequence_parallel_train_step():
    import dataclasses
    mesh = create_mesh({"data": 2, "fsdp": 1, "seq": 2, "model": 2})
    config = TransformerConfig(
        vocab_size=128, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, max_seq_len=32, dtype="float32", sequence_parallel=True)
    with jax.set_mesh(mesh):
        params = shard_pytree(init_params(config, jax.random.PRNGKey(0)),
                              mesh, param_specs(config))
        optimizer = optax.adam(1e-2)
        opt_state = optimizer.init(params)
        train_step = make_train_step(config, optimizer, sharded=True)
        tokens = jax.device_put(
            jax.random.randint(jax.random.PRNGKey(3), (4, 17), 0, 128)
            .astype(jnp.int32),
            NamedSharding(mesh, P("data", None)))
        params, opt_state, loss = train_step(params, opt_state, tokens)
        assert np.isfinite(float(loss))


def test_moe_forward_and_train():
    config = TransformerConfig(
        vocab_size=128, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, max_seq_len=32, dtype="float32", n_experts=4)
    params = init_params(config, jax.random.PRNGKey(0))
    assert params["layers"]["w_gate"]["w"].shape == (2, 4, 32, 64)
    tokens = (jax.random.randint(jax.random.PRNGKey(6), (2, 16), 0, 128)
              .astype(jnp.int32))
    logits = forward(params, config, tokens)
    assert logits.shape == (2, 16, 128)
    assert bool(jnp.isfinite(logits).all())
    optimizer = optax.adam(1e-2)
    train_step = make_train_step(config, optimizer)
    losses = []
    opt_state = optimizer.init(params)
    for _ in range(4):
        params, opt_state, loss = train_step(params, opt_state, tokens)
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_moe_expert_parallel_on_mesh():
    """EP: expert weights sharded on the 'expert' axis; the sharded train
    step runs and the expert dimension stays partitioned."""
    mesh = create_mesh({"data": 2, "expert": 2, "model": 2})
    config = TransformerConfig(
        vocab_size=128, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, max_seq_len=32, dtype="float32", n_experts=4)
    with jax.set_mesh(mesh):
        params = shard_pytree(init_params(config, jax.random.PRNGKey(0)),
                              mesh,
                              __import__("aiko_services_tpu.parallel",
                                         fromlist=["filter_specs"])
                              .filter_specs(param_specs(config), mesh))
        gate = params["layers"]["w_gate"]["w"]
        assert not gate.sharding.is_fully_replicated
        optimizer = optax.adam(1e-2)
        opt_state = optimizer.init(params)
        train_step = make_train_step(config, optimizer)
        tokens = jax.device_put(
            jax.random.randint(jax.random.PRNGKey(3), (4, 16), 0, 128)
            .astype(jnp.int32),
            NamedSharding(mesh, P("data", None)))
        params, opt_state, loss = train_step(params, opt_state, tokens)
        assert np.isfinite(float(loss))


def test_sharded_decode_on_mesh():
    mesh = create_mesh({"data": 2, "fsdp": 1, "seq": 2, "model": 2})
    config = TransformerConfig(
        vocab_size=128, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, max_seq_len=32, dtype="float32")
    with jax.set_mesh(mesh):
        params = shard_pytree(
            init_params(config, jax.random.PRNGKey(0)), mesh,
            param_specs(config))
        cache = shard_pytree(init_cache(config, batch=2, max_len=16),
                             mesh, cache_specs())
        prompt = jnp.ones((2, 4), jnp.int32)
        out, cache = generate(params, config, prompt, max_new_tokens=4,
                              cache=cache)
        assert out.shape == (2, 4)
        assert cache is not None


def test_sequence_parallel_generate():
    """Long-context generation with the KV cache sharded over the mesh
    "seq" axis (sp_decode_attention) must reproduce the unsharded greedy
    decode exactly (VERDICT round-1 item 4: SP decode path)."""
    import dataclasses
    mesh = create_mesh({"data": 2, "fsdp": 1, "seq": 2, "model": 2})
    config = TransformerConfig(
        vocab_size=128, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, max_seq_len=64, dtype="float32")
    sp_config = dataclasses.replace(config, sequence_parallel=True)
    params = init_params(config, jax.random.PRNGKey(0))
    prompt = (jax.random.randint(jax.random.PRNGKey(4), (2, 16), 0, 128)
              .astype(jnp.int32))
    dense_out, _ = generate(params, config, prompt, max_new_tokens=8)
    with jax.set_mesh(mesh):
        sp_params = shard_pytree(params, mesh, param_specs(config))
        cache = shard_pytree(
            init_cache(config, batch=2, max_len=24), mesh,
            cache_specs(sequence_parallel=True))
        sp_out, _ = generate(sp_params, sp_config, prompt,
                             max_new_tokens=8, cache=cache)
    np.testing.assert_array_equal(np.asarray(sp_out),
                                  np.asarray(dense_out))


class TestMoECapacityDispatch:
    """VERDICT round-1 item 8: capacity-based gather/scatter dispatch
    replacing masked-dense."""

    @staticmethod
    def _config(**kw):
        base = dict(vocab_size=128, d_model=32, n_layers=2, n_heads=4,
                    n_kv_heads=2, d_ff=64, max_seq_len=32,
                    dtype="float32", n_experts=4)
        base.update(kw)
        return TransformerConfig(**base)

    def test_capacity_matches_dense_oracle_when_unconstrained(self):
        """With capacity >= L no token is ever dropped, so capacity
        dispatch must agree exactly with the masked-dense oracle."""
        cap = self._config(moe_capacity_factor=8.0)  # C = L
        dense = dataclasses.replace(cap, moe_capacity_factor=0.0)
        params = init_params(cap, jax.random.PRNGKey(0))
        tokens = (jax.random.randint(jax.random.PRNGKey(6), (2, 16),
                                     0, 128).astype(jnp.int32))
        got = forward(params, cap, tokens)
        want = forward(params, dense, tokens)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4, rtol=1e-4)

    def test_aux_loss_reported_and_balanced_routing_lowers_it(self):
        config = self._config()
        params = init_params(config, jax.random.PRNGKey(0))
        tokens = (jax.random.randint(jax.random.PRNGKey(2), (2, 16),
                                     0, 128).astype(jnp.int32))
        _, aux = forward(params, config, tokens, return_aux=True)
        # Switch aux loss is >= 1 (perfectly balanced) for top-1 routing
        assert float(aux) >= 1.0 - 1e-5

    def test_capacity_train_step_learns(self):
        config = self._config(moe_capacity_factor=1.25)
        params = init_params(config, jax.random.PRNGKey(0))
        tokens = (jax.random.randint(jax.random.PRNGKey(6), (2, 16),
                                     0, 128).astype(jnp.int32))
        optimizer = optax.adam(1e-2)
        train_step = make_train_step(config, optimizer)
        opt_state = optimizer.init(params)
        losses = []
        for _ in range(4):
            params, opt_state, loss = train_step(params, opt_state,
                                                 tokens)
            losses.append(float(loss))
        assert losses[-1] < losses[0]

    def test_capacity_flops_scale_with_capacity_not_experts(self):
        """The compiled FLOP count of the capacity forward must be far
        below masked-dense (which pays E x the FFN): per-device FLOPs
        follow E_local x C, i.e. ~capacity_factor x one dense FFN."""
        cap = self._config(n_experts=8, d_ff=128,
                           moe_capacity_factor=1.0)
        dense = dataclasses.replace(cap, moe_capacity_factor=0.0)
        params = init_params(cap, jax.random.PRNGKey(0))
        tokens = jnp.zeros((2, 64), jnp.int32)

        def flops(config):
            compiled = (jax.jit(lambda p, t: forward(p, config, t))
                        .lower(params, tokens).compile())
            analysis = compiled.cost_analysis()
            return analysis["flops"]

        ratio = flops(cap) / flops(dense)
        assert ratio < 0.55, f"capacity dispatch not cheaper: {ratio}"

    def test_overflow_tokens_are_dropped_from_moe_output(self):
        """Identical tokens all route to one expert; with capacity 1 only
        the first is processed -- the MoE output rows for every dropped
        token must be exactly zero (they ride the residual in forward)."""
        from aiko_services_tpu.models.transformer import _switch_moe
        config = self._config(moe_capacity_factor=1e-9)  # C floors at 1
        params = init_params(config, jax.random.PRNGKey(0))
        layer0 = jax.tree_util.tree_map(lambda leaf: leaf[0],
                                        params["layers"])
        x = jnp.broadcast_to(
            jax.random.normal(jax.random.PRNGKey(3), (32,), jnp.float32),
            (1, 8, 32))
        out, _ = _switch_moe(config, layer0, x)
        assert float(jnp.abs(out[0, 0]).max()) > 0
        np.testing.assert_array_equal(np.asarray(out[0, 1:]),
                                      np.zeros((7, 32), np.float32))

    def test_decode_gather_matches_dense_oracle(self):
        """L < E routes through the per-token weight-gather path; it
        must agree with the masked-dense oracle (no capacity drops at
        L=1/L=2)."""
        cap = self._config(n_experts=8)
        dense = dataclasses.replace(cap, moe_capacity_factor=0.0)
        params = init_params(cap, jax.random.PRNGKey(0))
        tokens = (jax.random.randint(jax.random.PRNGKey(7), (2, 2),
                                     0, 128).astype(jnp.int32))
        got = forward(params, cap, tokens)
        want = forward(params, dense, tokens)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4, rtol=1e-4)

    def test_return_aux_with_cache_fails_fast(self):
        config = self._config()
        params = init_params(config, jax.random.PRNGKey(0))
        cache = init_cache(config, batch=1, max_len=8)
        with pytest.raises(ValueError, match="cache-less"):
            forward(params, config, jnp.zeros((1, 1), jnp.int32),
                    cache=cache, return_aux=True)


def test_ulysses_sp_mechanism_matches_dense():
    """sp_mechanism="ulysses": all-to-all sequence parallelism in the
    flagship prefill must match the dense forward (heads divisible by
    the seq axis)."""
    import dataclasses
    mesh = create_mesh({"seq": 4}, devices=jax.devices()[:4])
    config = TransformerConfig(
        vocab_size=128, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, max_seq_len=64, dtype="float32")
    sp_config = dataclasses.replace(config, sequence_parallel=True,
                                    sp_mechanism="ulysses")
    params = init_params(config, jax.random.PRNGKey(0))
    tokens = (jax.random.randint(jax.random.PRNGKey(5), (2, 32), 0, 128)
              .astype(jnp.int32))
    dense = forward(params, config, tokens)
    with jax.set_mesh(mesh):
        sharded = forward(params, sp_config, tokens)
    np.testing.assert_allclose(np.asarray(sharded), np.asarray(dense),
                               atol=2e-3, rtol=2e-3)


def test_ulysses_sp_generate_matches_dense():
    import dataclasses
    mesh = create_mesh({"data": 2, "fsdp": 1, "seq": 2, "model": 2})
    config = TransformerConfig(
        vocab_size=128, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, max_seq_len=64, dtype="float32")
    sp_config = dataclasses.replace(config, sequence_parallel=True,
                                    sp_mechanism="ulysses")
    params = init_params(config, jax.random.PRNGKey(0))
    prompt = (jax.random.randint(jax.random.PRNGKey(4), (2, 16), 0, 128)
              .astype(jnp.int32))
    dense_out, _ = generate(params, config, prompt, max_new_tokens=8)
    with jax.set_mesh(mesh):
        sp_params = shard_pytree(params, mesh, param_specs(config))
        cache = shard_pytree(
            init_cache(config, batch=2, max_len=24), mesh,
            cache_specs(sequence_parallel=True))
        sp_out, _ = generate(sp_params, sp_config, prompt,
                             max_new_tokens=8, cache=cache)
    np.testing.assert_array_equal(np.asarray(sp_out),
                                  np.asarray(dense_out))


def test_sp_mechanism_typo_fails_fast():
    with pytest.raises(ValueError, match="sp_mechanism"):
        TransformerConfig(sp_mechanism="Ulysses")


# -- sharded serving: llama32_1b ARCHITECTURE decode under param_specs -------
# (BASELINE config 4: mesh-sharded decode; tiny dims, real structure --
# GQA 4:1 ratio, tied embeddings, rope_theta 500000, scan-stacked layers)

class TestShardedServing:
    def _arch_config(self):
        from dataclasses import replace
        from aiko_services_tpu.models.configs import LLAMA32_1B
        return replace(
            LLAMA32_1B, vocab_size=256, d_model=64, n_layers=2,
            n_heads=8, n_kv_heads=2, d_ff=128, max_seq_len=128,
            dtype="float32")

    def test_decode_parity_with_param_specs_sharding(self):
        import jax
        import jax.numpy as jnp
        import numpy as np
        from aiko_services_tpu.models import (
            cache_specs, generate, init_cache, init_params, param_specs)
        from aiko_services_tpu.parallel import filter_specs, shard_pytree
        from aiko_services_tpu.parallel.mesh import create_mesh

        config = self._arch_config()
        params = init_params(config, jax.random.PRNGKey(7))
        prompt = jnp.asarray(
            np.random.default_rng(0).integers(3, 250, (4, 12)), jnp.int32)
        dense_tokens, _ = generate(params, config, prompt, 8)

        mesh = create_mesh({"data": 2, "fsdp": 2, "seq": 1, "model": 2})
        sharded_params = shard_pytree(
            params, mesh, filter_specs(param_specs(config), mesh))
        cache = shard_pytree(
            init_cache(config, 4, max_len=32), mesh,
            filter_specs(cache_specs(), mesh))
        with jax.set_mesh(mesh):
            sharded_tokens, _ = generate(
                sharded_params, config, prompt, 8, cache=cache)
        np.testing.assert_array_equal(np.asarray(dense_tokens),
                                      np.asarray(sharded_tokens))

    def test_decode_step_collective_count_is_bounded(self):
        """TP decode must cost O(n_layers) small all-reduces per step --
        not O(matmuls).  Megatron sharding: one fused all-reduce after
        attention out-proj + one after the MLP down-proj per layer, plus
        the logits reduction."""
        import re
        import jax
        import jax.numpy as jnp
        from functools import partial
        from aiko_services_tpu.models import (
            cache_specs, decode_step, init_cache, init_params, param_specs)
        from aiko_services_tpu.parallel import filter_specs, shard_pytree
        from aiko_services_tpu.parallel.mesh import create_mesh

        config = self._arch_config()
        mesh = create_mesh({"data": 2, "fsdp": 2, "seq": 1, "model": 2})
        params = shard_pytree(
            init_params(config, jax.random.PRNGKey(0)), mesh,
            filter_specs(param_specs(config), mesh))
        cache = shard_pytree(
            init_cache(config, 4, max_len=32), mesh,
            filter_specs(cache_specs(), mesh))
        token = jnp.ones((4, 1), jnp.int32)
        pos = jnp.int32(5)
        with jax.set_mesh(mesh):
            step = jax.jit(partial(decode_step, config=config))
            hlo = step.lower(params, cache=cache, token=token,
                             pos=pos).compile().as_text()
        # count instruction DEFINITIONS only ("%x = ty[] all-reduce(" --
        # bare name mentions recur at every operand use site)
        collectives = re.findall(
            r"= \S+ (all-reduce|all-gather|reduce-scatter|all-to-all|"
            r"collective-permute)\(", hlo)
        # fusion may merge but must never EXCEED the megatron budget:
        # 2 per layer + logits (+1 slack for the embedding gather path)
        budget = 2 * config.n_layers + 2
        assert 1 <= len(collectives) <= budget, (
            f"{len(collectives)} collectives per decode step "
            f"(budget {budget}): {collectives}")


class TestLlama38BArchitecture:
    """The flagship LLAMA3_8B preset instantiated (tiny width, REAL
    structure: 32 scan layers, 4:1 GQA, untied lm_head, rope 500k) --
    sharded decode over the full mesh vocabulary with an 8B-style
    param_specs tree including the untied head."""

    def test_8b_architecture_sharded_decode(self):
        import jax
        import jax.numpy as jnp
        import numpy as np
        from dataclasses import replace
        from aiko_services_tpu.models import (
            cache_specs, generate, init_cache, init_params, param_specs)
        from aiko_services_tpu.models.configs import LLAMA3_8B
        from aiko_services_tpu.parallel import filter_specs, shard_pytree
        from aiko_services_tpu.parallel.mesh import create_mesh

        config = replace(
            LLAMA3_8B, vocab_size=128, d_model=64, n_layers=32,
            n_heads=8, n_kv_heads=2, d_ff=96, max_seq_len=64,
            dtype="float32")
        assert config.n_layers == LLAMA3_8B.n_layers  # real depth
        assert (config.n_heads // config.n_kv_heads
                == LLAMA3_8B.n_heads // LLAMA3_8B.n_kv_heads)  # GQA 4:1
        params = init_params(config, jax.random.PRNGKey(1))
        prompt = jnp.asarray(
            np.random.default_rng(2).integers(3, 120, (2, 8)), jnp.int32)
        dense_tokens, _ = generate(params, config, prompt, 6)

        mesh = create_mesh({"data": 2, "fsdp": 2, "seq": 1, "model": 2})
        sharded = shard_pytree(
            params, mesh,
            filter_specs(param_specs(config, lm_head="lm_head" in params),
                         mesh))
        cache = shard_pytree(
            init_cache(config, 2, max_len=16), mesh,
            filter_specs(cache_specs(), mesh))
        with jax.set_mesh(mesh):
            sharded_tokens, _ = generate(sharded, config, prompt, 6,
                                         cache=cache)
        np.testing.assert_array_equal(np.asarray(dense_tokens),
                                      np.asarray(sharded_tokens))


class TestLlama8BFeasibility:
    """BASELINE config 4 feasibility: the REAL Llama-3-8B layout must
    FIT a v5e-8 serving mesh (VERDICT r3 item 7) -- checked by
    eval_shape (no weights materialize) against the published
    param_specs sharding and the serving KV cache."""

    V5E_HBM_BYTES = 16 * 1024**3          # per chip
    BUDGET = 0.90                          # leave 10% for XLA scratch

    def _per_device_bytes(self, shapes, specs, mesh_axes):
        """Bytes per device for a pytree of ShapeDtypeStructs sharded by
        PartitionSpecs over named mesh axis sizes (replicated where the
        spec names no axis)."""
        import numpy as np

        import jax

        total = 0
        flat_shapes, _ = jax.tree_util.tree_flatten(shapes)
        flat_specs, _ = jax.tree_util.tree_flatten(
            specs, is_leaf=lambda x: isinstance(
                x, jax.sharding.PartitionSpec))
        assert len(flat_shapes) == len(flat_specs)
        for struct, spec in zip(flat_shapes, flat_specs):
            divisor = 1
            for entry in tuple(spec):
                names = (entry if isinstance(entry, tuple)
                         else (entry,) if entry else ())
                for name in names:
                    divisor *= mesh_axes.get(name, 1)
            total += (int(np.prod(struct.shape)) // divisor
                      * struct.dtype.itemsize)
        return total

    def test_8b_params_and_cache_fit_v5e8(self):
        import jax

        from aiko_services_tpu.models import (
            cache_specs, init_cache, init_params, param_specs)
        from aiko_services_tpu.models.configs import LLAMA3_8B

        config = LLAMA3_8B
        # the serving mesh from examples/pipeline_llm_8b.json
        mesh_axes = {"data": 1, "fsdp": 2, "seq": 1, "model": 4}
        shapes = jax.eval_shape(
            lambda: init_params(config, jax.random.PRNGKey(0)))
        has_head = "lm_head" in shapes
        specs = param_specs(config, lm_head=has_head)
        specs = {key: specs[key] for key in shapes}  # align partial tree
        param_bytes = self._per_device_bytes(shapes, specs, mesh_axes)

        # serving KV cache: batch 8, full 8k context
        batch, max_len = 8, config.max_seq_len
        cache_shapes = jax.eval_shape(
            lambda: init_cache(config, batch, max_len=max_len))
        cache_bytes = self._per_device_bytes(
            cache_shapes, cache_specs(), mesh_axes)

        # activations at decode (1 token) are noise; prefill peak ~
        # batch x seq x d x a-few in bf16 under remat -- bound it
        # generously
        activation_bytes = 2 * batch * max_len * config.d_model * 8

        used = param_bytes + cache_bytes + activation_bytes
        budget = self.V5E_HBM_BYTES * self.BUDGET
        # HBM budget table (mirrored in BENCH_NOTES.md):
        #   params/device   2.11 GiB (8.03B bf16 over fsdp2 x model4)
        #   kv cache/device 2.00 GiB (batch 8 x 8k, GQA 8 kv heads / 4)
        #   activations     4.00 GiB bound
        #   total           8.11 GiB vs 14.4 GiB budget
        assert used < budget, (
            f"8B does not fit: params {param_bytes/2**30:.2f} GiB + "
            f"cache {cache_bytes/2**30:.2f} GiB + activations "
            f"{activation_bytes/2**30:.2f} GiB = {used/2**30:.2f} GiB "
            f"> budget {budget/2**30:.2f} GiB")
        # and the whole thing genuinely needed sharding: replicated
        # (params 15.0 GiB + cache + activations) blows the same budget
        replicated = self._per_device_bytes(shapes, specs, {})
        assert replicated + cache_bytes + activation_bytes > budget

    def test_8b_int8_fits_four_chips(self):
        """int8 weights + int8 KV shrink the REAL Llama-3-8B serving
        footprint enough for a v5e-4 (half the mesh the bf16 layout
        needs): quantization buys mesh size, not just batch."""
        import jax
        from dataclasses import replace

        from aiko_services_tpu.models import (
            cache_specs, init_cache, init_params, quantize_weights_int8,
            quantized_param_specs)
        from aiko_services_tpu.models.configs import LLAMA3_8B

        config = replace(LLAMA3_8B, kv_dtype="int8")
        mesh_axes = {"data": 1, "fsdp": 2, "seq": 1, "model": 2}  # 4 chips
        shapes = jax.eval_shape(lambda: quantize_weights_int8(
            init_params(config, jax.random.PRNGKey(0)), config))
        has_head = "lm_head" in shapes
        specs = quantized_param_specs(config, lm_head=has_head)
        specs = {key: specs[key] for key in shapes}
        param_bytes = self._per_device_bytes(shapes, specs, mesh_axes)

        batch, max_len = 8, config.max_seq_len
        cache_shapes = jax.eval_shape(
            lambda: init_cache(config, batch, max_len=max_len))
        cache_bytes = self._per_device_bytes(
            cache_shapes, cache_specs(quantized=True), mesh_axes)
        activation_bytes = 2 * batch * max_len * config.d_model * 8

        used = param_bytes + cache_bytes + activation_bytes
        budget = self.V5E_HBM_BYTES * self.BUDGET
        assert used < budget, (
            f"int8 8B does not fit 4 chips: params "
            f"{param_bytes/2**30:.2f} GiB + cache "
            f"{cache_bytes/2**30:.2f} GiB + activations "
            f"{activation_bytes/2**30:.2f} GiB = {used/2**30:.2f} GiB "
            f"> budget {budget/2**30:.2f} GiB")

    def test_8b_pipeline_definition_compiles_on_virtual_mesh(self):
        """examples/pipeline_llm_8b.json executes end to end on the
        virtual 8-CPU mesh at ARCHITECTURE dims (real depth/GQA/mesh
        layout, tiny width -- materializing 16 GB of weights on the
        test host is the only thing skipped)."""
        import json
        import pathlib
        import queue

        from aiko_services_tpu.pipeline import create_pipeline
        from aiko_services_tpu.runtime import Process

        path = (pathlib.Path(__file__).parent.parent / "examples"
                / "pipeline_llm_8b.json")
        definition = json.loads(path.read_text())
        lm = next(element for element in definition["elements"]
                  if element["name"] == "lm")
        # architecture dims: REAL depth + GQA ratio + the json's mesh
        # layout; width shrunk so the test host can materialize it
        lm["parameters"].pop("preset")
        lm["parameters"].update({
            "vocab_size": 256, "d_model": 64, "n_layers": 32,
            "n_heads": 8, "n_kv_heads": 2, "d_ff": 224,
            "max_seq_len": 512, "dtype": "float32",
            "max_new_tokens": 4, "tokenizer": "default",
            "stream_tokens": False})
        process = Process(transport_kind="loopback")
        pipeline = create_pipeline(process, definition)
        process.run(in_thread=True)
        responses = queue.Queue()
        pipeline.create_stream("s1", queue_response=responses)
        _, _, outputs = responses.get(timeout=300)
        assert "generated" in outputs and "text" in outputs
        process.terminate()


class TestLlama8BRealDimsLowering:
    """VERDICT r4 item 5: the TRUE-dims Llama-3-8B (4096 d_model, 32
    layers, 32/8 GQA heads, 128k vocab, untied head) decode and prefill
    programs must LOWER AND COMPILE over the 8-device serving mesh with
    megatron-bounded collectives -- proven from ABSTRACT inputs
    (ShapeDtypeStruct + NamedSharding; zero weight bytes materialize),
    completing the eval_shape HBM-budget proof with a program-level
    artifact.  Reference seat: BASELINE config 4 / the reference's LLM
    element (examples/llm/elements_llm.py:137)."""

    BATCH = 8

    def _mesh(self):
        from aiko_services_tpu.parallel.mesh import create_mesh
        # the serving mesh from examples/pipeline_llm_8b.json
        return create_mesh({"data": 1, "fsdp": 2, "seq": 1, "model": 4})

    def _abstract(self, shapes, specs_tree, mesh):
        import jax
        flat_shapes, treedef = jax.tree_util.tree_flatten(shapes)
        flat_specs, _ = jax.tree_util.tree_flatten(
            specs_tree, is_leaf=lambda x: isinstance(
                x, jax.sharding.PartitionSpec))
        assert len(flat_shapes) == len(flat_specs)
        return treedef.unflatten([
            jax.ShapeDtypeStruct(
                struct.shape, struct.dtype,
                sharding=jax.sharding.NamedSharding(mesh, spec))
            for struct, spec in zip(flat_shapes, flat_specs)])

    def _structs(self, mesh, max_len):
        import jax
        from aiko_services_tpu.models import (
            cache_specs, init_cache, init_params, param_specs)
        from aiko_services_tpu.models.configs import LLAMA3_8B
        from aiko_services_tpu.parallel import filter_specs

        config = LLAMA3_8B
        param_shapes = jax.eval_shape(
            lambda: init_params(config, jax.random.PRNGKey(0)))
        specs = filter_specs(
            param_specs(config, lm_head="lm_head" in param_shapes), mesh)
        specs = {key: specs[key] for key in param_shapes}
        params = self._abstract(param_shapes, specs, mesh)
        cache_shapes = jax.eval_shape(
            lambda: init_cache(config, self.BATCH, max_len=max_len))
        cache = self._abstract(
            cache_shapes, filter_specs(cache_specs(), mesh), mesh)
        return config, params, cache

    def _collectives(self, hlo):
        import re
        found = re.findall(
            r"= \S+ (all-reduce|all-gather|reduce-scatter|all-to-all|"
            r"collective-permute)\(", hlo)
        counts = {}
        for kind in found:
            counts[kind] = counts.get(kind, 0) + 1
        return found, counts

    def test_8b_decode_step_compiles_at_true_dims(self):
        from functools import partial

        import jax
        import jax.numpy as jnp

        from aiko_services_tpu.models import decode_step

        mesh = self._mesh()
        config, params, cache = self._structs(mesh, max_len=8192)
        replicated = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec())
        token = jax.ShapeDtypeStruct((self.BATCH, 1), jnp.int32,
                                     sharding=replicated)
        pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=replicated)
        with jax.set_mesh(mesh):
            step = jax.jit(partial(decode_step, config=config))
            hlo = step.lower(params, cache=cache, token=token,
                             pos=pos).compile().as_text()
        found, counts = self._collectives(hlo)
        print(f"8B decode step collectives over {dict(data=1, fsdp=2, seq=1, model=4)}: "
              f"{counts}")
        budget = 2 * config.n_layers + 2
        assert 1 <= len(found) <= budget, (
            f"{len(found)} collectives per 8B decode step "
            f"(budget {budget}): {counts}")

    def test_8b_prefill_compiles_at_true_dims(self):
        import jax
        import jax.numpy as jnp

        from aiko_services_tpu.models import forward

        mesh = self._mesh()
        config, params, cache = self._structs(mesh, max_len=8192)
        replicated = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec())
        tokens = jax.ShapeDtypeStruct((self.BATCH, 512), jnp.int32,
                                      sharding=replicated)
        with jax.set_mesh(mesh):
            prefill = jax.jit(
                lambda p, t, c: forward(p, config, t, cache=c, pos=0))
            hlo = prefill.lower(params, tokens, cache).compile().as_text()
        found, counts = self._collectives(hlo)
        print(f"8B prefill (512 tokens) collectives: {counts}")
        assert found, "sharded 8B prefill must lower with collectives"


class TestKVCacheInt8:
    """VERDICT r4 item 4: int8 KV cache -- halves cache HBM (doubling
    feasible decode batch) with numerics pinned against the
    full-precision cache."""

    def _config(self):
        from dataclasses import replace
        from aiko_services_tpu.models.configs import LLAMA32_1B
        return replace(
            LLAMA32_1B, vocab_size=256, d_model=64, n_layers=2,
            n_heads=8, n_kv_heads=2, d_ff=128, max_seq_len=128,
            dtype="float32")

    def test_int8_cache_halves_bytes(self):
        import jax
        from dataclasses import replace
        from aiko_services_tpu.models import init_cache

        config = self._config()

        def nbytes(cache):
            return sum(leaf.size * leaf.dtype.itemsize
                       for leaf in jax.tree_util.tree_leaves(cache))

        dense_bytes = nbytes(init_cache(config, 4, max_len=64))
        quant_bytes = nbytes(init_cache(
            replace(config, kv_dtype="int8"), 4, max_len=64))
        # int8 codes (1/4 of f32) + f32 scale per position (1/head_dim)
        assert quant_bytes < dense_bytes * 0.5

    def test_int8_cache_generation_matches_full_precision(self):
        import jax
        import jax.numpy as jnp
        import numpy as np
        from dataclasses import replace
        from aiko_services_tpu.models import generate, init_params

        config = self._config()
        params = init_params(config, jax.random.PRNGKey(3))
        prompt = jnp.asarray(
            np.random.default_rng(1).integers(3, 250, (4, 12)), jnp.int32)
        tokens_fp, _ = generate(params, config, prompt, 12)
        tokens_q, _ = generate(
            params, replace(config, kv_dtype="int8"), prompt, 12)
        np.testing.assert_array_equal(np.asarray(tokens_fp),
                                      np.asarray(tokens_q))

    def test_int8_decode_logits_drift_pinned(self):
        import jax
        import jax.numpy as jnp
        import numpy as np
        from dataclasses import replace
        from aiko_services_tpu.models import (
            decode_step, forward, init_cache, init_params)

        config = self._config()
        config_q = replace(config, kv_dtype="int8")
        params = init_params(config, jax.random.PRNGKey(5))
        prompt = jnp.asarray(
            np.random.default_rng(2).integers(3, 250, (2, 16)), jnp.int32)
        caches = {}
        logits = {}
        for name, cfg in (("fp", config), ("q", config_q)):
            cache = init_cache(cfg, 2, max_len=32)
            _, cache = forward(params, cfg, prompt, cache=cache, pos=0)
            token = jnp.full((2, 1), 7, jnp.int32)
            _, step_logits, cache = decode_step(
                params, cfg, cache, token, jnp.int32(16))
            caches[name], logits[name] = cache, np.asarray(step_logits)
        drift = np.max(np.abs(logits["q"] - logits["fp"]))
        span = np.max(np.abs(logits["fp"])) + 1e-9
        assert drift / span < 0.02, f"relative drift {drift / span:.4f}"

    def test_int8_rejects_sequence_parallel(self):
        import pytest
        from dataclasses import replace
        with pytest.raises(ValueError, match="sequence-parallel"):
            replace(self._config(), kv_dtype="int8",
                    sequence_parallel=True)

    def test_int8_sharded_decode_matches_unsharded(self):
        """cache_specs(quantized=True) lays the int8 cache (codes +
        scale planes) onto the serving mesh: sharded decode must equal
        the single-device int8 path -- the batch-headroom use case the
        quantized cache exists for."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        from dataclasses import replace
        from aiko_services_tpu.models import (
            cache_specs, generate, init_cache, init_params, param_specs)
        from aiko_services_tpu.parallel import filter_specs, shard_pytree
        from aiko_services_tpu.parallel.mesh import create_mesh

        config = replace(self._config(), kv_dtype="int8")
        params = init_params(config, jax.random.PRNGKey(7))
        prompt = jnp.asarray(
            np.random.default_rng(4).integers(3, 250, (4, 12)), jnp.int32)
        dense_tokens, _ = generate(params, config, prompt, 8)

        mesh = create_mesh({"data": 2, "fsdp": 2, "seq": 1, "model": 2})
        sharded_params = shard_pytree(
            params, mesh, filter_specs(param_specs(config), mesh))
        cache = shard_pytree(
            init_cache(config, 4, max_len=32), mesh,
            filter_specs(cache_specs(quantized=True), mesh))
        with jax.set_mesh(mesh):
            sharded_tokens, _ = generate(
                sharded_params, config, prompt, 8, cache=cache)
        np.testing.assert_array_equal(np.asarray(dense_tokens),
                                      np.asarray(sharded_tokens))


class TestWeightOnlyInt8:
    """Weight-only int8 for serving decode (beyond-parity round 5):
    small-batch decode is weight-streaming-bound, so 8-bit weights are
    ~2x step throughput at fixed batch.  Numerics pinned against the
    full-precision weights."""

    def _config(self):
        from dataclasses import replace
        from aiko_services_tpu.models.configs import LLAMA32_1B
        return replace(
            LLAMA32_1B, vocab_size=256, d_model=64, n_layers=2,
            n_heads=8, n_kv_heads=2, d_ff=128, max_seq_len=128,
            dtype="float32")

    def test_quantized_tree_halves_bytes(self):
        import jax
        from aiko_services_tpu.models import (
            init_params, quantize_weights_int8)

        config = self._config()
        params = init_params(config, jax.random.PRNGKey(0))

        def nbytes(tree):
            return sum(leaf.size * leaf.dtype.itemsize
                       for leaf in jax.tree_util.tree_leaves(tree))

        quantized = quantize_weights_int8(params, config)
        # f32 reference weights -> int8 codes + thin f32 scales
        assert nbytes(quantized) < nbytes(params) * 0.30

    def test_int8_weights_logits_drift_pinned(self):
        import jax
        import jax.numpy as jnp
        import numpy as np
        from aiko_services_tpu.models import (
            forward, init_params, quantize_weights_int8)

        config = self._config()
        params = init_params(config, jax.random.PRNGKey(9))
        quantized = quantize_weights_int8(params, config)
        tokens = jnp.asarray(
            np.random.default_rng(3).integers(3, 250, (2, 16)), jnp.int32)
        logits_fp = np.asarray(forward(params, config, tokens))
        logits_q = np.asarray(forward(quantized, config, tokens))
        drift = np.max(np.abs(logits_q - logits_fp))
        span = np.max(np.abs(logits_fp)) + 1e-9
        assert drift / span < 0.05, f"relative drift {drift / span:.4f}"

    def test_int8_weights_generation_functional(self):
        import jax
        import jax.numpy as jnp
        import numpy as np
        from aiko_services_tpu.models import (
            generate, init_params, quantize_weights_int8)

        config = self._config()
        params = init_params(config, jax.random.PRNGKey(2))
        quantized = quantize_weights_int8(params, config)
        prompt = jnp.asarray(
            np.random.default_rng(5).integers(3, 250, (2, 8)), jnp.int32)
        tokens, _ = generate(quantized, config, prompt, 8)
        assert tokens.shape == (2, 8)
        values = np.asarray(tokens)
        assert ((values >= 0) & (values < config.vocab_size)).all()

    def test_int8_weights_sharded_decode_matches_unsharded(self):
        import jax
        import jax.numpy as jnp
        import numpy as np
        from aiko_services_tpu.models import (
            generate, init_params, quantize_weights_int8,
            quantized_param_specs)
        from aiko_services_tpu.parallel import filter_specs, shard_pytree
        from aiko_services_tpu.parallel.mesh import create_mesh

        config = self._config()
        params = quantize_weights_int8(
            init_params(config, jax.random.PRNGKey(4)), config)
        prompt = jnp.asarray(
            np.random.default_rng(6).integers(3, 250, (4, 12)), jnp.int32)
        dense_tokens, _ = generate(params, config, prompt, 8)

        mesh = create_mesh({"data": 2, "fsdp": 2, "seq": 1, "model": 2})
        sharded = shard_pytree(
            params, mesh,
            filter_specs(quantized_param_specs(config), mesh))
        with jax.set_mesh(mesh):
            sharded_tokens, _ = generate(sharded, config, prompt, 8)
        np.testing.assert_array_equal(np.asarray(dense_tokens),
                                      np.asarray(sharded_tokens))

    def test_int8_weights_with_int8_kv_cache(self):
        import jax
        import jax.numpy as jnp
        import numpy as np
        from dataclasses import replace
        from aiko_services_tpu.models import (
            generate, init_params, quantize_weights_int8)

        config = self._config()
        config_q = replace(config, kv_dtype="int8")
        params = quantize_weights_int8(
            init_params(config, jax.random.PRNGKey(8)), config)
        prompt = jnp.asarray(
            np.random.default_rng(7).integers(3, 250, (2, 8)), jnp.int32)
        both_q, _ = generate(params, config_q, prompt, 8)
        weights_only, _ = generate(params, config, prompt, 8)
        # both quantizations compose: valid tokens, and the int8 cache's
        # rounding stays a PERTURBATION, not a derailment (exact
        # equality would be platform-fragile -- a near-tie argmax can
        # legitimately flip under different backend matmul numerics)
        values = np.asarray(both_q)
        assert values.shape == (2, 8)
        assert ((values >= 0) & (values < config.vocab_size)).all()
        agreement = float(np.mean(values == np.asarray(weights_only)))
        assert agreement >= 0.5, f"token agreement {agreement:.2f}"


class TestCachedPrefillThroughTheKernel:
    """A multi-token cached forward from the static position 0 attends
    over its own fresh K/V through flash_attention where the call's
    shape takes it (parallel/attention.py flash_attention_takes), and
    through the masked einsum over the cache buffer otherwise.  At test
    sizes the scores are far under what takes the kernel, so the tests
    steer by the module's threshold; the jits compiled per `config` get
    a second config that differs only in `max_seq_len`, which no
    computation here reads, so each arm has a program of its own."""
    # float32: blockwise against whole-row softmax; bf16: besides, the
    # two arms round h differently at every layer
    LOGITS_TOLERANCE = {"float32": 2e-5, "bfloat16": 5e-2}

    @staticmethod
    def _prefill(config, params, tokens, cache=None, pos=0):
        if cache is None:
            cache = init_cache(config, tokens.shape[0], max_len=48)
        return forward(params, config, tokens, cache=cache, pos=pos)

    @staticmethod
    def _tokens(shape, seed=3):
        return jax.random.randint(jax.random.PRNGKey(seed), shape, 0,
                                  CONFIG.vocab_size).astype(jnp.int32)

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_logits_to_tolerance_and_cache_written_alike(
            self, dtype, monkeypatch):
        config = dataclasses.replace(CONFIG, dtype=dtype)
        params = init_params(config, jax.random.PRNGKey(0))
        tokens = self._tokens((2, 40))
        want, want_cache = self._prefill(config, params, tokens)
        monkeypatch.setattr(attention, "_FLASH_MIN_SCORE_BYTES", 0)
        assert "pallas_call" in str(jax.make_jaxpr(
            lambda tokens: self._prefill(config, params, tokens))(tokens))
        got, got_cache = self._prefill(config, params, tokens)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want),
            atol=self.LOGITS_TOLERANCE[dtype], rtol=0)
        for name in want_cache:
            leaf = np.asarray(got_cache[name], np.float32)
            other = np.asarray(want_cache[name], np.float32)
            # K/V are written before the attention reads: the first
            # layer's are the same bits, a later layer's follow its
            # input, which the attention below it rounded differently
            np.testing.assert_array_equal(leaf[0], other[0], err_msg=name)
            np.testing.assert_allclose(
                leaf, other, atol=self.LOGITS_TOLERANCE[dtype], rtol=0,
                err_msg=name)
            assert not leaf[:, :, :, 40:].any(), name

    @pytest.mark.parametrize("case", ["int8_cache", "traced_pos",
                                      "one_token", "later_position"])
    def test_what_the_kernel_does_not_take_keeps_its_bits(
            self, case, monkeypatch):
        config = (dataclasses.replace(CONFIG, kv_dtype="int8")
                  if case == "int8_cache" else CONFIG)
        params = _params()
        tokens = self._tokens((2, 1 if case == "one_token" else 24))
        _, begun = self._prefill(config, params, tokens[:, :8])

        def run():
            if case == "traced_pos":
                return jax.jit(lambda pos: self._prefill(
                    config, params, tokens, pos=pos))(jnp.int32(0))
            if case == "later_position":
                return self._prefill(config, params, tokens[:, 8:],
                                     cache=begun, pos=8)
            return self._prefill(config, params, tokens)

        want, want_cache = run()
        monkeypatch.setattr(attention, "_FLASH_MIN_SCORE_BYTES", 0)
        got, got_cache = run()
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        for name in want_cache:
            np.testing.assert_array_equal(np.asarray(got_cache[name]),
                                          np.asarray(want_cache[name]))

    def test_generate_greedy_tokens_unchanged(self, monkeypatch):
        params = _params()
        prompt = self._tokens((2, 24), seed=5)
        want, _ = generate(params, CONFIG, prompt, max_new_tokens=8)
        monkeypatch.setattr(attention, "_FLASH_MIN_SCORE_BYTES", 0)
        got, _ = generate(
            params, dataclasses.replace(CONFIG, max_seq_len=65), prompt,
            max_new_tokens=8)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_paged_prefill_first_token_and_blocks_unchanged(
            self, monkeypatch):
        params = _params()
        block, true_len = 8, 29
        prompt = np.zeros((1, 32), np.int32)
        prompt[0, :true_len] = np.asarray(self._tokens((true_len,), seed=7))
        table_row = np.array([5, 2, 7, 1, 0, 0], np.int32)

        def run(config):
            pool = init_paged_pool(config, 9, block)
            return paged_prefill(params, config, pool, prompt, table_row,
                                 np.int32(true_len))

        want_pool, want_first = run(CONFIG)
        monkeypatch.setattr(attention, "_FLASH_MIN_SCORE_BYTES", 0)
        got_pool, got_first = run(
            dataclasses.replace(CONFIG, max_seq_len=65))
        assert int(got_first) == int(want_first)
        for name in want_pool:
            leaf, other = (np.asarray(got_pool[name]),
                           np.asarray(want_pool[name]))
            np.testing.assert_array_equal(leaf[0], other[0], err_msg=name)
            np.testing.assert_allclose(leaf, other, atol=2e-5, rtol=0)
            # only the four named blocks were written
            assert not leaf[:, [0, 3, 4, 6, 8]].any(), name


# case -> (config fields, what the contiguous cache's logits may differ
# by from the cache-less forward's).  float32 throughout.  dense and
# experts: blockwise softmax (the flash kernel) against the whole-row
# einsum.  int8: besides, every cached K/V is rounded to 8 bits once,
# which the cache-less forward never does.  The experts drop no token
# (capacity = the sequence), or what is dropped would follow the length
# a path happens to see; prefills dispatch by capacity, single tokens
# gather the chosen expert's weights.
STORE_CASES = {
    "dense": ({}, 2e-5),
    "int8_kv": ({"kv_dtype": "int8"}, 2e-2),
    "experts": ({"n_experts": 4, "moe_capacity_factor": 4.0}, 2e-5),
    # DeepSeek-V2's layer: latent attention (decompressed over the cache,
    # absorbed over the pool), routed + shared experts with nothing
    # dropped, a leading dense layer apart from the scanned stack
    "latent_routed": ({
        "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 8,
        "qk_rope_head_dim": 4, "v_head_dim": 8, "rope_factor": 40.0,
        "rope_original_max": 8, "rope_mscale": 0.707,
        "rope_mscale_all_dim": 0.707, "top_k": 2, "n_routed_experts": 8,
        "experts_held": (2, 6), "n_shared_experts": 1, "moe_d_ff": 16,
        "n_groups": 4, "topk_groups": 2, "routed_scaling": 4.0,
        "first_dense_layers": 1}, 2e-5),
}


@pytest.mark.parametrize("case", sorted(STORE_CASES))
def test_a_layer_is_the_same_layer_over_every_store(case):
    """One decoder layer, three KV stores: the same tokens through (a)
    the cache-less forward, (b) a prefill at position 0 and decode_steps
    over a contiguous cache, (c) paged_prefill and paged_decode_steps
    over a pool.  (b)'s logits agree with (a)'s to the case's tolerance
    at every position.  The paged steps hand out greedy tokens, no
    logits: each is the contiguous cache's own argmax, as
    tests/test_decode.py holds the engine to generate(), and the pool
    ends up holding what the cache holds."""
    fields, tolerance = STORE_CASES[case]
    config = dataclasses.replace(CONFIG, **fields)
    params = init_params(config, jax.random.PRNGKey(0))
    rows, prompt_len, length, block = 2, 8, 14, 4
    tokens = jax.random.randint(jax.random.PRNGKey(5), (rows, length), 0,
                                config.vocab_size).astype(jnp.int32)

    fresh = np.asarray(forward(params, config, tokens))

    cache = init_cache(config, rows, max_len=16)
    logits, cache = forward(params, config, tokens[:, :prompt_len],
                            cache=cache, pos=0)
    cached = [np.asarray(logits)]
    for position in range(prompt_len, length):
        _, logits, cache = decode_step(
            params, config, cache, tokens[:, position:position + 1],
            jnp.int32(position))
        cached.append(np.asarray(logits))
    cached = np.concatenate(cached, axis=1)
    np.testing.assert_allclose(cached, fresh, atol=tolerance, rtol=0)

    max_blocks = 16 // block
    tables = 1 + np.arange(rows * max_blocks, dtype=np.int32).reshape(
        rows, max_blocks)                    # block 0 is the trash block
    pool = init_paged_pool(config, 1 + rows * max_blocks, block)
    paged = np.zeros((rows, length), np.int32)
    for row in range(rows):
        # one executable: true_len is traced, so every position of the
        # prompt can be asked for its greedy token
        for true_len in range(1, prompt_len + 1):
            pool, first = paged_prefill(
                params, config, pool, tokens[row:row + 1, :prompt_len],
                tables[row], np.int32(true_len))
            paged[row, true_len - 1] = int(first)
    for position in range(prompt_len, length):
        # (routed experts hand their counts out third)
        pool, greedy, *_ = paged_decode_step(
            params, config, pool, tables,
            np.full((rows,), position, np.int32),
            tokens[:, position:position + 1],
            tables[:, position // block],
            np.full((rows,), position % block, np.int32))
        paged[:, position] = np.asarray(greedy)[:, 0]
    np.testing.assert_array_equal(paged, cached.argmax(axis=-1))

    for name, leaf in cache.items():
        # (layers, rows, H, 16, d) against the pool's blocks through the
        # tables: the same K/V to rounding (matmuls of other row counts,
        # blockwise attention below a later layer), where an int8 code
        # may differ by one step
        held = np.asarray(pool[name], np.float32)[:, tables]
        held = held.transpose(0, 1, 3, 2, 4, 5).reshape(leaf.shape)
        np.testing.assert_allclose(
            held[:, :, :, :length],
            np.asarray(leaf, np.float32)[:, :, :, :length],
            atol=1 if leaf.dtype == jnp.int8 else 2e-5, rtol=0,
            err_msg=name)


# -- a stored leaf is the published-orientation draw, re-laid out -------------
#
# The projections a decode step would otherwise copy transposed every layer
# (PR 33) are stored in the layout the step's matmul reads: wq and wk as
# (out, in), the latent wq_b as (nope + rope, H, q_rank), the
# published wkv_b as its keys' half (H, nope, rank) and its values' half
# (H, rank, v).  init_params draws each in the published orientation
# (in, out) from the key it always had and then transposes or splits:
# benchmark/reference/ draws the same numbers itself.

LATENT = {"q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 8,
          "qk_rope_head_dim": 4, "v_head_dim": 8}


def _as_stored(name: str, drawn, config: TransformerConfig):
    """The (in, out) draw of leaf `name` in the layout it is stored in."""
    if name in ("wq", "wk"):
        return drawn.T
    if name in ("wq_b", "wk_b", "wv_b"):
        nope = config.qk_nope_head_dim
        per_head = drawn.reshape(drawn.shape[0], config.n_heads, -1)
        return {"wq_b": per_head.transpose(2, 1, 0),
                "wk_b": per_head[..., :nope].transpose(1, 2, 0),
                "wv_b": per_head[..., nope:].transpose(1, 0, 2)}[name]
    return drawn


def _published_draws(config: TransformerConfig) -> dict:
    """leaf -> (index among a layer's keys, in, out) as init_params and
    the references draw it; wk_b and wv_b are halves of one draw."""
    d, heads, ff = config.d_model, config.n_heads, config.d_ff
    if not config.kv_lora_rank:
        hd, kv = config.head_dim, config.n_kv_heads
        return {"wq": (0, d, heads * hd), "wk": (1, d, kv * hd),
                "wv": (2, d, kv * hd), "wo": (3, heads * hd, d),
                "w_gate": (4, d, ff), "w_up": (5, d, ff),
                "w_down": (6, ff, d)}
    nope, rope = config.qk_nope_head_dim, config.qk_rope_head_dim
    rank, v = config.kv_lora_rank, config.v_head_dim
    wkv_b = (3, rank, heads * (nope + v))
    return {"wq_a": (0, d, config.q_lora_rank),
            "wq_b": (1, config.q_lora_rank, heads * (nope + rope)),
            "wkv_a": (2, d, rank + rope), "wk_b": wkv_b, "wv_b": wkv_b,
            "wo": (4, heads * v, d), "w_gate": (5, d, ff),
            "w_up": (6, d, ff), "w_down": (7, ff, d)}


@pytest.mark.parametrize("kind,name", [
    (kind, name) for kind, fields in (("dense", {}), ("latent", LATENT))
    for name in _published_draws(dataclasses.replace(CONFIG, **fields))])
def test_a_stored_projection_is_its_published_draw_exactly(kind, name):
    from aiko_services_tpu.models.layers import init_dense
    config = dataclasses.replace(CONFIG, **(LATENT if kind == "latent"
                                            else {}))
    seed = jax.random.PRNGKey(11)
    stored = init_params(config, seed)["layers"][name]["w"]
    index, rows, cols = _published_draws(config)[name]
    _, *layer_keys = jax.random.split(seed, config.n_layers + 1)
    for layer, layer_key in enumerate(layer_keys):
        keys = jax.random.split(layer_key, 12 if kind == "latent" else 8)
        drawn = init_dense(keys[index], rows, cols, config.jnp_dtype)["w"]
        np.testing.assert_array_equal(
            np.asarray(stored[layer]),
            np.asarray(_as_stored(name, drawn, config)))


def test_the_dense_model_is_the_plain_reference():
    """benchmark/reference/transformer.py draws its weights itself, in
    the published orientation: the stored leaves are those numbers (wq
    and wk transposed) and the forward pass gives its logits."""
    from benchmark.reference import transformer as reference
    published = {
        "vocab_size": CONFIG.vocab_size, "hidden_size": CONFIG.d_model,
        "num_hidden_layers": CONFIG.n_layers,
        "num_attention_heads": CONFIG.n_heads,
        "num_key_value_heads": CONFIG.n_kv_heads,
        "intermediate_size": CONFIG.d_ff, "head_dim": CONFIG.head_dim,
        "rope_theta": CONFIG.rope_theta, "rms_norm_eps": CONFIG.norm_eps,
        "torch_dtype": "float32"}
    shape = reference.shape_of(published)
    seed = 2 ** 31 + 77
    params = init_params(CONFIG, jax.random.PRNGKey(seed))
    _, layer_keys = reference._keys(shape, seed)
    for index, key in enumerate(layer_keys):
        for name, value in reference._layer_weights(key, shape).items():
            np.testing.assert_array_equal(
                np.asarray(_as_stored(name, value, CONFIG)),
                np.asarray(params["layers"][name]["w"][index]), name)
    tokens = np.random.default_rng(3).integers(
        1, CONFIG.vocab_size, (2, 40)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        ours = np.asarray(forward(params, CONFIG, tokens))
    theirs = np.asarray(reference.logits_at(
        shape, seed, tokens, np.tile(np.arange(40), (2, 1))))
    assert np.abs(ours - theirs).max() <= 1e-4 * np.abs(theirs).max()


@pytest.mark.parametrize("name", ["wq", "wk", "wv", "wo", "w_gate", "w_up",
                                  "w_down"])
def test_int8_scales_are_one_an_output_channel(name):
    """Whichever way a leaf is held, quantize_weights_int8 keeps one
    scale for each output channel (the largest magnitude among the
    inputs it sums, over 127), at the weight's rank with the contracted
    axis collapsed, and the scale's spec leaves that axis unsharded."""
    from aiko_services_tpu.models import (
        quantize_weights_int8, quantized_param_specs)
    params = _params()
    entry = quantize_weights_int8(params, CONFIG)["layers"][name]
    _, rows, cols = _published_draws(CONFIG)[name]
    held_out_in = name in ("wq", "wk")
    contracted = -1 if held_out_in else -2
    weight = np.asarray(params["layers"][name]["w"])
    assert weight.shape == ((CONFIG.n_layers, cols, rows) if held_out_in
                            else (CONFIG.n_layers, rows, cols))
    expected = np.abs(weight).max(axis=contracted, keepdims=True) / 127.0
    assert expected.size == CONFIG.n_layers * cols
    assert entry["w"].dtype == jnp.int8
    np.testing.assert_allclose(np.asarray(entry["w_scale"]), expected,
                               rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(entry["w"], np.float32) * expected, weight,
        atol=float(expected.max()) * 0.5 + 1e-7, rtol=0)
    specs = quantized_param_specs(CONFIG)["layers"][name]
    assert tuple(specs["w_scale"])[contracted] is None
    kept = [axis for axis in range(3) if axis != 3 + contracted]
    assert ([tuple(specs["w_scale"])[axis] for axis in kept]
            == [tuple(specs["w"])[axis] for axis in kept])


def test_a_meshed_projection_shards_the_axes_it_did():
    """(out, in) or (in, out), a column-parallel projection's outputs are
    split over "model" and its inputs over "fsdp"; the latent model's
    up-projections split by head."""
    dense, latent = param_specs(CONFIG)["layers"], param_specs(
        dataclasses.replace(CONFIG, **LATENT))["layers"]
    assert dense["wq"]["w"] == dense["wk"]["w"] == P(None, "model", "fsdp")
    assert dense["wv"]["w"] == P(None, "fsdp", "model")
    assert latent["wq_b"]["w"] == P(None, None, "model", None)
    assert latent["wk_b"]["w"] == latent["wv_b"]["w"] == P(
        None, "model", None, None)
    assert "wkv_b" not in latent
