# Parallelism layer tests: mesh construction, flash-attention kernel
# (interpreter mode on CPU), ring attention and Ulysses attention over the
# virtual 8-device mesh -- all checked against the plain-XLA oracle.

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aiko_services_tpu.parallel import (
    attention_reference, create_mesh, flash_attention, get_mesh,
    named_sharding, ring_attention, shard_pytree, ulysses_attention)


def _qkv(batch=1, heads=4, seq=64, dim=16, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (batch, heads, seq, dim)
    return tuple(jax.random.normal(key, shape, jnp.float32) for key in keys)


class TestMesh:
    def test_create_mesh_fill_axis(self):
        mesh = create_mesh({"data": -1, "model": 2})
        assert mesh.shape["model"] == 2
        assert mesh.shape["data"] == len(jax.devices()) // 2

    def test_axis_order_canonical(self):
        mesh = create_mesh({"model": 2, "data": 2, "seq": 2})
        assert tuple(mesh.axis_names) == ("data", "seq", "model")

    def test_get_mesh_cached(self):
        assert get_mesh({"data": -1}) is get_mesh({"data": -1})

    def test_bad_divisibility(self):
        with pytest.raises(ValueError):
            create_mesh({"data": -1, "model": 3})

    def test_shard_pytree(self):
        mesh = get_mesh({"data": -1})
        tree = {"w": jnp.zeros((8, 4)), "b": jnp.zeros((4,))}
        sharded = shard_pytree(tree, mesh, None)
        assert sharded["w"].sharding.is_fully_replicated

    def test_named_sharding_spec_coercion(self):
        mesh = get_mesh({"data": -1})
        sharding = named_sharding(mesh, ["data", None])
        assert sharding.spec == jax.sharding.PartitionSpec("data", None)


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference(self, causal):
        q, k, v = _qkv(seq=96)
        expected = attention_reference(q, k, v, causal=causal)
        actual = flash_attention(q, k, v, causal=causal, block_q=32,
                                 block_k=32)
        np.testing.assert_allclose(actual, expected, atol=2e-3, rtol=2e-3)

    def test_ragged_seq_padding(self):
        q, k, v = _qkv(seq=50)  # not a block multiple
        expected = attention_reference(q, k, v, causal=True)
        actual = flash_attention(q, k, v, causal=True, block_q=16,
                                 block_k=16)
        np.testing.assert_allclose(actual, expected, atol=2e-3, rtol=2e-3)

    def test_cross_attention_kv_longer(self):
        q, _, _ = _qkv(seq=32)
        _, k, v = _qkv(seq=80, seed=1)
        expected = attention_reference(q, k, v, causal=False)
        actual = flash_attention(q, k, v, block_q=16, block_k=16)
        np.testing.assert_allclose(actual, expected, atol=2e-3, rtol=2e-3)


def _grouped_qkv(repeats, seq, dtype, kv_heads=2, dim=16, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(keys[0], (2, kv_heads * repeats, seq, dim), dtype)
    k, v = (jax.random.normal(key, (2, kv_heads, seq, dim), dtype)
            for key in keys[1:])
    return q, k, v


def _grouped_reference(q, k, v, causal=True):
    """The float32 oracle over K/V repeated as repeat_kv lays them out:
    KV head g serves query heads g * repeats onward."""
    repeats = q.shape[1] // k.shape[1]
    return attention_reference(
        q.astype(jnp.float32),
        jnp.repeat(k.astype(jnp.float32), repeats, axis=1),
        jnp.repeat(v.astype(jnp.float32), repeats, axis=1), causal=causal)


class TestFlashGrouped:
    """The forward kernel reads K/V grouped -- a KV head's tile serves
    its `repeats` query heads, no repeated K/V exists -- and multiplies
    in the input dtype.  Interpreted on the CPU: short L, small tiles."""
    # bf16: operands and p rounded to 8 bits before the MXU, as in the
    # einsum this replaces; float32 keeps float32 dots
    TOLERANCE = {"float32": 2e-5, "bfloat16": 2e-2}

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("seq", [64, 50])       # on and off a tile
    @pytest.mark.parametrize("repeats", [1, 4])
    def test_kernel_matches_reference(self, repeats, seq, dtype):
        q, k, v = _grouped_qkv(repeats, seq, jnp.dtype(dtype),
                               seed=repeats + seq)
        expected = np.asarray(_grouped_reference(q, k, v))
        for tiles in ({"block_q": 16, "block_k": 32}, {}):
            actual = flash_attention(q, k, v, causal=True, **tiles)
            assert actual.shape == q.shape and actual.dtype == q.dtype
            np.testing.assert_allclose(
                np.asarray(actual, np.float32), expected,
                atol=self.TOLERANCE[dtype], rtol=0, err_msg=str(tiles))

    def test_operands_reach_the_dots_in_their_own_dtype(self):
        q, k, v = _grouped_qkv(4, 32, jnp.bfloat16)
        closed = jax.make_jaxpr(
            lambda q, k, v: flash_attention(q, k, v, causal=True))(q, k, v)

        def dots(jaxpr):
            for eqn in jaxpr.eqns:
                if eqn.primitive.name == "dot_general":
                    yield eqn
                for value in eqn.params.values():
                    # a pallas_call's body, a pl.when's branches
                    for inner in (value if isinstance(value, tuple)
                                  else (value,)):
                        inner = getattr(inner, "jaxpr", inner)
                        if hasattr(inner, "eqns"):
                            yield from dots(inner)

        found = list(dots(closed.jaxpr))
        assert len(found) >= 2 * 4          # QK^T and PV, a query head
        for eqn in found:
            # bf16 x bf16 on the MXU, accumulated in float32: no operand
            # is widened on its way in
            assert [var.aval.dtype for var in eqn.invars] == [
                jnp.bfloat16, jnp.bfloat16], eqn
            assert eqn.outvars[0].aval.dtype == jnp.float32, eqn

    @pytest.mark.parametrize("length,largest,block", [
        (16, 1024, 16), (37, 1024, 37), (128, 1024, 128), (250, 1024, 256),
        (1024, 1024, 1024), (1100, 1024, 640), (4096, 1024, 1024),
        (3000, 1024, 1024), (4096, 512, 512)])
    def test_tiles_follow_the_length(self, length, largest, block):
        from aiko_services_tpu.parallel.attention import _flash_block
        assert _flash_block(length, largest) == block

    @pytest.mark.parametrize("batch,heads,length,dtype,cache,takes", [
        (1, 32, 4096, "bfloat16", "bfloat16", True),    # lm.longprompt
        (1, 32, 1024, "bfloat16", "bfloat16", True),
        (1, 32, 512, "bfloat16", "bfloat16", False),    # scores fit VMEM
        (32, 32, 16, "bfloat16", "bfloat16", False),    # the graph's LM
        (32, 32, 256, "bfloat16", "bfloat16", True),
        (1, 32, 4096, "float32", "float32", True),
        (1, 32, 4096, "bfloat16", "int8", False),       # quantised cache
        (1, 32, 4096, "int8", "int8", False),
        (1, 32, 4096, "float32", "bfloat16", False)])
    def test_what_takes_the_kernel_is_decided_by_shape_and_dtype(
            self, batch, heads, length, dtype, cache, takes):
        from aiko_services_tpu.parallel.attention import (
            flash_attention_takes)
        assert flash_attention_takes(batch, heads, length, dtype,
                                     cache) is takes

    @pytest.mark.parametrize("repeats", [1, 4])
    def test_grouped_grad_parity(self, repeats):
        q, k, v = _grouped_qkv(repeats, 50, jnp.float32, seed=5)

        def loss(attend):
            return lambda q, k, v: jnp.sum(attend(q, k, v) ** 2)

        got = jax.grad(loss(lambda q, k, v: flash_attention(
            q, k, v, causal=True, block_q=16, block_k=32)),
            argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(loss(_grouped_reference), argnums=(0, 1, 2))(q, k, v)
        for actual, expected, name in zip(got, want, ("dq", "dk", "dv")):
            assert actual.shape == expected.shape, name
            np.testing.assert_allclose(
                np.asarray(actual), np.asarray(expected),
                atol=5e-3, rtol=5e-3, err_msg=name)

    def test_heads_must_group_evenly(self):
        q, _, _ = _grouped_qkv(3, 16, jnp.float32)         # 6 query heads
        _, k, v = _grouped_qkv(1, 16, jnp.float32, kv_heads=4)
        with pytest.raises(ValueError, match="not a multiple"):
            flash_attention(q, k, v, causal=True)


class TestSequenceParallel:
    @pytest.mark.parametrize("causal", [False, True])
    def test_ring_attention(self, causal):
        mesh = create_mesh({"seq": 8})
        q, k, v = _qkv(batch=2, heads=2, seq=64, dim=8)
        expected = attention_reference(q, k, v, causal=causal)
        actual = ring_attention(q, k, v, mesh, causal=causal)
        np.testing.assert_allclose(actual, expected, atol=2e-3, rtol=2e-3)

    def test_ulysses_attention(self):
        mesh = create_mesh({"seq": 8})
        q, k, v = _qkv(batch=1, heads=8, seq=64, dim=8)
        expected = attention_reference(q, k, v, causal=True)
        actual = ulysses_attention(q, k, v, mesh, causal=True)
        np.testing.assert_allclose(actual, expected, atol=2e-3, rtol=2e-3)


class TestFlashBackward:
    """Pallas backward kernels (dq; dk/dv) vs jax.grad of the XLA oracle
    (VERDICT round 1 item 3)."""

    @pytest.mark.parametrize("causal", [False, True])
    def test_grad_parity(self, causal):
        q, k, v = _qkv(seq=96)

        def loss_flash(q, k, v):
            out = flash_attention(q, k, v, causal=causal, block_q=32,
                                  block_k=32)
            return jnp.sum(out * jnp.cos(out.astype(jnp.float32)))

        def loss_ref(q, k, v):
            out = attention_reference(q, k, v, causal=causal)
            return jnp.sum(out * jnp.cos(out.astype(jnp.float32)))

        grads_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        grads_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for actual, expected, name in zip(grads_flash, grads_ref,
                                          ("dq", "dk", "dv")):
            np.testing.assert_allclose(
                np.asarray(actual), np.asarray(expected),
                atol=5e-3, rtol=5e-3, err_msg=name)

    def test_grad_parity_ragged_and_cross(self):
        # q/k lengths differ and are not block multiples
        q, _, _ = _qkv(seq=50)
        _, k, v = _qkv(seq=70, seed=3)

        def loss(fn):
            def inner(q, k, v):
                return jnp.sum(fn(q, k, v) ** 2)
            return inner

        flash = loss(lambda q, k, v: flash_attention(
            q, k, v, causal=True, block_q=16, block_k=16))
        ref = loss(lambda q, k, v: attention_reference(q, k, v,
                                                       causal=True))
        got = jax.grad(flash, argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(ref, argnums=(0, 1, 2))(q, k, v)
        for actual, expected in zip(got, want):
            np.testing.assert_allclose(np.asarray(actual),
                                       np.asarray(expected),
                                       atol=5e-3, rtol=5e-3)

    def test_grad_parity_seq_4k(self):
        # the VERDICT done-criterion sequence length, batch/head-reduced
        q, k, v = _qkv(batch=1, heads=1, seq=4096, dim=16, seed=7)

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=True) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(attention_reference(q, k, v, causal=True) ** 2)

        got = jax.grad(loss_flash)(q, k, v)
        want = jax.grad(loss_ref)(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-2, rtol=2e-2)

    def test_backward_memory_is_blockwise(self):
        # the jaxpr of the flash grad must contain no (L, L) intermediate:
        # residuals are q/k/v/o (L, D) + lse (L,) -- O(L x block) peak
        seq = 1024
        q, k, v = _qkv(batch=1, heads=1, seq=seq, dim=16, seed=1)

        def loss(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=True) ** 2)

        jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)

        def max_intermediate(jaxpr):
            worst = 0
            for eqn in jaxpr.eqns:
                for var in eqn.outvars:
                    shape = getattr(var.aval, "shape", ())
                    size = 1
                    for dim in shape:
                        size *= dim
                    worst = max(worst, size)
                for sub in eqn.params.values():
                    if hasattr(sub, "jaxpr"):
                        worst = max(worst, max_intermediate(sub.jaxpr))
            return worst

        worst = max_intermediate(jaxpr.jaxpr)
        # seq*seq would be 1M elements; blockwise peak is O(seq x 128)
        assert worst < seq * seq, (
            f"O(L^2) intermediate found: {worst} elements")
        assert worst <= seq * 256


class TestRingAttentionScale:
    """VERDICT round-1 item 4: flash-kernel inner hops, causal hop
    skipping, ring gradients, and sequence-parallel decode."""

    def test_causal_hops_are_skipped(self):
        # device i executes i+1 of the n hops under causal masking:
        # sum over 8 devices = 36 executed hops, vs 64 for dense
        from aiko_services_tpu.parallel import attention as attn_mod
        mesh = create_mesh({"seq": 8})
        q, k, v = _qkv(batch=1, heads=2, seq=64, dim=8)
        executed = []
        attn_mod._RING_HOP_CALLBACK = lambda step: executed.append(
            int(step))
        try:
            out = ring_attention(q, k, v, mesh, causal=True)
            jax.block_until_ready(out)
        finally:
            attn_mod._RING_HOP_CALLBACK = None
        n = mesh.shape["seq"]
        assert len(executed) == n * (n + 1) // 2, (
            f"expected {n * (n + 1) // 2} executed hops, "
            f"got {len(executed)}")
        expected = attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(out, expected, atol=2e-3, rtol=2e-3)

    @pytest.mark.parametrize("causal", [False, True])
    def test_ring_grad_parity(self, causal):
        mesh = create_mesh({"seq": 4}, devices=jax.devices()[:4])
        q, k, v = _qkv(batch=1, heads=2, seq=64, dim=8, seed=11)

        def loss_ring(q, k, v):
            out = ring_attention(q, k, v, mesh, causal=causal)
            return jnp.sum(out * jnp.cos(out.astype(jnp.float32)))

        def loss_ref(q, k, v):
            out = attention_reference(q, k, v, causal=causal)
            return jnp.sum(out * jnp.cos(out.astype(jnp.float32)))

        got = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for actual, expected, name in zip(got, want, ("dq", "dk", "dv")):
            np.testing.assert_allclose(
                np.asarray(actual), np.asarray(expected),
                atol=5e-3, rtol=5e-3, err_msg=name)

    @pytest.mark.parametrize("q_len", [1, 4])
    def test_sp_decode_attention_parity(self, q_len):
        from aiko_services_tpu.parallel import sp_decode_attention
        mesh = create_mesh({"seq": 8})
        cache_len, pos = 64, 37
        _, k, v = _qkv(batch=2, heads=2, seq=cache_len, dim=8, seed=5)
        q = jax.random.normal(jax.random.PRNGKey(9),
                              (2, 2, q_len, 8), jnp.float32)
        got = sp_decode_attention(q, k, v, pos, mesh=mesh)
        # oracle: dense masked attention over positions <= pos(+i)
        want = attention_reference(
            q, k[:, :, :pos + q_len], v[:, :, :pos + q_len],
            causal=True, q_offset=0)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-3, rtol=2e-3)

    def test_sp_decode_gqa_expands_in_shard(self):
        # kv cache stays at n_kv_heads through the shard_map boundary;
        # GQA expansion happens on the local shard only
        from aiko_services_tpu.parallel import sp_decode_attention
        mesh = create_mesh({"seq": 8})
        _, k, v = _qkv(batch=1, heads=2, seq=32, dim=8, seed=8)
        q = jax.random.normal(jax.random.PRNGKey(3), (1, 4, 1, 8),
                              jnp.float32)
        got = sp_decode_attention(q, k, v, 21, mesh=mesh)
        k_rep = jnp.repeat(k, 2, axis=1)
        v_rep = jnp.repeat(v, 2, axis=1)
        want = attention_reference(q, k_rep[:, :, :22], v_rep[:, :, :22],
                                   causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-3, rtol=2e-3)

    def test_sp_decode_collective_count(self):
        # decode steps are collective-LATENCY bound (Lq=1 payloads are
        # tiny): the merge must cost exactly one pmax + one fused psum,
        # and the cache must cross the shard_map boundary un-expanded
        # (no jnp.repeat of KV in the jaxpr)
        from aiko_services_tpu.parallel import sp_decode_attention
        mesh = create_mesh({"seq": 8})
        _, k, v = _qkv(batch=1, heads=2, seq=32, dim=8, seed=8)
        q = jax.random.normal(jax.random.PRNGKey(3), (1, 4, 1, 8),
                              jnp.float32)
        jaxpr = str(jax.make_jaxpr(
            lambda q, k, v: sp_decode_attention(q, k, v, 21, mesh=mesh)
        )(q, k, v))
        assert jaxpr.count("psum(") + jaxpr.count("psum[") == 1, jaxpr
        assert jaxpr.count("pmax(") + jaxpr.count("pmax[") == 1, jaxpr

    def test_sp_decode_composes_with_tp(self):
        from aiko_services_tpu.parallel import sp_decode_attention
        mesh = create_mesh({"data": 2, "seq": 2, "model": 2})
        _, k, v = _qkv(batch=2, heads=2, seq=32, dim=8, seed=6)
        q = jax.random.normal(jax.random.PRNGKey(2), (2, 2, 1, 8),
                              jnp.float32)
        got = sp_decode_attention(q, k, v, 19, mesh=mesh)
        want = attention_reference(q, k[:, :, :20], v[:, :, :20],
                                   causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-3, rtol=2e-3)


class TestShardPytreeSemantics:
    """shard_pytree spec-tree semantics: prefix broadcast (the old
    device_put behavior), partial trees (missing leaves replicate), and
    per-item structural lists."""

    def _mesh(self):
        from aiko_services_tpu.parallel.mesh import create_mesh
        return create_mesh({"data": 2, "model": 4})

    def test_axis_list_broadcasts_over_collection(self):
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from aiko_services_tpu.parallel import shard_pytree
        mesh = self._mesh()
        tree = {"a": [jnp.zeros((4, 8)), jnp.zeros((4, 8))]}
        out = shard_pytree(tree, mesh, {"a": ["data", None]})
        for leaf in out["a"]:
            assert leaf.sharding.spec == P("data", None), (
                leaf.sharding.spec)

    def test_partial_tree_missing_leaves_replicate(self):
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from aiko_services_tpu.parallel import shard_pytree
        mesh = self._mesh()
        tree = {"w": jnp.zeros((4, 8)), "b": jnp.zeros((8,))}
        out = shard_pytree(tree, mesh, {"w": P(None, "model")})
        assert out["w"].sharding.spec == P(None, "model")
        assert out["b"].sharding.is_fully_replicated

    def test_per_item_structural_list(self):
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from aiko_services_tpu.parallel import shard_pytree
        mesh = self._mesh()
        tree = {"stages": [{"w": jnp.zeros((4, 8))},
                           {"w": jnp.zeros((8, 4))}]}
        out = shard_pytree(tree, mesh, {"stages": [
            {"w": P("data", None)}, {"w": P(None, "data")}]})
        assert out["stages"][0]["w"].sharding.spec == P("data", None)
        assert out["stages"][1]["w"].sharding.spec == P(None, "data")

    def test_spec_broadcast_through_subtree(self):
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from aiko_services_tpu.parallel import shard_pytree
        mesh = self._mesh()
        tree = {"block": {"w1": jnp.zeros((4, 8)),
                          "w2": jnp.zeros((4, 8))}}
        out = shard_pytree(tree, mesh, {"block": P("data", None)})
        assert out["block"]["w1"].sharding.spec == P("data", None)
        assert out["block"]["w2"].sharding.spec == P("data", None)

    def test_namedtuple_rebuilt_with_positional_fields(self):
        # optax opt_states are namedtuples: type(node)(iterable) raises
        # TypeError for them, the rebuild must splat positionally
        import collections
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from aiko_services_tpu.parallel import shard_pytree
        State = collections.namedtuple("State", ["mu", "nu"])
        mesh = self._mesh()
        tree = {"opt": State(mu=jnp.zeros((4, 8)), nu=jnp.zeros((4, 8)))}
        out = shard_pytree(tree, mesh, {"opt": P("data", None)})
        assert isinstance(out["opt"], State)
        assert out["opt"].mu.sharding.spec == P("data", None)


class TestFlashMultiBlock:
    """Parity BEYOND one kernel block (block_q = block_k = 128): the
    grid loops and causal block-skipping only engage at seq > 128, and
    the long-context claim rests on them."""

    def _naive(self, q, k, v, causal):
        import jax.numpy as jnp
        import numpy as np
        scale = 1.0 / np.sqrt(q.shape[-1])
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
        if causal:
            q_len, k_len = q.shape[2], k.shape[2]
            mask = (jnp.arange(k_len)[None, :]
                    <= (jnp.arange(q_len)[:, None]
                        + (k_len - q_len)))
            scores = jnp.where(mask[None, None], scores, -jnp.inf)
        weights = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", weights, v)

    @pytest.mark.parametrize("causal", [False, True])
    def test_multi_block_parity_512(self, causal):
        import numpy as np
        from aiko_services_tpu.parallel.attention import flash_attention
        q, k, v = _qkv(batch=1, heads=2, seq=512, dim=32, seed=11)
        actual = np.asarray(flash_attention(q, k, v, causal=causal))
        expected = np.asarray(self._naive(q, k, v, causal))
        np.testing.assert_allclose(actual, expected, atol=2e-3, rtol=2e-3)

    def test_multi_block_ragged_641(self):
        import numpy as np
        from aiko_services_tpu.parallel.attention import flash_attention
        # 641 = 5 blocks + 1 row: exercises the padded tail block
        q, k, v = _qkv(batch=1, heads=2, seq=641, dim=32, seed=12)
        actual = np.asarray(flash_attention(q, k, v, causal=True))
        expected = np.asarray(self._naive(q, k, v, True))
        np.testing.assert_allclose(actual, expected, atol=2e-3, rtol=2e-3)


# -- a whole prefill's live length (PR 40) -----------------------------------

# the three shapes the cells run, at a length the interpreter affords:
# (query heads, K/V heads, width to score over, width to carry)
LIVE_SHAPES = {
    "dense_32q_8kv": (32, 8, 128, 128),       # mistral7b_l16
    "latent_128_heads": (128, 128, 256, 128),  # deepseek_v2_ep4_l5
    "hybrid_20q_1kv": (20, 1, 128, 128),      # jamba2_3b
}
LIVE_LENGTH, LIVE_BLOCK = 64, 16
_LIVE_RAN: dict = {}


def pallas_calls(jaxpr):
    """Every pallas_call equation of a jaxpr, those inside its loops,
    branches and jitted calls among them."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for value in eqn.params.values():
            for inner in (value if isinstance(value, tuple) else (value,)):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from pallas_calls(inner)


def _live_case(shape: str):
    """(q, k, v, the jitted live call, the oracle's float32 output) of
    `shape`, made and traced once."""
    if shape not in _LIVE_RAN:
        heads, kv_heads, width, value_width = LIVE_SHAPES[shape]
        keys = jax.random.split(jax.random.PRNGKey(len(shape)), 3)
        q = jax.random.normal(keys[0], (1, heads, LIVE_LENGTH, width))
        k = jax.random.normal(keys[1], (1, kv_heads, LIVE_LENGTH, width))
        v = jax.random.normal(keys[2],
                              (1, kv_heads, LIVE_LENGTH, value_width))
        repeats = heads // kv_heads
        expected = np.asarray(attention_reference(
            q, jnp.repeat(k, repeats, axis=1), jnp.repeat(v, repeats, axis=1),
            causal=True))
        call = jax.jit(lambda q, k, v, live: flash_attention(
            q, k, v, causal=True, block_q=LIVE_BLOCK, block_k=2 * LIVE_BLOCK,
            live=live))
        _LIVE_RAN[shape] = q, k, v, call, expected
    return _LIVE_RAN[shape]


class TestFlashLive:
    """flash_attention told a whole prefill's live length: the rows below
    it are the oracle's, every query block from the first dead one on is
    exactly zero (written, not left), and `live` is traced, so one
    program serves every length."""

    @pytest.mark.parametrize("live", [
        1, LIVE_BLOCK - 1, LIVE_BLOCK, LIVE_BLOCK + 1, LIVE_LENGTH - 1,
        LIVE_LENGTH])
    @pytest.mark.parametrize("shape", sorted(LIVE_SHAPES))
    def test_live_rows_are_the_oracles_and_dead_blocks_zero(self, shape,
                                                            live):
        q, k, v, call, expected = _live_case(shape)
        actual = np.asarray(call(q, k, v, np.int32(live)))
        assert actual.shape == expected.shape
        np.testing.assert_allclose(actual[:, :, :live],
                                   expected[:, :, :live], atol=2e-5, rtol=0)
        dead = -(-live // LIVE_BLOCK) * LIVE_BLOCK
        assert not actual[:, :, dead:].any()
        assert call._cache_size() == 1

    def test_a_live_length_a_batch_row(self):
        q, k, v = _grouped_qkv(4, 64, jnp.float32, seed=3)     # batch 2
        expected = np.asarray(_grouped_reference(q, k, v))
        actual = np.asarray(flash_attention(
            q, k, v, causal=True, block_q=16, block_k=16,
            live=np.array([5, 40], np.int32)))
        for row, (live, dead) in enumerate([(5, 16), (40, 48)]):
            np.testing.assert_allclose(actual[row, :, :live],
                                       expected[row, :, :live], atol=2e-5,
                                       rtol=0)
            assert not actual[row, :, dead:].any()

    def test_under_an_ambient_mesh_a_shard_is_told_its_own_rows(self):
        """The kernel runs in a shard_map under an ambient mesh (batch
        over "data", K/V heads over "model"): the live lengths ride it by
        batch row."""
        q, k, v = _grouped_qkv(2, 64, jnp.float32, kv_heads=4, seed=4)
        expected = np.asarray(_grouped_reference(q, k, v))
        told = jax.jit(lambda q, k, v, live: flash_attention(
            q, k, v, causal=True, block_q=16, block_k=16, live=live))
        live = np.array([40, 5], np.int32)                   # batch 2
        with jax.set_mesh(create_mesh({"data": 2, "model": 4})):
            assert "shard_map" in str(jax.make_jaxpr(told)(q, k, v, live))
            actual = np.asarray(told(q, k, v, live))
        for row, (rows, dead) in enumerate([(40, 48), (5, 16)]):
            np.testing.assert_allclose(actual[row, :, :rows],
                                       expected[row, :, :rows], atol=2e-5,
                                       rtol=0)
            assert not actual[row, :, dead:].any()

    @pytest.mark.parametrize("what", ["not_causal", "q_offset",
                                      "more_keys", "grad"])
    def test_what_is_no_whole_prefill_raises(self, what):
        q, k, v = _qkv(seq=32)
        live = jnp.int32(7)
        with pytest.raises(ValueError, match="live length"):
            if what == "not_causal":
                flash_attention(q, k, v, live=live)
            elif what == "q_offset":
                flash_attention(q, k, v, causal=True, q_offset=4, live=live)
            elif what == "more_keys":
                flash_attention(q[:, :, :16], k, v, causal=True, live=live)
            else:
                jax.grad(lambda q: flash_attention(
                    q, k, v, causal=True, live=live).sum())(q)

    def test_without_a_live_length_the_call_lowers_to_what_it_did(self):
        """The operand, its grid spec and the index maps' terms exist
        only in the trace that was given a `live`: without one the call
        has no scalar-prefetch operand and lowers to the text it lowered
        to at PR 39 (whisper's attention, training, the ring's hops),
        operation for operation; with one the call is another."""
        import collections
        import hashlib
        import re
        q = jax.ShapeDtypeStruct((1, 8, 256, 128), jnp.bfloat16)
        k = jax.ShapeDtypeStruct((1, 2, 256, 128), jnp.bfloat16)

        def plain(q, k, v):
            return flash_attention(q, k, v, causal=True, block_q=64,
                                   block_k=128)

        def told(q, k, v, live):
            return flash_attention(q, k, v, causal=True, block_q=64,
                                   block_k=128, live=live)

        live = jax.ShapeDtypeStruct((), jnp.int32)
        (call,) = pallas_calls(jax.make_jaxpr(plain)(q, k, k).jaxpr)
        assert call.params["grid_mapping"].num_index_operands == 0
        assert len(call.invars) == 3
        (call,) = pallas_calls(jax.make_jaxpr(told)(q, k, k, live).jaxpr)
        assert call.params["grid_mapping"].num_index_operands == 1
        assert len(call.invars) == 4

        text = jax.jit(plain).lower(q, k, k).as_text()
        operations = collections.Counter(re.findall(r"stablehlo\.\w+", text))
        # read on the parent commit (c686836), the interpreted kernel
        assert sum(operations.values()) == 2897
        assert (operations["stablehlo.while"],
                operations["stablehlo.dot_general"],
                operations["stablehlo.minimum"],
                operations["stablehlo.compare"]) == (1, 16, 2, 310)
        assert hashlib.sha256(text.encode()).hexdigest() == (
            "2366e65241395d1e7ec6605d0a0cbe3c"
            "9cda40bb649e680bf64da90a662de4af")
        assert jax.jit(told).lower(q, k, k, live).as_text() != text
