# Warm KV failover (ISSUE 13): incremental decode-state checkpointing
# (decode/checkpoint.py), DecodeEngine.restore_request, the AIKO409
# grammar, gateway restore hints + recovery-storm pacing, the per-peer
# transfer circuit breaker, and the seeded transfer_stall fault point.
#
# The acceptance invariant everywhere: a stream restored from a
# checkpoint is BIT-IDENTICAL to an uncrashed run (greedy determinism
# re-decodes the post-snapshot tail), streamed token offsets stay
# gapless, and EVERY degraded path -- dead keeper, stale snapshot,
# block-size mismatch, open circuit, stalled transfer -- falls back to
# the existing replay re-prefill, never losing a frame.

import json
import queue
import time

import numpy as np
import pytest

import jax

from aiko_services_tpu import faults as faults_module
from aiko_services_tpu.decode import (
    CheckpointKeeper, CheckpointPolicy, DecodeCheckpointer,
    DecodeEngine, PrefillEngine, register_keeper, reset_keepers)
from aiko_services_tpu.models import (
    TransformerConfig, generate, init_params)
from aiko_services_tpu.observe.metrics import get_registry
from aiko_services_tpu.pipeline import create_pipeline
from aiko_services_tpu.pipeline.transfer import (
    TransferError, fetch_many, get_transfer_server, reset_circuits,
    reset_transfer_server)
from aiko_services_tpu.runtime import Process
from aiko_services_tpu.serve import Gateway
from aiko_services_tpu.transport import reset_brokers
from aiko_services_tpu.utils import parse

from helpers import wait_for

ELEMENTS = "aiko_services_tpu.elements"

TINY = dict(vocab_size=64, n_layers=2, n_heads=2, n_kv_heads=2,
            d_model=32, d_ff=64, max_seq_len=64, dtype="float32")
# DeepSeek-V2's layer on TINY: the pool is one leaf of latent rows
LATENT = dict(q_lora_rank=16, kv_lora_rank=16, qk_nope_head_dim=8,
              qk_rope_head_dim=4, v_head_dim=8, top_k=2,
              n_routed_experts=4, n_shared_experts=1, moe_d_ff=16,
              n_groups=2, topk_groups=1, routed_scaling=2.0,
              first_dense_layers=1)


@pytest.fixture(autouse=True)
def clean(monkeypatch):
    reset_brokers()
    reset_keepers()
    reset_circuits()
    faults_module.reset_injector()
    yield
    reset_brokers()
    reset_keepers()
    reset_circuits()
    faults_module.reset_injector()


@pytest.fixture(scope="module")
def tiny_model():
    config = TransformerConfig(**TINY)
    return init_params(config, jax.random.PRNGKey(0)), config


def reference(params, config, prompt, max_new):
    out, _ = generate(params, config, np.asarray(prompt)[None],
                      max_new_tokens=max_new)
    return np.asarray(out)[0]


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


def run_with_checkpoints(params, config, prompt, max_new, *,
                         spec, steps, keeper=None):
    """Run one request on a checkpointed engine for `steps` engine
    ticks; returns (engine, checkpointer, keeper, emitted)."""
    keeper = keeper or CheckpointKeeper("k1")
    policy = CheckpointPolicy.parse(spec)
    engine = DecodeEngine(params, config, decode_slots=2,
                          kv_block_size=8)
    checkpointer = DecodeCheckpointer(engine, policy, keeper=keeper)
    engine.submit("r", prompt, max_new)
    emitted = []
    for _ in range(steps):
        report = engine.step()
        emitted.extend((offset, token) for _rid, offset, token
                       in report.emitted)
        checkpointer.tick()
    assert keeper.flush()
    return engine, checkpointer, keeper, emitted


# -- the checkpointer: incremental deltas, lag bound -------------------------


class TestCheckpointer:
    def test_ships_incremental_deltas(self, tiny_model):
        """KV is append-only: after the first full snapshot, later
        snapshots re-ship only the partial last block and anything
        after it -- never the whole prompt again."""
        params, config = tiny_model
        prompt = np.arange(1, 10, dtype=np.int32)  # 9 tokens, 2 blocks
        keeper = CheckpointKeeper("k1")
        shipped = []
        original = keeper.store

        def spy(snapshot):
            shipped.append((snapshot["delta_from"],
                            len(snapshot["kv_blocks"]),
                            snapshot["blocks_total"]))
            original(snapshot)

        keeper.store = spy
        engine, checkpointer, keeper, _ = run_with_checkpoints(
            params, config, prompt, 14,
            spec="checkpoint_every=2;max_checkpoint_lag=32;keeper=k1",
            steps=10, keeper=keeper)
        assert len(shipped) >= 3
        first_from, first_count, first_total = shipped[0]
        assert first_from == 0 and first_count == first_total
        for delta_from, count, total in shipped[1:]:
            assert delta_from > 0, "a later snapshot re-shipped block 0"
            assert count == total - delta_from
        assert checkpointer.counters["checkpoints"] == len(shipped)
        assert checkpointer.counters["checkpoint_bytes"] > 0
        assert keeper.kept_blocks("r") == shipped[-1][2]

    def test_max_checkpoint_lag_forces_snapshots(self, tiny_model):
        """With a glacial checkpoint_every, max_checkpoint_lag still
        bounds how many tokens any crash can force re-decoding."""
        params, config = tiny_model
        prompt = np.arange(1, 6, dtype=np.int32)
        keeper = CheckpointKeeper("k1")
        policy = CheckpointPolicy.parse(
            "checkpoint_every=10000;max_checkpoint_lag=3;keeper=k1")
        engine = DecodeEngine(params, config, decode_slots=1,
                              kv_block_size=8)
        checkpointer = DecodeCheckpointer(engine, policy, keeper=keeper)
        engine.submit("r", prompt, 12)
        while engine.has_work():
            engine.step()
            checkpointer.tick()
            request = (engine.slots[0].request
                       if engine.slots[0] is not None else None)
            if request is not None:
                entry = checkpointer._state.get("r")
                lag = len(request.generated) - (entry["gen"]
                                                if entry else 0)
                assert lag <= 3, f"crash lag {lag} exceeds the bound"
        assert checkpointer.counters["checkpoints"] >= 3

    def test_lost_delta_invalidates_instead_of_corrupting(
            self, tiny_model):
        """A delta that fails to ingest (dead producer, expired keys)
        leaves a SEQ GAP: the keeper must null the stale region so
        restore degrades to a re-prefill -- never silently serve the
        old partial block as if it were current (the bit-identity
        guarantee)."""
        params, config = tiny_model
        prompt = np.arange(1, 10, dtype=np.int32)
        keeper = CheckpointKeeper("k1")
        dropped = {"count": 0}
        original = keeper.store

        def lossy(snapshot):
            # swallow the SECOND delta, as a failed fetch would
            if snapshot["seq"] == 1:
                dropped["count"] += 1
                return
            original(snapshot)

        keeper.store = lossy
        # checkpoint_every=4 with block_size=8: the DROPPED delta is
        # the one that completes block 1 (positions 12->16), and the
        # next delta starts at block 2 -- so block 1 on the keeper is
        # a stale partial copy unless the seq gap invalidates it
        engine, checkpointer, keeper, _ = run_with_checkpoints(
            params, config, prompt, 16,
            spec="checkpoint_every=4;max_checkpoint_lag=32;keeper=k1",
            steps=13, keeper=keeper)
        assert dropped["count"] == 1
        assert checkpointer.counters["checkpoints"] >= 3
        with pytest.raises(KeyError, match="incomplete"):
            keeper.restore("r")
        # and the end-to-end ladder still completes via re-prefill
        survivor = DecodeEngine(params, config, decode_slots=1,
                                kv_block_size=8)
        record = None
        try:
            record = keeper.restore("r")
        except KeyError:
            pass
        report = survivor.restore_request(
            "r", record, prompt_tokens=prompt, max_new_tokens=16)
        done = {c.request_id: c for c in report.completions}
        drain(survivor, done)
        assert survivor.counters["restore_fallbacks"] == 1
        np.testing.assert_array_equal(
            done["r"].tokens, reference(params, config, prompt, 16))

    def test_forget_drops_keeper_state(self, tiny_model):
        params, config = tiny_model
        prompt = np.arange(1, 6, dtype=np.int32)
        engine, checkpointer, keeper, _ = run_with_checkpoints(
            params, config, prompt, 8,
            spec="checkpoint_every=1;keeper=k1", steps=4)
        assert keeper.kept_count() == 1
        checkpointer.forget("r")
        assert keeper.flush()
        assert keeper.kept_count() == 0
        assert keeper.counters["dropped"] == 1


# -- restore: bit-identity, gapless offsets, degraded paths ------------------


class TestRestore:
    @pytest.mark.parametrize("fields", (
        {"kv_dtype": ""}, {"kv_dtype": "int8"}, LATENT),
        ids=("f32", "int8", "latent"))
    def test_bit_identical_f32_and_int8(self, fields):
        """The tentpole invariant: a mid-decode crash restored from
        the keeper finishes BIT-IDENTICAL to an uncrashed run, for
        the f32, the int8 (codes + scales) and the latent (one leaf of
        latent rows) pool layouts."""
        config = TransformerConfig(**{**TINY, **fields})
        params = init_params(config, jax.random.PRNGKey(0))
        rng = np.random.default_rng(5)
        prompt = rng.integers(1, 64, size=11).astype(np.int32)
        max_new = 14
        engine, _, keeper, emitted = run_with_checkpoints(
            params, config, prompt, max_new,
            spec="checkpoint_every=2;max_checkpoint_lag=4;keeper=k1",
            steps=7)
        assert 0 < len(emitted) < max_new, "crash must be mid-decode"
        # the crash: abandon the engine, restore on a fresh one
        survivor = DecodeEngine(params, config, decode_slots=1,
                                kv_block_size=8)
        record = keeper.restore("r")
        report = survivor.restore_request("r", record)
        emitted2 = [(offset, token) for _rid, offset, token
                    in report.emitted]
        done = {c.request_id: c for c in report.completions}
        drain(survivor, done, emitted2)
        np.testing.assert_array_equal(
            done["r"].tokens, reference(params, config, prompt,
                                        max_new))
        assert survivor.counters["restores"] == 1
        assert survivor.counters["restore_fallbacks"] == 0
        assert survivor.counters["kv_migrated_bytes"] > 0
        # restored emission covers every offset exactly once
        assert sorted(dict(emitted2)) == list(range(max_new))
        assert survivor.stats()["free_blocks"] == \
            survivor.blocks.capacity

    def test_resume_from_is_gapless_and_counts_replayed(
            self, tiny_model):
        """A client that already holds offsets [0, crash) passes
        resume_from: tokens between the snapshot and the crash
        re-decode SILENTLY (decode.restore_replayed_tokens counts
        them, bounded by max_checkpoint_lag) and emission resumes at
        exactly the crash offset -- no duplicate, no gap."""
        params, config = tiny_model
        prompt = np.arange(1, 8, dtype=np.int32)
        max_new = 12
        # one early snapshot, then decode on without another
        engine, checkpointer, keeper, emitted = run_with_checkpoints(
            params, config, prompt, max_new,
            spec="checkpoint_every=2;max_checkpoint_lag=32;keeper=k1",
            steps=3)
        for _ in range(4):          # post-snapshot progress, unshipped
            report = engine.step()
            emitted.extend((offset, token) for _rid, offset, token
                           in report.emitted)
        crash_count = len(emitted)
        record = keeper.restore("r")
        snapshot_count = len(record["generated"])
        assert snapshot_count < crash_count
        survivor = DecodeEngine(params, config, decode_slots=1,
                                kv_block_size=8)
        report = survivor.restore_request("r", record,
                                          resume_from=crash_count)
        emitted2 = [(offset, token) for _rid, offset, token
                    in report.emitted]
        done = {c.request_id: c for c in report.completions}
        drain(survivor, done, emitted2)
        assert (survivor.counters["restore_replayed_tokens"]
                == crash_count - snapshot_count)
        offsets = sorted(dict(emitted2))
        assert offsets and offsets[0] == crash_count
        combined = dict(emitted)
        combined.update(dict(emitted2))
        assert sorted(combined) == list(range(max_new))
        np.testing.assert_array_equal(
            np.asarray([combined[i] for i in range(max_new)]),
            reference(params, config, prompt, max_new))

    def test_degraded_paths_fall_back_to_reprefill(self, tiny_model):
        """Every failure -- no record, unknown request, stale
        snapshot, block-size mismatch -- degrades to the existing
        replay re-prefill: the request completes bit-identically and
        the granted blocks are returned first."""
        params, config = tiny_model
        prompt = np.arange(1, 10, dtype=np.int32)
        max_new = 6
        expected = reference(params, config, prompt, max_new)

        def restored(engine, record, **kwargs):
            report = engine.restore_request("r", record,
                                            prompt_tokens=prompt,
                                            max_new_tokens=max_new,
                                            **kwargs)
            done = {c.request_id: c for c in report.completions}
            drain(engine, done)
            np.testing.assert_array_equal(done["r"].tokens, expected)

        # 1) no record at all (dead keeper)
        engine = DecodeEngine(params, config, decode_slots=1,
                              kv_block_size=8)
        restored(engine, None)
        assert engine.counters["restore_fallbacks"] == 1

        # 2) stale snapshot: keeper max_age expired
        keeper = CheckpointKeeper("k_stale", max_age_s=0.01)
        _, _, keeper, _ = run_with_checkpoints(
            params, config, prompt, max_new,
            spec="checkpoint_every=1;keeper=k_stale", steps=3,
            keeper=keeper)
        time.sleep(0.05)
        with pytest.raises(KeyError):
            keeper.restore("r")
        assert keeper.counters["expired"] == 1

        # 3) unknown request key
        with pytest.raises(KeyError):
            CheckpointKeeper("k_empty").restore("missing")

        # 4) block-size mismatch (mixed fleet)
        keeper2 = CheckpointKeeper("k2")
        _, _, keeper2, _ = run_with_checkpoints(
            params, config, prompt, max_new,
            spec="checkpoint_every=1;keeper=k2", steps=3,
            keeper=keeper2)
        record = keeper2.restore("r")
        other = DecodeEngine(params, config, decode_slots=1,
                             kv_block_size=16)
        free_before = other.blocks.free_count
        restored(other, record)
        assert other.counters["restore_fallbacks"] == 1
        assert other.counters["restores"] == 0
        assert other.blocks.free_count == free_before

        # 5) expired transfer keys (the keeper's server restarted)
        keeper3 = CheckpointKeeper("k3")
        _, _, keeper3, _ = run_with_checkpoints(
            params, config, prompt, max_new,
            spec="checkpoint_every=1;keeper=k3", steps=3,
            keeper=keeper3)
        record = keeper3.restore("r")
        reset_transfer_server()
        engine3 = DecodeEngine(params, config, decode_slots=1,
                               kv_block_size=8)
        restored(engine3, record, timeout=1)
        assert engine3.counters["restore_fallbacks"] == 1


# -- the AIKO409 grammar ------------------------------------------------------


class TestCheckpointGrammar:
    def test_scopes_parse_and_reject(self):
        engine_side = CheckpointPolicy.parse(
            "checkpoint_every=4;max_checkpoint_lag=8;keeper=k")
        engine_side.validate_engine()
        assert engine_side.checkpoint_every == 4
        gateway_side = CheckpointPolicy.parse(
            "recovery_rate=2.5;keeper=k")
        gateway_side.validate_gateway()
        assert gateway_side.recovery_rate == 2.5
        with pytest.raises(ValueError, match="gateway-side"):
            CheckpointPolicy.parse("recovery_rate=1").validate_engine()
        with pytest.raises(ValueError, match="engine-side"):
            CheckpointPolicy.parse(
                "checkpoint_every=4").validate_gateway()

    def test_lint_parity(self):
        from aiko_services_tpu.analyze.policies import (
            check_checkpoint_policy, check_decode_parameters)
        assert check_checkpoint_policy("recovery_rate=2;keeper=k") == []
        problems = check_checkpoint_policy("recovery_rate=-1")
        assert any(code == "AIKO409" for code, _ in problems)
        problems = check_checkpoint_policy("warp=9")
        assert any(code == "AIKO404" for code, _ in problems)
        problems = check_checkpoint_policy("recovery_rate=1",
                                           element=True)
        assert any(code == "AIKO409" for code, _ in problems)
        # element cross-fields: checkpoint rides the slot engine
        problems = check_decode_parameters(
            {"checkpoint": "checkpoint_every=2"})
        assert any(code == "AIKO409" for code, _ in problems)
        problems = check_decode_parameters(
            {"checkpoint": "checkpoint_every=2", "continuous": True})
        assert problems == []
        problems = check_decode_parameters(
            {"checkpoint": "checkpoint_every=2", "role": "prefill"})
        assert any(code == "AIKO409" for code, _ in problems)

    def test_gateway_construction_matches_lint(self):
        process = Process(transport_kind="loopback")
        with pytest.raises(ValueError, match="AIKO409"):
            Gateway(process, name="bad", checkpoint="recovery_rate=-1")
        with pytest.raises(ValueError, match="AIKO404"):
            Gateway(process, name="bad2", checkpoint="warp=9")
        with pytest.raises(ValueError, match="AIKO409"):
            Gateway(process, name="bad3",
                    checkpoint="checkpoint_every=4")


# -- gateway warm failover ----------------------------------------------------


LM_PARAMS = {"vocab_size": 300, "d_model": 32, "n_layers": 1,
             "n_heads": 2, "n_kv_heads": 1, "d_ff": 64,
             "max_seq_len": 128, "dtype": "float32"}


def lm_definition(name, extra):
    return {
        "name": name,
        "graph": ["(lm)"],
        "elements": [
            {"name": "lm",
             "input": [{"name": "tokens"},
                       {"name": "restore", "optional": True}],
             "output": [{"name": "generated"}],
             "parameters": {**LM_PARAMS, **extra},
             "deploy": {"local": {"module": ELEMENTS,
                                  "class_name": "LMGenerate"}}},
        ],
    }


DECODE_EXTRA = {"continuous": True, "decode_slots": 4,
                "kv_block_size": 8, "max_new_tokens": 24,
                "stream_tokens": True, "stream_chunk": 1,
                "checkpoint": ("checkpoint_every=1;"
                               "max_checkpoint_lag=4;keeper=gwk")}


def closed_batch_reference(frames, max_new):
    process = Process(transport_kind="loopback")
    pipeline = create_pipeline(process, lm_definition(
        "ref", {"max_new_tokens": max_new}))
    process.run(in_thread=True)
    responses = queue.Queue()
    stream = pipeline.create_stream("s", queue_response=responses,
                                    grace_time=300)
    for frame in frames:
        pipeline.create_frame(stream, {"tokens": frame})
    expected = [np.asarray(responses.get(timeout=120)[2]["generated"])
                for _ in frames]
    process.terminate()
    reset_brokers()
    return expected


def _collect_chunks(chunks, payload):
    try:
        command, parameters = parse(payload)
    except ValueError:
        return
    if command != "token_chunk" or len(parameters) < 5:
        return
    stream_id = str(parameters[0])
    row = int(parameters[2])
    offset = int(parameters[3])
    tokens = [int(token) for token in parameters[4][0]]
    chunks.append((stream_id, row, offset, tokens))


class TestGatewayWarmFailover:
    def test_decode_replica_kill_restores_paced_and_bit_identical(
            self):
        """The tentpole end to end: a decode replica dies mid-storm;
        the gateway's paced failover replays every stream with a
        RESTORE hint; the survivor adopts checkpoints instead of
        re-prefilling; completions AND streamed chunk offsets are
        bit-identical/gapless vs an uncrashed run."""
        rng = np.random.default_rng(13)
        streams_n = 4
        max_new = 24
        frames = [rng.integers(1, 300, size=(1, 6)).astype(np.int32)
                  for _ in range(streams_n)]
        expected = closed_batch_reference(frames, max_new)

        keeper = CheckpointKeeper("gwk")
        processes = []

        def make_replica(name):
            process = Process(transport_kind="loopback")
            processes.append(process)
            return process, create_pipeline(
                process, lm_definition(name, DECODE_EXTRA))

        process0, replica0 = make_replica("wf0")
        process1, replica1 = make_replica("wf1")
        gateway_process = Process(transport_kind="loopback")
        processes.append(gateway_process)
        gateway = Gateway(
            gateway_process, policy="max_inflight=16;queue=64",
            checkpoint="recovery_rate=2;keeper=gwk")
        gateway.attach_replica(replica0)
        chunks = []
        for process, replica in ((process0, replica0),
                                 (process1, replica1)):
            process.add_message_handler(
                lambda topic, payload: _collect_chunks(chunks, payload),
                f"{replica.elements['lm'].topic_path}/out")
        for process in processes:
            process.run(in_thread=True)
        try:
            responses = queue.Queue()
            for index, frame in enumerate(frames):
                stream_id = f"s{index}"
                gateway.submit_stream(stream_id, {},
                                      queue_response=responses)
                gateway.submit_frame(stream_id, {"tokens": frame},
                                     frame_id=0)
            # mid-storm: wait until every stream has checkpoints but
            # none has finished, then kill the only serving replica.
            # (Not for an idle keeper queue as well: while four streams
            # ship a delta a tick it idles only once they have finished
            # and been forgotten; restore() flushes for itself.)
            wait_for(lambda: keeper.kept_count() >= streams_n, timeout=60)
            gateway.attach_replica(replica1)
            gateway.post_message("_replica_lost", [
                replica0.topic_path, "decode_replica_kill"])
            got = {}
            deadline = time.monotonic() + 120
            while len(got) < streams_n:
                assert time.monotonic() < deadline
                stream_id, frame_id, outputs, status = responses.get(
                    timeout=120)
                assert status == "ok", (stream_id, outputs)
                got[stream_id] = np.asarray(outputs["generated"])
            for index in range(streams_n):
                np.testing.assert_array_equal(got[f"s{index}"],
                                              expected[index])
            survivor = replica1.elements["lm"].engine_stats()
            assert survivor is not None
            assert survivor["restores"] >= 1, survivor
            avoided = survivor["restores"] / max(
                survivor["restores"] + survivor["restore_fallbacks"], 1)
            assert avoided > 0
            # pacing: with recovery_rate=2 and 4 migrated streams, at
            # least one stream's replay wave was deferred
            assert gateway.telemetry.recovery_paced.value >= 1
            # streamed chunks: offsets assemble gaplessly into the
            # reference sequence; restore re-emissions are idempotent
            # duplicates (same offset, same token), never gaps
            def covered(stream_id):
                seen = set()
                for s, _row, offset, tokens in list(chunks):
                    if s == stream_id:
                        seen.update(range(offset,
                                          offset + len(tokens)))
                return len(seen)

            wait_for(lambda: all(covered(f"s{i}") >= max_new
                                 for i in range(streams_n)),
                     timeout=30)
            for index in range(streams_n):
                assembled = {}
                for stream_id, row, offset, tokens in chunks:
                    if stream_id != f"s{index}":
                        continue
                    for j, token in enumerate(tokens):
                        previous = assembled.get(offset + j)
                        assert previous in (None, token), (
                            f"offset {offset + j} re-emitted a "
                            f"DIFFERENT token")
                        assembled[offset + j] = token
                assert sorted(assembled) == list(range(max_new))
                np.testing.assert_array_equal(
                    np.asarray([assembled[i] for i in range(max_new)]),
                    expected[index][0])
            # the survivor's telemetry surfaces the restore ledger
            summary = replica1.telemetry.decode_summary()
            assert summary["restores"] == survivor["restores"]
        finally:
            for process in processes:
                process.terminate()

    @pytest.mark.parametrize("stream_chunk", [1, 4])
    def test_element_resume_from_publishes_floor_offsets(
            self, stream_chunk):
        """A replaying client that already holds offsets [0, crash)
        passes resume_from through the restore hint: the restored
        element's `(token_chunk …)` offsets must START at the floor --
        publishing them from 0 would make an offset-keyed consumer
        overwrite its held prefix with later tokens.  A row restored
        past offset 0 has no first token to hurry: its chunks are
        `stream_chunk` long from the floor on (only a row's offset 0
        is a chunk of its own)."""
        rng = np.random.default_rng(21)
        frame = rng.integers(1, 300, size=(1, 6)).astype(np.int32)
        max_new = 24
        [expected] = closed_batch_reference([frame], max_new)
        keeper = CheckpointKeeper("ek")
        extra = {"continuous": True, "decode_slots": 2,
                 "kv_block_size": 8, "max_new_tokens": max_new,
                 "stream_tokens": True, "stream_chunk": stream_chunk,
                 "checkpoint": ("checkpoint_every=1;"
                                "max_checkpoint_lag=4;keeper=ek")}
        chunks_a, chunks_b = [], []
        process_a = Process(transport_kind="loopback")
        replica_a = create_pipeline(process_a, lm_definition(
            "ra", extra))
        process_a.add_message_handler(
            lambda t, p: _collect_chunks(chunks_a, p),
            f"{replica_a.elements['lm'].topic_path}/out")
        # a paced engine: replica A's pump stops after 12 ticks, so the
        # crash is mid-stream by construction.  Left to run, the tiny
        # model can finish its 24 tokens between two polls of wait_for;
        # the completion then drops the keeper's snapshot, and the wait
        # for a kept one never ends
        lm_a = replica_a.elements["lm"]
        pump, ticks = lm_a._pump, []

        def paced_pump(engine):
            if len(ticks) < 12:
                ticks.append(engine)
                pump(engine)

        lm_a._pump = paced_pump
        process_a.run(in_thread=True)
        replica_a.create_stream("s", grace_time=300,
                                queue_response=queue.Queue())
        stream_a = replica_a.streams["s"]
        replica_a.create_frame(stream_a, {"tokens": frame})
        wait_for(lambda: keeper.flush(timeout=0.1)
                 and keeper.kept_count() >= 1
                 and sum(len(t) for _s, _r, _o, t in chunks_a) >= 4,
                 timeout=60)
        process_a.terminate()   # the crash: mid-decode, chunks held
        # before the crash: the first token alone, then whole chunks
        assert [(offset, len(tokens))
                for _s, _r, offset, tokens in chunks_a[:2]] == [
            (0, 1), (1, stream_chunk)]
        held = {}
        for _sid, _row, offset, tokens in chunks_a:
            for j, token in enumerate(tokens):
                held[offset + j] = token
        crash = 0
        while crash in held:
            crash += 1
        assert 0 < crash < max_new, "crash must be mid-stream"
        reset_brokers()

        process_b = Process(transport_kind="loopback")
        replica_b = create_pipeline(process_b, lm_definition(
            "rb", extra))
        process_b.add_message_handler(
            lambda t, p: _collect_chunks(chunks_b, p),
            f"{replica_b.elements['lm'].topic_path}/out")
        process_b.run(in_thread=True)
        try:
            responses = queue.Queue()
            replica_b.create_stream("s", grace_time=300,
                                    queue_response=responses)
            replica_b.create_frame(replica_b.streams["s"], {
                "tokens": frame,
                "restore": {"keeper": "ek",
                            "resume_from": {0: crash}}})
            _, _frame, outputs = responses.get(timeout=120)
            np.testing.assert_array_equal(
                np.asarray(outputs["generated"]), expected)
            stats = replica_b.elements["lm"].engine_stats()
            assert stats["restores"] == 1, stats
            wait_for(lambda: sum(len(t) for _s, _r, _o, t in chunks_b)
                     >= max_new - crash, timeout=30)
            offsets = sorted({offset + j
                              for _s, _r, offset, tokens in chunks_b
                              for j in range(len(tokens))})
            assert offsets[0] == crash, (
                f"restored chunks start at {offsets[0]}, the client "
                f"already holds [0, {crash})")
            assert offsets == list(range(crash, max_new))
            # gapless from the floor, in whole chunks but the last
            assert [(offset, len(tokens))
                    for _s, _r, offset, tokens in chunks_b] == [
                (start, min(stream_chunk, max_new - start))
                for start in range(crash, max_new, stream_chunk)]
            resumed = dict(held)
            for _sid, _row, offset, tokens in chunks_b:
                for j, token in enumerate(tokens):
                    resumed[offset + j] = token
            np.testing.assert_array_equal(
                np.asarray([resumed[i] for i in range(max_new)]),
                expected[0])
        finally:
            process_b.terminate()

    def test_journal_replay_dedupe_of_streamed_frames(self, tmp_path):
        """Continuous-mode analogue of the round-13 exactly-once test:
        after a gateway restart adopts the journal, a client's replay
        of an already-delivered frame is absorbed against the journaled
        delivered_floor -- the engine never re-admits it, and no
        duplicate completion reaches the client."""
        db_path = tmp_path / "gw.db"
        rng = np.random.default_rng(3)
        frame = rng.integers(1, 300, size=(1, 6)).astype(np.int32)
        process_r = Process(transport_kind="loopback")
        replica = create_pipeline(process_r, lm_definition(
            "jr0", {"continuous": True, "decode_slots": 2,
                    "kv_block_size": 8, "max_new_tokens": 8,
                    "stream_tokens": True, "stream_chunk": 1}))
        process_a = Process(transport_kind="loopback")
        gateway_a = Gateway(process_a, name="gwa",
                            policy="max_inflight=8;queue=16",
                            journal=f"path={db_path};interval=0")
        gateway_a.attach_replica(replica)
        for process in (process_r, process_a):
            process.run(in_thread=True)
        try:
            responses = queue.Queue()
            gateway_a.submit_stream("s", {}, queue_response=responses,
                                    grace_time=300)
            gateway_a.submit_frame("s", {"tokens": frame}, frame_id=0)
            _, frame_id, outputs, status = responses.get(timeout=120)
            assert status == "ok" and frame_id == 0
            gateway_a.journal_flush()
            engine_before = replica.elements["lm"].engine_stats()
            # the crash: a NEW gateway adopts the same journal
            process_b = Process(transport_kind="loopback")
            gateway_b = Gateway(process_b, name="gwb",
                                policy="max_inflight=8;queue=16",
                                journal=f"path={db_path};interval=0")
            gateway_b.attach_replica(replica)
            process_b.run(in_thread=True)
            wait_for(lambda: gateway_b.recover_now() or
                     "s" in gateway_b.streams, timeout=30)
            stream = gateway_b.streams["s"]
            assert stream.delivered_floor == 0, (
                "the journaled floor must survive the restart")
            replays = queue.Queue()
            stream.queue_response = replays
            # client replays its un-acked frame 0: absorbed exactly-once
            duplicates_before = gateway_b.telemetry.duplicates.value
            gateway_b.submit_frame("s", {"tokens": frame}, frame_id=0)
            wait_for(lambda: gateway_b.telemetry.duplicates.value
                     > duplicates_before, timeout=30)
            assert (replica.elements["lm"].engine_stats()["admitted"]
                    == engine_before["admitted"]), (
                "the replayed frame must not re-admit into the engine")
            assert replays.empty()
            # and the stream keeps serving: the NEXT frame decodes
            gateway_b.submit_frame("s", {"tokens": frame}, frame_id=1)
            _, frame_id, outputs, status = replays.get(timeout=120)
            assert status == "ok" and frame_id == 1
            gateway_b.stop()
            process_b.terminate()
        finally:
            gateway_a.stop()
            for process in (process_r, process_a):
                process.terminate()


# -- satellite: transfer_stall bounds a slow keeper ---------------------------


class TestTransferStall:
    def test_adopt_timeout_bounds_a_stalled_producer(
            self, monkeypatch, tiny_model):
        """A keeper/producer that accepts but answers after a long
        stall must not wedge the engine pump: the adopt_timeout cuts
        each attempt, the retry budget expires quickly, and the
        request degrades to a local re-prefill."""
        params, config = tiny_model
        prompt = np.arange(1, 10, dtype=np.int32)
        prefill = PrefillEngine(params, config, kv_block_size=8)
        prefill.submit("r", prompt, 5)
        [handoff] = prefill.step()
        monkeypatch.setenv("AIKO_FAULTS",
                           "transfer_stall:ms=5000:times=-1")
        monkeypatch.setenv("AIKO_TRANSFER_RETRY_MS", "1")
        faults_module.reset_injector()
        engine = DecodeEngine(params, config, decode_slots=1,
                              kv_block_size=8)
        started = time.perf_counter()
        report = engine.adopt_request("r", handoff, timeout=0.3)
        elapsed = time.perf_counter() - started
        assert elapsed < 4.0, (
            f"a 5 s stall held the adopt for {elapsed:.1f} s")
        assert engine.counters["adopt_fallbacks"] == 1
        done = {c.request_id: c for c in report.completions}
        drain(engine, done)
        np.testing.assert_array_equal(
            done["r"].tokens, reference(params, config, prompt, 5))

    def test_transient_stall_survives_on_retry(self, monkeypatch,
                                               tiny_model):
        """times=1: only the first connection stalls; the retry lands
        and the adoption still goes through warm."""
        params, config = tiny_model
        prompt = np.arange(1, 8, dtype=np.int32)
        prefill = PrefillEngine(params, config, kv_block_size=8)
        prefill.submit("r", prompt, 4)
        [handoff] = prefill.step()
        monkeypatch.setenv("AIKO_FAULTS",
                           "transfer_stall:ms=5000:times=1")
        monkeypatch.setenv("AIKO_TRANSFER_RETRY_MS", "1")
        faults_module.reset_injector()
        engine = DecodeEngine(params, config, decode_slots=1,
                              kv_block_size=8)
        report = engine.adopt_request("r", handoff, timeout=0.3)
        assert engine.counters["adopted"] == 1
        assert engine.counters["adopt_fallbacks"] == 0
        done = {c.request_id: c for c in report.completions}
        drain(engine, done)
        np.testing.assert_array_equal(
            done["r"].tokens, reference(params, config, prompt, 4))


# -- satellite: per-peer transfer circuit breaker -----------------------------


class TestCircuitBreaker:
    DEAD = {"host": "127.0.0.1", "port": 1, "key": "a" * 32,
            "dtype": "float32", "shape": [2]}

    def test_trips_fast_fails_and_heals(self, monkeypatch):
        monkeypatch.setenv("AIKO_TRANSFER_CIRCUIT_MS", "400")
        monkeypatch.setenv("AIKO_TRANSFER_RETRY_MS", "5")
        registry = get_registry()
        opens_before = registry.counter(
            "transfer.peer_open_circuits").value
        with pytest.raises(TransferError):
            fetch_many([dict(self.DEAD)], timeout=0.2)
        assert (registry.counter("transfer.peer_open_circuits").value
                == opens_before + 1)
        # the circuit is open: the next call fails FAST -- no retry
        # budget burned on the event loop
        started = time.perf_counter()
        with pytest.raises(TransferError, match="circuit open"):
            fetch_many([dict(self.DEAD)], timeout=5)
        assert time.perf_counter() - started < 0.05
        started = time.perf_counter()
        with pytest.raises(TransferError, match="circuit open"):
            from aiko_services_tpu.pipeline.transfer import fetch
            fetch(dict(self.DEAD), timeout=5)
        assert time.perf_counter() - started < 0.05
        # after the window the peer gets real attempts again
        time.sleep(0.45)
        errors_before = registry.counter("transfer.fetch_errors").value
        with pytest.raises(TransferError):
            fetch_many([dict(self.DEAD)], timeout=0.2)
        assert (registry.counter("transfer.fetch_errors").value
                > errors_before)

    def test_success_closes_an_open_circuit(self, monkeypatch):
        from aiko_services_tpu.pipeline import transfer
        monkeypatch.setenv("AIKO_TRANSFER_CIRCUIT_MS", "200")
        server = get_transfer_server()
        array = np.ones((8, 8), np.float32)
        descriptor = server.offer(array)
        address = (descriptor["host"], int(descriptor["port"]))
        transfer._trip_circuit(address)
        with pytest.raises(TransferError, match="circuit open"):
            fetch_many([descriptor])
        time.sleep(0.25)
        [fetched] = fetch_many([descriptor])
        np.testing.assert_array_equal(fetched, array)
        assert not transfer._circuit_open(address)

    def test_disabled_by_env(self, monkeypatch):
        monkeypatch.setenv("AIKO_TRANSFER_CIRCUIT_MS", "0")
        monkeypatch.setenv("AIKO_TRANSFER_RETRY_MS", "1")
        with pytest.raises(TransferError):
            fetch_many([dict(self.DEAD)], timeout=0.2)
        # no circuit was opened: the second call retries for real
        registry = get_registry()
        errors_before = registry.counter("transfer.fetch_errors").value
        with pytest.raises(TransferError):
            fetch_many([dict(self.DEAD)], timeout=0.2)
        assert (registry.counter("transfer.fetch_errors").value
                > errors_before)


# -- tune: the checkpoint-bound floor -----------------------------------------


class TestCheckpointBoundFloor:
    def _cost(self, checkpoint_ms, compute_ms=2.0, queue_ms=0.5):
        from aiko_services_tpu.tune.model import (
            CostModel, ElementCost, classify_elements)
        cost = ElementCost(name="lm", calls=50)
        cost.compute_median_s = compute_ms / 1e3
        cost.per_call_median_s = compute_ms / 1e3
        cost.queue_median_s = queue_ms / 1e3
        cost.engine = {
            "queue_median_s": queue_ms / 1e3,
            "prefill_median_s": 0.001, "decode_median_s": 0.002,
            "adopt_median_s": 0.0, "adoptions": 0,
            "checkpoint_median_s": checkpoint_ms / 1e3,
            "checkpoints": 20, "preemptions": 0, "tokens": 400,
            "requests": 20,
        }
        model = CostModel(elements={"lm": cost})
        classify_elements(model)
        return cost

    def test_classifies_checkpoint_bound_with_evidence(self):
        cost = self._cost(checkpoint_ms=25.0)
        assert cost.floor == "checkpoint-bound"
        assert cost.evidence["engine"]["checkpoint_median_s"] > 0
        # a cheap cadence stays compute-bound
        assert self._cost(checkpoint_ms=0.1).floor == "compute-bound"

    def test_recommender_stretches_the_cadence(self):
        from aiko_services_tpu.tune.recommend import (
            _engine_recommendations)
        cost = self._cost(checkpoint_ms=25.0)
        parameters = {"checkpoint":
                      "checkpoint_every=4;max_checkpoint_lag=8",
                      "decode_slots": 4}
        [recommendation] = _engine_recommendations(
            "lm", cost, parameters, None)
        assert recommendation.knob == "checkpoint"
        assert "checkpoint_every=8" in str(recommendation.proposed)
        assert recommendation.floor == "checkpoint-bound"

    def test_span_global_renders_a_duration_event(self):
        from aiko_services_tpu.observe.trace import Tracer
        tracer = Tracer()
        tracer.span_global("checkpoint:lm", "engine", 0.02,
                           {"bytes": 4096})
        events = tracer.chrome_events()
        [span] = [event for event in events
                  if event.get("name") == "checkpoint:lm"]
        assert span["ph"] == "X" and span["cat"] == "engine"
        assert span["dur"] == pytest.approx(20000.0, rel=0.5)
