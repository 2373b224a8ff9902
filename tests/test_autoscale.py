# Elastic replica fleet suite (ISSUE 7): load-driven autoscaling over
# the serving gateway -- watermark scale-up/down through a
# ReplicaFactory, warm-start replicas (persistent compile cache +
# live sibling weight hand-off over the transfer plane), loss-free
# scale-down through the shared failover migration path -- plus the
# satellite hooks: ProcessManager env overlay, the AIKO406 autoscale
# policy grammar, pool telemetry/dashboard/status surfacing.

import json
import os
import queue
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aiko_services_tpu import faults as faults_module
from aiko_services_tpu.pipeline import (
    PipelineElement, StreamEvent, create_pipeline)
from aiko_services_tpu.pipeline.tpu_element import ComputeElement
from aiko_services_tpu.runtime import (
    Process, ProcessManager, cache_stats, disable_compile_cache,
    enable_compile_cache)
from aiko_services_tpu.serve import (
    AutoScaler, Gateway, InProcessReplicaFactory, ProcessReplicaFactory,
    ScalePolicy)
from aiko_services_tpu.transport import reset_brokers
from helpers import wait_for


@pytest.fixture(autouse=True)
def clean():
    faults_module.reset_injector()
    reset_brokers()
    disable_compile_cache()
    yield
    faults_module.reset_injector()
    reset_brokers()
    disable_compile_cache()


class Scale(PipelineElement):
    """x -> x*10 (deterministic: migration replay must be
    bit-identical)."""

    def process_frame(self, stream, x):
        return StreamEvent.OKAY, {"y": x * 10.0}


class SlowScale(Scale):
    """Fixed host cost per frame so saturation (and therefore the
    autoscaler's utilization signal) is test-controlled."""

    def process_frame(self, stream, x):
        time.sleep(float(self.get_parameter("work_ms", 5, stream))
                   / 1000.0)
        return super().process_frame(stream, x)


class Affine(ComputeElement):
    """Stateful device element: y = x * w + b.  The state pytree is
    deliberately nested (dict + list) to exercise the hand-off tree
    walk."""

    def setup(self):
        return {"w": jnp.full((1, 2), 2.0, jnp.float32),
                "b": [jnp.zeros((1, 2), jnp.float32)]}

    def compute(self, state, x):
        return {"y": x * state["w"] + state["b"][0]}


class SlowAffine(Affine):
    """Affine plus a fixed host cost, so gateway load builds while the
    device math stays deterministic."""

    def process_frame(self, stream, **inputs):
        time.sleep(0.02)
        return super().process_frame(stream, **inputs)


def _definition(name, class_name="Scale", element="scale",
                element_parameters=None):
    return {
        "name": name,
        "graph": [f"({element})"],
        "elements": [
            {"name": element, "input": [{"name": "x"}],
             "output": [{"name": "y"}],
             "parameters": dict(element_parameters or {}),
             "deploy": {"local": {"module": "tests.test_autoscale",
                                  "class_name": class_name}}},
        ],
    }


def _frame(value):
    return {"x": np.ones((1, 2), np.float32) * value}


def _attach_pool(gateway, count, class_name="Scale",
                 element_parameters=None):
    """`count` in-process replicas attached directly (the fixed-pool
    baseline the autoscaler grows/shrinks)."""
    processes, replicas = [], []
    for index in range(count):
        process = Process(transport_kind="loopback")
        processes.append(process)
        pipeline = create_pipeline(process, _definition(
            f"replica{index}", class_name=class_name,
            element_parameters=element_parameters))
        replicas.append(pipeline)
        gateway.attach_replica(pipeline)
        process.run(in_thread=True)
    return processes, replicas


# -- policy grammar (AIKO406) ------------------------------------------------


class TestScalePolicy:
    def test_defaults_and_parse(self):
        policy = ScalePolicy.parse(None)
        assert (policy.min_replicas, policy.max_replicas) == (1, 2)
        policy = ScalePolicy.parse(
            "min_replicas=2;max_replicas=8;high_water=0.9;"
            "low_water=0.1;cooldown=3;drain_timeout=1;interval=0.25;"
            "warm_start=false")
        assert policy.max_replicas == 8
        assert policy.high_water == pytest.approx(0.9)
        assert policy.warm_start is False
        assert ScalePolicy.parse({"max_replicas": 3}).max_replicas == 3

    def test_cross_field_constraints_rejected(self):
        with pytest.raises(ValueError, match="must not exceed"):
            ScalePolicy.parse("min_replicas=4;max_replicas=2")
        with pytest.raises(ValueError, match="below"):
            ScalePolicy.parse("low_water=0.8;high_water=0.5")

    def test_construction_error_codes_match_offline_lint(self):
        from aiko_services_tpu.analyze.policies import (
            check_autoscale_policy)
        bad_value = "min_replicas=4;max_replicas=2"
        unknown = "replicas=4"
        process = Process(transport_kind="loopback")
        process.run(in_thread=True)
        with pytest.raises(ValueError, match="AIKO406"):
            Gateway(process, autoscale=bad_value)
        with pytest.raises(ValueError, match="AIKO404"):
            Gateway(process, name="gw2", autoscale=unknown)
        assert check_autoscale_policy(bad_value)[0][0] == "AIKO406"
        assert check_autoscale_policy(unknown)[0][0] == "AIKO404"
        assert check_autoscale_policy(
            "min_replicas=1;max_replicas=4") == []
        process.terminate()


# -- persistent compile cache ------------------------------------------------


class TestCompileCache:
    def test_hit_miss_counters_and_idempotence(self, tmp_path):
        directory = enable_compile_cache(str(tmp_path / "cache"))
        assert directory == str(tmp_path / "cache")
        assert enable_compile_cache(directory) == directory  # idempotent

        def fresh_program():
            # a NEW closure per call defeats the in-memory jit cache,
            # which is exactly a new replica's position
            def f(x):
                return jnp.sin(x) @ jnp.cos(x).T
            return jax.jit(f)

        before = cache_stats()
        fresh_program()(jnp.ones((32, 32))).block_until_ready()
        mid = cache_stats()
        assert mid["misses"] > before["misses"]  # cold: XLA compiled
        fresh_program()(jnp.ones((32, 32))).block_until_ready()
        after = cache_stats()
        assert after["hits"] > mid["hits"]       # warm: deserialized
        assert after["misses"] == mid["misses"]  # zero recompiles

    def test_default_directory_is_fixed_inside_the_checkout(
            self, monkeypatch):
        """No argument, no environment: the one in-checkout directory
        (the path is part of the cache key -- a tempfile-, pid- or
        time-derived name would never hit across runs)."""
        from aiko_services_tpu.runtime.compile_cache import (
            DEFAULT_CACHE_DIR)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert DEFAULT_CACHE_DIR == os.path.join(repo, ".jax_cache")
        assert cache_stats()["dir"] is None          # off until enabled
        assert enable_compile_cache() == DEFAULT_CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == DEFAULT_CACHE_DIR
        assert cache_stats()["dir"] == DEFAULT_CACHE_DIR

    def test_environment_placement_is_never_overridden(
            self, tmp_path, monkeypatch):
        """JAX_COMPILATION_CACHE_DIR decides: not an explicit argument,
        not a replica factory's directory, not disable_compile_cache()
        may point jax anywhere else."""
        placed = str(tmp_path / "placed")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
        assert enable_compile_cache(str(tmp_path / "other")) == placed
        assert jax.config.jax_compilation_cache_dir == placed
        assert enable_compile_cache() == placed
        # what a replica factory's _bring_up passes along
        assert enable_compile_cache(str(tmp_path / "factory")) == placed
        disable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == placed
        assert cache_stats()["dir"] == placed
        jax.jit(lambda x: x * 3.0 + 1.0)(
            jnp.ones((7, 5))).block_until_ready()
        assert os.listdir(placed)
        assert not (tmp_path / "other").exists()
        assert not (tmp_path / "factory").exists()


# -- live weight hand-off ----------------------------------------------------


class TestWeightHandoff:
    def _pipeline(self, name):
        process = Process(transport_kind="loopback")
        pipeline = create_pipeline(process, _definition(
            name, class_name="Affine", element="affine"))
        process.run(in_thread=True)
        return process, pipeline

    def _serve_one(self, pipeline, value):
        responses = queue.Queue()
        stream = pipeline.create_stream(
            f"probe{value}", queue_response=responses)
        pipeline.create_frame(stream, _frame(value))
        outputs = responses.get(timeout=30)[2]
        pipeline.destroy_stream(f"probe{value}")
        return np.asarray(outputs["y"])

    def test_handoff_is_bit_identical_and_really_transfers(self):
        source_process, source = self._pipeline("source")
        sibling_process, sibling = self._pipeline("sibling")
        try:
            baseline = self._serve_one(source, 3.0)
            # mutate the source's params AFTER setup: a hand-off that
            # secretly re-ran setup() would reproduce the fresh init,
            # not these values
            element = source.elements["affine"]
            element.state = jax.tree_util.tree_map(
                lambda leaf: leaf * 3.0, element.state)
            mutated = self._serve_one(source, 3.0)
            assert not np.array_equal(baseline, mutated)

            exported = source.export_weights()
            assert set(exported) == {"affine"}
            # the descriptor tree is wire-safe (the OS-process path
            # ships it through a JSON file)
            exported = json.loads(json.dumps(exported))
            from aiko_services_tpu.observe.metrics import get_registry
            registry = get_registry()
            connections_before = registry.counter(
                "transfer.connections").value
            batched_before = registry.counter(
                "transfer.batched_fetches").value
            installed = sibling.import_weights(exported)
            assert installed == ["affine"]
            handed_off = self._serve_one(sibling, 3.0)
            assert np.array_equal(handed_off, mutated)  # bit-identical
            # the whole hand-off rode fetch_many: ONE connection per
            # producing peer, not one TCP handshake per leaf
            leaves = json.dumps(exported).count('"__tensorref__"')
            assert leaves >= 2
            connections = (registry.counter("transfer.connections").value
                           - connections_before)
            assert connections < leaves, (
                f"{connections} connections for {leaves} leaves: the "
                f"hand-off is not batching")
            assert (registry.counter("transfer.batched_fetches").value
                    > batched_before)
        finally:
            source_process.terminate()
            sibling_process.terminate()

    def test_missing_element_is_skipped_not_fatal(self):
        source_process, source = self._pipeline("source2")
        try:
            self._serve_one(source, 1.0)  # state exists only once served
            exported = source.export_weights()
            exported["ghost"] = exported["affine"]
            other_process, other = self._pipeline("other2")
            try:
                assert other.import_weights(exported) == ["affine"]
            finally:
                other_process.terminate()
        finally:
            source_process.terminate()


# -- scale up under load -----------------------------------------------------


class TestScaleUp:
    def test_overload_spawns_replica_and_completes_all(self):
        gateway_process = Process(transport_kind="loopback")
        gateway = Gateway(gateway_process,
                          policy="max_inflight=2;queue=128",
                          router_seed=7)
        processes, _ = _attach_pool(
            gateway, 1, class_name="SlowScale",
            element_parameters={"work_ms": 20})
        processes.append(gateway_process)
        factory = InProcessReplicaFactory(
            _definition("template", class_name="SlowScale",
                        element_parameters={"work_ms": 20}),
            warmup=_frame(0.0))
        gateway.enable_autoscale(
            "min_replicas=1;max_replicas=2;high_water=0.5;"
            "low_water=0.01;cooldown=0.2;interval=0.05;"
            "warm_start=false", factory)
        for process in processes:
            process.run(in_thread=True)
        try:
            responses = queue.Queue()
            streams_n, per_stream = 4, 8
            for index in range(streams_n):
                gateway.submit_stream(f"s{index}",
                                      queue_response=responses)
            for frame_id in range(per_stream):
                for index in range(streams_n):
                    gateway.submit_frame(f"s{index}", _frame(frame_id),
                                         frame_id=frame_id)
            # the burst saturates the single replica; the controller
            # must grow the pool without any manual attach
            wait_for(lambda: len(gateway.replicas) == 2, timeout=60)
            assert gateway.telemetry.scale_ups.value >= 1
            statuses = [responses.get(timeout=60)[3]
                        for _ in range(streams_n * per_stream)]
            assert statuses == ["ok"] * (streams_n * per_stream)
            spawn = gateway.autoscaler.spawns[0]
            assert spawn["time_to_healthy_ms"] > 0
            assert gateway.telemetry.last_time_to_healthy_ms is not None
            # new streams spread over the grown pool
            gateway.submit_stream("late", queue_response=responses)
            wait_for(lambda: "late" in gateway.streams, timeout=10)
        finally:
            # gateway first: its stop() retires every factory-owned
            # (autoscaler-spawned) replica process
            for process in reversed(processes):
                process.terminate()


# -- warm start --------------------------------------------------------------


class TestWarmStart:
    @pytest.mark.parametrize("prewarmed", [False, True])
    def test_warm_spawn_zero_recompiles_and_identical_outputs(
            self, tmp_path, prewarmed):
        cache_dir = str(tmp_path / "compile_cache")
        if prewarmed:
            # the bench now keeps ONE fixed cache directory across
            # runs, so the "cold" replica may find every shape already
            # there.  The proof reads per-spawn hit/miss DELTAS, so a
            # directory warmed by an earlier fleet must not break it
            earlier = InProcessReplicaFactory(
                lambda name: _definition(name, class_name="SlowAffine",
                                         element="affine"),
                warmup=_frame(0.0), compile_cache=cache_dir)
            earlier_ready = queue.Queue()
            earlier.spawn("earlier", ready=lambda handle, info:
                          earlier_ready.put((handle, info)))
            handle, info = earlier_ready.get(timeout=120)
            assert handle is not None and info["cache_misses"] > 0
            earlier.retire(handle)
        gateway_process = Process(transport_kind="loopback")
        gateway = Gateway(gateway_process,
                          policy="max_inflight=2;queue=256",
                          router_seed=7)
        factory = InProcessReplicaFactory(
            lambda name: _definition(name, class_name="SlowAffine",
                                     element="affine"),
            warmup=_frame(0.0), compile_cache=cache_dir)

        # replica0 comes up COLD through the same factory: it pays the
        # XLA compiles once and populates the shared cache
        cold_ready = queue.Queue()
        factory.spawn("replica0",
                      ready=lambda handle, info: cold_ready.put(
                          (handle, info)))
        handle0, info0 = cold_ready.get(timeout=120)
        assert handle0 is not None, info0
        if prewarmed:
            assert info0["cache_misses"] == 0 and info0["cache_hits"] > 0
        else:
            assert info0["cache_misses"] > 0  # the cold arm compiled
        gateway.attach_replica(handle0.pipeline)

        # mutate replica0's params so only a REAL hand-off can match
        element = handle0.pipeline.elements["affine"]
        element.state = jax.tree_util.tree_map(
            lambda leaf: leaf * 3.0, element.state)

        gateway.enable_autoscale(
            "min_replicas=1;max_replicas=2;high_water=0.5;"
            "low_water=0.01;cooldown=0.2;interval=0.05", factory)
        gateway_process.run(in_thread=True)
        try:
            responses = queue.Queue()
            streams_n, per_stream = 4, 6
            for index in range(streams_n):
                gateway.submit_stream(f"s{index}",
                                      queue_response=responses)
            for frame_id in range(per_stream):
                for index in range(streams_n):
                    gateway.submit_frame(f"s{index}", _frame(frame_id),
                                         frame_id=frame_id)
            wait_for(lambda: len(gateway.replicas) == 2, timeout=120)
            for _ in range(streams_n * per_stream):
                assert responses.get(timeout=120)[3] == "ok"
            spawn = gateway.autoscaler.spawns[0]
            assert spawn["warm"] is True
            assert spawn["imported_elements"] == ["affine"]
            # the warm-start proof: a populated compile cache + sibling
            # hand-off means the new replica served its warmup frame
            # with ZERO recompiles of fleet-known shapes
            assert spawn["cache_misses"] == 0, spawn
            assert spawn["cache_hits"] > 0, spawn
            assert gateway.telemetry.warm_spawns.value == 1

            warm_replica = next(
                replica for replica in gateway.replicas.values()
                if replica.name != "replica0")
            assert warm_replica.warm is True
            # hand-off correctness: the warm replica's outputs are
            # bit-identical to the mutated source, frame for frame
            probe = _frame(7.0)
            source_out = self._direct(handle0.pipeline, probe)
            warm_out = self._direct(warm_replica.pipeline, probe)
            assert np.array_equal(source_out, warm_out)
        finally:
            # gateway stop retires the autoscaler-spawned replica;
            # replica0 was factory-spawned directly, so it is ours
            gateway_process.terminate()
            handle0.process.terminate()

    @staticmethod
    def _direct(pipeline, frame_data):
        responses = queue.Queue()
        stream_id = f"direct_{pipeline.name}"
        stream = pipeline.create_stream(stream_id,
                                        queue_response=responses)
        pipeline.create_frame(stream, dict(frame_data))
        outputs = responses.get(timeout=60)[2]
        pipeline.destroy_stream(stream_id)
        return np.asarray(outputs["y"])


# -- loss-free scale-down ----------------------------------------------------


class TestScaleDown:
    def _run(self, drain_mid_stream: bool):
        """20 frames through a 2-replica pool; optionally drain the
        stream's pinned replica after frame 9 (extends the seeded
        replica_kill family: same harness, graceful trigger)."""
        gateway_process = Process(transport_kind="loopback")
        gateway = Gateway(gateway_process,
                          policy="max_inflight=4;queue=64",
                          router_seed=7)
        processes, _ = _attach_pool(gateway, 2)
        processes.append(gateway_process)
        for process in processes:
            process.run(in_thread=True)
        try:
            responses = queue.Queue()
            gateway.submit_stream("s1", {}, queue_response=responses)
            wait_for(lambda: "s1" in gateway.streams, timeout=10)
            owner = gateway.streams["s1"].replica.topic_path
            for frame_id in range(20):
                gateway.submit_frame("s1", _frame(frame_id))
                if drain_mid_stream and frame_id == 9:
                    # mailbox routing keeps the drain ordered with the
                    # in-flight submissions, like every other command
                    gateway.post_message("drain_replica", [owner])
            got = {}
            for _ in range(20):
                _, frame_id, outputs, status = responses.get(timeout=60)
                assert status == "ok"
                got[frame_id] = np.asarray(outputs["y"]).tolist()
            summary = gateway.telemetry.summary()
            return got, summary
        finally:
            for process in processes:
                process.terminate()

    def test_drain_mid_stream_is_bit_identical_to_unscaled_run(self):
        baseline, base_summary = self._run(False)
        reset_brokers()
        drained, drain_summary = self._run(True)
        assert set(drained) == set(baseline)   # zero lost frames
        assert drained == baseline             # bit-identical replay
        assert base_summary["pool_size"] == 2
        assert drain_summary["pool_size"] == 1
        assert drain_summary["completed"] == 20
        assert drain_summary["replica_deaths"] == 0  # graceful, not a death

    def test_low_watermark_drains_pool_to_min(self):
        gateway_process = Process(transport_kind="loopback")
        gateway = Gateway(gateway_process,
                          policy="max_inflight=4;queue=16")
        processes, _ = _attach_pool(gateway, 2)
        processes.append(gateway_process)
        gateway.enable_autoscale(
            "min_replicas=1;max_replicas=2;high_water=0.9;"
            "low_water=0.5;cooldown=0.1;interval=0.05;drain_timeout=0",
            None)
        for process in processes:
            process.run(in_thread=True)
        try:
            # idle pool: utilization 0 <= low_water -> drain ONE (min
            # floor holds the last replica)
            wait_for(lambda: len(gateway.replicas) == 1, timeout=30)
            time.sleep(0.3)  # more ticks must not dip below min
            assert len(gateway.replicas) == 1
            assert gateway.telemetry.scale_downs.value == 1
            # the pool still serves
            responses = queue.Queue()
            gateway.submit_stream("s", {}, queue_response=responses)
            gateway.submit_frame("s", _frame(1.0))
            assert responses.get(timeout=30)[3] == "ok"
        finally:
            for process in processes:
                process.terminate()


# -- pool observability ------------------------------------------------------


class TestPoolObservability:
    def test_summary_pool_and_dashboard_row_and_status(self):
        from aiko_services_tpu.dashboard import _gateway_plugin

        gateway_process = Process(transport_kind="loopback")
        gateway = Gateway(gateway_process,
                          policy="max_inflight=4;queue=16",
                          metrics_interval=0.2)
        processes, _ = _attach_pool(gateway, 2)
        processes.append(gateway_process)
        gateway.enable_autoscale(
            "min_replicas=2;max_replicas=2;high_water=0.9;"
            "low_water=0.01", None)
        for process in processes:
            process.run(in_thread=True)
        try:
            summary = gateway.telemetry.summary()
            assert summary["pool_size"] == 2
            assert set(summary["pool"]) == {"replica0", "replica1"}
            row = summary["pool"]["replica0"]
            assert row["state"] == "live"
            assert row["warm"] is False
            assert "inflight" in row and "queue_depth" in row

            class _Model:
                selected_share = {"replica_count": 2, "stream_count": 0,
                                  "policy": "", "metrics": summary}

            lines = _gateway_plugin(_Model())
            pool_lines = [line for line in lines if "pool:" in line]
            assert pool_lines and "scale_up" in pool_lines[0]
            assert any("replica0" in line and "cold" in line
                       for line in lines)
        finally:
            for process in processes:
                process.terminate()

    def test_system_status_pool_discovers_gateway(self, tmp_path):
        from click.testing import CliRunner
        from aiko_services_tpu.cli import main as cli_main
        from aiko_services_tpu.runtime import Registrar

        registrar_process = Process(transport_kind="loopback")
        Registrar(registrar_process, search_timeout=0.05)
        registrar_process.run(in_thread=True)
        gateway_process = Process(transport_kind="loopback")
        gateway = Gateway(gateway_process, metrics_interval=0.2)
        gateway_process.run(in_thread=True)
        try:
            wait_for(lambda: gateway.ec_producer is not None, timeout=10)
            result = CliRunner().invoke(cli_main, [
                "system", "status", "--pool", "--transport", "loopback",
                "--wait", "5", "--state-file",
                str(tmp_path / "none.json")])
            # success-path content ONLY: the no-discovery message also
            # contains the word "pool", which once masked a filter bug
            assert gateway.topic_path in result.output, result.output
            assert "replicas:" in result.output, result.output
            assert "no gateway services" not in result.output
        finally:
            gateway_process.terminate()
            registrar_process.terminate()


# -- satellites: ProcessManager env overlay + process factory glue -----------


class TestProcessManagerEnv:
    def test_env_overlay_merges_and_removes(self, monkeypatch):
        monkeypatch.setenv("AIKO_ENV_KEEP", "inherited")
        monkeypatch.setenv("AIKO_ENV_DROP", "doomed")
        exits = []
        manager = ProcessManager(
            lambda process_id, code: exits.append((process_id, code)))
        probe = ("import os, sys; sys.exit(0 if "
                 "os.environ.get('AIKO_ENV_NEW') == 'set' and "
                 "os.environ.get('AIKO_ENV_KEEP') == 'inherited' and "
                 "'AIKO_ENV_DROP' not in os.environ else 3)")
        manager.spawn("probe", sys.executable, arguments=["-c", probe],
                      use_interpreter=False,
                      env={"AIKO_ENV_NEW": "set", "AIKO_ENV_DROP": None})
        wait_for(lambda: exits, timeout=30)
        assert exits[0] == ("probe", 0)
        manager.terminate()

    def test_process_factory_spawn_env_and_handoff_file(
            self, tmp_path, monkeypatch):
        """ProcessReplicaFactory glue, hermetically: the lifecycle
        manager is a recorder, so the test asserts exactly what a real
        spawn would inherit -- the compile-cache env overlay, the
        warm-weights descriptor file, and name-keyed retirement."""

        class _Recorder:
            def __init__(self):
                self.created, self.deleted = [], []

            def create_client(self, command, arguments,
                              use_interpreter=True, env=None):
                self.created.append((command, list(arguments), env))
                return len(self.created) - 1

            def delete_client(self, client_id):
                self.deleted.append(client_id)

            def client_state(self, client_id):
                return "running"

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        recorder = _Recorder()
        factory = ProcessReplicaFactory(
            recorder, "/tmp/defn.json", transport="mqtt",
            env={"JAX_PLATFORMS": "cpu"},
            compile_cache=str(tmp_path / "cache"))
        exports = {"affine": {"w": {"__tensorref__": {
            "host": "127.0.0.1", "port": 1, "key": "00" * 16,
            "dtype": "float32", "shape": [1, 2]}}}}
        launch = factory.spawn("gw-r1", warm_source=exports)
        launch.join(timeout=30)
        command, arguments, env = recorder.created[0]
        assert command == sys.executable
        assert arguments[:3] == ["-m", "aiko_services_tpu", "pipeline"]
        assert "--name" in arguments and "gw-r1" in arguments
        assert "--transport" in arguments and "mqtt" in arguments
        assert env["JAX_PLATFORMS"] == "cpu"
        assert env["JAX_COMPILATION_CACHE_DIR"] == str(tmp_path / "cache")
        with open(env["AIKO_WARM_WEIGHTS"]) as handoff:
            assert json.load(handoff) == exports
        os.unlink(env["AIKO_WARM_WEIGHTS"])
        factory.retire("gw-r1")
        assert recorder.deleted == [0]
        factory.retire("gw-r1")  # idempotent
        assert recorder.deleted == [0]

    def test_process_factory_reports_a_child_that_dies_first(
            self, tmp_path):
        """A replica child that exits (or misses its handshake lease)
        before becoming healthy is a REPORTED spawn failure -- what a
        second OS process on a one-chip host amounts to, since it cannot
        open the device its parent holds.  Here the child dies at once
        (its definition does not exist); a real LifeCycleManager drops
        the client and the factory hands the autoscaler's `ready` the
        error instead of leaving the spawn pending."""
        from aiko_services_tpu.runtime import LifeCycleManager
        manager_process = Process(transport_kind="loopback")
        manager = LifeCycleManager(manager_process, "lcm_replicas",
                                   handshake_lease_time=20.0)
        manager_process.run(in_thread=True)
        try:
            factory = ProcessReplicaFactory(
                manager, str(tmp_path / "no_such_definition.json"),
                env={"JAX_PLATFORMS": "cpu"})
            reports = queue.Queue()
            factory.spawn("gw-r9", ready=lambda handle, info:
                          reports.put((handle, info)))
            handle, info = reports.get(timeout=60)
            assert handle is None
            assert info["name"] == "gw-r9"
            assert "before becoming healthy" in info["error"]
            assert manager.clients == {}
            factory.retire("gw-r9")  # nothing left to retire
        finally:
            manager_process.terminate()
