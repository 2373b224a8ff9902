# Start-up measured from inside (runtime/compile_cache.py; the setup.*
# rows of observe/trace.py's table): a toy pipeline with a continuous
# LMGenerate and a closed-batch ComputeElement is built and served twice
# under a compile cache of its own, and what the program wrote is read
# back through jax.profiler on the CPU backend and from the
# process-global registry.  The registry is the OS process's, so every
# case reads differences.

import queue
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import monitoring

from aiko_services_tpu import PROCESS_EPOCH
from aiko_services_tpu.observe.metrics import get_registry
from aiko_services_tpu.pipeline import (
    ComputeElement, PipelineElement, StreamEvent, create_pipeline)
from aiko_services_tpu.runtime import (
    Process, disable_compile_cache, enable_compile_cache)
from aiko_services_tpu.transport import reset_brokers
from test_decode import lm_definition
from test_program_spans import _profiled

SETUP_HISTOGRAMS = ("setup.weights_s", "setup.state_s", "setup.compile_s")
SETUP_COUNTERS = ("setup.cache_hits", "setup.cache_requests")
PROMPT = np.arange(1, 6, dtype=np.int32)[None]


class Weigh(ComputeElement):
    """Closed-batch, with a state of its own: served through the
    element's own jitted call."""

    def setup(self):
        return {"w": jnp.ones((4, 8), jnp.float32)}

    def compute(self, state, generated):
        return {"weighed": generated.astype(jnp.float32).sum()
                * state["w"].sum()}


class Stray(PipelineElement):
    """Compiles a program of its own on the loop's thread, inside no
    bracket: the code path nobody instrumented."""

    strayed = staticmethod(jax.jit(lambda x: jnp.tanh(x) * 3.0 + 1.0))

    def process_frame(self, stream, weighed):
        return StreamEvent.OKAY, {"out": np.asarray(self.strayed(weighed))}


def _local(class_name):
    return {"local": {"module": "tests.test_setup_spans",
                      "class_name": class_name}}


def _definition():
    definition = lm_definition(
        {"continuous": True, "decode_slots": 2, "kv_block_size": 8,
         "max_new_tokens": 4})
    definition["parameters"] = {"metrics_interval": 0}
    definition["graph"] = ["(lm (weigh (stray)))"]
    definition["elements"] += [
        {"name": "weigh", "input": [{"name": "generated"}],
         "output": [{"name": "weighed"}], "deploy": _local("Weigh")},
        {"name": "stray", "input": [{"name": "weighed"}],
         "output": [{"name": "out"}], "deploy": _local("Stray")}]
    return definition


def _records():
    """The process-global start-up records, as numbers."""
    registry = get_registry()
    records = {}
    for name in SETUP_HISTOGRAMS:
        histogram = registry.histogram(name)
        records[name] = (histogram.count, histogram.total)
    for name in SETUP_COUNTERS:
        records[name] = registry.counter(name).value
    records["ready"] = registry.gauge("setup.ready_s").value
    return records


def _serve(pipeline, responses):
    stream = pipeline.streams.get("s") or pipeline.create_stream(
        "s", queue_response=responses, grace_time=300)
    pipeline.create_frame(stream, {"tokens": PROMPT})
    return responses.get(timeout=180)


class _Build:
    """One pipeline built and served once under the profiler; then a
    second request of the same shapes, with jax's events counted."""

    def __init__(self, directory):
        self.before = _records()
        self.began = time.perf_counter() - PROCESS_EPOCH
        self.recorded, _ = _profiled(directory, self._run)
        self.after = _records()

    def _run(self):
        process = Process(transport_kind="loopback")
        self.pipeline = create_pipeline(process, _definition())
        self.loop_name = process.event.name
        self.loop_thread = process.run(in_thread=True)
        responses = queue.Queue()
        assert _serve(self.pipeline, responses)[2]["out"] is not None
        self.served = _records()
        events = []

        def heard(event, *_args, **_kwargs):
            if threading.get_ident() == self.loop_thread.ident:
                events.append(event)

        monitoring.register_event_duration_secs_listener(heard)
        try:
            _serve(self.pipeline, responses)
        finally:
            monitoring.unregister_event_duration_listener(heard)
        self.events_of_the_second_request = events
        lm = self.pipeline.elements["lm"]
        self.state_bytes = {
            name: sum(int(leaf.nbytes) for leaf in
                      jax.tree_util.tree_leaves(element.state))
            for name, element in self.pipeline.elements.items()
            if isinstance(element, ComputeElement)}
        self.pool_bytes = sum(
            int(leaf.nbytes) for leaf in lm._engine.pool.values())
        process.terminate()
        self.loop_thread.join(timeout=30)

    def taken(self, name):
        """A histogram's (samples, seconds) this build added."""
        return (self.after[name][0] - self.before[name][0],
                self.after[name][1] - self.before[name][1])

    def marks(self, what):
        return [event[4] for event in self.recorded.named("compile")
                if event[4]["what"] == what]


@pytest.fixture(scope="module")
def builds(tmp_path_factory):
    """The same pipeline twice: into an empty compile cache, then, the
    in-memory caches dropped, out of it."""
    reset_brokers()
    enable_compile_cache(str(tmp_path_factory.mktemp("cache")))
    jax.clear_caches()
    try:
        cold = _Build(tmp_path_factory.mktemp("cold"))
        jax.clear_caches()
        warm = _Build(tmp_path_factory.mktemp("warm"))
    finally:
        disable_compile_cache()
        jax.clear_caches()
        reset_brokers()
    return {"cold": cold, "warm": warm}


@pytest.mark.parametrize("node", ["lm", "weigh"])
def test_weights_are_one_interval_an_element(builds, node):
    cold = builds["cold"]
    [span] = [event[4] for event in cold.recorded.named("setup.weights")
              if event[4]["node"] == node]
    assert span["source"] == "init"
    assert int(span["bytes"]) == cold.state_bytes[node] > 0
    assert int(span["leaves"]) == len(jax.tree_util.tree_leaves(
        cold.pipeline.elements[node].state))
    assert int(span["compile_us"]) >= 0
    assert cold.taken("setup.weights_s")[0] == 2


def test_the_pool_is_one_state_interval(builds):
    cold = builds["cold"]
    [event] = cold.recorded.named("setup.state")
    span = event[4]
    assert (span["node"], span["what"]) == ("lm", "pool")
    assert int(span["bytes"]) == cold.pool_bytes > 0
    assert int(span["blocks"]) == cold.pipeline.elements[
        "lm"]._engine.blocks.num_blocks
    samples, seconds = cold.taken("setup.state_s")
    assert samples == 1
    assert seconds == pytest.approx(event[3] / 1e9, rel=0.5, abs=0.01)


@pytest.mark.parametrize("what,program", [
    ("paged_prefill", "jit(paged_prefill)"),
    ("paged_decode_step", "jit(paged_decode_step)"),
    ("element", "jit(_call)")])
def test_a_call_that_compiled_is_marked_with_jaxs_durations(
        builds, what, program):
    [mark] = builds["cold"].marks(what)
    assert mark["program"] == program and int(mark["programs"]) == 1
    assert mark["cache"] == "miss" and "retrieval_us" not in mark
    parts = [int(mark[part])
             for part in ("trace_us", "lower_us", "backend_us")]
    assert all(part > 0 for part in parts)
    assert sum(parts) == int(mark["waited_us"])


def test_compile_seconds_are_the_marks_sum(builds):
    cold = builds["cold"]
    marks = [event[4] for event in cold.recorded.named("compile")]
    samples, seconds = cold.taken("setup.compile_s")
    assert samples == len(marks) == 4
    assert seconds == pytest.approx(
        sum(int(mark["waited_us"]) for mark in marks) / 1e6, abs=1e-4)


def test_the_intervals_are_disjoint(builds):
    """What lies before this build is its boot; what it named fits
    between that and the newest interval's close."""
    for build in builds.values():
        named = sum(build.taken(name)[1] for name in SETUP_HISTOGRAMS)
        assert named > 0
        assert build.began + named <= build.after["ready"]
        assert build.after["ready"] <= (time.perf_counter()
                                        - PROCESS_EPOCH)
    assert 0 < get_registry().gauge("setup.boot_s").value \
        <= builds["cold"].after["ready"]


def test_what_compiled_inside_the_weights_is_not_in_compile_s(builds):
    """The eager initialiser's small programs ride the weights span as
    `compile_us`; `setup.compile_s` holds the marks' seconds alone."""
    cold = builds["cold"]
    inside = sum(int(event[4]["compile_us"]) for event in
                 cold.recorded.named("setup.weights")) / 1e6
    assert 0 < inside <= cold.taken("setup.weights_s")[1]


def test_a_compile_on_the_tests_own_thread_moves_nothing(builds):
    before = _records()
    jax.jit(lambda x: jnp.cos(x) * 5.0 - 2.0)(
        jnp.ones((3, 7))).block_until_ready()
    assert _records() == before


def test_a_compile_outside_every_bracket_on_a_loops_thread_is_marked(
        builds):
    [mark] = builds["cold"].marks("unbracketed")
    assert mark["node"] == builds["cold"].loop_name
    assert "lambda" in mark["program"]
    assert int(mark["backend_us"]) > 0


@pytest.mark.parametrize("what", [
    "paged_prefill", "paged_decode_step", "element", "unbracketed"])
def test_the_second_build_reads_the_cache(builds, what):
    [mark] = builds["warm"].marks(what)
    assert mark["cache"] == "hit"
    assert int(mark["retrieval_us"]) > 0 and "backend_us" not in mark
    assert int(mark["saved_us"]) != 0
    assert (int(mark["trace_us"]) + int(mark["lower_us"])
            + int(mark["retrieval_us"])) == int(mark["waited_us"])


def test_the_cache_counters_say_which_build_was_warm(builds):
    cold, warm = builds["cold"], builds["warm"]

    def share(build):
        hits = (build.after["setup.cache_hits"]
                - build.before["setup.cache_hits"])
        requests = (build.after["setup.cache_requests"]
                    - build.before["setup.cache_requests"])
        assert requests > 0
        return hits / requests

    assert share(cold) == 0.0
    assert share(warm) == 1.0


def test_a_warmed_bucket_marks_nothing_and_wakes_no_listener(builds):
    for build in builds.values():
        assert build.events_of_the_second_request == []
        assert build.after == build.served
