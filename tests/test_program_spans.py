# Program spans on the profiler's clock (observe/trace.py's PROGRAM SPANS
# table): every span is emitted with its arguments on a toy two-stage
# graph and a toy served engine, read back through jax.profiler on the CPU
# backend and from the Tracer ring; closing marks give the right interval;
# `telemetry: false` emits nothing; an old sender's trace context still
# parses; the fused program's lowered text carries the node's scope.

import glob
import queue
import subprocess
import sys
import threading
import time

import jax
import numpy as np
import pytest

from aiko_services_tpu.observe import trace as trace_module
from aiko_services_tpu.observe.trace import (
    NO_SPAN, NO_SPANS, TRACE_CONTEXT_KEY, Tracer, ingress_wait_s,
    make_trace_context, program_mark, program_span)
from aiko_services_tpu.pipeline import (
    ComputeElement, PipelineElement, StreamEvent, create_pipeline)
from aiko_services_tpu.runtime import Process
from aiko_services_tpu.runtime.event import EventEngine
from aiko_services_tpu.serve import Gateway
from aiko_services_tpu.transport import reset_brokers
from aiko_services_tpu.utils import parse
from helpers import wait_for
from test_decode import lm_definition

PREFIX = "aiko:"
HOLD_MS = 40


@pytest.fixture(autouse=True)
def clean_brokers():
    reset_brokers()
    yield
    reset_brokers()


class Doubler(ComputeElement):
    """Pure compute: the scheduler runs its groups through the fused
    whole-group program."""

    def compute(self, state, x):
        return {"y": x * 2.0}


class AddOne(ComputeElement):
    def compute(self, state, y):
        return {"z": y + 1.0}


class Tail(PipelineElement):
    """No micro_batch: an inline element call."""

    def process_frame(self, stream, z):
        return StreamEvent.OKAY, {"out": np.asarray(z)}


def _local(class_name):
    return {"local": {"module": "tests.test_program_spans",
                      "class_name": class_name}}


def graph_definition(telemetry=True):
    scheduling = {"micro_batch": 2, "micro_batch_wait_ms": HOLD_MS}
    return {
        "name": "two_stage",
        "parameters": {"telemetry": telemetry, "metrics_interval": 0},
        "graph": ["(first (second (tail)))"],
        "elements": [
            {"name": "first", "input": [{"name": "x"}],
             "output": [{"name": "y"}], "parameters": scheduling,
             "deploy": _local("Doubler")},
            {"name": "second", "input": [{"name": "y"}],
             "output": [{"name": "z"}], "parameters": scheduling,
             "deploy": _local("AddOne")},
            {"name": "tail", "input": [{"name": "z"}],
             "output": [{"name": "out"}], "deploy": _local("Tail")},
        ],
    }


class Recorded:
    """The `aiko:` events of one profiler session: (name, line, start_ns,
    duration_ns, stats), and what encloses what on a thread's line."""

    def __init__(self, directory):
        [path] = glob.glob(f"{directory}/plugins/profile/*/*.xplane.pb")
        profile = jax.profiler.ProfileData.from_file(path)
        self.events = []
        lines = [line for plane in profile.planes
                 if plane.name == "/host:CPU" for line in plane.lines]
        for index, line in enumerate(lines):
            for event in line.events:
                if event.name.startswith(PREFIX):
                    self.events.append((
                        event.name[len(PREFIX):], index, event.start_ns,
                        event.duration_ns, dict(event.stats)))
        self.events.sort(key=lambda event: event[2])

    def named(self, name):
        return [event for event in self.events if event[0] == name]

    def names(self):
        return {event[0] for event in self.events}

    def inside(self, outer):
        """Events on `outer`'s line that lie within it."""
        _, line, start, duration, _ = outer
        return [event for event in self.events
                if event is not outer and event[1] == line
                and event[2] >= start
                and event[2] + event[3] <= start + duration]


def _profiled(directory, body):
    jax.profiler.start_trace(str(directory))
    try:
        result = body()
    finally:
        jax.profiler.stop_trace()
    return Recorded(directory), result


# -- the seam itself ----------------------------------------------------------

class TestSeam:
    def test_scoped_span_lands_on_the_frame_trace(self):
        frame_trace = Tracer(pid=7).begin("s", 1)
        with program_span("engine.prefill", frame_trace, bucket=32) as span:
            time.sleep(0.002)
            span.set(true_len=20)
        [(kind, name, category, start, duration, args)] = frame_trace.events
        assert (kind, name, category) == ("X", "aiko:engine.prefill",
                                          "program")
        assert duration >= 2000 and start >= frame_trace.start_us
        assert args == {"bucket": 32, "true_len": 20}

    def test_closing_mark_is_the_interval_that_ends_now(self):
        frame_trace = Tracer(pid=7).begin("s", 1)
        before = trace_module.now_us()
        program_mark("ingress", 0.25, frame_trace, stream="s")
        after = trace_module.now_us()
        [(kind, name, category, start, duration, args)] = frame_trace.events
        assert (kind, name, category) == ("X", "aiko:ingress", "program")
        assert duration == pytest.approx(250_000)
        assert before <= start + duration <= after
        assert args == {"stream": "s", "waited_us": 250_000}

    def test_mark_without_a_wait_is_an_instant(self):
        frame_trace = Tracer(pid=7).begin("s", 1)
        program_mark("engine.submit", None, frame_trace, stream="s")
        [(kind, name, _, _, duration, args)] = frame_trace.events
        assert (kind, name, duration) == ("i", "aiko:engine.submit", 0.0)
        assert args == {"stream": "s"}

    def test_the_disabled_seam_does_nothing(self):
        with NO_SPAN as span:
            span.set(anything=1)
        with NO_SPANS.span("engine.step", waiting=1) as span:
            assert span is NO_SPAN
        assert NO_SPANS.mark("compile", node="n", what="w") is None
        assert NO_SPANS.record_engine_submit(("s", 0, 0)) is None
        assert NO_SPANS.record_chunk(("s", 0, 0), 0, 8, 0.1, 0.1) is None
        assert NO_SPANS.enabled is False

    def test_without_jax_in_the_process_a_span_is_a_no_op(self):
        """runtime/ and observe/ import no jax, and the seam imports
        none on a control-plane process's behalf."""
        code = (
            "import sys\n"
            "import aiko_services_tpu.runtime.event\n"
            "from aiko_services_tpu.observe.trace import (\n"
            "    program_mark, program_span)\n"
            "with program_span('loop.idle', loop='p') as span:\n"
            "    span.set(more=1)\n"
            "program_mark('ingress', 0.5, stream='s')\n"
            "assert 'jax' not in sys.modules, 'jax was imported'\n"
            "print('ok')\n")
        done = subprocess.run([sys.executable, "-c", code],
                              capture_output=True, text=True, timeout=120)
        assert done.returncode == 0 and "ok" in done.stdout, done.stderr

    def test_spans_reach_the_profiler_with_their_arguments(self, tmp_path):
        def body():
            with program_span("engine.step", waiting=2) as span:
                with program_span("engine.decode", decoding=3):
                    time.sleep(0.001)
                span.set(active=3)
            program_mark("engine.chunk", 0.0125, offset=0, tokens=8)

        recorded, _ = _profiled(tmp_path, body)
        [step] = recorded.named("engine.step")
        assert step[4] == {"waiting": 2, "active": 3}
        assert [event[0] for event in recorded.inside(step)] == [
            "engine.decode"]
        [chunk] = recorded.named("engine.chunk")
        assert chunk[4] == {"offset": 0, "tokens": 8, "waited_us": 12500}
        assert chunk[2] >= step[2] + step[3]


# -- the trace context's dispatch time ----------------------------------------

class TestDispatchTime:
    def test_dispatched_context_carries_the_senders_clock(self):
        frame_trace = Tracer(pid=3).begin("s", 0)
        plain = make_trace_context(frame_trace)
        assert plain == {"trace_id": frame_trace.trace_id,
                         "span_id": frame_trace.span_id}
        before = time.time() * 1e6
        sent = make_trace_context(frame_trace, dispatched=True)
        assert set(sent) == {"trace_id", "span_id", "sent_unix_us"}
        assert before - 1 <= sent["sent_unix_us"] <= time.time() * 1e6 + 1
        time.sleep(0.01)
        assert 0.01 <= ingress_wait_s(sent) < 5.0

    @pytest.mark.parametrize("context", [
        None, {}, {"trace_id": "a-1", "span_id": "a.1"},
        {"trace_id": "a-1", "span_id": "a.1", "sent_unix_us": "soon"},
        {"trace_id": "a-1", "span_id": "a.1", "sent_unix_us": None}])
    def test_a_context_without_a_dispatch_time_still_parses(self, context):
        """An old sender (or one whose hop does not dispatch) sends no
        time: the frame continues the trace and no wait is invented."""
        assert ingress_wait_s(context) is None
        process = Process(transport_kind="loopback")
        pipeline = create_pipeline(process, graph_definition())
        process.run(in_thread=True)
        try:
            responses = queue.Queue()
            pipeline.create_stream("s", queue_response=responses)
            data = {"x": np.ones((2, 4), np.float32)}
            if context is not None:
                data[TRACE_CONTEXT_KEY] = context
            pipeline.post_message("process_frame",
                                  [{"stream_id": "s"}, data])
            _, _, outputs = responses.get(timeout=60)
            assert TRACE_CONTEXT_KEY not in outputs
            [done] = pipeline.telemetry.tracer.completed
            assert done.ingress_wait_s is None
            assert not [event for event in done.events
                        if event[1] == "aiko:ingress"]
            if context and context.get("trace_id"):
                assert done.trace_id == context["trace_id"]
        finally:
            process.terminate()

    def test_a_clock_that_runs_behind_never_gives_a_negative_wait(self):
        future = {"sent_unix_us": (time.time() + 30) * 1e6}
        assert ingress_wait_s(future) == 0.0


# -- the event loop's waits ---------------------------------------------------

class _FakeSpan:
    def __init__(self, log, name, args):
        self.log, self.name, self.args = log, name, args

    def __enter__(self):
        self.started = time.perf_counter()
        return self

    def __exit__(self, *exc_info):
        self.log.append((self.name, self.args,
                         time.perf_counter() - self.started))
        return False


class TestLoopWaits:
    def _engine(self):
        engine = EventEngine(name="loop-under-test")
        log = []
        engine.trace_waits(
            lambda name, **args: _FakeSpan(log, name, args))
        return engine, log

    def test_an_untraced_loop_asks_for_no_span(self):
        engine = EventEngine()
        assert engine._wait_span is None
        assert not hasattr(engine, "mailbox_high_water")

    def test_idle_and_hold_waits_are_named(self):
        engine, log = self._engine()
        fired = threading.Event()

        def hold():
            engine.remove_timer_handler(hold)
            fired.set()

        hold.hold_node = "asr"
        thread = engine.loop_in_thread()
        time.sleep(0.12)                   # nothing due: idle slices
        engine.add_timer_handler(hold, 0.03)
        assert fired.wait(5)
        time.sleep(0.02)
        engine.terminate()
        thread.join(5)
        idle = [entry for entry in log if entry[0] == "loop.idle"]
        held = [entry for entry in log if entry[0] == "sched.hold"]
        assert idle and all(entry[1] == {"loop": "loop-under-test"}
                            for entry in idle)
        # a long wait is written in slices, so a profiler session that
        # starts inside it loses at most one
        assert len(idle) >= 2 and max(entry[2] for entry in idle) < 0.2
        assert held and all(
            entry[1] == {"loop": "loop-under-test", "node": "asr"}
            for entry in held)
        assert 0.02 <= sum(entry[2] for entry in held) < 0.2

    def test_a_plain_timer_is_an_idle_wait(self):
        engine, log = self._engine()
        fired = threading.Event()
        engine.add_timer_handler(fired.set, 0.03)
        thread = engine.loop_in_thread()
        assert fired.wait(5)
        engine.terminate()
        thread.join(5)
        assert log and {entry[0] for entry in log} == {"loop.idle"}


# -- a toy two-stage graph under the profiler ---------------------------------

def _run_graph(telemetry=True):
    """One lone frame (held down, then a group of one), then two frames
    at once (a full group, flushed at capacity)."""
    process = Process(transport_kind="loopback")
    pipeline = create_pipeline(process, graph_definition(telemetry))
    process.run(in_thread=True)
    responses = queue.Queue()
    stream = pipeline.create_stream("s", queue_response=responses)
    frame = {"x": np.ones((2, 4), np.float32)}
    pipeline.create_frame(stream, dict(frame))
    first = responses.get(timeout=120)
    pipeline.create_frame(stream, dict(frame))
    pipeline.create_frame(stream, dict(frame))
    rest = [responses.get(timeout=120) for _ in range(2)]
    time.sleep(0.08)                       # the loop goes idle
    return pipeline, process, [first] + rest


@pytest.fixture(scope="module")
def graph_run(tmp_path_factory):
    reset_brokers()
    recorded, (pipeline, process, results) = _profiled(
        tmp_path_factory.mktemp("graph_profile"), _run_graph)
    # the loop stops here: left running, its idle slices would land in
    # the profiles of the tests that follow
    process.terminate()
    yield recorded, pipeline, results
    reset_brokers()


# `aiko:compile` closes the call that compiled: jax's own durations,
# whose sum is `waited_us`; a miss carries `backend_us`, a hit
# `retrieval_us` and `saved_us`
COMPILE_MARK = {"node", "what", "program", "programs", "trace_us",
                "lower_us", "cache", "waited_us"}
COMPILE_BY_CACHE = {"backend_us", "retrieval_us", "saved_us"}


def _holds_the_marks_shape(name, args, expected):
    if name != "compile":
        return set(args) == expected
    return (set(args) - COMPILE_BY_CACHE == expected
            and int(args["waited_us"]) == sum(
                int(args.get(part, 0)) for part in (
                    "trace_us", "lower_us", "backend_us", "retrieval_us"))
            and args["cache"] in ("hit", "miss", "off"))


GRAPH_SPANS = {
    "loop.idle": {"loop"},
    "sched.hold": {"loop", "node"},
    "sched.group": {"node", "frames", "rows", "target", "path"},
    "element": {"node", "path", "stream", "frame", "trace_id"},
    "compile": COMPILE_MARK,
}


class TestGraphSpans:
    @pytest.mark.parametrize("name", sorted(GRAPH_SPANS))
    def test_span_is_emitted_with_its_arguments(self, graph_run, name):
        recorded, _, results = graph_run
        assert len(results) == 3
        events = recorded.named(name)
        assert events, f"no aiko:{name} in {sorted(recorded.names())}"
        for event in events:
            assert _holds_the_marks_shape(name, event[4],
                                          GRAPH_SPANS[name]), event

    def test_the_hold_names_its_node_and_lasts_the_window(self, graph_run):
        recorded, _, _ = graph_run
        held: dict = {}
        for event in recorded.named("sched.hold"):
            held[event[4]["node"]] = held.get(event[4]["node"], 0) \
                + event[3] / 1e6
        # the lone frame is held at both stages; the pair fills `first`
        # at once and is flushed at capacity, so only `second` may hold
        assert set(held) == {"first", "second"}
        assert HOLD_MS * 0.5 <= held["first"] <= HOLD_MS * 2.5

    def test_groups_say_what_they_held_and_what_they_ran_at(self,
                                                            graph_run):
        recorded, pipeline, _ = graph_run
        groups = [event[4] for event in recorded.named("sched.group")]
        lone = [group for group in groups if group["frames"] == 1]
        full = [group for group in groups if group["frames"] == 2]
        assert {group["node"] for group in lone} == {"first", "second"}
        assert {group["node"] for group in full} == {"first", "second"}
        assert all(group["rows"] == 2 and group["target"] == 4
                   for group in lone)
        assert all(group["rows"] == 4 and group["target"] == 4
                   for group in full)
        assert {group["path"] for group in groups} == {"fused"}
        registry = pipeline.telemetry.registry
        held = registry.histogram("group_held_rows:first")
        padded = registry.histogram("group_rows:first")
        assert (held.count, held.total) == (2, 6.0)
        assert (padded.count, padded.total) == (2, 8.0)

    def test_inline_element_span_carries_the_frames_identity(self,
                                                             graph_run):
        recorded, pipeline, _ = graph_run
        tails = [event[4] for event in recorded.named("element")]
        assert {tail["node"] for tail in tails} == {"tail"}
        assert {tail["path"] for tail in tails} == {"inline"}
        ring = {trace.trace_id: trace
                for trace in pipeline.telemetry.tracer.completed}
        assert {tail["trace_id"] for tail in tails} == set(ring)
        assert sorted(tail["frame"] for tail in tails) == [0, 1, 2]

    def test_the_resumed_frames_run_inside_their_group(self, graph_run):
        """sched.group reaches from the queue-wait close to the last
        frame's outputs handed on: the downstream inline call of a
        resumed frame lies inside `second`'s group span."""
        recorded, _, _ = graph_run
        for group in recorded.named("sched.group"):
            inner = [event[0] for event in recorded.inside(group)]
            if group[4]["node"] == "second":
                assert inner.count("element") == group[4]["frames"]

    def test_compile_mark_names_the_fused_program(self, graph_run):
        """One mark a call that compiled, none for the groups after
        (the lone frame's group is padded to the full one's program)."""
        recorded, _, _ = graph_run
        compiled = [event[4] for event in recorded.named("compile")]
        for node in ("first", "second"):
            [mark] = [mark for mark in compiled if mark["node"] == node]
            assert (mark["what"], mark["program"]) == ("fused", "jit(fused)")
            assert int(mark["programs"]) == 1
            assert int(mark["trace_us"]) > 0 and int(mark["lower_us"]) > 0

    def test_fused_programs_lowered_text_carries_the_nodes_scope(
            self, graph_run):
        _, pipeline, _ = graph_run
        stream = pipeline.create_stream("lowering")
        element = pipeline.elements["first"]
        kernel, context = pipeline._resolve_group_kernel(element, stream)
        program = pipeline._fused_program_for("first", kernel)
        rows = np.ones((2, 4), np.float32)
        text = program.lower(
            context, {"x": [rows, rows]}, target=4, counts=(2, 2),
            shared=()).as_text(debug_info=True)
        assert "jit_fused" in text
        assert "jit(fused)/first/" in text


# -- a toy served engine under the profiler -----------------------------------

CHUNK = 2
NEW_TOKENS = 6
# a request's first token is a chunk of its own; chunks of CHUNK follow,
# the last what is left: 1 + 2 + 2 + 1
CHUNKS = 1 + -(-(NEW_TOKENS - 1) // CHUNK)


def _run_served(telemetry=True, requests=3):
    """A gateway in front of one `continuous: true` LMGenerate replica
    that streams a request's first token, then chunks of CHUNK."""
    replica_process = Process(transport_kind="loopback")
    definition = lm_definition(
        {"continuous": True, "decode_slots": 2, "kv_block_size": 8,
         "stream_tokens": True, "stream_chunk": CHUNK,
         "max_new_tokens": NEW_TOKENS})
    definition["name"] = "replica0"
    definition["parameters"] = {"telemetry": telemetry,
                                "metrics_interval": 0}
    replica = create_pipeline(replica_process, definition)
    gateway_process = Process(transport_kind="loopback")
    gateway = Gateway(gateway_process, policy="max_inflight=8;queue=32",
                      telemetry=telemetry, metrics_interval=60.0)
    gateway.attach_replica(replica)
    chunks = []
    client = Process(transport_kind="loopback")
    client.add_message_handler(
        lambda topic, payload: chunks.append(parse(payload)),
        f"{replica.elements['lm'].topic_path}/out")
    dispatched = []
    original_post = replica.post_message

    def recording_post(command, parameters, **kwargs):
        if command == "process_frame":
            # a copy: the replica pops the context at ingress
            dispatched.append(dict(parameters[1]))
        return original_post(command, parameters, **kwargs)

    replica.post_message = recording_post
    processes = [replica_process, gateway_process, client]
    for process in processes:
        process.run(in_thread=True)
    responses = queue.Queue()
    rng = np.random.default_rng(5)
    for index in range(requests):
        gateway.submit_stream(f"r{index}", {}, queue_response=responses)
        gateway.submit_frame(
            f"r{index}",
            {"tokens": rng.integers(1, 300, size=(1, 5 + index)).astype(
                np.int32)}, frame_id=0)
    results = [responses.get(timeout=180) for _ in range(requests)]
    wait_for(lambda: len(chunks) >= requests * CHUNKS, timeout=30)
    return dict(gateway=gateway, replica=replica, processes=processes,
                results=results, chunks=chunks, dispatched=dispatched)


@pytest.fixture(scope="module")
def served_run(tmp_path_factory):
    reset_brokers()
    # the paged programs are jitted by config and shape for the process:
    # whatever this worker served before, this run compiles its own and
    # closes each with an `aiko:compile` mark
    jax.clear_caches()
    recorded, run = _profiled(
        tmp_path_factory.mktemp("served_profile"), _run_served)
    for process in run["processes"]:
        process.terminate()
    yield recorded, run
    reset_brokers()


REQUEST = {"stream", "frame", "row", "trace_id"}
SERVED_SPANS = {
    "gateway.route": {"stream", "frame", "trace_id", "replica", "pool"},
    "gateway.admit": {"stream", "frame", "trace_id", "waited_us"},
    "ingress": {"stream", "frame", "trace_id", "waited_us"},
    "engine.submit": REQUEST | {"waited_us"},
    "engine.step": {"waiting", "active", "decoding", "admitted"},
    "engine.prefill": REQUEST | {"bucket", "true_len", "queue_us",
                                 "attention", "rows", "attn_rows"},
    "engine.decode": {"decoding", "ahead", "live_blocks", "table_blocks",
                      "write"},
    "engine.readback": set(),
    "engine.chunk": REQUEST | {"offset", "tokens", "waited_us", "first_us",
                               "ingress_us"},
    "engine.pump": {"waited_us"},
    "compile": COMPILE_MARK,
}


class TestServedSpans:
    @pytest.mark.parametrize("name", sorted(SERVED_SPANS))
    def test_span_is_emitted_with_its_arguments(self, served_run, name):
        recorded, run = served_run
        assert all(result[3] == "ok" for result in run["results"])
        events = recorded.named(name)
        assert events, f"no aiko:{name} in {sorted(recorded.names())}"
        for event in events:
            assert _holds_the_marks_shape(name, event[4],
                                          SERVED_SPANS[name]), event

    def test_a_tick_encloses_its_three_kinds_of_child(self, served_run):
        recorded, _ = served_run
        kinds = set()
        for step in recorded.named("engine.step"):
            inner = [event[0] for event in recorded.inside(step)]
            # (and the marks of the chunks it published: a token goes
            # out inside the tick that surfaced it)
            assert set(inner) <= {"engine.prefill", "engine.decode",
                                  "engine.readback", "engine.chunk",
                                  "compile"}, inner
            # a tick that decodes dispatches one step, and no tick reads
            # more than one: the step before, or none after an admission
            # (the prefill's first token settled what was in flight)
            assert inner.count("engine.decode") == bool(
                step[4]["decoding"])
            assert inner.count("engine.readback") <= 1
            assert inner.count("engine.prefill") == step[4]["admitted"]
            kinds |= set(inner)
        assert {"engine.prefill", "engine.decode",
                "engine.readback"} <= kinds
        # every step dispatched is read once
        assert len(recorded.named("engine.readback")) == len(
            recorded.named("engine.decode"))
        for pump in recorded.named("engine.pump"):
            assert [event[0] for event in recorded.inside(pump)
                    ].count("engine.step") == 1

    def test_the_engines_running_counts_agree_with_its_spans(
            self, served_run):
        """`engine_stats()` counts the steps dispatched, those of them
        dispatched ahead of the readback before (`ahead=1` on their
        span), and the tokens read and dropped."""
        recorded, run = served_run
        stats = run["replica"].elements["lm"].engine_stats()
        decodes = recorded.named("engine.decode")
        assert stats["decode_steps"] == len(decodes)
        assert stats["steps_ahead"] == sum(
            event[4]["ahead"] for event in decodes)
        assert 0 < stats["steps_ahead"] < stats["decode_steps"]
        assert stats["overrun_tokens"] == 0
        # every step's rows were written by the paged kernel itself
        assert {event[4]["write"] for event in decodes} == {"kernel"}
        assert (stats["writes_kernel"], stats["writes_updates"]) == (
            len(decodes), 0)

    def test_spans_of_one_request_share_its_trace_id(self, served_run):
        recorded, run = served_run
        ring = {trace.stream_id: trace.trace_id
                for trace in run["gateway"].telemetry.tracer.completed}
        assert len(ring) == 3
        for name in ("gateway.route", "gateway.admit", "ingress",
                     "engine.submit", "engine.prefill", "engine.chunk"):
            for event in recorded.named(name):
                assert event[4]["trace_id"] == ring[event[4]["stream"]], \
                    (name, event)
        prefill = {event[4]["stream"]: event[4]
                   for event in recorded.named("engine.prefill")}
        assert sorted(args["true_len"] for args in prefill.values()) == [
            5, 6, 7]
        assert {args["bucket"] for args in prefill.values()} == {8}
        # a bucket of 8 is far under what the flash kernel takes
        assert {args["attention"] for args in prefill.values()} == {
            "einsum"}

    def test_a_whole_prefill_says_the_rows_it_ran(self, served_run):
        """`rows` beside `bucket` on every whole `engine.prefill`: what
        models.prefill_rows says, here the bucket (8 rows are under two
        row tiles, so the program runs whole); `engine_stats()` sums
        both."""
        from aiko_services_tpu.models import prefill_rows
        recorded, run = served_run
        element = run["replica"].elements["lm"]
        prefills = [event[4] for event in recorded.named("engine.prefill")]
        assert len(prefills) == 3
        for args in prefills:
            assert args["rows"] == args["bucket"] == prefill_rows(
                element.config, args["bucket"], args["true_len"])
            # and `attn_rows` (PR 40), the rows its attention ran: the
            # bucket too, the einsum's (tests/test_decode.py steers the
            # flash kernel, which is told the length)
            assert args["attn_rows"] == args["bucket"]
        stats = element.engine_stats()
        assert stats["prefill_rows_run"] == stats["prefill_rows_bucket"] \
            == stats["prefill_attn_rows"] \
            == sum(args["bucket"] for args in prefills)

    def test_ingress_mark_reaches_back_to_the_gateways_dispatch(
            self, served_run):
        """[start - waited_us, start] of the replica's `ingress` begins
        inside the gateway's `route` span of the same frame, where the
        dispatch time was stamped (two clocks: allow a millisecond)."""
        recorded, _ = served_run
        routes = {event[4]["stream"]: event
                  for event in recorded.named("gateway.route")}
        marks = recorded.named("ingress")
        assert len(marks) == 3
        for mark in marks:
            route = routes[mark[4]["stream"]]
            began = mark[2] - mark[4]["waited_us"] * 1e3
            assert route[2] - 1e6 <= began <= route[2] + route[3] + 1e6
            assert mark[2] >= route[2]

    def test_first_chunk_mark_counts_from_the_end_of_the_prefill(
            self, served_run):
        recorded, _ = served_run
        prefills = {event[4]["stream"]: event
                    for event in recorded.named("engine.prefill")}
        firsts = [event for event in recorded.named("engine.chunk")
                  if event[4]["offset"] == 0]
        assert len(firsts) == 3
        for chunk in firsts:
            prefill = prefills[chunk[4]["stream"]]
            began = chunk[2] - chunk[4]["waited_us"] * 1e3
            # the first token is stamped right after the prefill's
            # readback, inside the same tick
            assert 0 <= began - (prefill[2] + prefill[3]) < 5e6
            # and it goes out alone, however long a chunk is
            assert chunk[4]["tokens"] == 1
        later = [event for event in recorded.named("engine.chunk")
                 if event[4]["offset"] > 0]
        assert {(event[4]["offset"], event[4]["tokens"])
                for event in later} == {(1, CHUNK), (3, CHUNK), (5, 1)}
        by_stream: dict = {}
        for event in recorded.named("engine.chunk"):
            by_stream.setdefault(event[4]["stream"], []).append(event)
        for events in by_stream.values():
            for previous, following in zip(events, events[1:]):
                # a later chunk counts from the chunk before it
                began = following[2] - following[4]["waited_us"] * 1e3
                assert abs(began - previous[2]) < 2e6

    def test_first_chunk_leaves_inside_the_tick_of_its_prefill(
            self, served_run):
        """The offset-0 mark lies inside the `engine.step` that holds its
        request's prefill, after that prefill and before the tick's next
        prefill or step is dispatched: the token is published the moment
        the host holds it, not when the tick ends."""
        recorded, _ = served_run
        firsts = {event[4]["stream"]: event
                  for event in recorded.named("engine.chunk")
                  if event[4]["offset"] == 0}
        assert len(firsts) == 3
        seen = 0
        for step in recorded.named("engine.step"):
            inner = sorted(recorded.inside(step), key=lambda event: event[2])
            for index, event in enumerate(inner):
                if event[0] != "engine.prefill":
                    continue
                following = [other for other in inner[index + 1:]
                             if other[0] != "compile"]
                assert following and following[0] is firsts[
                    event[4]["stream"]], (event, following[:1])
                seen += 1
        assert seen == 3

    def test_every_chunk_says_how_its_request_began(self, served_run):
        """A short profile holds requests in flight whose beginning lies
        before it: each of their chunks carries the first chunk's wait
        and the wait before DecodeEngine.submit."""
        recorded, _ = served_run
        waits = {}
        for name in ("ingress", "engine.submit"):
            for event in recorded.named(name):
                stream = event[4]["stream"]
                waits[stream] = waits.get(stream, 0) + event[4]["waited_us"]
        by_stream: dict = {}
        for event in recorded.named("engine.chunk"):
            by_stream.setdefault(event[4]["stream"], []).append(event[4])
        assert len(by_stream) == 3
        for stream, chunks in by_stream.items():
            assert len(chunks) == CHUNKS
            assert chunks[0]["offset"] == 0
            assert chunks[0]["first_us"] == chunks[0]["waited_us"]
            assert {args["first_us"] for args in chunks} == {
                chunks[0]["first_us"]}
            for args in chunks:
                # two roundings of the same two perf_counter differences
                assert abs(args["ingress_us"] - waits[stream]) <= 2

    def test_the_ring_holds_the_requests_program_spans(self, served_run):
        _, run = served_run
        traces = list(run["replica"].telemetry.tracer.completed)
        assert len(traces) == 3
        for frame_trace in traces:
            program = [event for event in frame_trace.events
                       if event[2] == "program"]
            names = [event[1] for event in program]
            assert names[:3] == ["aiko:ingress", "aiko:engine.submit",
                                 "aiko:engine.prefill"]
            assert names.count("aiko:engine.chunk") == CHUNKS
            assert frame_trace.ingress_wait_s is not None
            for kind, _, _, start, duration, _ in program:
                assert kind == "X" and duration >= 0
                assert start + duration >= frame_trace.start_us - 1

    def test_instruments_at_the_same_boundaries(self, served_run):
        recorded, run = served_run
        registry = run["replica"].telemetry.registry
        ticks = len(recorded.named("engine.step"))
        assert registry.histogram("decode.ingress_wait_s").count == 3
        assert registry.histogram("decode.first_chunk_s").count == 3
        assert registry.histogram("decode.tick_s").count >= ticks
        decoding = registry.histogram("decode.tick_decoding")
        assert decoding.count == registry.histogram("decode.tick_s").count
        assert decoding.total >= sum(
            event[4]["decoding"] for event in recorded.named("engine.step"))
        first = registry.histogram("decode.first_chunk_s")
        marks = [event[4]["waited_us"] / 1e6
                 for event in recorded.named("engine.chunk")
                 if event[4]["offset"] == 0]
        assert first.total == pytest.approx(sum(marks), abs=1e-4)

    def test_the_dispatched_payload_carries_the_dispatch_time(
            self, served_run):
        _, run = served_run
        assert len(run["dispatched"]) == 3
        for payload in run["dispatched"]:
            assert set(payload[TRACE_CONTEXT_KEY]) == {
                "trace_id", "span_id", "sent_unix_us"}


# -- telemetry: false ---------------------------------------------------------

class TestTelemetryOff:
    def test_a_served_engine_emits_nothing_and_sends_no_context(
            self, tmp_path):
        recorded, run = _profiled(
            tmp_path, lambda: _run_served(telemetry=False, requests=2))
        try:
            assert all(result[3] == "ok" for result in run["results"])
            assert recorded.events == []
            assert run["dispatched"] and all(
                TRACE_CONTEXT_KEY not in payload
                for payload in run["dispatched"])
            replica = run["replica"]
            assert not replica.telemetry.tracer.completed
            assert replica.elements["lm"]._spans is NO_SPANS
            assert replica.process.event._wait_span is None
            assert "decode.tick_s" not in replica.telemetry.snapshot().get(
                "histograms", {})
        finally:
            for process in run["processes"]:
                process.terminate()

    def test_a_graph_emits_nothing(self, tmp_path):
        recorded, (pipeline, process, results) = _profiled(
            tmp_path, lambda: _run_graph(telemetry=False))
        try:
            assert len(results) == 3
            assert recorded.events == []
            assert process.event._wait_span is None
            assert not pipeline.telemetry.snapshot().get("histograms")
        finally:
            process.terminate()


# -- a model's own fields -------------------------------------------------------

# what the engine's spans carry, beside a dense model's, for a model whose
# lightning layers' state is a slot's and whose attention selects its blocks
# (ISSUE 47; the model's record says them, models/transformer.py)
SELECTING_HYBRID_FIELDS = {
    "engine.prefill": {"scan", "scan_rows", "select_rows"},
    "engine.decode": {"state_step", "state_slots", "state_bytes",
                      "cache_rows", "sparse_blocks_read",
                      "sparse_blocks_live", "compressed_rows"},
}


class TestAModelsOwnFields:
    @pytest.mark.parametrize("name", sorted(SELECTING_HYBRID_FIELDS))
    def test_a_selecting_hybrids_spans_carry_the_dense_fields_and_its_own(
            self, name):
        """The engine writes down what the model's record says and no
        other field: a dense model's (SERVED_SPANS, less the request's
        own, which the seam resolves) and the table's."""
        from aiko_services_tpu.decode import DecodeEngine
        from aiko_services_tpu.models.transformer import init_params
        from test_decode import _Order
        from test_minicpm_sala import PUBLISHED
        from aiko_services_tpu.models.configs import minicpm_sala_config
        config = minicpm_sala_config(PUBLISHED, max_seq_len=256)
        spans = _Order()
        engine = DecodeEngine(
            init_params(config, jax.random.PRNGKey(0)), config,
            decode_slots=1, kv_block_size=8, max_context=160, spans=spans)
        engine.submit("r", np.arange(1, 71, dtype=np.int32), 6)
        while engine.has_work():
            engine.step()
        events = spans.named(name)
        assert events
        for _, fields in events:
            assert set(fields) == (SERVED_SPANS[name] - REQUEST
                                   | SELECTING_HYBRID_FIELDS[name])
        stats = engine.stats()
        for counter in ("select_rows", "sparse_blocks_read",
                        "sparse_blocks_live", "compressed_rows",
                        "scan_lightning_chunk", "prefill_sparse"):
            assert stats[counter] > 0, counter
