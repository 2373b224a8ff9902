# PipelineElement: one node of a pipeline graph.
#
# Capability parity with the reference element layer (reference:
# src/aiko_services/main/pipeline.py:288-456): elements are Actors (remotely
# discoverable/controllable), implement start_stream / process_frame /
# stop_stream returning (StreamEvent, ...), can inject frames via
# create_frame or a threaded frame generator (create_frames, reference
# pipeline.py:365-416), and resolve parameters with stream > element >
# pipeline precedence (reference pipeline.py:422-456).
#
# The TPU compute contract lives in ComputeElement (tpu_element.py): element
# math is a pure JAX function jitted once and fed jax.Array swag values.

from __future__ import annotations

import threading
import time

from ..runtime import Actor
from ..utils import get_logger, parse_float, parse_int
from .stream import Stream, StreamEvent, StreamState

__all__ = ["ErrorPolicy", "PipelineElement", "AsyncHostElement",
           "FrameGeneratorHandle"]

_LOGGER = get_logger("element")

# `on_error` values an element / stream / pipeline may declare.  The
# default preserves the original engine contract: an element error
# destroys the stream (the pipeline survives).
ERROR_POLICIES = ("stop_stream", "drop_frame", "retry")
DEFAULT_MAX_RETRIES = 2
DEFAULT_RETRY_BACKOFF_MS = 10.0


class ErrorPolicy:
    """Resolved per-element error policy: what the engine does when one
    element call fails for one frame.  Resolved through the normal
    parameter precedence (stream > element > pipeline), so operators set
    a pipeline-wide `on_error` and override per element or per stream."""

    __slots__ = ("on_error", "max_retries", "backoff_s")

    def __init__(self, on_error: str, max_retries: int, backoff_s: float):
        self.on_error = on_error
        self.max_retries = max_retries
        self.backoff_s = backoff_s

    def retry_delay(self, attempt: int) -> float:
        """Exponential backoff: base * 2^(attempt-1) for attempt >= 1."""
        return self.backoff_s * (2.0 ** max(attempt - 1, 0))


class FrameGeneratorHandle:
    """Owns one frame-generator thread for (element, stream)."""

    def __init__(self, element, stream: Stream, frame_generator, rate=None,
                 frame_window: int = 16):
        self.element = element
        self.stream = stream
        self.frame_generator = frame_generator
        self.rate = rate
        self.frame_window = frame_window
        self._terminated = False
        # downstream backpressure (serving gateway `(throttle ...)`
        # control message): a positive override CAPS the generation rate
        # below the configured one; 0/None lifts the cap.  Read each
        # tick so a throttle lands mid-stream without a restart.
        self._rate_cap: float | None = None
        self._thread = threading.Thread(
            target=self._run,
            name=f"frames-{element.name}-{stream.stream_id}", daemon=True)

    def start(self):
        self._thread.start()

    def terminate(self):
        self._terminated = True

    def set_rate(self, rate) -> None:
        """Cap the generation rate (frames/sec); rate <= 0 lifts the
        cap back to the configured rate.  Thread-safe: the generator
        loop re-reads the effective interval every tick."""
        try:
            rate = float(rate)
        except (TypeError, ValueError):
            return
        self._rate_cap = rate if rate > 0 else None

    def _interval(self) -> float:
        rate = self.rate
        cap = self._rate_cap
        if cap is not None and (not rate or cap < rate):
            rate = cap
        return 1.0 / rate if rate else 0.0

    def _run(self):
        pipeline = self.element.pipeline
        stream = self.stream
        interval = self._interval()
        next_time = time.monotonic()
        while not self._terminated and stream.state == StreamState.RUN:
            # backpressure: bound in-flight frames so a fast generator
            # cannot grow the pipeline mailbox without limit
            if stream.pending >= self.frame_window:
                time.sleep(0.0005)
                continue
            effective = self._interval()
            if effective != interval:
                # a throttle landed (or lifted): clamp the schedule to
                # now so a long idle gap is not "owed" as a burst
                interval = effective
                next_time = time.monotonic()
            try:
                stream_event, frame_data = self.frame_generator(
                    stream, stream.frame_id)
            except Exception as error:
                _LOGGER.error("%s: frame generator failed: %s",
                              self.element.name, error)
                stream_event, frame_data = StreamEvent.ERROR, {
                    "diagnostic": str(error)}
            if stream_event == StreamEvent.OKAY:
                pipeline.create_frame(stream, frame_data or {})
            elif stream_event == StreamEvent.STOP:
                # post through the mailbox so the destroy is ordered AFTER
                # already-posted frames, then drains gracefully
                pipeline.post_message(
                    "destroy_stream", [stream.stream_id, "stop", True])
                return
            elif stream_event == StreamEvent.ERROR:
                _LOGGER.error("%s: frame generator error: %s",
                              self.element.name, frame_data)
                # the source's own error policy decides whether a bad
                # tick kills the stream (the historical default) or is
                # skipped like a dropped frame (transient ingest faults
                # -- a camera hiccup -- must not destroy a long-lived
                # serving stream when the operator opts into drop_frame)
                policy = self.element.resolve_error_policy(stream)
                if policy.on_error == "stop_stream":
                    pipeline.post_message(
                        "destroy_stream", [stream.stream_id, "error", True])
                    return
                # drop_frame / retry: skip this tick, keep generating --
                # with a backoff floor so a PERSISTENTLY failing
                # rate-less source (unplugged camera) degrades to a slow
                # error log, not a busy-spinning hot thread
                if not interval:
                    time.sleep(max(policy.backoff_s, 0.001))
            # DROP_FRAME: skip this tick
            if interval:
                next_time += interval
                delay = next_time - time.monotonic()
                if delay > 0:
                    time.sleep(delay)


class PipelineElement(Actor):
    def __init__(self, process, pipeline, definition):
        self.pipeline = pipeline
        self.definition = definition
        name = f"{pipeline.name}.{definition.name}" if pipeline else (
            definition.name)
        super().__init__(process, name)
        self.share.update(dict(definition.parameters))
        self._generators: dict[str, FrameGeneratorHandle] = {}

    # -- the element contract (override these) -----------------------------

    def start_stream(self, stream: Stream, stream_id) -> tuple:
        return StreamEvent.OKAY, None

    def process_frame(self, stream: Stream, **inputs) -> tuple:
        raise NotImplementedError

    def stop_stream(self, stream: Stream, stream_id) -> tuple:
        return StreamEvent.OKAY, None

    def group_kernel(self, stream: Stream):
        """Optional fused whole-group execution hook for the micro-batch
        scheduler.  Return `(kernel, context)` where
        `kernel(context, **batch) -> dict` is a PURE jit-traceable
        function (batch-in/batch-out on axis 0, no host side effects)
        and `context` is a pytree of traced values (model state, dynamic
        parameters).  When present, the scheduler traces
        concat+pad+kernel+split as ONE compiled program per (input
        names, arity, shapes) signature instead of three dispatches:
        fewer launches per group, so the fused program is the serving
        hot path.  Contract details:

        - `context` rides the program as a traced argument, never a
          baked-in constant: checkpoint restores and live parameter
          updates apply without a stale executable (return fresh
          context each call; keep the KERNEL's identity stable -- the
          scheduler caches the compiled program per kernel object).
        - Outputs whose leading axis equals the coalesced batch are
          split per frame (recursing into dicts); anything else -- and
          ports declared "batched": false -- is shared whole.
        - Return None (the default) to use the chained
          concat -> process_frame -> split path.
        """
        return None

    def engine_managed(self, stream: Stream) -> bool:
        """True when the element runs its OWN batching engine for this
        stream (e.g. LMGenerate's `continuous: true` slot-based decode
        engine): the micro-batch scheduler must hand it frames
        one-by-one -- the engine admits them into a running device
        loop at prefill boundaries, which strictly dominates
        coalescing whole frames.  Default False (scheduler-managed)."""
        return False

    def eval_kernel(self):
        """Optional abstract-interpretation hook for the static
        analyzer (analyze/shape_eval.py): return `(kernel, state_fn)`
        where `kernel(state, **inputs) -> dict` is the element's pure
        device program and `state_fn()` builds its state pytree (None
        for stateless elements).  Both are ONLY ever called under
        jax.eval_shape, so nothing allocates, compiles, or touches a
        device -- the analyzer synthesizes ShapeDtypeStructs from the
        declared port specs and proves declared outputs match traced
        outputs.  Return None (the default) when the element has no
        pure device program (sources, host elements)."""
        return None

    # -- frame creation ----------------------------------------------------

    def create_frame(self, stream: Stream, frame_data: dict) -> None:
        self.pipeline.create_frame(stream, frame_data)

    def create_frames(self, stream: Stream, frame_generator,
                      rate: float = None) -> None:
        """Spawn the frame-generator thread for a DataSource element
        (reference pipeline.py:365-416)."""
        window = int(self.get_parameter("frame_window", 16, stream))
        handle = FrameGeneratorHandle(
            self, stream, frame_generator, rate=rate, frame_window=window)
        self._generators[stream.stream_id] = handle
        handle.start()

    def stop_frame_generation(self, stream_id) -> None:
        handle = self._generators.pop(stream_id, None)
        if handle:
            handle.terminate()

    def throttle_frame_generation(self, stream_id, rate) -> None:
        """Backpressure sibling of stop_frame_generation: cap this
        stream's generator at `rate` frames/sec (rate <= 0 lifts the
        cap).  Driven by the serving gateway's `(throttle stream rate)`
        control message when downstream replicas saturate -- a slowed
        source beats a shed frame."""
        handle = self._generators.get(stream_id)
        if handle:
            handle.set_rate(rate)

    # -- parameters (reference pipeline.py:422-456) ------------------------

    def get_parameter(self, name: str, default=None, stream: Stream = None):
        """Resolution order: stream "Element.name"-scoped -> stream ->
        element share/definition -> pipeline share/definition -> default."""
        if stream is not None:
            scoped = f"{self.definition.name}.{name}"
            if scoped in stream.parameters:
                return stream.parameters[scoped]
            if name in stream.parameters:
                return stream.parameters[name]
        if name in self.share:
            return self.share[name]
        if self.pipeline is not None:
            pipeline_share = getattr(self.pipeline, "share", {})
            if name in pipeline_share:
                return pipeline_share[name]
            pipeline_definition = getattr(self.pipeline, "definition", None)
            if (pipeline_definition is not None
                    and name in pipeline_definition.parameters):
                return pipeline_definition.parameters[name]
        return default

    def set_parameter(self, name: str, value) -> None:
        if self.ec_producer is not None:
            self.ec_producer.update(name, value)
        else:
            self.share[name] = value

    def resolve_error_policy(self, stream: Stream = None) -> ErrorPolicy:
        """The element's effective error policy for `stream` (resolved
        only on the error path -- the no-fault hot path never pays the
        parameter lookups)."""
        on_error = str(self.get_parameter(
            "on_error", ERROR_POLICIES[0], stream)
            or ERROR_POLICIES[0]).lower()
        if on_error not in ERROR_POLICIES:
            _LOGGER.warning("%s: unknown on_error %r; using stop_stream",
                            self.definition.name, on_error)
            on_error = ERROR_POLICIES[0]
        max_retries = parse_int(
            self.get_parameter("max_retries", DEFAULT_MAX_RETRIES,
                               stream), DEFAULT_MAX_RETRIES)
        backoff_ms = parse_float(
            self.get_parameter("retry_backoff_ms",
                               DEFAULT_RETRY_BACKOFF_MS, stream),
            DEFAULT_RETRY_BACKOFF_MS)
        return ErrorPolicy(on_error, max(max_retries, 0),
                           max(backoff_ms, 0.0) / 1000.0)

    def stop(self) -> None:
        for handle in list(self._generators.values()):
            handle.terminate()
        self._generators.clear()
        super().stop()


class AsyncHostElement(PipelineElement):
    """PipelineElement whose work runs on a WORKER THREAD while the frame
    parks (StreamEvent.PENDING) -- the host-boundary counterpart of a
    remote hop.

    Device->host readbacks (token decode, image sinks) wait for the
    device and carry a link round-trip; run inline on the event loop
    they serialize the whole pipeline.  Subclasses implement
    process_async(stream, **inputs) -> dict (worker thread, blocking I/O
    welcome); the frame resumes through the pipeline mailbox when it
    returns, so other frames flow through the graph meanwhile.  An
    exception in process_async releases the frame as an error (no leak).
    Worker concurrency: the "workers" parameter (default 2) bounds
    simultaneous readbacks per element.
    """

    _executor = None

    def process_async(self, stream: Stream, **inputs) -> dict:
        raise NotImplementedError

    def _get_executor(self):
        if self._executor is None:
            from concurrent.futures import ThreadPoolExecutor
            self._executor = ThreadPoolExecutor(
                max_workers=int(self.get_parameter("workers", 2)),
                thread_name_prefix=f"async-{self.definition.name}")
        return self._executor

    def process_frame(self, stream: Stream, **inputs) -> tuple:
        frame_id = stream.current_frame_id
        stream_id = stream.stream_id
        pipeline = self.pipeline

        node = self.definition.name  # responses name their node so
        # sibling branches can be in flight concurrently

        def work():
            start = time.perf_counter()
            try:
                outputs = self.process_async(stream, **inputs)
                pipeline.post_message("process_frame_response", [
                    {"stream_id": stream_id, "frame_id": frame_id,
                     "node": node,
                     "time": time.perf_counter() - start},
                    outputs or {}])
            except Exception as error:
                _LOGGER.error("%s: async work failed: %s",
                              self.definition.name, error)
                pipeline.post_message("process_frame_response", [
                    {"stream_id": stream_id, "frame_id": frame_id,
                     "node": node, "event": "error"}, {}])

        self._get_executor().submit(work)
        return StreamEvent.PENDING, None

    def stop(self) -> None:
        if self._executor is not None:
            self._executor.shutdown(wait=False, cancel_futures=True)
            self._executor = None
        super().stop()

