# Frame tracer: Dapper-style per-frame traces exported as Chrome-trace
# JSON (Perfetto-loadable).
#
# A trace id is minted per frame at stream ingress; the pipeline engine
# appends span records (element execution, queue wait, fused vs chained
# dispatch, park/resume, compile events) to the frame's FrameTrace as the
# frame moves through the graph.  Completed traces land in a bounded ring;
# export renders them as Chrome trace-event JSON ("X" complete events for
# spans, "i" instants for point events, "M" metadata naming the process
# and one thread lane per stream), which chrome://tracing and Perfetto
# both load directly.
#
# SPAN TAXONOMY -- the one canonical reference (telemetry.py, the
# serving gateway, and tune/loader.py all follow this table):
#
#   category   span names                    meaning
#   --------   ---------------------------   ---------------------------
#   frame      "frame {id}"                  one frame's whole lifetime
#                                            in ONE process (per-process
#                                            root; carries span_id and,
#                                            on a propagated trace, the
#                                            upstream parent span id)
#   element    "{node}"                      one element call; args.path
#                                            = inline|fused|chained|
#                                            async|remote
#   queue      "queue:{node}"                scheduler-induced wait
#                                            (micro-batch park -> flush,
#                                            or engine slot wait when
#                                            row-suffixed "queue:lm[3]")
#   engine     "prefill:{node}",             continuous-batching engine
#              "decode_steps:{node}",        phases; "adopt:" = KV
#              "adopt:{node}",               migration (disagg or warm
#              "checkpoint:{node}"           restore), "checkpoint:" =
#                                            snapshot shipping (global
#                                            lane: covers every slot)
#   gateway    "admit:gateway",              serving-tier spans: admit =
#              "route:gateway",              frame submit -> replica
#              "replay:gateway",             dispatch (parked/admission
#              "shed:gateway",               wait), route = placement
#              "throttle:gateway",           decision, replay = failover
#              "paced_replay:gateway"        _migrate_streams wave,
#                                            paced_replay = deferred
#                                            recovery wave; shed/
#                                            throttle are instants
#   compile    "compile:{node}"              (re)compilation instants
#                                            (what `aiko tune` reads; the
#                                            durations ride the program
#                                            mark `aiko:compile`, below)
#   park/fault instants                      park/resume, retries,
#                                            deadline + breaker events
#   program    "aiko:{layer}.{what}"         program spans (below); in
#                                            the ring only where a frame
#                                            exists and no older record
#                                            already covers the interval
#
# PROGRAM SPANS -- the same work, named from inside, on the JAX
# profiler's clock.  program_span / program_mark (the one function pair
# PipelineTelemetry, GatewayTelemetry and the event loop go through)
# write a TraceMe on the calling thread, so a `jax.profiler.start_trace`
# session shows them over the device lines on one time axis; arguments
# come back as the event's stats.  With no session a span costs one
# inactive TraceMe (~0.6 us); without jax in the process, nothing.
#
#   a SCOPED span is a `with` block on one thread (work);
#   a CLOSING MARK is written at the instant an interval that crossed
#   threads or mailboxes CLOSES, carrying `waited_us` (a perf_counter
#   difference): the interval is [start - waited_us, start] on the
#   profiler's clock, with no second clock to align.
#
#   span (aiko: ...)   kind     written in / arguments
#   ----------------   ------   ------------------------------------
#   loop.idle          scoped   EventEngine.loop's wait, nothing due
#                               soon, in slices of 50 ms (a span open
#                               when a session starts is lost): loop
#   sched.hold         scoped   the same wait when the nearest timer is
#                               a micro-batch hold-down: loop, node
#   sched.group        scoped   one coalesced group, queue-wait close to
#                               the last frame resumed: node, frames,
#                               rows (held), target (padded), path
#   element            scoped   one inline element call: node, path
#   gateway.route      scoped   one placement: stream, replica, pool
#   gateway.admit      mark     frame submit -> first dispatch: stream
#   ingress            mark     gateway dispatch -> replica ingress (the
#                               mailbox wait): stream, frame
#   engine.submit      mark     replica ingress -> DecodeEngine.submit
#   engine.step        scoped   one engine tick: waiting, active,
#                               decoding, admitted
#   engine.prefill     scoped   one prefill call + its readback, inside
#                               engine.step: bucket, true_len, queue_us
#                               (the engine's own; a prefill engine's
#                               whole prefill: bucket, true_len), and
#                               the MODEL'S FIELDS of the call.  Those,
#                               here and on engine.decode, are what the
#                               model's record of the call says
#                               (models.prefill_record, window_record,
#                               step_counts, beside the paged programs
#                               in models/transformer.py: each answers
#                               by the predicates the traced code
#                               decides by and returns the span's fields
#                               and the increments of the running
#                               counters of engine_stats(); the engines
#                               write both down and know no name in
#                               either, and models.RECORD_COUNTERS holds
#                               every counter at its zero, so stats()
#                               has every key for every model).  A whole
#                               prefill: attention (flash | einsum: what
#                               the bucket's attention takes; counts
#                               prefill_flash, prefill_einsum), rows (the
#                               rows the program runs of the bucket's:
#                               true_len rounded up to a row tile where
#                               the bucket runs by row tiles) and
#                               attn_rows (the query rows its attention
#                               runs: true_len rounded up to the flash
#                               kernel's query block where the kernel is
#                               told the length, else the bucket);
#                               running sums prefill_rows_run,
#                               prefill_rows_bucket, prefill_attn_rows.
#                               A chunk call instead: live_blocks,
#                               table_blocks (the pool's geometry: the
#                               engine's own) and write.  Of a looped
#                               stack also ut_passes and cache_rows (as
#                               on engine.decode; here the rows the call
#                               leaves behind, true_len or the chunk's
#                               end, x caches); of a model with a
#                               recurrent state (mamba or delta layers)
#                               a whole prefill also scan (what the
#                               layers' scan runs through: kernel | jnp
#                               of a selective scan, jnp of the gated
#                               delta rule's chunks) and scan_rows (the
#                               rows it runs of the bucket's: the kernel
#                               stops after the block of rows that holds
#                               row true_len - 1); running sum
#                               scan_rows, counts scan_kernel, scan_jnp
#                               (lightning layers: scan lightning_chunk,
#                               count scan_lightning_chunk).  Of an
#                               attention that selects its blocks
#                               attention is sparse for a bucket past
#                               sparse_dense_len (count prefill_sparse),
#                               attn_rows then the selection's query
#                               tiles that hold a live row, and
#                               select_rows the rows that chose their
#                               blocks, a layer (running sum)
#   engine.decode      scoped   table build + dispatch.  The engine's
#                               own: decoding, ahead (1: dispatched
#                               while the step before was unread, from
#                               its tokens on the device; 0: from the
#                               host's, after an admission or an empty
#                               engine), live_blocks (blocks the paged
#                               attention walks this step), table_blocks
#                               (what the tables can name: slots x
#                               max_blocks).  The model's record's:
#                               write (kernel | updates: who puts the
#                               step's new rows into the pool, the paged
#                               attention kernel itself at window 1 or
#                               one dynamic_update_slice a row; running
#                               counts writes_kernel, writes_updates; on
#                               a chunk's engine.prefill too);
#                               over a latent pool latent_positions (the
#                               live rows this step's slots attend over,
#                               its own among them); with routed experts
#                               experts_read (distinct held experts hit,
#                               summed over the expert layers) and
#                               expert_pairs (token-expert pairs computed
#                               here), both of the NEWEST STEP READ BACK
#                               when the span opens -- the device counts
#                               them, so they come with that step's
#                               tokens, two dispatches late under the
#                               run-ahead; absent until one has.  Running
#                               sums of all three in engine_stats().
#                               Of a looped stack (ut_steps > 1):
#                               ut_passes (passes the program ran, its
#                               config's), cache_rows (the live positions
#                               this step's slots attend over x the
#                               model's n_layers x ut_steps caches, host-
#                               counted like latent_positions) and
#                               exit_expected_step (mean over the decoding
#                               slots of sum_t (t + 1) p[t], the pass the
#                               exit gate expects a token to leave after:
#                               device-counted, so of the newest step
#                               read back, like experts_read); running
#                               sums in engine_stats(), the last of the
#                               steps' means.
#                               Of a model with a recurrent state:
#                               state_slots (the decoding slots whose
#                               state the step advances), state_bytes
#                               (host-counted: those slots' state, a
#                               convolution tail and an SSM state a Mamba
#                               layer or a matrix a head a delta layer,
#                               read and written once) and
#                               cache_rows (their live positions x the
#                               attention layers' K/V caches); running
#                               sums in engine_stats(); state_step
#                               (kernel | jnp: what advances the state,
#                               the layer's blocks of the stacked leaf
#                               read and written once where they lie --
#                               ssm_row_step, gdn_step -- or XLA's passes
#                               over the layer's slice; running counts
#                               state_step_kernel, state_step_jnp).
#                               Of an attention that selects its blocks
#                               (host-counted from the positions, a K/V
#                               head of an attention layer a slot):
#                               sparse_blocks_read (the blocks the
#                               step's attention reads: sparse_topk from
#                               sparse_dense_len on), sparse_blocks_live
#                               (the blocks it chose from) and
#                               compressed_rows (the compressed keys it
#                               scored); running sums in engine_stats()
#   engine.readback    scoped   the settle's readback of the step in
#                               flight: in a tick after that tick's
#                               engine.decode where it ran ahead, or
#                               after its last engine.prefill; outside
#                               engine.step where a cancel, an adoption,
#                               a restore or a checkpoint settles.
#                               Running counts in engine_stats():
#                               decode_steps, steps_ahead (of them, with
#                               ahead=1), overrun_tokens (read and
#                               dropped: the slot was released after the
#                               dispatch, by an EOS seen a step late)
#   engine.chunk       mark     first token (offset 0) or previous chunk
#                               -> this token_chunk: offset, tokens;
#                               and, so that ANY chunk of a request in a
#                               short profile tells how it began,
#                               first_us (its first token -> its first
#                               chunk) and ingress_us (dispatch ->
#                               DecodeEngine.submit), where known.  A
#                               request's offset-0 chunk is its first
#                               token alone, published inside the
#                               engine.step of its prefill, right after
#                               that engine.prefill
#   engine.pump        scoped   LMGenerate._engine_pump; waited_us = the
#                               pump message's mailbox wait
#   compile            mark     closes a bracketed call that compiled
#                               (runtime/compile_cache.py): node, what
#                               (the engine's call, `fused`, `element`,
#                               or `unbracketed`: a compile on an event
#                               loop's thread that no bracket expected),
#                               program (jax's `fun_name`s), programs,
#                               and jax's OWN durations on the calling
#                               thread: trace_us, lower_us, and
#                               backend_us (a cache miss, or no cache)
#                               or retrieval_us + saved_us (a hit: the
#                               key, the read and the deserialisation),
#                               cache = hit | miss | off.  waited_us is
#                               their sum, not the call's wall time: the
#                               program's first execution is not in it.
#                               One sample of `setup.compile_s`; nothing
#                               is written for a call that compiled
#                               nothing
#   setup.weights      scoped   ComputeElement._ensure_ready around
#                               setup() and the placing of the state (or
#                               restore_state; a draft model's too): node,
#                               source (init | load | restore), bytes,
#                               leaves (of the state), compile_us (jax's
#                               durations that fell inside: the eager
#                               initialiser's small programs).  One
#                               sample of `setup.weights_s`
#   setup.state        scoped   DecodeEngine / PrefillEngine around the
#                               paged pool and its tables: node, what
#                               (pool | draft_pool; recurrent: a model's
#                               recurrent state, by slot, with slots in
#                               the blocks' place), blocks, bytes,
#                               leaves, compile_us.  One sample of
#                               `setup.state_s`.  generate()'s contiguous
#                               cache is made inside its jitted program
#                               every call: no interval
#
# The three setup.* histograms, `setup.cache_hits`/`setup.cache_requests`
# and the gauges `setup.boot_s` (the package's import -> the first
# weights interval opens) and `setup.ready_s` (-> the newest interval
# closed) are the process-global registry's, written with telemetry on
# or off; they are disjoint (an interval inside another records no
# sample), so boot + weights + state + compile <= ready.
#
# Spans of one request also carry stream, frame (and row) and the
# frame's trace_id; a span's parent is the span enclosing it on its
# thread, across threads the trace_id.
#
# Naming scheme: "{kind}:{node}" -- tune/loader._node_of strips the
# prefix (and the "[row]" suffix) to join spans to typed graph nodes.
# The matching frame.metrics keys split the SAME way on every dispatch
# path: `time_{node}` is element/device compute, `time_queue_{node}` is
# scheduler wait (micro-batch fill, engine slot wait) -- never mixed.
#
# Cross-process propagation: a TRACE CONTEXT ({trace_id, span_id}, and
# `sent_unix_us` when it rides a dispatched frame) rides
# frame data under TRACE_CONTEXT_KEY.  The serving gateway mints the
# trace at admission (root-span owner); every downstream process pops
# the context at stream ingress and CONTINUES the same trace -- its
# frame span carries the propagated trace_id plus parent = the upstream
# span id, so a merged artifact (observe/collector.py) nests gateway ->
# replica -> prefill/keeper spans on one timeline.
#
# Cost contract: when tracing is disabled the frame carries trace=None
# and every hook is a single `is None` check; when enabled, a span is one
# perf_counter read and one tuple append -- no dict churn on the hot
# path, events materialize only at export.

from __future__ import annotations

import hashlib
import itertools
import json
import os
import sys
import time
from collections import deque

__all__ = ["FrameTrace", "Tracer", "TRACE_CONTEXT_KEY",
           "PROGRAM_PREFIX", "NO_SPAN", "NO_SPANS",
           "attach_trace_context", "chrome_trace_document",
           "clock_epoch_unix_us", "definition_fingerprint",
           "make_trace_context", "pop_trace_context", "program_mark",
           "program_span", "trace_context_of",
           "trace_metadata", "trace_metadata_of"]

# trace-metadata schema version: bumped when the embedded layout
# changes; the tune/ loader refuses versions it does not understand
# instead of silently mis-reading spans
TRACE_METADATA_SCHEMA = 1

# One clock epoch per process: every span timestamp is microseconds since
# this moment, so spans from different streams/elements line up on one
# export timeline.
_EPOCH = time.perf_counter()


def now_us() -> float:
    return (time.perf_counter() - _EPOCH) * 1e6


def to_us(perf_counter_s: float) -> float:
    """A raw time.perf_counter() reading on the export timeline."""
    return (perf_counter_s - _EPOCH) * 1e6


def clock_epoch_unix_us() -> float:
    """Wall-clock microseconds (Unix epoch) at THIS process's trace
    timestamp 0.  Every export stamps it into trace_metadata so the
    fleet merger (observe/collector.py) can shift per-process
    timestamps onto one shared timeline: two processes whose spans are
    concurrent in wall time stay concurrent in the merged artifact,
    regardless of when each process booted."""
    return time.time() * 1e6 - now_us()


# reserved frame-data key the cross-process trace context rides under:
# popped at stream ingress (never reaches element inputs), absent
# entirely when the sender's telemetry is disabled -- the wire payload
# is then byte-identical to an untraced build's
TRACE_CONTEXT_KEY = "_trace_context"


def make_trace_context(trace: "FrameTrace",
                       dispatched: bool = False) -> dict:
    """The propagable identity of one frame trace: the (possibly
    already-propagated) trace id plus THIS process's frame span id as
    the downstream parent.  `dispatched` stamps the sender's dispatch
    time (Unix microseconds, the clock clock_epoch_unix_us aligns
    processes on): the receiver's `aiko:ingress` mark measures its
    mailbox wait from it."""
    context = {"trace_id": trace.trace_id, "span_id": trace.span_id}
    if dispatched:
        context["sent_unix_us"] = round(time.time() * 1e6)
    return context


def ingress_wait_s(context: dict | None) -> float | None:
    """Seconds since the sender dispatched the frame `context` rode
    in on; None for a context with no dispatch time (a sender older
    than the stamp, or a hop that does not dispatch)."""
    try:
        sent = float((context or {})["sent_unix_us"])
    except (KeyError, TypeError, ValueError):
        return None
    # two hosts' clocks may disagree by more than the wait
    return max(time.time() - sent / 1e6, 0.0)


def trace_context_of(frame_data) -> dict | None:
    """Read (without removing) the trace context riding `frame_data`."""
    if not isinstance(frame_data, dict):
        return None
    context = frame_data.get(TRACE_CONTEXT_KEY)
    return context if isinstance(context, dict) else None


def attach_trace_context(frame_data: dict, context: dict) -> dict:
    """A COPY of `frame_data` carrying `context` -- the original stays
    untouched so failover replay / byte-compare semantics hold."""
    merged = dict(frame_data)
    merged[TRACE_CONTEXT_KEY] = context
    return merged


def pop_trace_context(frame_data) -> dict | None:
    """Remove and return the trace context (stream-ingress side): the
    context must never leak into element inputs or outputs."""
    if not isinstance(frame_data, dict):
        return None
    context = frame_data.pop(TRACE_CONTEXT_KEY, None)
    return context if isinstance(context, dict) else None


# -- program spans on the profiler's clock ---------------------------------

PROGRAM_PREFIX = "aiko:"
_trace_me = None  # jax.profiler.TraceAnnotation, once jax is in the process


def _annotation():
    """jax's TraceMe class, or None while the process has not imported
    jax (a control-plane process never pays for the import, and has no
    profiler session to write into)."""
    global _trace_me
    if _trace_me is None and "jax" in sys.modules:
        from jax.profiler import TraceAnnotation
        _trace_me = TraceAnnotation
    return _trace_me


class _ProgramSpan:
    """One scoped span: a TraceMe from __enter__ to __exit__ on the
    calling thread, and an X event on `trace` (a FrameTrace) when the
    span belongs to a frame.  set() adds arguments known only once the
    work has run."""

    __slots__ = ("name", "args", "trace", "_scope", "_start_us")

    def __init__(self, name: str, trace, args: dict):
        self.name = name
        self.args = args
        self.trace = trace
        self._scope = None

    def __enter__(self):
        annotation = _annotation()
        if annotation is not None:
            # a TraceMe starts its interval when it is constructed
            self._scope = annotation(self.name, **self.args)
            self._scope.__enter__()
        if self.trace is not None:
            self._start_us = now_us()
        return self

    def set(self, **args) -> None:
        self.args.update(args)
        if self._scope is not None:
            self._scope.set_metadata(**args)

    def __exit__(self, *exc_info):
        if self._scope is not None:
            self._scope.__exit__(*exc_info)
            self._scope = None
        if self.trace is not None:
            self.trace.events.append(
                ("X", self.name, "program", self._start_us,
                 now_us() - self._start_us, self.args))
        return False


class _NoSpan:
    """What a disabled seam hands back: a `with` block that does
    nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def set(self, **args) -> None:
        pass

    def __exit__(self, *exc_info):
        return False


NO_SPAN = _NoSpan()


def program_span(name: str, trace: "FrameTrace | None" = None, **args):
    """A scoped span `aiko:{name}` around a `with` block of work."""
    return _ProgramSpan(PROGRAM_PREFIX + name, trace, args)


def program_mark(name: str, waited_s: float | None = None,
                 trace: "FrameTrace | None" = None, **args) -> None:
    """A closing mark `aiko:{name}`: the interval that ends NOW and
    began `waited_s` ago (a perf_counter difference, carried as
    `waited_us`).  Without `waited_s` it is an instant."""
    name = PROGRAM_PREFIX + name
    if waited_s is not None:
        args["waited_us"] = round(waited_s * 1e6)
    annotation = _annotation()
    if annotation is not None:
        with annotation(name, **args):
            pass
    if trace is not None:
        end_us = now_us()
        if waited_s is None:
            trace.events.append(("i", name, "program", end_us, 0.0, args))
        else:
            trace.events.append(
                ("X", name, "program", end_us - waited_s * 1e6,
                 waited_s * 1e6, args))


class _NoSpans:
    """The seam of a component built without telemetry (a bare
    DecodeEngine): every hook is a call that does nothing."""

    __slots__ = ()
    enabled = False

    def span(self, name, request_id=None, **args):
        return NO_SPAN

    def mark(self, name, waited_s=None, request_id=None, **args) -> None:
        pass

    def record_engine_submit(self, request_id) -> None:
        pass

    def record_chunk(self, request_id, offset, tokens, waited_s,
                     first_s=None) -> None:
        pass


NO_SPANS = _NoSpans()


class FrameTrace:
    """Span accumulator for ONE frame: rides Frame.trace through the
    graph.  `marks` holds open interval starts (queue parks) keyed by
    node; `events` holds finished records as tuples
    (kind, name, category, ts_us, dur_us, args).  The frame's own
    top-level span is NOT an event -- it is built at export from
    start/end/status, keeping the per-frame hot path to appends."""

    __slots__ = ("pid", "seq", "stream_id", "frame_id", "start_us",
                 "end_us", "status", "events", "marks",
                 "origin_trace_id", "parent_span_id", "ingress_wait_s",
                 "submit_wait_s")

    def __init__(self, pid: int, seq: int, stream_id: str,
                 frame_id: int):
        self.pid = pid
        self.seq = seq
        self.stream_id = stream_id
        self.frame_id = frame_id
        self.start_us = now_us()
        self.end_us = None
        self.status = "ok"
        self.events: list = []
        self.marks: dict | None = None  # lazily built on first park
        # cross-process propagation (see TRACE_CONTEXT_KEY): when an
        # upstream process (the serving gateway) minted the trace, this
        # frame CONTINUES it -- same trace id, parented to the
        # upstream frame span
        self.origin_trace_id: str | None = None
        self.parent_span_id: str | None = None
        # sender's dispatch -> this process's ingress (the mailbox
        # wait), when the propagated context carried a dispatch time
        self.ingress_wait_s: float | None = None
        # the whole wait before a decode engine's own clock started:
        # dispatch (where known, else ingress) -> DecodeEngine.submit
        self.submit_wait_s: float | None = None

    @property
    def trace_id(self) -> str:
        # formatted on demand: minting a frame costs no string build
        if self.origin_trace_id is not None:
            return self.origin_trace_id
        return f"{self.pid:x}-{self.seq:x}"

    @property
    def span_id(self) -> str:
        """This frame span's own identity -- what downstream processes
        record as their parent.  (pid, seq) is unique per tracer and
        pids are synthetic-per-process, so ids survive a fleet merge."""
        return f"{self.pid:x}.{self.seq:x}"

    def adopt(self, context: dict | None) -> None:
        """Continue a propagated trace: keep the upstream trace id and
        parent this process's frame span under the upstream span."""
        if not context:
            return
        trace_id = context.get("trace_id")
        if trace_id:
            self.origin_trace_id = str(trace_id)
        parent = context.get("span_id")
        if parent:
            self.parent_span_id = str(parent)

    def span(self, name: str, category: str, start_us: float,
             args: dict | None = None) -> None:
        self.events.append(("X", name, category, start_us,
                            now_us() - start_us, args))

    def instant(self, name: str, category: str,
                args: dict | None = None) -> None:
        self.events.append(("i", name, category, now_us(), 0.0, args))

    def mark(self, key: str) -> None:
        if self.marks is None:
            self.marks = {}
        self.marks[key] = now_us()

    def take_mark(self, key: str) -> float | None:
        if not self.marks:
            return None
        return self.marks.pop(key, None)


class Tracer:
    """Mints trace ids, keeps a bounded ring of completed frame traces,
    and renders Chrome-trace documents.  Global (non-frame) events --
    fused-program compiles, scheduler decisions -- accumulate in their
    own bounded list and export on a dedicated lane."""

    _pids = itertools.count()

    def __init__(self, ring_size: int = 256, pid: int | None = None):
        self._ids = itertools.count(1)
        # synthetic per-tracer pid: several pipelines' traces merged
        # into ONE file stay distinct processes in the Perfetto UI
        self._pid = (pid if pid is not None
                     else os.getpid() * 100 + next(Tracer._pids) % 100)
        self.completed: deque = deque(maxlen=ring_size)
        self.global_events: deque = deque(maxlen=1024)
        self._stream_lanes: dict[str, int] = {}
        # frames evicted from the bounded ring: exports surface this so
        # a truncated artifact never silently reads as full coverage
        self.dropped = 0

    def begin(self, stream_id: str, frame_id: int) -> FrameTrace:
        return FrameTrace(self._pid, next(self._ids), stream_id,
                          frame_id)

    def finish(self, trace: FrameTrace, status: str = "ok") -> None:
        trace.end_us = now_us()
        trace.status = status
        if len(self.completed) == self.completed.maxlen:
            self.dropped += 1
        self.completed.append(trace)

    def instant_global(self, name: str, category: str,
                       args: dict | None = None) -> None:
        self.global_events.append(("i", name, category, now_us(), 0.0,
                                   args))

    def span_global(self, name: str, category: str, elapsed_s: float,
                    args: dict | None = None) -> None:
        """A finished duration event on the global/scheduler lane --
        work that belongs to no single frame (a decode-state
        checkpoint covering every active slot).  Rendered as an X
        span ending now, so the tune loader can median it like any
        frame-attributed span."""
        self.global_events.append(
            ("X", name, category, now_us() - elapsed_s * 1e6,
             elapsed_s * 1e6, args))

    def _lane(self, stream_id: str) -> int:
        lane = self._stream_lanes.get(stream_id)
        if lane is None:
            lane = self._stream_lanes[stream_id] = (
                len(self._stream_lanes) + 1)
        return lane

    def chrome_events(self, process_name: str = "pipeline") -> list:
        """All completed traces + global events as Chrome trace-event
        dicts.  One pid per tracer, one tid lane per stream (lane 0 is
        the global/scheduler lane), metadata events name both."""
        events = [
            {"ph": "M", "name": "process_name", "pid": self._pid,
             "tid": 0, "args": {"name": process_name}},
            {"ph": "M", "name": "thread_name", "pid": self._pid,
             "tid": 0, "args": {"name": "scheduler"}},
        ]
        if self.dropped:
            events.append(self._event(
                "i", f"trace ring dropped {self.dropped} frames",
                "truncation", now_us(), 0.0,
                {"dropped_frames": self.dropped,
                 "ring_size": self.completed.maxlen}, tid=0))
        for kind, name, category, ts, dur, args in self.global_events:
            events.append(self._event(kind, name, category, ts, dur,
                                      args, tid=0))
        named_lanes = set()
        for trace in list(self.completed):
            lane = self._lane(trace.stream_id)
            if lane not in named_lanes:
                named_lanes.add(lane)
                events.append(
                    {"ph": "M", "name": "thread_name", "pid": self._pid,
                     "tid": lane,
                     "args": {"name": f"stream {trace.stream_id}"}})
            end_us = (trace.end_us if trace.end_us is not None
                      else now_us())
            frame_args = {"trace_id": trace.trace_id,
                          "span_id": trace.span_id,
                          "status": trace.status,
                          "stream": trace.stream_id}
            if trace.parent_span_id is not None:
                # propagated trace: this process's frame span nests
                # under the upstream (gateway) span in a merged artifact
                frame_args["parent"] = trace.parent_span_id
            events.append(self._event(
                "X", f"frame {trace.frame_id}", "frame", trace.start_us,
                end_us - trace.start_us, frame_args, tid=lane))
            for kind, name, category, ts, dur, args in trace.events:
                merged = {"trace_id": trace.trace_id,
                          "frame_id": trace.frame_id}
                if args:
                    merged.update(args)
                events.append(self._event(kind, name, category, ts, dur,
                                          merged, tid=lane))
        return events

    def _event(self, kind, name, category, ts, dur, args, tid) -> dict:
        event = {"ph": kind, "name": name, "cat": category,
                 "ts": round(ts, 3), "pid": self._pid, "tid": tid,
                 "args": args or {}}
        if kind == "X":
            event["dur"] = round(dur, 3)
        if kind == "i":
            event["s"] = "t"  # instant scope: thread
        return event

    def export(self, path: str, process_name: str = "pipeline",
               metadata: dict | None = None) -> int:
        """Write a Perfetto-loadable trace file; returns event count.
        `metadata` (see trace_metadata) makes the artifact
        self-describing for `aiko tune`."""
        document = chrome_trace_document(
            self.chrome_events(process_name=process_name),
            metadata=metadata)
        with open(path, "w") as handle:
            json.dump(document, handle)
        return len(document["traceEvents"])


def chrome_trace_document(events: list,
                          metadata: dict | None = None) -> dict:
    """Chrome-trace JSON document.  The optional `metadata` dict rides
    the spec's top-level "metadata" key under an "aiko" namespace --
    Perfetto/chrome://tracing ignore it, `aiko tune` requires it: a
    trace artifact that embeds its own pipeline definition + parameter
    fingerprint + bench config block is replayable with no side-channel
    files."""
    document = {"traceEvents": list(events), "displayTimeUnit": "ms"}
    if metadata is not None:
        document["metadata"] = {"aiko": metadata}
    return document


def definition_fingerprint(document: dict) -> str:
    """Stable content hash of a definition document (canonical JSON):
    the parameter fingerprint a trace is stamped with, so tune can
    tell whether a recommendation was computed against the SAME
    definition+parameters it is about to be applied to."""
    canonical = json.dumps(document, sort_keys=True, default=str)
    return "sha256:" + hashlib.sha256(
        canonical.encode("utf-8")).hexdigest()


def trace_metadata(definition_document: dict | None = None,
                   config: dict | None = None,
                   config_name: str | None = None,
                   metrics: dict | None = None,
                   clock_epoch: bool = False) -> dict:
    """Assemble the self-describing metadata block one trace artifact
    carries: the pipeline definition it was recorded under (with its
    fingerprint), the bench config block that produced it, and a
    metrics-registry snapshot taken at export.

    `clock_epoch=True` additionally stamps this process's
    clock_epoch_unix_us (what the fleet merger aligns timestamps
    with).  LIVE exporters (PipelineTelemetry / GatewayTelemetry) pass
    it; synthesized fixtures must not -- the stamp is wall-clock
    dependent and would break their byte-deterministic regeneration."""
    metadata: dict = {"schema": TRACE_METADATA_SCHEMA}
    if clock_epoch:
        metadata["clock_epoch_unix_us"] = round(
            clock_epoch_unix_us(), 3)
    if definition_document is not None:
        metadata["definition"] = definition_document
        metadata["fingerprint"] = definition_fingerprint(
            definition_document)
    if config is not None:
        metadata["config"] = config
    if config_name is not None:
        metadata["config_name"] = config_name
    if metrics is not None:
        metadata["metrics"] = metrics
    return metadata


def trace_metadata_of(document: dict) -> dict | None:
    """The aiko metadata block of a loaded trace document, or None for
    pre-metadata traces (any Chrome-trace JSON from another tool)."""
    if not isinstance(document, dict):
        return None
    metadata = document.get("metadata")
    if not isinstance(metadata, dict):
        return None
    aiko = metadata.get("aiko")
    return aiko if isinstance(aiko, dict) else None
