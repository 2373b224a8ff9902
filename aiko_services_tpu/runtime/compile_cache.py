# Persistent compile cache: warm-start replicas skip the compile storm.
#
# XLA compiles cost seconds to tens of seconds per shape; a freshly
# spawned replica that re-traces every shape the fleet already serves
# arrives too late to absorb the load spike that caused it to be
# spawned.  JAX's persistent compilation cache keys serialized
# executables by (HLO, compile options, backend), so every process that
# points at the SAME cache directory deserializes instead of compiling:
# the fleet pays each shape's compile exactly once, and a warm replica's
# time-to-healthy is dominated by weight hand-off + deserialize, not XLA.
#
# Where the cache lives is decided OUTSIDE the program when
# JAX_COMPILATION_CACHE_DIR is set (jax reads it itself; nothing here
# ever points jax anywhere else), and otherwise is one fixed directory
# inside the checkout -- the path is part of the cache key, so a
# directory that moves never hits.
#
# This module is the one place that flips the JAX knobs and the one
# place that counts: a jax monitoring listener mirrors the cache's
# hit/miss events into the process-global metrics registry
# (`compile_cache.hits` / `compile_cache.misses` /
# `compile_cache.requests`), so "zero recompiles of fleet-known shapes"
# is a published counter, not a hope.  The autoscaler's warm-start proof
# and the `autoscale` bench block both read cache_stats() deltas.
#
# START-UP, MEASURED FROM INSIDE.  The same listener takes jax's own
# durations of a compile (tracing, lowering, the backend's compile or
# the cache's retrieval, each with the program's name) and adds them to
# the CALLING THREAD's record.  The program brackets what it knows can
# compile and what it builds once:
#
#   compile_bracket(noted, ...)   around one call of a jitted program;
#       on the path that compiles nothing it costs two reads of the
#       thread's record.  When the call compiled, `noted` is handed
#       what to put on the `aiko:compile` mark, and the sum of jax's
#       durations is one sample of `setup.compile_s`
#   setup_interval(kind, span)    around an element's weights or an
#       engine's state: one sample of `setup.weights_s` /
#       `setup.state_s`; what compiled inside rides the span as
#       `compile_us` and is NOT in `setup.compile_s` (the records are
#       disjoint: an interval inside another records no sample)
#   program_thread(loop)          an event loop names its thread: a
#       compile there outside every bracket is marked all the same,
#       `what=unbracketed`, so a new code path cannot hide.  On any
#       other thread (a harness, a reference, a test) an unbracketed
#       compile moves nothing under `setup.*`
#
# all in the process-global registry, from the package's epoch:
# histograms `setup.weights_s`, `setup.state_s`, `setup.compile_s`,
# counters `setup.cache_hits`, `setup.cache_requests`, gauges
# `setup.boot_s` (epoch -> the first weights interval opens) and
# `setup.ready_s` (epoch -> the newest interval closed).  Spans and
# marks go through observe/trace.py's seam (its table has their
# arguments).

from __future__ import annotations

import os
import threading
import time
from collections import deque

from .. import PROCESS_EPOCH
from ..utils import get_logger

__all__ = ["enable_compile_cache", "disable_compile_cache",
           "compile_cache_dir", "cache_stats", "thread_cache_snapshot",
           "thread_cache_delta", "compile_bracket", "setup_interval",
           "program_thread", "DEFAULT_CACHE_DIR", "ENV_CACHE_DIR"]

_LOGGER = get_logger("compile_cache")

# jax's own variable: set from outside, it wins over every argument
ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"
# git-ignored, next to the package: the same path for every entry point
# (chip_smoke.py, bench.py, `aiko pipeline`) run from this checkout
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_LOCK = threading.Lock()
# the process-global `setup.*` records: loops' threads close intervals
# side by side, and a listener may run while _LOCK is held
_RECORDS_LOCK = threading.Lock()
_ENABLED_DIR: str | None = None
_LISTENER_INSTALLED = False

# jax-internal monitoring event names (jax 0.9: compiler.py /
# compilation_cache.py); tests/test_autoscale.py::TestCompileCache
# fails if a rename ever leaves the counters silent
_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"
_REQUEST_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
# jax's three intervals of one compile (dispatch.py's log_elapsed_time:
# each announces its start as a scalar and its end as a duration with
# `fun_name`); the backend's holds the cache's key, read and
# deserialisation on a hit
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_SAVED_EVENT = "/jax/compilation_cache/compile_time_saved_sec"

# a thread's running totals, one immutable tuple the listener replaces:
# a bracket keeps the tuple it saw and knows by identity that nothing
# compiled
(_HITS, _MISSES, _REQUESTS, _PROGRAMS, _TRACE_S, _LOWER_S, _BACKEND_S,
 _RETRIEVAL_S, _SAVED_S) = range(9)
_PHASES = {_TRACE_EVENT: _TRACE_S, _LOWER_EVENT: _LOWER_S,
           _BACKEND_EVENT: _BACKEND_S}


def compile_cache_dir() -> str | None:
    """The directory in force: JAX_COMPILATION_CACHE_DIR when set, else
    the one enable_compile_cache() put in place, else None (off)."""
    return os.environ.get(ENV_CACHE_DIR) or _ENABLED_DIR


def enable_compile_cache(directory: str | None = None) -> str:
    """Turn on JAX's persistent compilation cache and install the
    hit/miss counter listener.  The directory is, in order:
    JAX_COMPILATION_CACHE_DIR (never overridden), the `directory`
    argument, DEFAULT_CACHE_DIR.  Idempotent; returns the directory in
    force.  A directory that cannot be created raises.

    Thresholds are forced to cache EVERYTHING (min compile time 0, no
    minimum entry size): the fleet's hot shapes include sub-second toy
    programs in tests and smoke benches, and a threshold that skips them
    would make the warm-start proof flaky."""
    global _ENABLED_DIR
    import jax
    from jax._src import compilation_cache

    # a directory placed from outside is taken verbatim, as jax reads it
    directory = (os.environ.get(ENV_CACHE_DIR)
                 or os.path.abspath(directory or DEFAULT_CACHE_DIR))
    with _LOCK:
        _install_listener()
        if _ENABLED_DIR == directory:
            return directory
        os.makedirs(directory, exist_ok=True)
        if jax.config.jax_compilation_cache_dir != directory:
            jax.config.update("jax_compilation_cache_dir", directory)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        # jax initializes its cache object AT MOST ONCE per process:
        # any compile that ran before the directory was configured
        # latches it disabled, and the config update above would be
        # silently ignored.  reset_cache() drops only the in-memory
        # latch (disk entries survive), so the next compile
        # re-initializes against the directory just set
        compilation_cache.reset_cache()
        _ENABLED_DIR = directory
        _LOGGER.info("persistent compile cache at %s", directory)
        return directory


def disable_compile_cache() -> None:
    """Point JAX back at no cache directory (test hygiene: the config
    is process-global, so a suite that enabled a tmpdir cache must be
    able to hand the next test a cold configuration).  A cache placed
    by JAX_COMPILATION_CACHE_DIR stays where it was placed."""
    global _ENABLED_DIR
    with _LOCK:
        if _ENABLED_DIR is None:
            return
        _ENABLED_DIR = None
        if os.environ.get(ENV_CACHE_DIR):
            return
        import jax
        from jax._src import compilation_cache
        jax.config.update("jax_compilation_cache_dir", None)
        compilation_cache.reset_cache()


def _install_listener() -> None:
    global _LISTENER_INSTALLED
    if _LISTENER_INSTALLED:
        return
    from jax._src import monitoring

    from ..observe.metrics import get_registry

    def _on_event(event: str, **_kwargs) -> None:
        if event == _HIT_EVENT:
            get_registry().counter("compile_cache.hits").inc()
            record = _record()
            record.hit = True
            _add(record, _HITS, 1)
        elif event == _MISS_EVENT:
            get_registry().counter("compile_cache.misses").inc()
            _add(_record(), _MISSES, 1)
        elif event == _REQUEST_EVENT:
            get_registry().counter("compile_cache.requests").inc()
            _add(_record(), _REQUESTS, 1)

    def _on_start(event: str, _value, **_kwargs) -> None:
        if event in _PHASES:
            _record().depth += 1

    def _on_duration(event: str, seconds: float, fun_name: str = "",
                     **_kwargs) -> None:
        index = _PHASES.get(event)
        if index is None:
            if event == _SAVED_EVENT:
                _add(_record(), _SAVED_S, seconds)
            return
        record = _record()
        record.depth = max(record.depth - 1, 0)
        if index == _BACKEND_S and record.hit:
            index, record.hit = _RETRIEVAL_S, False
        if record.depth:
            # an eager operation while another program traces: the
            # enclosing interval's seconds hold these
            return
        _add(record, index, seconds)
        if index in (_BACKEND_S, _RETRIEVAL_S):
            record.names.append(fun_name)
            _add(record, _PROGRAMS, 1)
            if record.loop is not None and not record.open:
                _unbracketed(record)

    monitoring.register_event_listener(_on_event)
    monitoring.register_scalar_listener(_on_start)
    monitoring.register_event_duration_secs_listener(_on_duration)
    _LISTENER_INSTALLED = True


def _listen() -> None:
    """The listener is there before the first bracket opens, whether or
    not anything enabled the cache."""
    if not _LISTENER_INSTALLED:
        with _LOCK:
            _install_listener()


class _ThreadRecord:
    """What jax reported on ONE thread.  Compiles land on the thread
    that dispatched them, and every virtual Process runs its services on
    its own event-loop thread -- so a replica's bring-up, or one call's
    compile, can be attributed exactly even while sibling replicas in
    the same OS process compile concurrently (the global counters cannot
    tell them apart)."""

    __slots__ = ("totals", "names", "depth", "hit", "open", "loose",
                 "loop")

    def __init__(self):
        self.totals = (0, 0, 0, 0, 0.0, 0.0, 0.0, 0.0, 0.0)
        self.names = deque(maxlen=16)   # jax's `fun_name`s, newest last
        self.depth = 0          # of jax's three intervals now open
        self.hit = False        # the backend interval open is a hit
        self.open = 0           # brackets and intervals open here
        self.loose = self.totals    # totals when the last one closed
        self.loop = None        # the event loop whose thread this is


_THREAD_COUNTS: dict[int, _ThreadRecord] = {}


def _record() -> _ThreadRecord:
    record = _THREAD_COUNTS.get(threading.get_ident())
    if record is None:
        record = _THREAD_COUNTS.setdefault(threading.get_ident(),
                                           _ThreadRecord())
    return record


def _add(record: _ThreadRecord, index: int, amount) -> None:
    totals = record.totals
    record.totals = (totals[:index] + (totals[index] + amount,)
                     + totals[index + 1:])


def program_thread(loop) -> None:
    """The calling thread is `loop`'s (an EventEngine: `name`, `traced`)
    from here on, or nobody's (None, as the loop ends: a thread's ident
    is handed to a later thread).  What compiles on a loop's thread
    outside every bracket is marked `what=unbracketed`."""
    _record().loop = loop


def _taken(record: _ThreadRecord, before: tuple) -> tuple:
    """What jax reported on `record`'s thread since `before`:
    (seconds compiling, programs, the `aiko:compile` mark's arguments,
    cache hits, cache requests).  The parts are rounded first and the
    whole is their sum, so a reader can add them up."""
    taken = [now - was for now, was in zip(record.totals, before)]
    programs = taken[_PROGRAMS]
    names = list(record.names)[-programs:] if programs else []
    args = {"program": ",".join(dict.fromkeys(names)),
            "programs": programs,
            "trace_us": round(taken[_TRACE_S] * 1e6),
            "lower_us": round(taken[_LOWER_S] * 1e6)}
    waited_us = args["trace_us"] + args["lower_us"]
    hits, requests = taken[_HITS], taken[_REQUESTS]
    if taken[_BACKEND_S] or not hits:
        args["backend_us"] = round(taken[_BACKEND_S] * 1e6)
        waited_us += args["backend_us"]
    if hits:
        args["retrieval_us"] = round(taken[_RETRIEVAL_S] * 1e6)
        args["saved_us"] = round(taken[_SAVED_S] * 1e6)
        waited_us += args["retrieval_us"]
    # jax computes a key wherever its cache is not disabled; without a
    # directory nothing is read or kept
    args["cache"] = ("off" if not requests or compile_cache_dir() is None
                     else "hit" if hits == requests else "miss")
    return waited_us / 1e6, programs, args, hits, requests


def _note_interval(record: _ThreadRecord, kind: str, seconds: float,
                   hits: int, requests: int,
                   started: float | None = None) -> None:
    """One start-up interval of `kind` closed on `record`'s thread."""
    record.loose = record.totals
    if record.open:
        return  # inside another interval: its seconds hold these
    from ..observe.metrics import get_registry
    registry = get_registry()
    with _RECORDS_LOCK:
        registry.histogram(f"setup.{kind}_s").record(seconds)
        registry.counter("setup.cache_hits").inc(hits)
        registry.counter("setup.cache_requests").inc(requests)
        if started is not None and not registry.has_gauge("setup.boot_s"):
            registry.gauge("setup.boot_s").set(started - PROCESS_EPOCH)
        registry.gauge("setup.ready_s").set(
            time.perf_counter() - PROCESS_EPOCH)


def _unbracketed(record: _ThreadRecord) -> None:
    """A program compiled on an event loop's thread with no bracket
    open: the listener marks it itself."""
    waited_s, _, args, hits, requests = _taken(record, record.loose)
    _note_interval(record, "compile", waited_s, hits, requests)
    if record.loop.traced:
        from ..observe.trace import program_mark
        program_mark("compile", waited_s, node=record.loop.name,
                     what="unbracketed", **args)


class _CompileBracket:
    __slots__ = ("_noted", "_args", "_record", "_before")

    def __init__(self, noted, args):
        self._noted = noted
        self._args = args

    def __enter__(self):
        record = self._record = _record()
        record.open += 1
        self._before = record.totals

    def __exit__(self, *exc_info):
        record = self._record
        record.open -= 1
        if record.totals is not self._before:
            waited_s, programs, args, hits, requests = _taken(
                record, self._before)
            if programs:
                _note_interval(record, "compile", waited_s, hits,
                               requests)
                self._noted(waited_s, programs, args, *self._args)
            else:
                # traced anew and found compiled (a static argument's
                # new value, the same program): milliseconds, no mark
                record.loose = record.totals
        return False


def compile_bracket(noted, *args) -> _CompileBracket:
    """A `with` block around one call of a jitted program.  If jax
    compiled a program on this thread inside it, or took one from its
    persistent cache, `noted(waited_s, programs, mark_args, *args)` is
    called as it closes: the seconds jax spent (not the call's: the
    program's first execution is not in them), how many programs it
    compiled or retrieved, and the `aiko:compile` mark's arguments."""
    _listen()
    return _CompileBracket(noted, args)


class _SetupInterval:
    __slots__ = ("_kind", "_span", "_record", "_before", "_start")

    def __init__(self, kind: str, span):
        self._kind = kind
        self._span = span

    def __enter__(self):
        record = self._record = _record()
        record.open += 1
        self._before = record.totals
        self._span.__enter__()
        self._start = time.perf_counter()
        return self

    def holds(self, tree) -> None:
        """What the interval made, a pytree of arrays: its `bytes` and
        `leaves` ride the span."""
        import jax
        leaves = jax.tree_util.tree_leaves(tree)
        self._span.set(
            bytes=sum(int(getattr(leaf, "nbytes", 0)) for leaf in leaves),
            leaves=len(leaves))

    def __exit__(self, *exc_info):
        seconds = time.perf_counter() - self._start
        record = self._record
        record.open -= 1
        waited_s, _, _, hits, requests = _taken(record, self._before)
        self._span.set(compile_us=round(waited_s * 1e6))
        self._span.__exit__(*exc_info)
        _note_interval(
            record, self._kind, seconds, hits, requests,
            self._start if self._kind == "weights" else None)
        return False


def setup_interval(kind: str, span) -> _SetupInterval:
    """A `with` block around what a process builds once: `kind` is
    `weights` (an element's setup) or `state` (an engine's pool), `span`
    the seam's `aiko:setup.{kind}` span (NO_SPAN with telemetry off: the
    records are written all the same, once a process).  holds() puts
    the size of what was made on the span; `compile_us`, jax's durations
    that fell inside, is added as it closes."""
    _listen()
    return _SetupInterval(kind, span)


def thread_cache_snapshot() -> dict:
    """{thread_ident: (hits, misses)} at this moment; diff two
    snapshots over a known thread set to scope a bring-up's compile
    traffic to exactly the threads that ran it."""
    return {ident: record.totals[:2]
            for ident, record in list(_THREAD_COUNTS.items())}


def thread_cache_delta(before: dict, after: dict, idents) -> dict:
    """Hits/misses accumulated between two snapshots on `idents` only."""
    hits = misses = 0
    for ident in idents:
        if ident is None:
            continue
        base = before.get(ident, (0, 0))
        now = after.get(ident, (0, 0))
        hits += now[0] - base[0]
        misses += now[1] - base[1]
    return {"hits": hits, "misses": misses}


def cache_stats() -> dict:
    """Current counter values (zeros until the listener sees traffic):
    read before/after a replica bring-up and diff to get that replica's
    compiles_in_window."""
    from ..observe.metrics import get_registry
    registry = get_registry()
    return {
        "dir": compile_cache_dir(),
        "hits": registry.counter("compile_cache.hits").value,
        "misses": registry.counter("compile_cache.misses").value,
        "requests": registry.counter("compile_cache.requests").value,
    }
