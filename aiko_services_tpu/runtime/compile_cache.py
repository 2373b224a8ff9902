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

from __future__ import annotations

import os
import threading

from ..utils import get_logger

__all__ = ["enable_compile_cache", "disable_compile_cache",
           "compile_cache_dir", "cache_stats", "thread_cache_snapshot",
           "thread_cache_delta", "DEFAULT_CACHE_DIR", "ENV_CACHE_DIR"]

_LOGGER = get_logger("compile_cache")

# jax's own variable: set from outside, it wins over every argument
ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"
# git-ignored, next to the package: the same path for every entry point
# (chip_smoke.py, bench.py, `aiko pipeline`) run from this checkout
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_LOCK = threading.Lock()
_ENABLED_DIR: str | None = None
_LISTENER_INSTALLED = False

# jax-internal monitoring event names (jax 0.9: compiler.py /
# compilation_cache.py); tests/test_autoscale.py::TestCompileCache
# fails if a rename ever leaves the counters silent
_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"
_REQUEST_EVENT = "/jax/compilation_cache/compile_requests_use_cache"


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
            _bump_thread(0)
        elif event == _MISS_EVENT:
            get_registry().counter("compile_cache.misses").inc()
            _bump_thread(1)
        elif event == _REQUEST_EVENT:
            get_registry().counter("compile_cache.requests").inc()

    monitoring.register_event_listener(_on_event)
    _LISTENER_INSTALLED = True


# hit/miss counts PER THREAD (ident -> [hits, misses]): compiles land
# on the thread that dispatched them, and every virtual Process runs
# its services on its own event-loop thread -- so a replica's bring-up
# can be attributed exactly even while sibling replicas in the same OS
# process compile concurrently (the global counters cannot tell them
# apart)
_THREAD_COUNTS: dict[int, list] = {}


def _bump_thread(index: int) -> None:
    ident = threading.get_ident()
    with _LOCK:  # pairs with thread_cache_snapshot's iteration
        entry = _THREAD_COUNTS.get(ident)
        if entry is None:
            entry = _THREAD_COUNTS.setdefault(ident, [0, 0])
        entry[index] += 1


def thread_cache_snapshot() -> dict:
    """{thread_ident: (hits, misses)} at this moment; diff two
    snapshots over a known thread set to scope a bring-up's compile
    traffic to exactly the threads that ran it."""
    with _LOCK:
        return {ident: (entry[0], entry[1])
                for ident, entry in _THREAD_COUNTS.items()}


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
