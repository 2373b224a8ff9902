"""Start-up as the program measured it from inside (ISSUE 37), for the
seven `setup_*` readers.

The program writes its start-up records into its process-global metrics
registry (`aiko_services_tpu/runtime/compile_cache.py`): a histogram a
kind of interval (`setup.weights_s`, one sample an element's weights;
`setup.state_s`, an engine's pool; `setup.compile_s`, jax's own compile
durations of a bracketed call that compiled), the counters
`setup.cache_hits` / `setup.cache_requests`, and two gauges from the
package's import, `setup.boot_s` (until the first weights interval
opens) and `setup.ready_s` (until the newest interval closed).  A
benchmark run is one OS process and the registry is the process's, so a
reader takes it as it stands when the readers run: `RunData` carries no
registry, and the replica's own is gone by then.  What the harness or
the reference compiled is in none of these records.

A program without the records (the parent of the PR that added them)
has no `setup.ready_s` gauge: every function here then says None.
"""

from __future__ import annotations

HISTOGRAMS = {"weights": "setup.weights_s", "state": "setup.state_s",
              "compile": "setup.compile_s"}
GAUGES = {"boot": "setup.boot_s", "ready": "setup.ready_s"}


def _registry():
    """The program's registry, or None where it keeps no start-up
    record at all (no interval has closed: `setup.ready_s` is unset)."""
    from aiko_services_tpu.observe.metrics import get_registry
    registry = get_registry()
    return registry if registry.has_gauge(GAUGES["ready"]) else None


def total_s(kind: str) -> float | None:
    """Seconds summed over the samples of one kind of interval; 0 for a
    kind the run had none of (a graph cell makes no pool)."""
    registry = _registry()
    if registry is None:
        return None
    return registry.histogram(HISTOGRAMS[kind]).total


def gauge_s(which: str) -> float | None:
    registry = _registry()
    if registry is None or not registry.has_gauge(GAUGES[which]):
        return None
    return registry.gauge(GAUGES[which]).value


def cache_hit_pct() -> float | None:
    registry = _registry()
    if registry is None:
        return None
    requests = registry.counter("setup.cache_requests").value
    if not requests:
        return None
    return registry.counter("setup.cache_hits").value / requests * 100


def unnamed_s() -> float | None:
    """What lies between the named intervals: ready less boot, weights,
    state and compile."""
    boot, ready = gauge_s("boot"), gauge_s("ready")
    if boot is None or ready is None:
        return None
    return ready - boot - sum(total_s(kind) for kind in HISTOGRAMS)
