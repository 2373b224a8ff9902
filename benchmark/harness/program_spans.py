"""The program's own spans (`aiko:*`, observe/trace.py's table) out of the
run's profile, for the per-layer readers that go by them.

The program writes them as TraceMe events on the thread that did the work,
so they sit on the host plane's thread lines, on the clock the device
events use, with their arguments as stats.  A *scoped* span is a `with`
block; a *closing mark* is written where an interval that crossed threads
ends and carries `waited_us`: the interval is [start - waited_us, start].

`of_run(run)` finds the run's `.xplane.pb` where `common.start_tracer` put
it (`<checkout>/.bench_out/trace-<cell>/plugins/profile/*/`), loads it once
per process and returns a `ProgramSpans`, or None where there is no
profile.  A program without the spans (the parent of the PR that added
them) gives a `ProgramSpans` with nothing in it: every reader then finds
under `MIN_SAMPLES` samples and returns None.
"""

from __future__ import annotations

import glob
import os
import statistics
from dataclasses import dataclass, field

from . import cells
from .trace import WINDOW_SPAN, _clip, _module_runs, _stats, _union

PREFIX = "aiko:"
# a reader with fewer samples than this in the traced window says None
MIN_SAMPLES = 3
# the closing marks of observe/trace.py's table; every other span is scoped
MARKS = ("gateway.admit", "ingress", "engine.submit", "engine.chunk")

_LOADED: dict = {}


@dataclass
class Span:
    name: str              # without the `aiko:` prefix
    line: int              # index of its thread line among the host's
    start_ns: float
    duration_ns: float
    stats: dict
    parent: "Span | None" = None
    children: list = field(default_factory=list)

    @property
    def stop_ns(self) -> float:
        return self.start_ns + self.duration_ns

    def interval_ns(self) -> tuple:
        """What the span covers: a closing mark is expanded to the
        interval it closes."""
        if self.name in MARKS:
            return (self.start_ns - float(self.stats.get("waited_us", 0))
                    * 1e3, self.start_ns)
        return self.start_ns, self.stop_ns

    def waited_ms(self) -> float:
        return float(self.stats.get("waited_us", 0)) / 1e3

    def self_ns(self) -> float:
        """Its duration less that of the spans directly inside it."""
        return self.duration_ns - sum(child.duration_ns
                                      for child in self.children)


class ProgramSpans:
    """The `aiko:` spans that lie whole inside `bench:trace_window`, in
    order of start, and when the device was busy."""

    def __init__(self, spans: list, window: tuple, busy: list | None):
        self.spans = spans
        self.window = window
        # merged [start, stop] of the device's program executions inside
        # the window; None where the profile has no device line
        self.busy = busy

    def named(self, name: str) -> list:
        return [span for span in self.spans if span.name == name]

    def idle_overlap_ns(self, spans: list) -> float | None:
        """Nanoseconds of `spans` during which the device ran nothing."""
        if self.busy is None:
            return None
        total = 0.0
        for span in spans:
            start, stop = span.interval_ns()
            total += (stop - start) - sum(
                high - low for low, high in _clip(self.busy, start, stop))
        return total


def _host_lines(profile) -> list:
    return [line for plane in profile.planes if plane.name == "/host:CPU"
            for line in plane.lines]


def _window(lines: list) -> tuple:
    for line in lines:
        for event in line.events:
            if event.name == WINDOW_SPAN:
                return (event.start_ns,
                        event.start_ns + event.duration_ns)
    return (float("-inf"), float("inf"))


def _nest(spans: list) -> None:
    """Children by containment, line by line."""
    by_line: dict = {}
    for span in spans:
        by_line.setdefault(span.line, []).append(span)
    for line_spans in by_line.values():
        line_spans.sort(key=lambda span: (span.start_ns, -span.duration_ns))
        stack = []
        for span in line_spans:
            while stack and span.start_ns >= stack[-1].stop_ns:
                stack.pop()
            if stack and span.stop_ns <= stack[-1].stop_ns:
                span.parent = stack[-1]
                stack[-1].children.append(span)
            stack.append(span)


def _device_busy(profile, window: tuple) -> list | None:
    """Merged [start, stop] of the first device's program executions (its
    `XLA Modules` line) inside the window; None where the profile has no
    such line (a CPU recording)."""
    low, high = window
    for plane in profile.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        runs = _module_runs(plane)
        if not runs:
            return None
        return _union(_clip([run for executions in runs.values()
                             for run in executions], low, high))
    return None


def parse(profile) -> ProgramSpans:
    """`profile` is a `jax.profiler.ProfileData` or anything shaped like
    one (benchmark/tests/test_program_spans.py feeds a synthetic one)."""
    lines = _host_lines(profile)
    low, high = window = _window(lines)
    spans = []
    for index, line in enumerate(lines):
        for event in line.events:
            if not event.name.startswith(PREFIX):
                continue
            stop = event.start_ns + event.duration_ns
            if event.start_ns < low or stop > high:
                continue
            spans.append(Span(event.name[len(PREFIX):], index,
                              event.start_ns, event.duration_ns,
                              _stats(event)))
    _nest(spans)
    spans.sort(key=lambda span: span.start_ns)
    return ProgramSpans(spans, window, _device_busy(profile, window))


def profile_path(cell_name: str, out_dir: str | None = None) -> str | None:
    found = sorted(glob.glob(os.path.join(
        out_dir or os.path.join(cells.ROOT, ".bench_out"),
        f"trace-{cell_name}", "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def of_run(run) -> ProgramSpans | None:
    """The spans of this run's traced window; None where the run was not
    traced or left no profile."""
    if not run.trace or run.cell is None:
        return None
    path = profile_path(run.cell.name)
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _LOADED:
        import jax
        _LOADED.clear()
        _LOADED[key] = parse(jax.profiler.ProfileData.from_file(path))
    return _LOADED[key]


# -- what several readers share ---------------------------------------------

def mean_or_none(values: list) -> float | None:
    return statistics.fmean(values) if len(values) >= MIN_SAMPLES else None


def median_or_none(values: list) -> float | None:
    return statistics.median(values) if len(values) >= MIN_SAMPLES else None


def _per_request_ms(run, key: str) -> float | None:
    """Mean of `key` (us) over the requests that published a token chunk
    inside the traced window.  Every `aiko:engine.chunk` mark carries how
    its request began, so a request in flight counts, not only one that
    began inside the window: five seconds hold a dozen of the former and,
    in one run in four, under three of the latter."""
    spans = of_run(run)
    if spans is None:
        return None
    by_request = {}
    for span in spans.named("engine.chunk"):
        if key in span.stats:
            by_request[tuple(span.stats.get(part) for part in (
                "stream", "frame", "row"))] = float(span.stats[key]) / 1e3
    return mean_or_none(list(by_request.values()))


def ingress_wait_ms(run) -> float | None:
    """What a request waited before `DecodeEngine.submit` stamped it: the
    gateway's dispatch -> replica ingress (the replica's mailbox, the
    `aiko:ingress` mark) plus ingress -> submit (`aiko:engine.submit`),
    as its chunk marks carry the sum (`ingress_us`)."""
    return _per_request_ms(run, "ingress_us")


def first_chunk_ms(run) -> float | None:
    """First token (the end of the request's prefill) -> first
    `token_chunk`: the `waited_us` of the request's `aiko:engine.chunk`
    mark with offset 0, which its later chunks carry as `first_us`."""
    return _per_request_ms(run, "first_us")


def idle_gap_seconds(run, name: str) -> float | None:
    """Seconds of device-idle time the reduced trace names `name`; 0.0
    where the list of gaps is short enough to be whole and lacks it,
    None where the run has no such list."""
    gaps = ((run.trace or {}).get("breakdown") or {}).get("idle_gaps")
    if gaps is None or not run.trace.get("window_s"):
        return None
    for gap_name, seconds in gaps:
        if gap_name == name:
            return float(seconds)
    return 0.0 if len(gaps) < 10 else None
