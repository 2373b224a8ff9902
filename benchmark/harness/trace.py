"""From the profiler's trace to device time: busy and idle seconds, time by
jitted program, the operations that took most, and idle gaps named by what
the host was doing.

Works on `jax.profiler.ProfileData` (planes -> lines -> events with a
name, a start and a duration in nanoseconds, and stats), or anything
shaped like it: benchmark/tests/test_trace.py feeds it a synthetic trace
laid out like a TPU's and a small one recorded on the spot.

A program is named by its module, `jit_<function>` (the device's `XLA
Modules` line has one event per execution), never by a compiler-numbered
operation.  Programs that share a module name (every fused group program
is `jit_fused`) are told apart by their fingerprint and listed as
`jit_fused#1`, `jit_fused#2` ... in order of device time.
"""

from __future__ import annotations

import bisect

HARNESS_PREFIX = "bench:"
WINDOW_SPAN = "bench:trace_window"
_WAITS = ("bench:wait",)
_SHORT_GAP_NS = 20_000


def load(path: str):
    import jax
    return jax.profiler.ProfileData.from_file(path)


def _stats(event) -> dict:
    return {key: value for key, value in event.stats}


def _union(intervals: list) -> list:
    merged = []
    for start, stop in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], stop)
        else:
            merged.append([start, stop])
    return merged


def _clip(intervals: list, low: float, high: float) -> list:
    return [(max(start, low), min(stop, high))
            for start, stop in intervals
            if min(stop, high) > max(start, low)]


def _device_planes(profile) -> list:
    planes = [plane for plane in profile.planes
              if plane.name.startswith("/device:TPU:")]
    # a CPU recording (the tests) keeps XLA's operations on the host
    # plane's worker threads
    return planes or [plane for plane in profile.planes
                      if plane.name == "/host:CPU"]


def _module_runs(plane) -> dict:
    """(module, program) -> [(start, stop)] from the device's own
    `XLA Modules` line: one event per execution of a program, named
    `jit_step(<fingerprint>)`.  {} where the plane has no such line."""
    runs: dict = {}
    for line in plane.lines:
        if line.name != "XLA Modules":
            continue
        for event in line.events:
            if event.duration_ns <= 0:
                continue
            module, _, rest = event.name.partition("(")
            runs.setdefault((module, rest.rstrip(")")), []).append(
                (event.start_ns, event.start_ns + event.duration_ns))
    return runs


def _device_ops(plane, module_runs: dict) -> list:
    """[(start, stop, op name, module, program)] of one device.  On a
    TPU an operation is an event of the `XLA Ops` line and belongs to
    the program execution it falls inside; a CPU recording says so in
    the operation's own `hlo_module` and `program_id` stats."""
    executions = sorted((start, stop, key)
                        for key, runs in module_runs.items()
                        for start, stop in runs)
    starts = [execution[0] for execution in executions]
    ops = []
    for line in plane.lines:
        if executions and line.name != "XLA Ops":
            continue
        for event in line.events:
            if event.duration_ns <= 0:
                continue
            stop = event.start_ns + event.duration_ns
            if executions:
                index = bisect.bisect_right(starts, event.start_ns) - 1
                inside = index >= 0 and stop <= executions[index][1]
                key = executions[index][2] if inside else ("outside", "")
                name = event.name.split(" = ")[0].lstrip("%")
            else:
                stats = _stats(event)
                if "hlo_module" not in stats:
                    continue
                key = (str(stats["hlo_module"]),
                       str(stats.get("program_id", "")))
                name = event.name
            ops.append((event.start_ns, stop, name, key[0], key[1]))
    return ops


def _host_spans(profile) -> list:
    """[(start, stop, name)] of what ran on the host's Python threads:
    the harness's own spans and jax's (`PjitFunction(...)`,
    `np.asarray(jax.Array)`)."""
    spans = []
    for plane in profile.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for event in line.events:
                named = event.name.startswith(HARNESS_PREFIX)
                # "$..." is the Python tracer's own call record
                if event.duration_ns > 0 and (
                        named or (line.name.startswith("python")
                                  and not event.name.startswith("$"))):
                    spans.append((event.start_ns,
                                  event.start_ns + event.duration_ns,
                                  event.name))
    return spans


def _name_gap(middle: float, spans: list) -> str:
    """What the host was doing in the middle of an idle gap: the
    narrowest harness span that is not a wait, else the narrowest jax
    call, else the harness's wait."""
    covering = [span for span in spans
                if span[0] <= middle < span[1] and span[2] != WINDOW_SPAN]
    if not covering:
        return "no_host_span"

    def rank(span):
        harness = span[2].startswith(HARNESS_PREFIX)
        waiting = span[2].startswith(_WAITS)
        return (0 if harness and not waiting else 2 if waiting else 1,
                span[1] - span[0])

    return min(covering, key=rank)[2]


def _window(spans: list, ops_by_device: list) -> tuple:
    for start, stop, name in spans:
        if name == WINDOW_SPAN:
            return start, stop
    starts = [op[0] for ops in ops_by_device for op in ops]
    stops = [op[1] for ops in ops_by_device for op in ops]
    return (min(starts), max(stops)) if starts else (0, 0)


def _program_names(seconds_by_key: dict) -> dict:
    """(module, program) -> display name; same-named programs are
    numbered in order of device time."""
    by_module: dict = {}
    for key in seconds_by_key:
        by_module.setdefault(key[0], []).append(key)
    names = {}
    for module, keys in by_module.items():
        keys.sort(key=lambda key: -seconds_by_key[key])
        for rank, key in enumerate(keys, start=1):
            names[key] = module if len(keys) == 1 else f"{module}#{rank}"
    return names


def reduce(profile, chips: int = 1) -> dict:
    """The numbers the benchmark takes from a trace.  Seconds are
    averaged over the `chips` devices the cell uses."""
    planes = _device_planes(profile)[:chips]
    runs_by_device = [_module_runs(plane) for plane in planes]
    ops_by_device = [_device_ops(plane, runs)
                     for plane, runs in zip(planes, runs_by_device)]
    spans = _host_spans(profile)
    low, high = _window(spans, ops_by_device)
    window_s = (high - low) / 1e9
    devices = max(len(planes), 1)

    busy_ns = 0.0
    program_ns: dict = {}
    program_runs: dict = {}     # key -> seconds of each whole execution
    op_ns: dict = {}
    gaps_ns: dict = {}
    for module_runs, ops in zip(runs_by_device, ops_by_device):
        ops = [op for op in ops if op[1] > low and op[0] < high]
        merged = _union(_clip([(op[0], op[1]) for op in ops], low, high))
        busy_ns += sum(stop - start for start, stop in merged)
        per_program: dict = {}
        for start, stop, name, module, program in ops:
            per_program.setdefault((module, program), []).append(
                (start, stop))
            op_ns[(module, program, name)] = op_ns.get(
                (module, program, name), 0) + (min(stop, high)
                                               - max(start, low))
        for key, intervals in per_program.items():
            union = _union(_clip(intervals, low, high))
            program_ns[key] = program_ns.get(key, 0) + sum(
                stop - start for start, stop in union)
            executions = module_runs.get(key)
            if executions is None:
                # no module line: an execution is a run of operations
                # with no gap over 1 ms between them
                executions = []
                for start, stop in union:
                    if executions and start - executions[-1][1] <= 1e6:
                        executions[-1][1] = stop
                    else:
                        executions.append([start, stop])
            # executions cut by an end of the window are not whole
            program_runs.setdefault(key, []).extend(
                (stop - start) / 1e9 for start, stop in executions
                if start > low and stop < high)
        # idle gaps, named by what the host was doing in their middle
        edges = [(low, low)] + merged + [(high, high)]
        for left, right in zip(edges, edges[1:]):
            gap = right[0] - left[1]
            if gap <= 0:
                continue
            name = ("gaps_under_20us" if gap < _SHORT_GAP_NS
                    else _name_gap((left[1] + right[0]) / 2, spans))
            gaps_ns[name] = gaps_ns.get(name, 0) + gap

    seconds_by_key = {key: value / 1e9 / devices
                      for key, value in program_ns.items()}
    names = _program_names(seconds_by_key)
    programs = {names[key]: {"seconds": seconds,
                             "run_seconds": program_runs.get(key, []),
                             "module": key[0]}
                for key, seconds in seconds_by_key.items()}
    top_ops = sorted(((f"{names[(module, program)]}/{name}",
                       value / 1e9 / devices)
                      for (module, program, name), value in op_ns.items()),
                     key=lambda item: -item[1])
    top_programs = sorted(((name, facts["seconds"])
                           for name, facts in programs.items()),
                          key=lambda item: -item[1])
    idle = sorted(((name, value / 1e9 / devices)
                   for name, value in gaps_ns.items()),
                  key=lambda item: -item[1])
    return {
        "window_s": window_s,
        "busy_s": busy_ns / 1e9 / devices,
        "programs": programs,
        "breakdown": {
            # programs first: their names are stable from PR to PR; the
            # compiler-numbered operations under them are not
            "device_ops": [list(item) for item in
                           (top_programs[:6] + top_ops)[:10]],
            "idle_gaps": [list(item) for item in idle[:10]],
        },
    }


def describe(profile, limit: int = 8) -> str:
    """Planes, lines and first events: for looking at a trace by hand."""
    out = []
    for plane in profile.planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            out.append(f"  LINE {line.name} events={len(events)}")
            for event in events[:limit]:
                out.append(f"    {event.name[:160]} start={event.start_ns} "
                           f"dur={event.duration_ns} "
                           f"stats={list(event.stats)[:6]}")
    return "\n".join(out)
