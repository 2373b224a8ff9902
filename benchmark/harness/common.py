"""What both drivers share: the device, the window's bookkeeping, the
profiler, the per-layer readers and the last line."""

from __future__ import annotations

import gc
import glob
import json
import os
import shutil
import threading
import time
from contextlib import contextmanager

from . import cells, roofline


class RunData(dict):
    """What a window recorded, as the per-layer readers see it: a dict
    with attribute access.  A reader takes what it needs and returns
    None where there is nothing to read."""
    __getattr__ = dict.get


def log(text: str) -> None:
    print(f"[bench] {text}", flush=True)


def device_facts(chips: int, require_tpu: bool) -> dict:
    """The device as jax reports it; no TPU, or fewer chips than the
    cell asks for, is an error (never a fallback)."""
    import jax
    devices = jax.devices()
    first = devices[0]
    if require_tpu and first.platform != "tpu":
        raise SystemExit(f"benchmark: jax found platform "
                         f"{first.platform!r}, not a TPU; nothing was run")
    if len(devices) < chips:
        raise SystemExit(f"benchmark: the cell needs {chips} chip(s), jax "
                         f"found {len(devices)}")
    return {"platform": first.platform, "kind": first.device_kind,
            "count": chips}


def memory_peak_bytes(chips: int) -> int:
    import jax
    peaks = []
    for device in jax.devices()[:chips]:
        stats = device.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def compile_requests() -> int:
    from aiko_services_tpu.runtime import cache_stats
    return int(cache_stats()["requests"])


def histogram_totals(registry, name: str) -> tuple:
    histogram = registry.histogram(name)
    return histogram.count, histogram.total


def stop_processes(processes: list, threads: list) -> None:
    """terminate() only signals an event loop; its thread drops the
    elements (weights, KV pool) when it exits.  Join it, so device
    memory is back before the reference runs."""
    for process in reversed(processes):
        process.terminate()
    for thread in threads:
        thread.join(timeout=60)
    gc.collect()


@contextmanager
def span(name: str):
    """A harness span in the profiler's own trace (and nothing when the
    profiler is off): idle gaps are named by these."""
    import jax
    with jax.profiler.TraceAnnotation(name):
        yield


class WindowTracer:
    """Profiles `seconds` of the steady window from a thread of its own,
    `delay` seconds after start(); the trace lands under `directory`."""

    def __init__(self, directory: str, delay: float, seconds: float):
        self.directory, self.delay, self.seconds = directory, delay, seconds
        self.started_at = self.stopped_at = None
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="bench-tracer")

    def start(self) -> None:
        shutil.rmtree(self.directory, ignore_errors=True)
        os.makedirs(self.directory, exist_ok=True)
        self._thread.start()

    def _run(self) -> None:
        import jax
        time.sleep(self.delay)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.directory, profiler_options=options)
        self.started_at = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench:trace_window"):
            time.sleep(self.seconds)
        self.stopped_at = time.perf_counter()
        jax.profiler.stop_trace()

    def finish(self) -> str | None:
        """Path of the recorded .xplane.pb, once the profiler is done."""
        self._thread.join(timeout=300)
        found = sorted(glob.glob(os.path.join(
            self.directory, "plugins", "profile", "*", "*.xplane.pb")))
        return found[-1] if found else None


def start_tracer(trace: bool, out_dir: str, cell, delay: float,
                 seconds: float) -> WindowTracer | None:
    """With --trace 1, profile `trace_seconds` of the mix (at most half
    the window) from `delay` seconds on."""
    if not trace:
        return None
    tracer = WindowTracer(
        f"{out_dir}/trace-{cell.name}", delay,
        min(float(cell.traffic.get("trace_seconds", 5.0)), seconds / 2))
    tracer.start()
    return tracer


def check_served(cell, lm: dict, seed: int, samples: list, pad_to: int,
                 what: str) -> bool:
    """Hold what the LM stage served to the reference and the cell's
    limits, every number printed beside its limit.  No sample is not
    correct."""
    from . import checks
    if not samples:
        log("check: nothing finished that could be compared")
        return False
    started = time.perf_counter()
    measured = checks.served_gaps(lm, seed, samples, pad_to=pad_to)
    correct, lines = checks.judge(measured, cell.limits)
    for line in lines:
        log(line)
    log(f"reference: {measured['tokens_compared']} served tokens of "
        f"{what} in {time.perf_counter() - started:.1f} s: {measured}")
    return correct


def read_per_layer(cell, run: RunData) -> dict:
    values = {}
    for name in cell.per_layer:
        value = cells.load_reader(name)(run)
        if value is not None:
            values[name] = float(value)
    return values


def report(manifest: dict, cell, *, tracer: WindowTracer | None,
           trace_path: str | None, correct: bool, attempted: int,
           failed: int, device: dict, end_to_end: dict,
           recorded: dict) -> str:
    """The run's last line.  An untraced run reports the cell's
    end-to-end metrics; a traced one reduces the trace, hands the per-layer
    readers what the window `recorded`, and reports what they read."""
    if tracer is None:
        values = {name: value for name, value in end_to_end.items()
                  if name in cell.end_to_end}
        return result_line(manifest, correct=correct, attempted=attempted,
                           failed=failed, values=values, device=device,
                           breakdown=None)
    facts = trace_facts(trace_path, tracer, cell.chips)
    breakdown = None
    if facts:
        device = dict(device, busy_s=facts["busy_s"],
                      window_s=facts["window_s"])
        breakdown = facts["breakdown"]
    run = RunData(recorded, cell=cell, trace=facts,
                  peaks=peaks_for(device))
    return result_line(manifest, correct=correct, attempted=attempted,
                       failed=failed, values=read_per_layer(cell, run),
                       device=device, breakdown=breakdown)


def result_line(manifest: dict, *, correct: bool, attempted: int,
                failed: int, values: dict, device: dict,
                breakdown: dict | None) -> str:
    units = cells.units(manifest)
    result = {
        "correct": bool(correct), "attempted": int(attempted),
        "failed": int(failed),
        "metrics": {name: {"value": value, "unit": units[name]}
                    for name, value in values.items()},
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    return json.dumps(result)


def trace_facts(path: str | None, tracer: WindowTracer, chips: int) -> dict:
    """Reduce the recorded trace; {} when there is none."""
    if path is None:
        return {}
    from . import trace
    reduced = trace.reduce(trace.load(path), chips=chips)
    reduced["host_window_s"] = tracer.stopped_at - tracer.started_at
    return reduced


def peaks_for(device: dict) -> dict:
    return roofline.peaks(device["kind"]) if device["platform"] == "tpu" \
        else {}
