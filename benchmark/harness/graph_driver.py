"""Drive one cell of the speech -> LM -> vision graph:
`(sources (asr (text) (lm (reply))) (detector))` through create_pipeline,
the micro-batch scheduler and the fused group path.

Two ways of feeding it, chosen by the mix's `loop`:

  closed  one stream whose source element keeps `frames_in_flight` frames
          in flight (inputs made on the device by the source), so every
          scheduler group is full;
  open    `streams` edge boxes, each a stream of its own; the harness
          posts each frame when it is due, with inputs it made on the
          device beforehand.

A frame is `rows_per_frame` rows; every metric counts rows as frames.
"""

from __future__ import annotations

import gc
import queue
import statistics
import threading
import time

import numpy as np

from . import common, estimators, traffic as traffic_mod
from .common import log, span

ELEMENTS = "aiko_services_tpu.elements"
RESPONSE_TIMEOUT_S = 300.0
SAMPLE_RATE = 16000
NODES = ("asr", "lm", "detector")
TONE_FRAMES = 8


def _local(class_name: str) -> dict:
    return {"local": {"module": ELEMENTS, "class_name": class_name}}


def definition(config: dict, seed: int, tones: list) -> dict:
    """The headline graph as chip_smoke.py::_pipeline_definition builds
    it, at this configuration's sizes."""
    graph, asr, lm, detector = (config["graph"], config["asr"],
                                config["lm"], config["detector"])
    micro = int(graph["micro_batch"])
    rows = int(graph["rows_per_frame"])
    seconds = float(config["clip_seconds"])
    samples = int(seconds * SAMPLE_RATE)
    size = int(detector["image_size"])
    max_tokens = int(asr["transcript_tokens"])
    max_new = int(lm["max_new_tokens"])
    audio_t = f"f32[b,{samples}]"
    image_t = f"f32[b,3,{size},{size}]"
    tokens_t = f"i32[b,{max_tokens}]"
    generated_t = f"i32[b,{max_new}]"
    scheduling = {"micro_batch": micro}
    if graph.get("micro_batch_wait_ms"):
        scheduling["micro_batch_wait_ms"] = graph["micro_batch_wait_ms"]

    if "preset" in asr:
        asr_model = {"preset": asr["preset"],
                     "max_frames": asr["max_source_positions"]}
    else:
        asr_model = {"d_model": asr["d_model"],
                     "enc_layers": asr["encoder_layers"],
                     "dec_layers": asr["decoder_layers"],
                     "n_heads": asr["encoder_attention_heads"],
                     "vocab_size": asr["vocab_size"],
                     "n_mels": asr["num_mel_bins"],
                     "max_frames": asr["max_source_positions"]}
    if "preset" in detector:
        detector_model = {"preset": detector["preset"]}
    else:
        detector_model = {"n_classes": detector["n_classes"],
                          "base_channels": detector["base_channels"],
                          "image_size": size}
    lm_model = {
        "vocab_size": lm["vocab_size"], "d_model": lm["hidden_size"],
        "n_layers": lm["num_hidden_layers"],
        "n_heads": lm["num_attention_heads"],
        "n_kv_heads": lm["num_key_value_heads"],
        "d_ff": lm["intermediate_size"], "max_seq_len": lm["max_seq_len"]}
    return {
        "name": "bench_graph",
        "parameters": {"metrics_interval": 60.0},
        "graph": ["(sources (asr (text) (lm (reply))) (detector))"],
        "elements": [
            {"name": "sources",
             "output": [{"name": "audio", "type": audio_t},
                        {"name": "image", "type": image_t},
                        {"name": "t0", "type": "float"}],
             "parameters": {
                 "data_sources": [[tone, seconds] for tone in tones],
                 "image_shape": [3, size, size],
                 "data_batch_size": rows, "timestamps": True,
                 "on_device": True, "count": 10 ** 9, "seed": seed},
             "deploy": _local("MultiModalSource")},
            {"name": "asr",
             "input": [{"name": "audio", "type": audio_t}],
             "output": [{"name": "tokens", "type": tokens_t}],
             "parameters": dict(asr_model, dtype=asr["dtype"], seed=seed,
                                max_tokens=max_tokens, **scheduling),
             "deploy": _local("SpeechToText")},
            {"name": "text",
             "input": [{"name": "tokens", "type": tokens_t}],
             "output": [{"name": "text", "type": "str"}],
             "parameters": {"workers": 4},
             "deploy": _local("TokensToText")},
            {"name": "lm",
             "input": [{"name": "tokens", "type": tokens_t}],
             "output": [{"name": "generated", "type": generated_t}],
             "parameters": dict(lm_model, dtype=lm["dtype"], seed=seed,
                                max_new_tokens=max_new, **scheduling),
             "deploy": _local("LMGenerate")},
            {"name": "reply",
             "input": [{"name": "tokens", "type": generated_t}],
             "output": [{"name": "text", "type": "str"}],
             "map_in": {"tokens": "generated"},
             "map_out": {"text": "reply"},
             "parameters": {"workers": 4},
             "deploy": _local("TokensToText")},
            {"name": "detector",
             "input": [{"name": "image", "type": image_t}],
             "output": [{"name": "detections", "type": "dict"}],
             "parameters": dict(detector_model, dtype=detector["dtype"],
                                seed=seed, **scheduling),
             "deploy": _local("Detector")},
        ],
    }


def _make_inputs(tones: list, rows: int, seconds: float, size: int,
                 seed: int) -> list:
    """Frames of `rows` rows of inputs on the device, each made in one
    jitted call: a tone per row, `rows` tones to a frame, and
    uniform-noise images drawn from the seed."""
    import jax
    import jax.numpy as jnp

    samples = int(seconds * SAMPLE_RATE)

    @jax.jit
    def make(freqs, key):
        t = jnp.arange(samples) / SAMPLE_RATE
        audio = jnp.sin(2 * jnp.pi * freqs[:, None] * t[None, :])
        image = jax.random.uniform(
            key, (freqs.shape[0], 3, size, size), jnp.float32)
        return audio.astype(jnp.float32), image

    frames = len(tones) // rows
    keys = jax.random.split(jax.random.PRNGKey(seed), frames)
    made = [make(jnp.asarray(tones[index * rows:(index + 1) * rows],
                             jnp.float32), key)
            for index, key in enumerate(keys)]
    jax.block_until_ready(made)
    return made


class _Collector:
    """Takes responses off the pipeline's queue, waits for each frame's
    outputs on the device, and stamps the completion."""

    def __init__(self, responses: queue.Queue, keep):
        self.responses = responses
        self.keep = keep                 # completion -> bool: keep outputs
        self.completions = []            # (done_at, stream_id, frame_id, rows)
        self.kept = {}                   # (stream_id, frame_id) -> outputs
        self.done = set()                # keys completed
        self.bad = []
        self.lock = threading.Condition()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="bench-collector")
        self._thread.start()

    def _run(self) -> None:
        import jax
        while True:
            item = self.responses.get()
            if item is None:
                return
            stream, frame, outputs = item
            with span("bench:wait_frame_outputs"):
                jax.block_until_ready(
                    [outputs.get("generated"), outputs.get("detections"),
                     outputs.get("tokens")])
            now = time.perf_counter()
            generated = outputs.get("generated")
            key = (stream.stream_id, frame.frame_id)
            if generated is None or not isinstance(
                    outputs.get("reply"), (list, str)):
                self.bad.append(key)
                rows = 0
            else:
                rows = int(generated.shape[0])
            with self.lock:
                index = len(self.completions)
                self.completions.append((now, key[0], key[1], rows))
                self.done.add(key)
                if self.keep(index, key):
                    with span("bench:readback"):
                        self.kept[key] = {
                            "tokens": np.asarray(outputs["tokens"]),
                            "generated": np.asarray(generated),
                            "detections": jax.tree_util.tree_map(
                                np.asarray, outputs["detections"])}
                self.lock.notify_all()

    def wait_count(self, count: int, timeout: float) -> bool:
        deadline = time.perf_counter() + timeout
        with self.lock:
            while len(self.completions) < count:
                left = deadline - time.perf_counter()
                if left <= 0:
                    return False
                self.lock.wait(timeout=min(left, 0.5))
        return True

    def has_done(self, keys) -> bool:
        with self.lock:
            return all(key in self.done for key in keys)

    def close(self) -> None:
        self.responses.put(None)
        self._thread.join(timeout=10)


def _registry_state(registry) -> dict:
    state = {"fused": registry.counter("pipeline.fused_groups").value,
             "chained": registry.counter("pipeline.chained_groups").value,
             "fused_failures": registry.counter(
                 "pipeline.fused_failures").value}
    for node in NODES:
        for name in (f"group_frames:{node}", f"queue_s:{node}"):
            histogram = registry.histogram(name)
            state[name] = (histogram.count, histogram.total)
    return state


def _delta(before: dict, after: dict) -> dict:
    return {name: (tuple(a - b for a, b in zip(after[name], before[name]))
                   if isinstance(after[name], tuple)
                   else after[name] - before[name])
            for name in after}


def run(cell, manifest: dict, *, seed: int, seconds: float, trace: bool,
        started_at: float, out_dir: str, require_tpu: bool = True) -> str:
    """One run of one graph cell; returns the result line."""
    import jax
    from aiko_services_tpu.pipeline import create_pipeline
    from aiko_services_tpu.runtime import Process, enable_compile_cache

    device = common.device_facts(cell.chips, require_tpu)
    log(f"compile cache at {enable_compile_cache()}")
    config, mix = cell.config, cell.traffic
    rows = int(config["graph"]["rows_per_frame"])
    # eight frames' worth of tones, so eight consecutive frames differ in
    # every row (the source cycles them; the open loop's inputs too)
    tones = traffic_mod.tone_frequencies(mix, seed, rows * TONE_FRAMES)
    closed = mix["loop"] == "closed"
    check_frames = int(mix.get("check_frames", 4))

    process = Process(transport_kind="loopback")
    pipeline = create_pipeline(process, definition(config, seed, tones))
    threads = [process.run(in_thread=True)]
    responses: queue.Queue = queue.Queue()
    if closed:
        outcome = _closed_window(
            cell, pipeline, responses, seed, seconds, trace, out_dir,
            check_frames)
    else:
        outcome = _open_window(
            cell, pipeline, responses, seed, seconds, trace, out_dir,
            check_frames, tones)
    tracer = outcome["tracer"]
    trace_path = tracer.finish() if tracer else None
    peak = common.memory_peak_bytes(cell.chips)
    fused_state = (sorted(pipeline._fused_disabled),
                   sorted(pipeline._fused_rejected))
    vocab = pipeline.elements["lm"].config.vocab_size
    common.stop_processes([process], threads)
    del pipeline, process, threads
    gc.collect()

    counters = outcome["counters"]
    values = dict(outcome["values"],
                  setup_s=outcome["window_start"] - started_at)
    attempted, failed = outcome["attempted"], outcome["failed"]
    log(f"window: attempted={attempted} failed={failed} "
        f"compiles_in_window={outcome['compiles']} "
        f"fused={counters['fused']} chained={counters['chained']} "
        f"fused_failures={counters['fused_failures']} "
        f"fused_disabled={fused_state[0]} fused_rejected={fused_state[1]}")

    kept = outcome["kept"]
    samples, in_range = [], True
    for outputs in kept:
        for prompt, served in zip(outputs["tokens"], outputs["generated"]):
            samples.append((prompt.astype(np.int32),
                            served.astype(np.int32)))
        in_range = in_range and bool(
            outputs["generated"].min() >= 0
            and outputs["generated"].max() < vocab
            and all(np.all(np.isfinite(np.asarray(leaf, np.float32)))
                    for leaf in jax.tree_util.tree_leaves(
                        outputs["detections"])))
    if kept:
        log(f"sample row: asr tokens {kept[0]['tokens'][0].tolist()} -> "
            f"generated {kept[0]['generated'][0].tolist()}")
    pad_to = -(-(samples[0][0].size + samples[0][1].size) // 64) * 64 \
        if samples else 0
    correct = common.check_served(
        cell, config["lm"], seed, samples, pad_to,
        f"{len(samples)} rows from {len(kept)} frames") and in_range
    log(f"check generated_in_range_and_detections_finite={in_range}")
    if outcome["compiles"] or counters["fused_failures"] or failed:
        log("a compile, a failed fused group or a failed frame inside "
            "the window: the run is not correct")
        correct = False
    return common.report(
        manifest, cell, tracer=tracer, trace_path=trace_path,
        correct=correct, attempted=attempted, failed=failed,
        device=dict(device, memory_peak_bytes=peak), end_to_end=values,
        recorded=dict(
            seconds=seconds, counters=counters,
            late_s=outcome.get("late_s"),
            latency_s=outcome.get("latency_s"),
            frames_in_window=outcome["frames"], rows_per_frame=rows,
            stage_order=NODES_BY_DEVICE_TIME))


# the three fused group programs share the module name `jit_fused`; the
# trace lists them in order of device time, which at these sizes is the
# LM (16 layers of 7B widths, 32 steps), whisper_small, yolov8n
NODES_BY_DEVICE_TIME = ("lm", "asr", "detector")


def _closed_window(cell, pipeline, responses, seed, seconds, trace,
                   out_dir, check_frames) -> dict:
    mix = cell.traffic
    warm_frames = int(mix.get("warm_frames", 24))
    # consecutive frames, from a seeded place in the window's first 32:
    # the source cycles its tones, so they differ in every row
    first = warm_frames + int(np.random.default_rng(seed + 2).integers(24))
    wanted = set(range(first, first + check_frames))
    collector = _Collector(responses, lambda index, key: index in wanted)
    pipeline.create_stream(
        "window", queue_response=responses, grace_time=1800,
        parameters={"frame_window": int(mix["frames_in_flight"])})
    if not collector.wait_count(warm_frames, RESPONSE_TIMEOUT_S):
        raise SystemExit("benchmark: the graph did not warm up")
    registry = pipeline.telemetry.registry
    before = _registry_state(registry)
    compiles_before = common.compile_requests()
    ready_at = time.perf_counter()
    tracer = common.start_tracer(trace, out_dir, cell, 0.4 * seconds,
                                 seconds)
    result = None
    deadline = ready_at + seconds + 30.0
    time.sleep(seconds)
    while result is None and time.perf_counter() < deadline:
        time.sleep(0.25)
        with collector.lock:
            done = list(collector.completions)
        result = estimators.rate_between_barriers(
            [entry[0] for entry in done], [entry[3] for entry in done],
            ready_at, seconds)
    after = _registry_state(registry)
    compiles = common.compile_requests() - compiles_before
    pipeline.destroy_stream("window")
    collector.close()
    if result is None:
        raise SystemExit("benchmark: no two completion barriers "
                         f"{seconds} s apart were seen")
    rate, counted, t0, t1 = result
    frames = [entry for entry in done if t0 < entry[0] <= t1]
    log(f"frames_per_s: {counted} rows in {t1 - t0:.6f} s between two "
        f"completion barriers ({len(frames)} frames)")
    return {"values": {"frames_per_s": rate}, "window_start": t0,
            "attempted": len(frames),
            "failed": sum(1 for entry in frames if entry[3] == 0)
            + len(collector.bad),
            "compiles": compiles, "counters": _delta(before, after),
            "kept": list(collector.kept.values()), "tracer": tracer,
            "frames": len(frames)}


def _open_window(cell, pipeline, responses, seed, seconds, trace, out_dir,
                 check_frames, tones) -> dict:
    config, mix = cell.config, cell.traffic
    warm_in = float(mix.get("warm_in_s", 5.0))
    drain = float(mix.get("drain_s", 8.0))
    boxes = int(mix["streams"])
    schedule = traffic_mod.frame_schedule(
        mix, seed, warm_in + seconds + drain)
    rows = int(config["graph"]["rows_per_frame"])
    inputs = _make_inputs(tones, rows, float(config["clip_seconds"]),
                          int(config["detector"]["image_size"]), seed)
    first_measured = sum(1 for due, _ in schedule if due < warm_in)
    first = boxes + first_measured + int(
        np.random.default_rng(seed + 2).integers(8))
    wanted = set(range(first, first + check_frames))
    collector = _Collector(responses, lambda index, key: index in wanted)
    # a box is a stream of its own.  Its source element is left with
    # nothing to send (one frame at creation, which warms the shapes,
    # then a period of 1e6 s): the harness posts the frames, each when
    # it is due, and they pass through the source as they are
    streams = [pipeline.create_stream(
        f"box{index}", queue_response=responses, grace_time=1800,
        parameters={"rate": 1e-6, "frame_window": 64})
        for index in range(boxes)]
    if not collector.wait_count(boxes, RESPONSE_TIMEOUT_S):
        raise SystemExit("benchmark: the graph did not warm up")
    registry = pipeline.telemetry.registry
    load_start = time.perf_counter()
    window_start, window_end = (load_start + warm_in,
                                load_start + warm_in + seconds)
    tracer = common.start_tracer(trace, out_dir, cell,
                                 warm_in + 0.4 * seconds, seconds)
    before = compiles_before = None
    posted = {}                  # (stream_id, frame_id) -> (due, sent_at)
    next_id = [1] * boxes        # frame 0 was the source's own
    measured = []
    backlog = []                 # (at, frames posted and not yet done)
    for number, (offset, box) in enumerate(schedule):
        due = load_start + offset
        left = due - time.perf_counter()
        if left > 0:
            with span("bench:wait_next_arrival"):
                time.sleep(left)
        if due >= window_end and collector.has_done(measured):
            break  # every measured frame is done: stop offering load
        if before is None and due >= window_start:
            before = _registry_state(registry)
            compiles_before = common.compile_requests()
        audio, image = inputs[number % len(inputs)]
        key = (f"box{box}", next_id[box])
        next_id[box] += 1
        with span("bench:post_frame"):
            sent_at = time.perf_counter()
            pipeline.create_frame(streams[box], {
                "audio": audio, "image": image, "t0": time.time()})
        posted[key] = (due, sent_at)
        backlog.append((sent_at, len(posted) + boxes
                        - len(collector.completions)))
        if window_start <= due < window_end:
            measured.append(key)
    collector.wait_count(boxes + len(posted), drain)
    after = _registry_state(registry)
    compiles = common.compile_requests() - (compiles_before or 0)
    for index in range(boxes):
        pipeline.destroy_stream(f"box{index}")
    collector.close()
    done_at = {(entry[1], entry[2]): entry[0]
               for entry in collector.completions if entry[3]}
    latency = [done_at[key] - posted[key][0]
               for key in measured if key in done_at]
    late = [posted[key][1] - posted[key][0] for key in measured]
    failed = len(measured) - len(latency)
    growth = estimators.queue_growth(backlog, window_start, seconds)
    values = {}
    if latency:
        values["frame_p50_ms"] = statistics.median(latency) * 1e3
        log(f"frame latency: p50={values['frame_p50_ms']:.3f} ms p95="
            f"{estimators.percentile(latency, 95) * 1e3:.3f} ms over "
            f"{len(latency)} frames of {int(config['graph']['rows_per_frame'])} rows")
    if growth:
        log(f"frames in flight: first third mean={growth[0]:.3f} "
            f"last sixth mean={growth[1]:.3f}")
    return {"values": values, "window_start": window_start,
            "attempted": len(measured), "failed": failed,
            "compiles": compiles,
            "counters": _delta(before or after, after),
            "kept": list(collector.kept.values()), "tracer": tracer,
            "late_s": late, "latency_s": latency,
            "frames": len(measured)}
