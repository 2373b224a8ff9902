# Benchmark harness: the five BASELINE.json configurations, measured
# through the real framework path, with MFU per compute stage.
#
#   1 text      single-stage text PipelineElement (CPU-class reference:
#               the reference multitude ceiling was ~50 frames/sec over a
#               localhost MQTT broker, run_small.sh:9,21)
#   2 asr       Whisper-small-shape speech->text element, 1 chip
#   3 detector  YOLOv8n-shape detection element, batched stream
#   4 llm       Llama-family decode: time-to-first-token + tokens/sec,
#               streamed through generate_stream (the serving path)
#   5 pipeline  3-stage multi-modal graph (speech -> LM, vision ->
#               detections) end-to-end
#
# Prints ONE JSON line.  Headline metric = config 5 end-to-end frames/sec.
# vs_baseline: with the pipeline config, the end-to-end AUDIO-REALTIME
# factor divided by the reference's whisper-small single-GPU 6x realtime
# (speech_elements.py:186-192); for subset runs without the pipeline
# config, the ratio over the reference's 50 frames/sec broker ceiling.
# The "baseline" key names which denominator applied.  Per-config
# results ride in "configs".
#
# Env knobs: AIKO_BENCH_SMOKE=1 shrinks models/frame counts for CPU smoke
# runs; AIKO_BENCH_CONFIGS=csv subset (e.g. "llm,pipeline");
# AIKO_BENCH_PEAK_TFLOPS overrides the per-chip peak used for MFU.

from __future__ import annotations

import json
import os
import queue
import random
import sys
import time

REFERENCE_FRAMES_PER_SEC = 50.0  # multitude ceiling, run_small.sh:9
# reference whisper-small on a single GPU: 6x realtime (relative-speed
# table, speech_elements.py:186-192)
REFERENCE_GPU_SPEECH_REALTIME = 6.0
SMOKE = os.environ.get("AIKO_BENCH_SMOKE", "") not in ("", "0")
# sources synthesize in HBM by default (measure model compute, not host
# ingest); AIKO_BENCH_ON_DEVICE=0 reverts to host-synthesized frames
ON_DEVICE = os.environ.get("AIKO_BENCH_ON_DEVICE", "1") != "0"
# pipeline telemetry (metrics + frame tracing) rides every benched
# pipeline unless AIKO_BENCH_TELEMETRY=0 -- the off arm measures the
# instrumentation overhead; the flag is
# published in every config block so A/B JSON is self-describing
TELEMETRY = os.environ.get("AIKO_BENCH_TELEMETRY", "1") != "0"
# --trace <path>: accumulate Chrome-trace events from every benched
# pipeline (the config-5 graph included).  EVERY pipeline-running
# config writes its OWN self-describing artifact named by config
# (<path minus .json>.<config>.json -- definition + parameter
# fingerprint + config block + metrics snapshot embedded in the trace
# metadata, so `aiko tune` replays it with no side-channel files), the
# artifact path is published in that config's block, and the combined
# legacy file at <path> still carries every span
_TRACE_PATH = None
_TRACE_EVENTS: list = []
_TRACE_DROPPED = 0
_TRACE_RUNS: dict = {}  # config label -> {events, metadata, dropped}
# --faults <seed>: the serving config runs under a seeded 1%-frame
# transient fault rate at the detector (on_error: retry recovers every
# poisoned frame), publishing injected/retry/dead-letter counts in its
# config block -- throughput under fault load becomes a measured number.
# Without the flag every fault hook is one is-None check (the <2%
# regression budget of the acceptance gate).
_FAULTS_SEED = None

ELEMENTS = "aiko_services_tpu.elements"


def _local(class_name):
    return {"local": {"module": ELEMENTS, "class_name": class_name}}


def _peak_flops_per_chip():
    """bf16 peak FLOP/s of one chip, by `device_kind`.  Under the
    explicit CPU platform (AIKO_BENCH_PLATFORM=cpu: counts and
    correctness only) there is no peak and MFU prints null; on the
    device path a kind missing from the table is an error."""
    import jax
    override = os.environ.get("AIKO_BENCH_PEAK_TFLOPS")
    if override:
        return float(override) * 1e12
    device = jax.devices()[0]
    if device.platform == "cpu":
        return None
    kind = device.device_kind.lower()
    table = {  # bf16 peak per chip
        "v5 lite": 197e12, "v5e": 197e12, "v5p": 459e12, "v5": 197e12,
        "v6 lite": 918e12, "v6e": 918e12, "v4": 275e12, "v3": 123e12,
        "v2": 45e12,
    }
    for key, value in table.items():
        if key in kind:
            return value
    raise RuntimeError(
        f"no peak FLOP/s on record for device_kind "
        f"{device.device_kind!r}; add it to _peak_flops_per_chip or "
        f"set AIKO_BENCH_PEAK_TFLOPS")


def _mfu(flops_per_sec, peak):
    if not peak or not flops_per_sec:
        return None
    return round(flops_per_sec / peak, 4)


def _sync(value):
    """Device synchronization that does not rest on
    jax.block_until_ready (on the runtime this was written for it
    returned at dispatch, so timing loops ending in it measured
    dispatch, not compute).  A one-element dependent readback forces
    completion of the whole array with no bulk transfer.  Whether
    today's chip still needs this is chip_smoke.py's `link` finding
    (ROADMAP D10)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    for leaf in jax.tree_util.tree_leaves(value):
        if hasattr(leaf, "ndim"):
            np.asarray(jnp.ravel(leaf)[:1])
            break
    return value


_BARRIER_JIT = None
_BARRIER_CHUNK = 256


def _barrier(refs):
    """Force completion of EVERY collected device value.  A sync on only
    the LAST dispatched program is NOT a barrier on this runtime:
    independent programs are not serialized by a dependent read of the
    newest one (measured: 60 independent detector groups "complete" in
    9.6 ms/group by last-sync but are genuinely still running).  One
    jitted program folds the refs into a single dispatch (a per-ref
    eager slice is one dispatch EACH, which would swamp the quantity
    under measurement); the chunk results then materialize through one
    readback."""
    global _BARRIER_JIT
    import jax
    import jax.numpy as jnp
    import numpy as np
    leaves = []
    for value in refs:
        for leaf in jax.tree_util.tree_leaves(value):
            if hasattr(leaf, "ndim"):
                leaves.append(leaf)
                break
    if not leaves:
        return
    if _BARRIER_JIT is None:
        _BARRIER_JIT = jax.jit(lambda arrays: jnp.stack(
            [jnp.ravel(a)[0].astype(jnp.float32) for a in arrays]))
    outs = []
    for index in range(0, len(leaves), _BARRIER_CHUNK):
        chunk = leaves[index:index + _BARRIER_CHUNK]
        while len(chunk) < _BARRIER_CHUNK:  # stable arity: one compile
            chunk.append(chunk[-1])
        outs.append(_BARRIER_JIT(tuple(chunk)))
    np.asarray(outs[0] if len(outs) == 1 else jnp.concatenate(outs))


def _honest_elapsed(start, refs):
    """Wall seconds from `start` until every ref's program has been
    FORCED complete.  Includes the barrier's own dispatch cost, making
    the result a conservative LOWER bound on throughput -- preferred
    over subtracting a second-pass overhead estimate, whose jitter can
    exceed the residual backlog and turn the correction negative."""
    _barrier(refs)
    return max(time.perf_counter() - start, 1e-9)


def _harvest_trace(pipeline, config_label: str | None = None) -> None:
    """Collect one benched pipeline's frame traces before teardown:
    into the combined file's event list AND into the per-config run
    (self-describing metadata captured here, while the live pipeline
    can still report its definition + metrics snapshot)."""
    if not _TRACE_PATH:
        return
    global _TRACE_DROPPED
    label = config_label or pipeline.definition.name
    if label.startswith("bench_"):
        label = label[len("bench_"):]
    events = pipeline.telemetry.chrome_events()
    _TRACE_EVENTS.extend(events)
    _TRACE_DROPPED += pipeline.telemetry.tracer.dropped
    run = _TRACE_RUNS.setdefault(label, {"events": [], "dropped": 0})
    run["events"].extend(events)
    run["dropped"] += pipeline.telemetry.tracer.dropped
    metadata = pipeline.telemetry.trace_metadata(config_name=label)
    previous = run.get("metadata")
    if previous is not None:
        # several pipelines harvested under ONE config (router
        # replicas + the gateway, serving arms): the metrics snapshot
        # must cover them ALL, not just the last -- counters from a
        # single-replica snapshot would understate an N-replica trace
        # -- and the pid list must name every tracer so the tune
        # loader keeps all of this config's spans (and ONLY them).
        # The gateway's metadata carries no definition: keep the
        # replicas' (tune joins element spans against it)
        from aiko_services_tpu.observe import merge_snapshots
        metadata["metrics"] = merge_snapshots(
            previous.get("metrics") or {}, metadata.get("metrics")
            or {})
        metadata["pids"] = sorted(
            set(previous.get("pids") or [])
            | set(metadata.get("pids") or []))
        for key in ("definition", "fingerprint"):
            if key not in metadata and key in previous:
                metadata[key] = previous[key]
        if previous.get("role") != metadata.get("role"):
            # replicas + gateway under one config: no single role
            # describes the artifact (last-harvested must not win)
            metadata.pop("role", None)
    run["metadata"] = metadata


def _write_config_traces(configs: dict, result: dict) -> dict:
    """One artifact per harvested config, named by config, path
    published in the config block.  Returns the combined-file metadata
    (every run's metadata under a "runs" map)."""
    from aiko_services_tpu.observe import chrome_trace_document
    from aiko_services_tpu.observe.trace import TRACE_METADATA_SCHEMA
    base, ext = os.path.splitext(_TRACE_PATH)
    # harvest label (definition name minus "bench_") -> config key
    config_key_of = {"multimodal": "pipeline_multimodal",
                     "det": "detector"}
    trace_files = {}
    runs_metadata = {}
    for label in sorted(_TRACE_RUNS):
        run = _TRACE_RUNS[label]
        key = config_key_of.get(label, label)
        block = configs.get(key)
        metadata = dict(run.get("metadata") or {})
        if block is not None:
            # the config block is embedded BEFORE trace_file is added
            # to it (no self-reference); tune reads capacity/MFU/peak
            # evidence from it
            metadata["config"] = dict(block)
            metadata["config_name"] = key
        metadata["dropped_frames"] = run["dropped"]
        runs_metadata[label] = metadata
        path = f"{base}.{label}{ext or '.json'}"
        try:
            with open(path, "w") as handle:
                json.dump(chrome_trace_document(run["events"],
                                                metadata=metadata),
                          handle)
        except OSError as error:
            result["trace_error"] = str(error)
            continue
        trace_files[key] = path
        if block is not None:
            block["trace_file"] = path
            block["trace_events"] = len(run["events"])
    if trace_files:
        result["trace_files"] = trace_files
    return {"schema": TRACE_METADATA_SCHEMA, "runs": runs_metadata}


def _run_pipeline(definition, warmup: int, measure: int,
                  ready_key: str, timeout: float = 900,
                  latency_frames: int | None = None,
                  window: int | None = None):
    """Drive a pipeline with its own frame generator.

    Two phases: (1) throughput -- the generator keeps the pipeline full
    (frame_window in flight); (2) latency -- a second stream with
    frame_window=1, so exactly one frame is in the system and t0 ->
    completion is true per-frame service latency, not queueing depth.
    Returns (frames/sec, p50 arrival latency s, amortized drain s per
    latency frame, last outputs).
    """
    import numpy as np

    from aiko_services_tpu.pipeline import create_pipeline
    from aiko_services_tpu.runtime import Process

    if latency_frames is None:
        latency_frames = 5 if SMOKE else 30

    # pipeline-level parameters: telemetry on/off is the measured A/B
    # knob; the long metrics_interval keeps the export timer out of
    # short measurement windows
    definition.setdefault("parameters", {}).setdefault(
        "telemetry", TELEMETRY)
    definition["parameters"].setdefault("metrics_interval", 60.0)
    process = Process(transport_kind="loopback")
    pipeline = create_pipeline(process, definition)
    process.run(in_thread=True)
    responses = queue.Queue()
    if window is None:
        window = int(os.environ.get("AIKO_BENCH_WINDOW", "64"))
    pipeline.create_stream("bench", queue_response=responses,
                           grace_time=1800,
                           parameters={"frame_window": window})
    for _ in range(warmup):
        _, _, outputs = responses.get(timeout=timeout)
    if warmup:
        _sync(outputs[ready_key])  # drain once: program order covers all
    start = time.perf_counter()
    refs = []
    for _ in range(measure):
        _, _, outputs = responses.get(timeout=timeout)
        refs.append(outputs.get(ready_key))
    # barrier over EVERY measured frame's output (independent programs
    # are NOT forced by a sync on the last one -- see _barrier); the
    # barrier's own dispatch overhead is measured and subtracted
    elapsed = _honest_elapsed(start, refs)
    pipeline.destroy_stream("bench")

    latencies = []
    lat_refs = []
    lat_responses = queue.Queue()
    pipeline.create_stream(
        "latency", queue_response=lat_responses, grace_time=1800,
        parameters={"frame_window": 1, "count": latency_frames + 2})
    for index in range(latency_frames):
        _, _, lat_outputs = lat_responses.get(timeout=timeout)
        # response-arrival latency: dispatch + graph + host stages.  A
        # per-frame _sync here would interleave readbacks with the
        # event loop's dispatch stream, so the device-side residual is
        # measured ONCE as drain time below.
        if "t0" in lat_outputs:
            latencies.append(time.time() - lat_outputs["t0"])
        lat_refs.append(lat_outputs.get(ready_key))
    drain_start = time.perf_counter()
    drain = _honest_elapsed(drain_start, lat_refs)  # device backlog
    pipeline.destroy_stream("latency")
    # harvest this pipeline's frame traces before teardown; every
    # benched graph lands in its own per-config artifact AND the
    # combined Perfetto file (distinct process names per config)
    _harvest_trace(pipeline)
    process.terminate()
    # a stage that drops "t0" would silently degrade p50 into a
    # throughput-derived estimate -- fail loudly instead
    assert latencies, (
        "no t0 timestamps reached the response: latency was not measured")
    p50 = float(np.percentile(latencies[1:] or latencies, 50))
    # drain is reported SEPARATELY (not folded into p50): if the device
    # lagged dispatch, drain/latency_frames is each frame's amortized
    # share of the backlog -- readers see when backlog dominated
    return measure / elapsed, p50, drain / max(latency_frames, 1), outputs


def _latency_fields(p50, drain_pf, digits=2):
    """The reported latency triple: total (arrival + amortized drain),
    and its two components, so readers can see when device backlog
    dominated the measurement."""
    return {"p50_ms": round((p50 + drain_pf) * 1000, digits),
            "p50_arrival_ms": round(p50 * 1000, digits),
            "drain_per_frame_ms": round(drain_pf * 1000, digits)}


# -- config 1: text ----------------------------------------------------------

def _text_definition(measure):
    return {
        "name": "bench_text",
        "graph": ["(source (transform))"],
        "elements": [
            {"name": "source",
             "output": [{"name": "text", "type": "str"},
                        {"name": "t0", "type": "float"}],
             "parameters": {"data_sources": ["hello pipeline world"],
                            "count": measure + 60, "timestamps": True},
             "deploy": _local("TextSource")},
            {"name": "transform",
             "input": [{"name": "text", "type": "str"}],
             "output": [{"name": "text", "type": "str"}],
             "parameters": {"transform": "upper"},
             "deploy": _local("TextTransform")},
        ],
    }


def bench_text():
    measure = 200 if SMOKE else 2000
    definition = _text_definition(measure)
    fps, p50, drain_pf, _ = _run_pipeline(
        definition, warmup=50, measure=measure, ready_key="text")
    return {"frames_per_sec": round(fps, 1),
            "telemetry": TELEMETRY,
            **_latency_fields(p50, drain_pf, digits=3),
            "vs_reference_broker_ceiling": round(
                fps / REFERENCE_FRAMES_PER_SEC, 1)}


# -- config 2: ASR -----------------------------------------------------------

def _asr_definition(batch, seconds, max_tokens, preset, count):
    samples = int(seconds * 16000)  # elements/audio_io SAMPLE_RATE
    return {
        "name": "bench_asr",
        "graph": ["(tone (asr))"],
        "elements": [
            {"name": "tone",
             "output": [{"name": "audio",
                         "type": f"f32[b,{samples}]"},
                        {"name": "t0", "type": "float"}],
             "parameters": {"data_sources": [[440, seconds]],
                            "data_batch_size": batch, "timestamps": True,
                            "on_device": ON_DEVICE,
                            "count": count},
             "deploy": _local("ToneSource")},
            {"name": "asr",
             "input": [{"name": "audio", "type": f"f32[b,{samples}]"}],
             "output": [{"name": "tokens",
                         "type": f"i32[b,{max_tokens}]"}],
             "parameters": {"preset": preset, "max_tokens": max_tokens,
                            # 5 s serving chunks need a 512-frame window,
                            # not whisper's full 30 s (1500): encoder
                            # cost scales with the window
                            "max_frames": 192 if SMOKE else 512,
                            "dtype": ("float32" if SMOKE
                                      else "bfloat16")},
             "deploy": _local("SpeechToText")},
        ],
    }


def bench_asr(peak):
    from aiko_services_tpu.models import asr_flops_per_example
    from aiko_services_tpu.models.configs import (
        WHISPER_SMALL, WHISPER_TINY)
    config = WHISPER_TINY if SMOKE else WHISPER_SMALL
    preset = "whisper_tiny" if SMOKE else "whisper_small"
    # batch 16 amortizes the per-call floor 4x better than batch 4
    # (measured r5: MFU 0.026 -> 0.112, 491 -> 2015 audio-sec/s) at
    # p50 44 ms -- still far under the 5 s chunk cadence
    batch = 2 if SMOKE else int(os.environ.get("AIKO_BENCH_ASR_BATCH",
                                               "16"))
    seconds = 1.0 if SMOKE else 5.0
    max_tokens = 8 if SMOKE else 32
    warmup, measure = (2, 4) if SMOKE else (5, 40)
    definition = _asr_definition(batch, seconds, max_tokens, preset,
                                 warmup + measure + 4)
    fps, p50, drain_pf, _ = _run_pipeline(
        definition, warmup=warmup, measure=measure, ready_key="tokens")
    n_frames = int(seconds * 100) // 2  # mel 10 ms hop, conv /2
    flops = asr_flops_per_example(config, n_frames, max_tokens) * batch
    return {"frames_per_sec_chip": round(fps, 2),
            "telemetry": TELEMETRY,
            "audio_sec_per_sec": round(fps * batch * seconds, 1),
            **_latency_fields(p50, drain_pf),
            "model": preset,
            "batch": batch,
            "mfu": _mfu(fps * flops, peak)}


# -- config 3: detector ------------------------------------------------------

def _detector_definition(batch, size, preset, count):
    return {
        "name": "bench_det",
        "graph": ["(camera (detector))"],
        "elements": [
            {"name": "camera",
             "output": [{"name": "image",
                         "type": f"f32[b,3,{size},{size}]"},
                        {"name": "t0", "type": "float"}],
             "parameters": {"data_sources": [[batch, 3, size, size]],
                            "timestamps": True, "on_device": ON_DEVICE,
                            "count": count},
             "deploy": _local("ImageSource")},
            {"name": "detector",
             "input": [{"name": "image",
                        "type": f"f32[b,3,{size},{size}]"}],
             "output": [{"name": "detections", "type": "dict"}],
             "parameters": {"preset": preset,
                            "dtype": ("float32" if SMOKE
                                      else "bfloat16")},
             "deploy": _local("Detector")},
        ],
    }


def bench_detector(peak):
    from aiko_services_tpu.models import detector_flops_per_image
    from aiko_services_tpu.models.configs import (
        DETECTOR_TOY, YOLOV8N_SHAPE)
    config = DETECTOR_TOY if SMOKE else YOLOV8N_SHAPE
    preset = "toy" if SMOKE else "yolov8n"
    # the detect call's time was flat for ANY batch <= 32 at the last
    # on-chip capture (a per-call floor), so bigger batches win; batch
    # 32 however OOMs: the in-flight working set is images
    # (frame_window 32 x 157 MB = 5 GB) PLUS every queued call's
    # activation footprint (~30 MB/image), together past 16 GiB.
    # 16 is the deployable sweet spot (1,099 images/s measured)
    batch = 2 if SMOKE else int(os.environ.get("AIKO_BENCH_DET_BATCH",
                                               "16"))
    warmup, measure = (2, 6) if SMOKE else (10, 100)
    size = config.image_size
    definition = _detector_definition(batch, size, preset,
                                      warmup + measure + 4)
    fps, p50, drain_pf, _ = _run_pipeline(
        definition, warmup=warmup, measure=measure, ready_key="detections")
    flops = detector_flops_per_image(config) * batch
    return {"frames_per_sec_chip": round(fps, 2),
            "telemetry": TELEMETRY,
            "images_per_sec": round(fps * batch, 1),
            **_latency_fields(p50, drain_pf),
            "model": f"{preset} {size}x{size}",
            "batch": batch,
            "mfu": _mfu(fps * flops, peak)}


# -- config 4: LLM decode ----------------------------------------------------

def bench_llm(peak):
    import jax
    import jax.numpy as jnp

    from aiko_services_tpu.models import (
        count_params, generate_stream, init_params,
        transformer_flops_per_token)
    from aiko_services_tpu.models.configs import LLAMA32_1B, LM_TOY

    config = LM_TOY if SMOKE else LLAMA32_1B
    name = "lm_toy" if SMOKE else "llama32_1b"
    prompt_len = 32 if SMOKE else 128
    max_new = 16 if SMOKE else 128
    batch = 1 if SMOKE else 4
    params = init_params(config, jax.random.PRNGKey(0))
    n_params = count_params(params)
    prompt = jnp.ones((batch, prompt_len), jnp.int32)

    # warmup compiles prefill + decode chunks at the MEASURED cache shape
    # (cache max_len is a compile-time shape: warming with a different
    # max_new would leave the real compile inside the TTFT measurement)
    chunk = 8 if SMOKE else 32
    for _ in generate_stream(params, config, prompt, max_new, chunk=chunk):
        pass

    start = time.perf_counter()
    ttft = None
    produced = 0
    for offset, block in generate_stream(params, config, prompt, max_new,
                                         chunk=chunk):
        if ttft is None:
            ttft = time.perf_counter() - start
        produced += block.shape[1]
    elapsed = time.perf_counter() - start
    tokens_per_sec = produced * batch / elapsed
    decode_flops = transformer_flops_per_token(config, prompt_len)

    def measure_decode(row_params, row_config, scale_batch):
        """tokens/sec for one decode row: warmup pass (compiles this
        batch's shapes), then one timed full generation."""
        scale_prompt = jnp.ones((scale_batch, prompt_len), jnp.int32)
        for _ in generate_stream(row_params, row_config, scale_prompt,
                                 max_new, chunk=chunk):
            pass  # compile at this batch
        scale_start = time.perf_counter()
        scale_produced = 0
        for _, block in generate_stream(row_params, row_config,
                                        scale_prompt, max_new,
                                        chunk=chunk):
            scale_produced += block.shape[1]
        return round(scale_produced * scale_batch
                     / (time.perf_counter() - scale_start), 1)

    # batch-scaling rows: decode throughput vs batch (serving headroom --
    # decode is HBM-bound, so tokens/sec should scale with batch until
    # the KV cache saturates bandwidth)
    scaling = {}
    for scale_batch in ((2,) if SMOKE else (16, 64)):
        scaling[f"batch_{scale_batch}"] = measure_decode(
            params, config, scale_batch)

    # int8 KV cache (kv_dtype="int8"): halved cache HBM and cache-read
    # bandwidth, doubling the feasible decode batch at fixed memory;
    # numerics pinned in tests/test_transformer.py::TestKVCacheInt8
    from dataclasses import replace
    config_q = replace(config, kv_dtype="int8")
    for scale_batch in ((2,) if SMOKE else (128,)):
        scaling[f"batch_{scale_batch}_kv_int8"] = measure_decode(
            params, config_q, scale_batch)

    # weight-only int8 (quantize_weights_int8): halves the weight bytes
    # streamed per step (the dominant term at TTFT-class batch; the
    # residual per-step floor is loop/cache/attention work, so the
    # measured win is ~1.26x, not 2x); combined with the
    # int8 KV cache at the big batch.  Numerics pinned in
    # TestWeightOnlyInt8
    from aiko_services_tpu.models import quantize_weights_int8
    params_q = quantize_weights_int8(params, config)
    if SMOKE:
        scaling["batch_2_w8"] = measure_decode(params_q, config, 2)
    else:
        scaling[f"batch_{batch}_w8"] = measure_decode(
            params_q, config, batch)
        scaling["batch_128_w8_kv8"] = measure_decode(
            params_q, config_q, 128)
    return {"model": f"{name} ({n_params / 1e6:.0f}M params)",
            "batch": batch,
            "prompt_len": prompt_len,
            "time_to_first_token_ms": round(ttft * 1000, 1),
            "tokens_per_sec": round(tokens_per_sec, 1),
            "tokens_per_sec_by_batch": scaling,
            "decode_mfu": _mfu(tokens_per_sec * decode_flops, peak)}


# -- config 4d: long-context prefill (SURVEY: long context first-class) -----

def bench_longcontext(peak):
    """Flash-attention prefill at long sequence on the flagship
    architecture: one full causal forward (the serving prefill / scoring
    path).  The reference handles long audio by CHUNKING (5 s windows,
    speech_elements.py:54-83) and has no long-context capability at all;
    this measures the real thing on the chip -- at 16k the quadratic
    attention term is ~1/3 of total FLOPs, so sustained MFU here proves
    the Pallas flash kernel, not just the matmuls."""
    import jax
    import jax.numpy as jnp

    from aiko_services_tpu.models import (
        count_params, forward, init_params, transformer_flops_per_token)
    from aiko_services_tpu.models.configs import LLAMA32_1B, LM_TOY
    from dataclasses import replace

    if SMOKE:
        config, name, lengths, batch = LM_TOY, "lm_toy", (128,), 1
    else:
        # half-depth llama32_1b architecture (activation headroom at 16k)
        config = replace(LLAMA32_1B, n_layers=8, max_seq_len=16384)
        name = "llama32_1b architecture, 8 layers"
        lengths, batch = (4096, 16384), 1
    params = init_params(config, jax.random.PRNGKey(0))
    n_params = count_params(params)
    # jit with a stable identity: raw forward() outside jit re-traces
    # per call (lax.scan compiles each invocation).  Return ONLY the
    # last position's logits: the full (L, 128256) tensor is 8.4 GB at
    # 16k and XLA dead-code-eliminates the unused head positions, so the
    # measurement covers the transformer body + one head row (the
    # serving prefill shape: next-token after the prompt)
    prefill = jax.jit(lambda p, t: forward(p, config, t)[:, -1])
    rows = {}
    for length in lengths:
        tokens = jnp.ones((batch, length), jnp.int32)
        logits = prefill(params, tokens)  # compile
        _sync(logits)
        steps = 2 if SMOKE else 4
        start = time.perf_counter()
        for _ in range(steps):
            logits = prefill(params, tokens)
        _sync(logits)  # program order: all steps complete
        elapsed = time.perf_counter() - start
        tokens_per_sec = steps * batch * length / elapsed
        # causal prefill: average attended context is length/2 (full
        # length would overstate MFU); subtract the per-token head term
        # (2*d*V) since only ONE position's logits are computed
        per_token = (transformer_flops_per_token(config, length // 2)
                     - 2 * config.d_model * config.vocab_size)
        flops = per_token * tokens_per_sec
        rows[f"seq_{length}"] = {
            "tokens_per_sec": round(tokens_per_sec, 1),
            "prefill_ms": round(elapsed / steps * 1000, 1),
            "mfu": _mfu(flops, peak)}
    return {"model": f"{name} ({n_params / 1e6:.0f}M params)",
            "batch": batch, "prefill": rows}


# -- config 4c: training step (beyond the reference: it never trains) -------

def bench_train(peak):
    """make_train_step throughput on the flagship architecture: full
    fwd+bwd+adamw per step.  Training is where the MXU saturates (big
    batched matmuls, no decode memory-wall), so this row carries the
    framework's compute ceiling."""
    import jax
    import jax.numpy as jnp
    import optax

    from aiko_services_tpu.models import (
        count_params, init_params, make_train_step)
    from aiko_services_tpu.models.configs import LLAMA32_1B, LM_TOY
    from dataclasses import replace

    if SMOKE:
        config, name = LM_TOY, "lm_toy"
        batch, seq, steps = 2, 64, 2
    else:
        # 1B-class training on ONE v5e chip: f32 adam moments + grads
        # need headroom, so train a half-depth variant of the llama32_1b
        # architecture (8 layers) at seq 1024
        config = replace(LLAMA32_1B, n_layers=8)
        name = "llama32_1b architecture, 8 layers"
        batch, seq, steps = 4, 1024, 8
    params = init_params(config, jax.random.PRNGKey(0))
    n_params = count_params(params)
    optimizer = optax.adamw(1e-4)
    opt_state = optimizer.init(params)
    # remat sweep knob (ROADMAP #3b): AIKO_BENCH_REMAT names a
    # models.REMAT_POLICIES entry; losses are bit-identical across
    # policies (tested), so sweeping it walks the step-time/HBM
    # frontier toward the >= 0.45 train-MFU target
    remat = os.environ.get("AIKO_BENCH_REMAT", "none")
    train_step = make_train_step(config, optimizer, remat_policy=remat)
    tokens = jnp.ones((batch, seq + 1), jnp.int32)
    params, opt_state, loss = train_step(params, opt_state, tokens)  # compile
    _sync(loss)
    start = time.perf_counter()
    for _ in range(steps):
        params, opt_state, loss = train_step(params, opt_state, tokens)
    _sync(loss)  # forces the whole dependent step chain to complete
    elapsed = time.perf_counter() - start
    tokens_per_sec = steps * batch * seq / elapsed
    # fwd+bwd ~ 6 * params FLOPs per token (+ attention terms omitted:
    # conservative MFU)
    flops_per_sec = tokens_per_sec * 6 * n_params
    return {"model": f"{name} ({n_params / 1e6:.0f}M params)",
            "batch": batch, "seq_len": seq,
            "remat": remat,
            "tokens_per_sec": round(tokens_per_sec, 1),
            "step_ms": round(elapsed / steps * 1000, 1),
            "train_mfu": _mfu(flops_per_sec, peak),
            "loss_finite": bool(jnp.isfinite(loss))}


# -- config 4b: mesh-sharded decode (BASELINE config 4's sharded shape) -----

_SHARDED_SCRIPT = r"""
import json, os, re, time
from dataclasses import replace
from functools import partial

import jax
import jax.numpy as jnp

from aiko_services_tpu.models import (
    cache_specs, decode_step, generate, init_cache, init_params,
    param_specs)
from aiko_services_tpu.models.configs import LLAMA32_1B
from aiko_services_tpu.parallel import filter_specs, shard_pytree
from aiko_services_tpu.parallel.mesh import create_mesh

# llama32_1b ARCHITECTURE (16 scan layers, 32/8 GQA heads, tied
# embeddings, rope 500k) at reduced width: the virtual CPU mesh measures
# SHARDING overhead/collective structure, not chip FLOPs
config = replace(LLAMA32_1B, vocab_size=32768, d_model=512, d_ff=2048,
                 dtype="bfloat16")
if os.environ.get("AIKO_BENCH_SMOKE", "") not in ("", "0"):
    config = replace(config, vocab_size=4096, d_model=128, d_ff=512,
                     n_layers=4)
mesh = create_mesh({"data": 2, "fsdp": 1, "seq": 1, "model": 4})
params = shard_pytree(init_params(config, jax.random.PRNGKey(0)), mesh,
                      filter_specs(param_specs(config), mesh))
batch, prompt_len, max_new = 4, 32, 16

def fresh_cache():
    return shard_pytree(
        init_cache(config, batch, max_len=prompt_len + max_new), mesh,
        filter_specs(cache_specs(), mesh))

prompt = jnp.ones((batch, prompt_len), jnp.int32)
with jax.set_mesh(mesh):
    tokens, _ = generate(params, config, prompt, max_new,
                         cache=fresh_cache())  # compile
    jax.block_until_ready(tokens)
    start = time.perf_counter()
    tokens, _ = generate(params, config, prompt, max_new,
                         cache=fresh_cache())
    jax.block_until_ready(tokens)
    elapsed = time.perf_counter() - start
    step = jax.jit(partial(decode_step, config=config))
    hlo = step.lower(params, cache=fresh_cache(),
                     token=jnp.ones((batch, 1), jnp.int32),
                     pos=jnp.int32(5)).compile().as_text()
collectives = re.findall(
    r"= \S+ (all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)\(", hlo)
print(json.dumps({
    "tokens_per_sec": round(max_new * batch / elapsed, 1),
    "collectives_per_decode_step": len(collectives),
    "collective_kinds": sorted(set(collectives)),
    "n_layers": config.n_layers,
}))
"""


def bench_llm_sharded():
    """Decode with params sharded by param_specs over a mesh.  Runs in
    a subprocess pinned to the virtual 8-device CPU mesh (data 2 x
    model 4) -- the child never opens the chip this process holds --
    so the numbers characterize the sharded program (collective count
    per decode step), not chip throughput; the driver's
    dryrun_multichip covers compile+execute of the full training step
    the same way."""
    import subprocess
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8"
                        ).strip()
    try:
        probe = subprocess.run(
            [sys.executable, "-c", _SHARDED_SCRIPT], env=env,
            capture_output=True, text=True, timeout=600)
    except subprocess.TimeoutExpired:
        return {"error": "sharded decode subprocess timed out (600s)"}
    if probe.returncode != 0:
        tail = (probe.stderr or "").strip().splitlines()[-1:]
        return {"error": f"exit {probe.returncode}"
                + (f": {tail[0]}" if tail else "")}
    result = json.loads(probe.stdout.strip().splitlines()[-1])
    result["mesh"] = "virtual 8-device CPU (data=2, model=4)"
    result["model"] = (
        f"llama32_1b architecture at reduced width "
        f"({result.pop('n_layers')} layers, 32/8 GQA heads, "
        f"tied embeddings)")
    return result


# -- config 5: 3-stage multi-modal pipeline ---------------------------------

def _multimodal_setup(name, batch, micro, max_tokens, max_new,
                      audio_seconds, frame_count):
    """Definition + model configs for the config-5 graph at one
    operating point (rows per frame, frames coalesced per jit call) --
    shared by the throughput (micro 8 / window 64) and latency
    (micro 1 / window 1) configs so the two frontier points measure
    the SAME graph."""
    from aiko_services_tpu.models import configs as model_configs
    from aiko_services_tpu.models.asr import AsrConfig
    from aiko_services_tpu.models.detector import DetectorConfig
    from aiko_services_tpu.models.transformer import TransformerConfig

    if SMOKE:
        image_size = 64
        lm = dict(vocab_size=1024, d_model=256, n_layers=2, n_heads=8,
                  n_kv_heads=4, d_ff=768, max_seq_len=2048,
                  dtype="float32", max_new_tokens=max_new)
        asr = dict(d_model=64, enc_layers=1, dec_layers=1, n_heads=2,
                   vocab_size=1024, max_tokens=max_tokens, max_frames=192,
                   dtype="float32")
        det = dict(n_classes=16, base_channels=8, image_size=image_size,
                   dtype="float32")
        asr_config = AsrConfig(**{k: v for k, v in asr.items()
                                  if k != "max_tokens"})
        lm_config = TransformerConfig(**{k: v for k, v in lm.items()
                                         if k != "max_new_tokens"})
        det_config = DetectorConfig(**det)
    else:
        # the flagship presets, by name (BASELINE.md config 5)
        asr = {"preset": "whisper_small", "max_frames": 512,
               "max_tokens": max_tokens, "dtype": "bfloat16",
               "micro_batch": micro}
        lm = {"preset": "llama32_1b", "dtype": "bfloat16",
              "micro_batch": micro, "max_new_tokens": max_new}
        det = {"preset": "yolov8n", "dtype": "bfloat16",
               "micro_batch": micro}
        from dataclasses import replace
        asr_config = replace(model_configs.WHISPER_SMALL, max_frames=512)
        lm_config = model_configs.LLAMA32_1B
        det_config = model_configs.YOLOV8N_SHAPE
        image_size = det_config.image_size
    # typed tensor ports (analyze/ tensor-spec grammar): the symbolic
    # batch `b` ties every stage to the same coalesced leading axis, and
    # `aiko lint` dry-runs asr/lm/detector under jax.eval_shape against
    # these specs -- the config-5 graph is the shipped proof the
    # shape-flow pass verifies a real multi-stage serving graph
    samples = int(audio_seconds * 16000)
    audio_t = f"f32[b,{samples}]"
    image_t = f"f32[b,3,{image_size},{image_size}]"
    tokens_t = f"i32[b,{max_tokens}]"
    generated_t = f"i32[b,{max_new}]"
    definition = {
        "name": name,
        "graph": ["(sources (asr (text) (lm (reply))) (detector))"],
        "elements": [
            {"name": "sources",
             "output": [{"name": "audio", "type": audio_t},
                        {"name": "image", "type": image_t},
                        {"name": "t0", "type": "float"}],
             "parameters": {"data_sources": [[440, audio_seconds]],
                            "image_shape": [3, image_size, image_size],
                            "data_batch_size": batch,
                            "timestamps": True, "on_device": ON_DEVICE,
                            "count": frame_count},
             "deploy": _local("MultiModalSource")},
            {"name": "asr",
             "input": [{"name": "audio", "type": audio_t}],
             "output": [{"name": "tokens", "type": tokens_t}],
             "parameters": asr, "deploy": _local("SpeechToText")},
            {"name": "text",
             "input": [{"name": "tokens", "type": tokens_t}],
             "output": [{"name": "text", "type": "str"}],
             "parameters": {"workers": 32},
             "deploy": _local("TokensToText")},
            {"name": "lm",
             "input": [{"name": "tokens", "type": tokens_t}],
             "output": [{"name": "generated", "type": generated_t}],
             "parameters": lm, "deploy": _local("LMGenerate")},
            {"name": "reply",
             "input": [{"name": "tokens", "type": generated_t}],
             "output": [{"name": "text", "type": "str"}],
             "map_in": {"tokens": "generated"},
             "map_out": {"text": "reply"},
             "parameters": {"workers": 32},
             "deploy": _local("TokensToText")},
            {"name": "detector",
             "input": [{"name": "image", "type": image_t}],
             "output": [{"name": "detections", "type": "dict"}],
             "parameters": det, "deploy": _local("Detector")},
        ],
    }
    return definition, asr_config, lm_config, det_config


def _multimodal_flops(asr_config, lm_config, det_config, batch,
                      max_tokens, max_new, audio_seconds):
    """Per-frame compute across the three model stages (batch rows
    each)."""
    from aiko_services_tpu.models import (
        asr_flops_per_example, detector_flops_per_image,
        transformer_flops_per_token)
    n_frames = int(audio_seconds * 100) // 2
    # LM: prefill over the prompt + max_new decode steps (per-token
    # flops at the FINAL context slightly overstates the quadratic
    # attention term; negligible at ctx <= 48 on a 1B)
    lm_tokens = max_tokens + max_new
    return batch * (
        asr_flops_per_example(asr_config, n_frames, max_tokens)
        + transformer_flops_per_token(lm_config, lm_tokens) * lm_tokens
        + detector_flops_per_image(det_config))


_MULTIMODAL_STAGES = ("whisper_small -> (text, llama32_1b decode -> "
                      "reply text) + yolov8n-640 -> detections")
_MULTIMODAL_STAGES_SMOKE = ("speech->(text,lm decode) + "
                            "vision->detections (smoke)")


def bench_multimodal(peak):
    """BASELINE config 5 at the NAMED reference-scale stages: the
    whisper_small ASR preset, the llama32_1b LM, and the yolov8n 640 px
    detector -- the same model configs benched individually as configs
    2/3/4 (SMOKE shrinks everything for CPU runs).  Each frame carries
    `batch` audio windows + images; micro_batch coalesces queued frames
    into one jit call per stage.  This is the THROUGHPUT operating
    point; the `latency` config runs the same graph at rows 2 / micro 1
    / window 1 (the two ends of the frontier)."""
    warmup, measure = (2, 8) if SMOKE else (10, 120)
    # 5 s chunks = the reference speech cadence (audio_io.py:455-460)
    audio_seconds = 1.0 if SMOKE else 5.0
    # rows per frame (data_batch_size) x frames coalesced per jit call;
    # env-tunable for scaling experiments.  Measured on v5e round 5
    # (after the jitted coalesce program landed): rows 16 / micro 8 /
    # window 64 -> 18.95 fps, MFU 0.263; micro 4 -> 10.7 fps / 0.149;
    # rows 24 collapsed to 3.2 fps (compile-bound) and micro 16
    # (batch-256 stages) stalled the 900 s response timeout compiling
    batch = 1 if SMOKE else int(os.environ.get("AIKO_BENCH_ROWS", "16"))
    micro = 1 if SMOKE else int(os.environ.get("AIKO_BENCH_MICRO", "8"))
    max_tokens = 16
    # the LM stage DECODES (greedy, one jit: prefill + fori_loop), the
    # reference's chat semantics (elements_llm.py:181-210) -- not a
    # scoring pass
    max_new = 8 if SMOKE else int(os.environ.get("AIKO_BENCH_NEW", "32"))
    definition, asr_config, lm_config, det_config = _multimodal_setup(
        "bench_multimodal", batch, micro, max_tokens, max_new,
        audio_seconds, warmup + measure + 4)
    fps, p50, drain_pf, _ = _run_pipeline(
        definition, warmup=warmup, measure=measure, ready_key="detections")
    flops = _multimodal_flops(asr_config, lm_config, det_config, batch,
                              max_tokens, max_new, audio_seconds)
    return {"frames_per_sec_chip": round(fps, 2),
            "telemetry": TELEMETRY,
            **_latency_fields(p50, drain_pf),
            "audio_seconds_per_frame": audio_seconds,
            "rows_per_frame": batch,
            "audio_realtime_factor": round(
                fps * batch * audio_seconds, 2),
            "tokens_generated_per_frame": batch * max_new,
            "stages": (_MULTIMODAL_STAGES if not SMOKE
                       else _MULTIMODAL_STAGES_SMOKE),
            "micro_batch": micro,
            "mfu": _mfu(fps * flops, peak)}, fps, (p50 + drain_pf), (
                audio_seconds), batch


# -- config 5L: the latency operating point of the same graph ----------------

def bench_latency(peak):
    """The LATENCY end of the config-5 frontier (the driver metric is
    throughput AND p50 frame latency, but only the throughput-mode
    operating point -- 533 ms at micro 8 / window 64 -- was on
    record).  Same graph, rows 2 / micro_batch 1 /
    frame_window 1: at most ONE frame in flight end-to-end, so p50 is
    true per-frame service latency (dispatch + graph + host stages),
    not queueing depth.  Together with config 5 this records the
    throughput<->latency frontier the serving scheduler can be operated
    on."""
    warmup, measure = (2, 6) if SMOKE else (5, 40)
    audio_seconds = 1.0 if SMOKE else 5.0
    batch = 1 if SMOKE else 2
    max_tokens = 16
    max_new = 8 if SMOKE else 32
    definition, asr_config, lm_config, det_config = _multimodal_setup(
        "bench_latency", batch, 1, max_tokens, max_new, audio_seconds,
        warmup + measure + 4)
    fps, p50, drain_pf, _ = _run_pipeline(
        definition, warmup=warmup, measure=measure,
        ready_key="detections", window=1)
    flops = _multimodal_flops(asr_config, lm_config, det_config, batch,
                              max_tokens, max_new, audio_seconds)
    result = {"frames_per_sec_chip": round(fps, 2),
              "telemetry": TELEMETRY,
              **_latency_fields(p50, drain_pf),
              "audio_seconds_per_frame": audio_seconds,
              "rows_per_frame": batch,
              "micro_batch": 1,
              "frame_window": 1,
              "operating_point": "latency (one frame in flight)",
              "stages": (_MULTIMODAL_STAGES if not SMOKE
                         else _MULTIMODAL_STAGES_SMOKE),
              "mfu": _mfu(fps * flops, peak)}
    if TELEMETRY:
        # tracing-overhead A/B on the latency operating point: the
        # SAME graph with `telemetry: false` (the AIKO_BENCH_TELEMETRY
        # knob's per-config form) -- the published delta is the cost
        # of metrics + frame tracing per frame, where one frame is in
        # flight and nothing amortizes it
        off_definition, _, _, _ = _multimodal_setup(
            "bench_latency_off", batch, 1, max_tokens, max_new,
            audio_seconds, warmup + measure + 4)
        off_definition.setdefault("parameters", {})["telemetry"] = False
        off_fps, off_p50, off_drain, _ = _run_pipeline(
            off_definition, warmup=warmup, measure=measure,
            ready_key="detections", window=1)
        off_fields = _latency_fields(off_p50, off_drain)
        result["telemetry_off"] = {
            "frames_per_sec_chip": round(off_fps, 2),
            **off_fields,
        }
        result["tracing_overhead_p50_ms"] = round(
            result["p50_ms"] - off_fields["p50_ms"], 2)
    return result


# -- config 6: many-stream serving (multitude) -------------------------------

def _serving_definition(name, size, pipeline_parameters,
                        detector_parameters):
    """The one-node serving graph shared by the multitude (config 6)
    and gateway (`--router`) workloads."""
    return {
        "name": name,
        "parameters": pipeline_parameters,
        "graph": ["(detector)"],
        "elements": [
            {"name": "detector",
             "input": [{"name": "image",
                        "type": f"f32[b,3,{size},{size}]"}],
             "output": [{"name": "detections", "type": "dict"}],
             "parameters": detector_parameters,
             "deploy": _local("Detector")},
        ],
    }


def bench_serving(peak):
    """Multitude-style load: MANY concurrent streams, one small frame
    each, all hitting ONE shared detector element -- the reference's
    actual scale test (multitude/run_small.sh: dozens of processes over
    a broker, ~50 frames/sec ceiling).  Frames are INJECTED per stream
    (requests arriving from outside, no generator threads), so the
    measurement is engine + device, and cross-stream continuous
    batching coalesces them into shared jit calls; the same run with
    micro_batch=1 gives the uncoalesced comparison."""
    import jax
    import jax.numpy as jnp

    from aiko_services_tpu.models import detector_flops_per_image
    from aiko_services_tpu.models.configs import DETECTOR_TOY, YOLOV8N_SHAPE
    from aiko_services_tpu.pipeline import create_pipeline
    from aiko_services_tpu.runtime import Process

    streams_n = 4 if SMOKE else 32
    # 60 frames/stream: a ~1-2 s window per arm -- the 30-frame window
    # was short enough for run-to-run jitter to dominate the uncoalesced
    # arm
    # (observed medians 585 vs 1667 frames/s across two round-5 runs)
    per_stream = 4 if SMOKE else 60
    config = DETECTOR_TOY if SMOKE else YOLOV8N_SHAPE
    preset = "toy" if SMOKE else "yolov8n"
    size = config.image_size
    images = [
        jax.random.uniform(jax.random.PRNGKey(index), (1, 3, size, size),
                           jnp.float32)
        for index in range(4)]

    fault_totals = {"injected": 0, "retries": 0, "dead_letters": 0,
                    "frames_errored": 0}

    def run(micro):
        pipeline_parameters = {"telemetry": TELEMETRY,
                               "metrics_interval": 60.0}
        detector_parameters = {"preset": preset,
                               "micro_batch": micro,
                               "dtype": ("float32" if SMOKE
                                         else "bfloat16")}
        if _FAULTS_SEED is not None:
            # transient 1%-frame faults (each poisoned frame fails
            # exactly once); the retry policy must recover every one or
            # the response drain below hangs -- completion IS the gate.
            # Telemetry is FORCED on: the retry/dead-letter counters in
            # the published faults block come from it, and zeros under
            # AIKO_BENCH_TELEMETRY=0 would read as silently lost frames
            pipeline_parameters["telemetry"] = True
            pipeline_parameters["faults"] = (
                f"seed={_FAULTS_SEED};"
                f"element_raise:node=detector:rate=0.01:once=1:times=-1")
            detector_parameters.update(
                {"on_error": "retry", "max_retries": 3,
                 "retry_backoff_ms": 1})
        definition = _serving_definition(
            "bench_serving", size, pipeline_parameters,
            detector_parameters)
        process = Process(transport_kind="loopback")
        pipeline = create_pipeline(process, definition)
        responses = queue.Queue()
        # warm stream: compiles the coalesced (and singleton) shapes
        warm_stream = pipeline.create_stream(
            "warm", queue_response=responses, grace_time=1800)
        for index in range(max(micro, 2)):
            pipeline.create_frame(warm_stream, {"image": images[index % 4]})
        process.run(in_thread=True)
        warm_refs = [responses.get(timeout=900)[2].get("detections")
                     for _ in range(max(micro, 2))]
        _barrier(warm_refs)
        streams = [
            pipeline.create_stream(f"s{index}", queue_response=responses,
                                   grace_time=1800)
            for index in range(streams_n)]
        total = streams_n * per_stream
        start = time.perf_counter()
        # requests land interleaved across streams, as a broker delivers
        for round_index in range(per_stream):
            for stream in streams:
                pipeline.create_frame(
                    stream, {"image": images[round_index % 4]})
        refs = []
        for _ in range(total):
            _, _, outputs = responses.get(timeout=900)
            refs.append(outputs.get("detections"))
        elapsed = _honest_elapsed(start, refs)
        _harvest_trace(pipeline)
        if _FAULTS_SEED is not None:
            stats = (pipeline.faults.stats()
                     if pipeline.faults is not None else {})
            registry = pipeline.telemetry.registry
            fault_totals["injected"] += stats.get("element_raise", 0)
            fault_totals["retries"] += registry.counter(
                "pipeline.retries").value
            fault_totals["dead_letters"] += registry.counter(
                "pipeline.dead_letters").value
            fault_totals["frames_errored"] += registry.counter(
                "pipeline.frames_errored").value
        process.terminate()
        return total / elapsed

    import numpy as np

    micro = 4 if SMOKE else 16
    # the round-4 A/B was ONE trial per arm, coalesced first -- and the
    # driver's run recorded the opposite conclusion from the builder's
    # (speedup 1.95 claimed, 0.37 recorded).  Interleaved repeated
    # trials with ALTERNATING order make order effects and run-to-run
    # variance visible as spread instead of silently deciding the
    # verdict; medians decide the speedup.  >= 5 trials per arm with
    # per-trial values PUBLISHED: the round-5 coalesced spread was
    # [1030, 1896] and min/max alone could not show whether that was
    # one outlier or a bimodal distribution
    trials = 1 if SMOKE else 5
    fps_coalesced, fps_single = [], []
    for trial in range(trials):
        arms = [(micro, fps_coalesced), (1, fps_single)]
        if trial % 2:
            arms.reverse()
        for arm_micro, sink in arms:
            sink.append(run(arm_micro))
    med_coalesced = float(np.median(fps_coalesced))
    med_single = float(np.median(fps_single))
    flops = detector_flops_per_image(config)
    faults_block = (
        {"faults": {"seed": _FAULTS_SEED,
                    "spec": "element_raise detector rate=0.01 once",
                    "telemetry_forced": not TELEMETRY,
                    **fault_totals}}
        if _FAULTS_SEED is not None else {})
    return {
        "streams": streams_n,
        "telemetry": TELEMETRY,
        **faults_block,
        "frames_per_sec_total": round(med_coalesced, 1),
        "coalesced_trials": [round(value, 1) for value in fps_coalesced],
        "coalesced_spread": [round(min(fps_coalesced), 1),
                             round(max(fps_coalesced), 1)],
        "frames_per_sec_uncoalesced": round(med_single, 1),
        "uncoalesced_trials": [round(value, 1) for value in fps_single],
        "uncoalesced_spread": [round(min(fps_single), 1),
                               round(max(fps_single), 1)],
        "coalescing_speedup": round(
            med_coalesced / max(med_single, 1e-9), 2),
        "trials_per_arm": trials,
        "micro_batch": micro,
        "model": f"{preset} {size}x{size}",
        "vs_reference_broker_ceiling": round(
            med_coalesced / REFERENCE_FRAMES_PER_SEC, 1),
        "mfu": _mfu(med_coalesced * flops, peak),
    }


# -- router: the serving config behind the gateway ---------------------------

def bench_router(peak, replicas_n: int):
    """`--router N`: the serving workload fronted by the Gateway with N
    in-process replicas under OPEN-LOOP overload -- frames offered at
    2x the measured aggregate capacity regardless of completions, the
    regime where an unprotected pipeline grows its queue without bound.
    Published numbers: goodput (admitted completions/sec), shed rate,
    and p50/p99 admitted latency (submit -> completion through the
    gateway, each response device-synced before timestamping, so the
    latency is conservative)."""
    import threading

    import jax
    import jax.numpy as jnp
    import numpy as np

    from aiko_services_tpu.models import detector_flops_per_image
    from aiko_services_tpu.models.configs import DETECTOR_TOY, YOLOV8N_SHAPE
    from aiko_services_tpu.pipeline import create_pipeline
    from aiko_services_tpu.runtime import Process
    from aiko_services_tpu.serve import Gateway

    config = DETECTOR_TOY if SMOKE else YOLOV8N_SHAPE
    preset = "toy" if SMOKE else "yolov8n"
    size = config.image_size
    micro = 4 if SMOKE else 16
    streams_n = 4 if SMOKE else 16
    per_stream = 4 if SMOKE else 30
    images = [
        jax.random.uniform(jax.random.PRNGKey(index), (1, 3, size, size),
                           jnp.float32)
        for index in range(4)]

    def definition(name):
        return _serving_definition(
            name, size,
            {"telemetry": TELEMETRY, "metrics_interval": 60.0},
            {"preset": preset, "micro_batch": micro,
             "dtype": "float32" if SMOKE else "bfloat16"})

    # phase 1: ONE replica driven closed-loop to saturation -- the
    # capacity the overload is calibrated against
    process = Process(transport_kind="loopback")
    pipeline = create_pipeline(process, definition("capacity_probe"))
    responses = queue.Queue()
    warm = pipeline.create_stream("warm", queue_response=responses,
                                  grace_time=1800)
    for index in range(max(micro, 2)):
        pipeline.create_frame(warm, {"image": images[index % 4]})
    process.run(in_thread=True)
    _barrier([responses.get(timeout=900)[2].get("detections")
              for _ in range(max(micro, 2))])
    streams = [pipeline.create_stream(f"s{index}",
                                      queue_response=responses,
                                      grace_time=1800)
               for index in range(streams_n)]
    total = streams_n * per_stream
    start = time.perf_counter()
    for round_index in range(per_stream):
        for stream in streams:
            pipeline.create_frame(stream,
                                  {"image": images[round_index % 4]})
    refs = [responses.get(timeout=900)[2].get("detections")
            for _ in range(total)]
    capacity = total / _honest_elapsed(start, refs)
    process.terminate()

    # phase 2: N replicas behind the gateway, offered 2x aggregate
    # capacity open-loop
    processes, replicas = [], []
    for index in range(replicas_n):
        replica_process = Process(transport_kind="loopback")
        processes.append(replica_process)
        replicas.append(create_pipeline(
            replica_process, definition(f"replica{index}")))
    gateway_process = Process(transport_kind="loopback")
    processes.append(gateway_process)
    policy = (f"max_inflight={4 * micro};"
              f"queue={4 * micro * max(replicas_n, 1)}")
    gateway = Gateway(gateway_process, policy=policy, router_seed=7,
                      telemetry=True, metrics_interval=60.0)
    for replica in replicas:
        gateway.attach_replica(replica)
    for proc in processes:
        proc.run(in_thread=True)

    gateway_responses = queue.Queue()
    for index in range(streams_n):
        gateway.submit_stream(f"g{index}",
                              queue_response=gateway_responses)
    # warm every replica's compiled shapes before the measured window
    for index in range(streams_n):
        gateway.submit_frame(f"g{index}", {"image": images[index % 4]})
    warm_refs = []
    for _ in range(streams_n):
        _, _, outputs, status = gateway_responses.get(timeout=900)
        if status == "ok":
            warm_refs.append(outputs.get("detections"))
    _barrier(warm_refs)

    offered_rate = 2.0 * capacity * replicas_n
    window_s = 1.0 if SMOKE else 3.0
    offered = max(int(offered_rate * window_s), streams_n)
    submit_times = {}
    latencies, ok_refs = [], []
    counts = {"ok": 0, "shed": 0, "error": 0}
    done = threading.Event()

    def drain():
        for _ in range(offered):
            stream_id, frame_id, outputs, status = gateway_responses.get(
                timeout=900)
            if status == "ok":
                _sync(outputs.get("detections"))
                end = time.perf_counter()
                submitted = submit_times.pop((stream_id, frame_id), None)
                if submitted is not None:
                    latencies.append(end - submitted)
                ok_refs.append(outputs.get("detections"))
                counts["ok"] += 1
            else:
                counts[status if status in counts else "error"] += 1
                submit_times.pop((stream_id, frame_id), None)
        done.set()

    drainer = threading.Thread(target=drain, daemon=True)
    drainer.start()
    interval = 1.0 / offered_rate
    start = time.perf_counter()
    # frame ids start AFTER the warm frame (id 0): a reused id would be
    # deduped by the gateway's exactly-once delivery, not re-served
    cursors = {f"g{index}": 1 for index in range(streams_n)}
    for index in range(offered):
        stream_id = f"g{index % streams_n}"
        frame_id = cursors[stream_id]
        cursors[stream_id] += 1
        submit_times[(stream_id, frame_id)] = time.perf_counter()
        gateway.submit_frame(stream_id, {"image": images[index % 4]},
                             frame_id=frame_id)
        ahead = start + (index + 1) * interval - time.perf_counter()
        if ahead > 0:
            time.sleep(ahead)
    done.wait(timeout=900)
    elapsed = _honest_elapsed(start, ok_refs)
    goodput = counts["ok"] / elapsed
    shed_rate = counts["shed"] / max(offered, 1)
    summary = gateway.telemetry.summary()
    for replica in replicas:  # every replica's spans, one router run
        _harvest_trace(replica, config_label="router")
    # the GATEWAY contributes its root spans too (admit-wait, route,
    # shed) -- without them the router trace had no admission story
    # and `aiko tune` could only ever see the replica side
    _harvest_trace(gateway, config_label="router")
    for proc in processes:
        proc.terminate()
    flops = detector_flops_per_image(config)
    return {
        "replicas": replicas_n,
        "streams": streams_n,
        # in-process replicas share the host CPU with the gateway's
        # event loop, so goodput_vs_aggregate_capacity includes that
        # contention -- deployed replicas (own hosts) only pay the
        # gateway's per-frame routing cost
        "topology": "in-process replicas, shared host",
        "policy": policy,
        "model": f"{preset} {size}x{size}",
        "micro_batch": micro,
        "capacity_single_fps": round(capacity, 1),
        "offered_fps": round(offered_rate, 1),
        "offered_frames": offered,
        "goodput_fps": round(goodput, 1),
        "goodput_vs_aggregate_capacity": round(
            goodput / max(capacity * replicas_n, 1e-9), 3),
        "shed_rate": round(shed_rate, 3),
        "errors": counts["error"],
        "p50_admitted_ms": (round(float(np.percentile(
            latencies, 50)) * 1000, 2) if latencies else None),
        "p99_admitted_ms": (round(float(np.percentile(
            latencies, 99)) * 1000, 2) if latencies else None),
        "gateway": summary,
        "mfu": _mfu(goodput * flops, peak),
    }


# -- autoscale: the elastic fleet under a mid-run load doubling --------------

# one spec, three surfaces: the running gateway's autoscaler, the
# definition parameter `aiko lint --bench` checks (AIKO406), and the
# published config block
_AUTOSCALE_POLICY = ("min_replicas=1;max_replicas=2;high_water=0.6;"
                     "low_water=0.01;cooldown=1;interval=0.1")


def bench_autoscale(peak):
    """`autoscale` config: the serving workload behind the gateway with
    the elastic replica fleet enabled.  Closed-loop session load (N
    concurrent bounded sessions, each keeping a window of frames in
    flight) DOUBLES mid-run; the autoscaler must spawn a warm replica
    (persistent compile cache + sibling weight hand-off over the
    transfer plane) and goodput must recover with NO manual replica
    attach.  Published: time-to-healthy for every spawned replica --
    the cold baseline bring-up through the SAME factory vs the warm
    spawn -- plus the warm replica's compile-cache delta
    (`compiles_in_window == 0` is the warm-start proof CI asserts) and
    goodput before/during/after the spike."""
    import threading

    import jax
    import jax.numpy as jnp
    import numpy as np

    from aiko_services_tpu.models import detector_flops_per_image
    from aiko_services_tpu.models.configs import DETECTOR_TOY, YOLOV8N_SHAPE
    from aiko_services_tpu.runtime import Process, enable_compile_cache
    from aiko_services_tpu.serve import Gateway, InProcessReplicaFactory

    config = DETECTOR_TOY if SMOKE else YOLOV8N_SHAPE
    preset = "toy" if SMOKE else "yolov8n"
    size = config.image_size
    micro = 4 if SMOKE else 16
    streams_n = 4 if SMOKE else 16
    images = [
        jax.random.uniform(jax.random.PRNGKey(index), (1, 3, size, size),
                           jnp.float32)
        for index in range(4)]
    # the run's ONE cache directory (idempotent: main() already
    # enabled it): the path is part of the cache key, so a private
    # temporary directory would never hit and would leave every later
    # config compiling cold.  A directory warmed by an earlier run only
    # turns the cold replica's misses into hits; the warm-start proof
    # reads per-spawn deltas
    cache_dir = enable_compile_cache()

    def definition(name):
        return _serving_definition(
            name, size,
            {"telemetry": TELEMETRY, "metrics_interval": 60.0,
             "autoscale_policy": _AUTOSCALE_POLICY},
            {"preset": preset, "micro_batch": micro,
             "dtype": "float32" if SMOKE else "bfloat16"})

    factory = InProcessReplicaFactory(
        definition, warmup={"image": images[0]},
        compile_cache=cache_dir)

    # phase 1: replica0 comes up COLD through the same factory the
    # autoscaler will use -- it pays the XLA compiles once (populating
    # the shared cache) and its bring-up is the warm spawn's baseline
    cold_ready = queue.Queue()
    cold_start = time.perf_counter()
    factory.spawn("replica0",
                  ready=lambda handle, info: cold_ready.put(
                      (handle, info)))
    handle0, cold_info = cold_ready.get(timeout=900)
    if handle0 is None:
        raise RuntimeError(f"cold replica bring-up failed: {cold_info}")
    time_to_healthy_cold_ms = (time.perf_counter() - cold_start) * 1000.0

    # phase 2: the gateway fronting replica0; capacity is measured
    # CLOSED-LOOP THROUGH THE GATEWAY (submit on completion), because
    # the offered rates must saturate the serving path the autoscaler
    # watches -- the raw pipeline is faster than the routed path on a
    # shared host, and calibrating against it would just shed
    pipeline = handle0.pipeline
    gateway_process = Process(transport_kind="loopback")
    # sized against the closed-loop session load below: base = N
    # sessions x a `micro` window = 0.5 of one replica's cap (under the
    # 0.6 high watermark), the doubling = 1.0 (over it) -- so the
    # controller fires ON the spike, not during the base phase
    policy = (f"max_inflight={8 * micro};"
              f"queue={16 * micro * streams_n}")
    gateway = Gateway(gateway_process, policy=policy, router_seed=7,
                      telemetry=True, metrics_interval=60.0)
    gateway.attach_replica(pipeline)
    gateway_process.run(in_thread=True)

    gateway_responses = queue.Queue()
    for index in range(streams_n):
        gateway.submit_stream(f"g{index}",
                              queue_response=gateway_responses)
    for index in range(streams_n):
        gateway.submit_frame(f"g{index}", {"image": images[index % 4]})
    warm_refs = []
    for _ in range(streams_n):
        _, _, outputs, status = gateway_responses.get(timeout=900)
        if status == "ok":
            warm_refs.append(outputs.get("detections"))
    _barrier(warm_refs)

    cursors = {f"g{index}": 1 for index in range(streams_n)}

    def submit_next(index):
        stream_id = f"g{index % streams_n}"
        frame_id = cursors[stream_id]
        cursors[stream_id] += 1
        gateway.submit_frame(stream_id, {"image": images[index % 4]},
                             frame_id=frame_id)

    per_stream = 4 if SMOKE else 30
    probe_total = streams_n * per_stream
    window = 2 * micro
    start = time.perf_counter()
    probe_refs = []
    for index in range(min(window, probe_total)):
        submit_next(index)
    issued = min(window, probe_total)
    for _ in range(probe_total):
        _, _, outputs, status = gateway_responses.get(timeout=900)
        if status == "ok":
            probe_refs.append(outputs.get("detections"))
        if issued < probe_total:
            submit_next(issued)
            issued += 1
    capacity = probe_total / _honest_elapsed(start, probe_refs)
    for index in range(streams_n):
        gateway.post_message("destroy_stream", [f"g{index}"])

    # phase 3: base load, then the mid-run doubling -- only now does
    # the autoscaler watch (the probe's deliberate saturation must not
    # pre-trigger it).  Load is CLOSED-LOOP SESSION traffic: N
    # concurrent sessions, each keeping `window_per_session` frames in
    # flight (N users awaiting responses), and the doubling arrives as
    # N MORE sessions.  Sessions are bounded (`session_frames`) and
    # replaced on completion, so successors RE-PLACE on whatever pool
    # exists -- streams pin to a replica for their lifetime, and a load
    # swing made of immortal pinned streams could never use a grown
    # pool.  A session rejected at admission (typed `overloaded` while
    # every replica is saturated) is retried shortly after, like a real
    # client.
    gateway.enable_autoscale(_AUTOSCALE_POLICY, factory)
    window_per_session = micro
    session_frames = 10 * micro
    base_window = 1.5 if SMOKE else 3.0
    # the spike must outlive the warm bring-up: recovery is only
    # observable once the second replica is serving (and on a
    # shared-CPU smoke host, the bring-up itself steals cycles)
    spike_window = 8.0 if SMOKE else 10.0
    completions = []                      # perf_counter per ok frame
    counts = {"ok": 0, "shed": 0, "error": 0, "rejected_sessions": 0}
    ok_refs = []
    done = threading.Event()
    offering_done = threading.Event()
    lock = threading.Lock()
    sessions: dict = {}    # id -> {"cursor", "outstanding"}
    state = {"sequence": 0}

    def submit_one(stream_id, session):
        frame_id = session["cursor"]
        session["cursor"] += 1
        session["outstanding"] += 1
        gateway.submit_frame(stream_id,
                             {"image": images[frame_id % 4]},
                             frame_id=frame_id)

    def open_session():
        with lock:
            stream_id = f"sess{state['sequence']}"
            state["sequence"] += 1
            session = sessions[stream_id] = {"cursor": 0,
                                             "outstanding": 0}
        gateway.submit_stream(stream_id,
                              queue_response=gateway_responses)
        for _ in range(window_per_session):
            submit_one(stream_id, session)

    def drain():
        # the closed loop lives HERE: each ok/shed response funds the
        # session's next frame; an exhausted session is destroyed and
        # replaced (placement sees the CURRENT pool).  Timestamps are
        # engine-completion times (no per-frame device sync: on a
        # shared-CPU host a blocking sync in this thread becomes the
        # bottleneck); the final _honest_elapsed barrier keeps the
        # OVERALL number device-honest
        retry_at: list = []
        while True:
            now = time.perf_counter()
            while retry_at and retry_at[0] <= now:
                retry_at.pop(0)
                if not offering_done.is_set():
                    open_session()
            try:
                stream_id, frame_id, outputs, status = (
                    gateway_responses.get(
                        timeout=0.05 if retry_at else 2.0))
            except queue.Empty:
                if offering_done.is_set() and not any(
                        session["outstanding"]
                        for session in sessions.values()):
                    break
                continue
            if status == "overloaded":
                counts["rejected_sessions"] += 1
                sessions.pop(stream_id, None)
                retry_at.append(time.perf_counter() + 0.1)
                continue
            if status == "ok":
                completions.append(time.perf_counter())
                ok_refs.append(outputs.get("detections"))
                counts["ok"] += 1
            else:
                counts[status if status in counts else "error"] += 1
            session = sessions.get(stream_id)
            if session is None:
                continue
            session["outstanding"] -= 1
            if offering_done.is_set():
                continue
            if session["cursor"] < session_frames:
                submit_one(stream_id, session)
            elif session["outstanding"] <= 0:
                gateway.post_message("destroy_stream", [stream_id])
                sessions.pop(stream_id, None)
                open_session()
        done.set()

    pool_grew_at = []

    def watch_pool():
        while not done.is_set():
            if len(gateway.replicas) >= 2:
                pool_grew_at.append(time.perf_counter())
                return
            time.sleep(0.01)

    threading.Thread(target=watch_pool, daemon=True).start()
    start = time.perf_counter()
    for _ in range(streams_n):
        open_session()
    threading.Thread(target=drain, daemon=True).start()
    time.sleep(base_window)
    spike_started_at = time.perf_counter()
    for _ in range(streams_n):   # the doubling: N more sessions
        open_session()
    time.sleep(spike_window)
    offer_end = time.perf_counter()
    offering_done.set()
    done.wait(timeout=900)
    offered = counts["ok"] + counts["shed"] + counts["error"]
    elapsed = _honest_elapsed(start, ok_refs)

    def goodput_in(window_start, window_end):
        if window_end <= window_start:
            return None
        inside = sum(1 for moment in completions
                     if window_start <= moment <= window_end)
        return inside / (window_end - window_start)

    goodput_base = goodput_in(start, spike_started_at or offer_end)
    goodput_spike = goodput_in(spike_started_at or offer_end, offer_end)
    # the recovery window: from shortly after the pool actually grew
    # (the warm replica is serving and its bring-up no longer steals
    # host cycles) to the end of the offered spike; if the pool never
    # grew, fall back to the final quarter of the spike
    if pool_grew_at:
        recovery_start = min(pool_grew_at[0] + 1.0, offer_end)
    else:
        recovery_start = (spike_started_at or start) + 0.75 * (
            offer_end - (spike_started_at or start))
    goodput_recovered = goodput_in(recovery_start, offer_end)

    spawns = list(gateway.autoscaler.spawns)
    summary = gateway.telemetry.summary()
    scale_latency_s = (
        round(pool_grew_at[0] - spike_started_at, 3)
        if pool_grew_at and spike_started_at else None)
    # gateway teardown retires every factory-owned replica; replica0
    # was spawned directly (not autoscaler-owned), so it is ours
    gateway_process.terminate()
    handle0.process.terminate()

    warm_spawn = next((spawn for spawn in spawns if spawn["warm"]),
                      spawns[0] if spawns else None)
    flops = detector_flops_per_image(config)
    return {
        "model": f"{preset} {size}x{size}",
        "policy": policy,
        "autoscale": _AUTOSCALE_POLICY,
        "topology": "in-process replicas, shared host",
        "capacity_single_fps": round(capacity, 1),
        "sessions_base": streams_n,
        "sessions_spike": 2 * streams_n,      # the mid-run doubling
        "window_per_session": window_per_session,
        "session_frames": session_frames,
        "responses": offered,
        "goodput_base_fps": (round(goodput_base, 1)
                             if goodput_base is not None else None),
        "goodput_spike_fps": (round(goodput_spike, 1)
                              if goodput_spike is not None else None),
        "goodput_recovered_fps": (round(goodput_recovered, 1)
                                  if goodput_recovered is not None
                                  else None),
        "recovered_vs_single_capacity": (
            round(goodput_recovered / max(capacity, 1e-9), 2)
            if goodput_recovered is not None else None),
        "completed": counts["ok"],
        "shed": counts["shed"],
        "rejected_sessions": counts["rejected_sessions"],
        "errors": counts["error"],
        "goodput_overall_fps": round(counts["ok"] / elapsed, 1),
        "scale_ups": summary["scale_ups"],
        "scale_latency_s": scale_latency_s,
        "time_to_healthy_cold_ms": round(time_to_healthy_cold_ms, 1),
        "cold_compiles": cold_info.get("cache_misses"),
        "spawns": spawns,
        "time_to_healthy_warm_ms": (warm_spawn["time_to_healthy_ms"]
                                    if warm_spawn else None),
        "warm_vs_cold_speedup": (
            round(time_to_healthy_cold_ms
                  / max(warm_spawn["time_to_healthy_ms"], 1e-9), 2)
            if warm_spawn else None),
        # the CI-asserted warm-start proof: zero recompiles of
        # fleet-known shapes during the warm replica's bring-up
        "compiles_in_window": (warm_spawn.get("cache_misses")
                               if warm_spawn else None),
        "mfu": _mfu((goodput_recovered or 0.0) * flops, peak),
    }


# -- chaos: the whole control plane under seeded process-level faults --------

# one spec, three surfaces: the HA gateway pair's journal, the
# definition parameter `aiko lint --bench` checks (AIKO407), and the
# published config block
_CHAOS_JOURNAL = "backend=retained;interval=0.02;search_timeout=0.5"


def _chaos_definition(name):
    """One deterministic integer element (x*3): the chaos scenario
    measures RECOVERY, not compute, and integer outputs make the
    bit-identical comparison exact by construction."""
    return {
        "name": name,
        "parameters": {"telemetry": TELEMETRY, "metrics_interval": 60.0,
                       "journal_policy": _CHAOS_JOURNAL},
        "graph": ["(multiply)"],
        "elements": [
            {"name": "multiply",
             "input": [{"name": "number", "type": "int"}],
             "output": [{"name": "number", "type": "int"}],
             "parameters": {"constant": 3},
             "deploy": {"local": {"module": ELEMENTS,
                                  "class_name": "PE_Multiply"}}},
        ],
    }


def bench_chaos(peak, seed: int | None = None):
    """`chaos` config: one seeded scenario kills the REGISTRAR primary,
    a REPLICA, and the GATEWAY primary mid-run under open client load,
    and proves the whole control plane recovers: the registrar
    secondary promotes and re-registers the fleet (round-8 LWT reap),
    the gateway migrates the dead replica's streams (PR-4 failover),
    and the HA standby adopts the retained journal and resumes every
    stream exactly-once (this round).  Two arms -- chaos and an
    uncrashed reference -- must produce BIT-IDENTICAL per-frame
    outputs with frames_lost == 0; published numbers are the
    time-to-recover per event, the standby takeover latency, and the
    registrar promote latency.  Runs entirely host-side (loopback
    broker, virtual processes): the number is a robustness bound, not
    a throughput figure."""
    import threading

    from aiko_services_tpu.faults import create_injector
    from aiko_services_tpu.pipeline import create_pipeline
    from aiko_services_tpu.pipeline.tensors import (
        decode_frame_data, encode_frame_data)
    from aiko_services_tpu.runtime import Process, Registrar
    from aiko_services_tpu.serve import Gateway
    from aiko_services_tpu.transport import reset_brokers
    from aiko_services_tpu.utils import generate, parse

    seed = int(os.environ.get("AIKO_CHAOS_SEED", "11")
               if seed is None else seed)
    streams_n = 4 if SMOKE else 8
    per_stream = 25 if SMOKE else 50
    total = streams_n * per_stream
    # the three kills land at seeded fractions of the submission run:
    # registrar first (so the replica kill is reaped by the PROMOTED
    # primary), then the replica, then the gateway
    kill_registrar = max(total // 4, 1)
    kill_replica = max(total // 2, 2)
    kill_gateway = max((3 * total) // 4, 3)
    group = "chaos"

    def wait(predicate, timeout=30.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if predicate():
                return True
            time.sleep(0.005)
        raise TimeoutError("chaos fleet condition not met")

    def run(chaos: bool):
        processes = []

        def make_process():
            process = Process(transport_kind="loopback")
            processes.append(process)
            return process

        registrar_1_process = make_process()
        registrar_1 = Registrar(registrar_1_process, name="reg1",
                                search_timeout=0.2)
        registrar_1_process.run(in_thread=True)
        wait(lambda: registrar_1.state == "primary")
        registrar_2_process = make_process()
        registrar_2 = Registrar(registrar_2_process, name="reg2",
                                search_timeout=0.2)
        registrar_2_process.run(in_thread=True)
        wait(lambda: registrar_2.state == "secondary")
        replicas = []
        for index in range(2):
            process = make_process()
            replicas.append((process, create_pipeline(
                process, _chaos_definition(f"chaos_replica{index}"))))
            process.run(in_thread=True)

        def make_gateway():
            process = make_process()
            gateway = Gateway(process, policy="max_inflight=16;queue=256",
                              router_seed=seed, journal=_CHAOS_JOURNAL,
                              ha=group, metrics_interval=60.0)
            gateway.discover(name="chaos_replica*")
            process.run(in_thread=True)
            return gateway

        gateway_a = make_gateway()
        wait(lambda: gateway_a.role == "primary")
        gateway_b = make_gateway()
        wait(lambda: gateway_b.election.state == "secondary")
        for gateway in (gateway_a, gateway_b):
            wait(lambda: len(gateway.replicas) == 2 and all(
                replica.consumer.last_update is not None
                for replica in gateway.replicas.values()))

        client_process = make_process()
        reply_topic = (f"{client_process.topic_path_process}/0/"
                       f"chaos_client")
        lock = threading.Lock()
        responses: dict = {}
        response_times: list = []
        primary = {"topic": gateway_a.topic_path}

        def on_reply(topic, payload):
            try:
                command, parameters = parse(payload)
            except ValueError:
                return
            if command != "process_frame_response" or not parameters:
                return
            reply = parameters[0]
            if not isinstance(reply, dict) or reply.get("event"):
                return
            key = (str(reply.get("stream_id")),
                   int(reply.get("frame_id", -1)))
            outputs = (decode_frame_data(parameters[1])
                       if len(parameters) > 1 else {})
            now = time.perf_counter()
            with lock:
                if key not in responses:
                    responses[key] = outputs.get("number")
                    response_times.append((now, key))

        def on_boot(topic, payload):
            try:
                command, parameters = parse(payload)
            except ValueError:
                return
            if (command == "primary" and parameters
                    and parameters[0] == "found" and len(parameters) > 1):
                primary["topic"] = str(parameters[1])

        client_process.add_message_handler(on_reply, reply_topic)
        client_process.add_message_handler(
            on_boot, f"{client_process.namespace}/gateway/{group}")
        client_process.run(in_thread=True)
        stream_ids = [f"c{index}" for index in range(streams_n)]

        def create(stream_id):
            client_process.publish(
                f"{primary['topic']}/in",
                generate("create_stream", [
                    stream_id, json.dumps({}).encode("ascii"), 600.0,
                    reply_topic]))

        def submit(stream_id, frame_id):
            client_process.publish(
                f"{primary['topic']}/in",
                generate("process_frame", [
                    {"stream_id": stream_id, "frame_id": frame_id},
                    encode_frame_data(
                        {"number": frame_id}).encode("ascii")]))

        injector = create_injector(
            f"seed={seed};"
            f"registrar_kill:node=reg1:frame={kill_registrar};"
            f"process_kill:node=replica0:frame={kill_replica};"
            f"process_kill:node=gateway_a:frame={kill_gateway}"
        ) if chaos else None
        events: list = []
        start = time.perf_counter()

        def chaos_tick():
            """One seeded consult per submission per point -- the
            deterministic chaos plan (faults.py process-scoped points,
            exercised through Process.crash / transport sever)."""
            if injector is None:
                return
            now = round(time.perf_counter() - start, 3)
            if injector.registrar_kill("reg1"):
                registrar_1_process.crash()
                event = {"type": "registrar_kill", "target": "reg1",
                         "at_s": now}
                events.append(event)

                def note_promote(event=event):
                    t0 = time.perf_counter()
                    while (registrar_2.state != "primary"
                           and time.perf_counter() - t0 < 30):
                        time.sleep(0.002)
                    event["promote_ms"] = round(
                        (time.perf_counter() - t0) * 1000, 1)

                threading.Thread(target=note_promote,
                                 daemon=True).start()
            if injector.process_kill("replica0"):
                replicas[0][0].crash()
                events.append({"type": "replica_kill",
                               "target": "chaos_replica0", "at_s": now})
            if injector.process_kill("gateway_a"):
                gateway_a.process.crash()
                events.append({"type": "gateway_kill",
                               "target": "gateway_a", "at_s": now})

        try:
            for stream_id in stream_ids:
                create(stream_id)
            cursors = {stream_id: 0 for stream_id in stream_ids}
            for index in range(total):
                stream_id = stream_ids[index % streams_n]
                frame_id = cursors[stream_id]
                cursors[stream_id] += 1
                submit(stream_id, frame_id)
                chaos_tick()
                time.sleep(0.002)
            # drain: the client replays un-acked frames against the
            # CURRENT primary (the retained announce) until every
            # frame is answered -- the exactly-once dedupe makes the
            # replay idempotent
            expected = {(stream_id, frame_id)
                        for stream_id in stream_ids
                        for frame_id in range(per_stream)}
            deadline = time.monotonic() + (60 if SMOKE else 120)
            resubmit_rounds = 0
            while time.monotonic() < deadline:
                with lock:
                    missing = expected - set(responses)
                if not missing:
                    break
                resubmit_rounds += 1
                for stream_id in {key[0] for key in missing}:
                    create(stream_id)   # idempotent re-assertion
                for stream_id, frame_id in sorted(missing):
                    submit(stream_id, frame_id)
                time.sleep(0.4)
            with lock:
                got = dict(responses)
                times = list(response_times)
            for event in events:
                after = [t for t, _ in times
                         if t - start > event["at_s"]]
                event["ttr_ms"] = (round(
                    (min(after) - start - event["at_s"]) * 1000, 1)
                    if after else None)
            summary = (gateway_b if chaos
                       else gateway_a).telemetry.summary()
            return {
                "outputs": got,
                "events": events,
                "frames_lost": len(expected) - len(got),
                "resubmit_rounds": resubmit_rounds,
                "takeover_ms": (gateway_b.telemetry.last_takeover_ms
                                if chaos else None),
                "injected": injector.stats() if injector else {},
                "ha": summary.get("ha", {}),
            }
        finally:
            for process in processes:
                try:
                    process.terminate()
                except Exception:
                    pass

    reference = run(chaos=False)
    reset_brokers()
    chaotic = run(chaos=True)
    reset_brokers()
    bit_identical = chaotic["outputs"] == reference["outputs"]
    result = {
        "seed": seed,
        "streams": streams_n,
        "frames_total": total,
        "frames_lost": chaotic["frames_lost"],
        "frames_lost_reference": reference["frames_lost"],
        "bit_identical_to_uncrashed": bit_identical,
        "events": chaotic["events"],
        "takeover_ms": chaotic["takeover_ms"],
        "registrar_promote_ms": next(
            (event.get("promote_ms") for event in chaotic["events"]
             if event["type"] == "registrar_kill"), None),
        "resubmit_rounds": chaotic["resubmit_rounds"],
        "injected": chaotic["injected"],
        "journal": chaotic["ha"],
        "topology": ("registrar pair + 2 wire-discovered replicas + "
                     "HA gateway pair, loopback broker"),
    }
    result["decode_replica_kill"] = _chaos_decode_replica_kill(seed)
    result["region_partition"] = _chaos_region_partition(seed)
    timeline_path = os.environ.get("AIKO_CHAOS_TIMELINE")
    if timeline_path:
        try:
            with open(timeline_path, "w") as handle:
                json.dump({key: value for key, value in result.items()
                           if key != "outputs"}, handle, indent=2)
            result["timeline_file"] = timeline_path
        except OSError as error:
            result["timeline_error"] = str(error)
    return result


def _chaos_decode_definition(name, max_new=24, slots=6,
                             keeper="bench_ckpt_keeper"):
    """One checkpointed continuous decode replica (warm KV failover):
    the `decode_replica_kill` scenario's definition, also collected
    into the `aiko lint --bench` surface so its AIKO405/408/409
    parameter set stays strict-mode clean."""
    return {
        "name": name,
        "parameters": {"telemetry": TELEMETRY,
                       "metrics_interval": 60.0},
        "graph": ["(lm)"],
        "elements": [
            {"name": "lm",
             "input": [{"name": "tokens", "type": "any"},
                       {"name": "restore", "type": "any",
                        "optional": True}],
             "output": [{"name": "generated", "type": "any"}],
             "parameters": {
                 "vocab_size": 300, "d_model": 32, "n_layers": 1,
                 "n_heads": 2, "n_kv_heads": 1, "d_ff": 64,
                 "max_seq_len": 128, "dtype": "float32",
                 "max_new_tokens": max_new, "continuous": True,
                 "decode_slots": slots, "kv_block_size": 8,
                 "stream_tokens": True, "stream_chunk": 1,
                 "checkpoint": (f"checkpoint_every=1;"
                                f"max_checkpoint_lag=4;"
                                f"keeper={keeper}")},
             "deploy": {"local": {"module": ELEMENTS,
                                  "class_name": "LMGenerate"}}},
        ],
    }


def _chaos_decode_replica_kill(seed: int):
    """Warm KV failover under a continuous-batching storm: a gateway
    fronts two checkpointed decode replicas, a seeded plan kills one
    MID-DECODE, and the paced failover replays every migrated stream
    with a restore hint -- the survivor adopts each stream's
    checkpointed KV (decode/checkpoint.py) and re-decodes at most
    `max_checkpoint_lag` tokens instead of re-prefilling the prompt.
    Two arms (kill vs uncrashed) must be BIT-IDENTICAL with
    frames_lost == 0 and ZERO survivor recompiles in the measured
    window; the published numbers are the reprefill-avoided fraction
    and the recovery TTFT (kill -> first post-kill token per migrated
    stream)."""
    import threading

    from aiko_services_tpu.decode import CheckpointKeeper, reset_keepers
    from aiko_services_tpu.faults import create_injector
    from aiko_services_tpu.pipeline import create_pipeline
    from aiko_services_tpu.runtime import Process
    from aiko_services_tpu.serve import Gateway
    from aiko_services_tpu.transport import reset_brokers
    from aiko_services_tpu.utils import parse

    import numpy as np

    streams_n = 6 if SMOKE else 12
    max_new = 24 if SMOKE else 48
    prompt_len = 6
    keeper_name = "bench_ckpt_keeper"
    checkpoint_spec = (f"checkpoint_every=1;max_checkpoint_lag=4;"
                       f"keeper={keeper_name}")
    rng = np.random.default_rng(seed)
    frames = [rng.integers(1, 300, size=(1, prompt_len))
              .astype(np.int32) for _ in range(streams_n)]

    def lm_definition(name):
        return _chaos_decode_definition(name, max_new=max_new,
                                        slots=streams_n,
                                        keeper=keeper_name)

    def wait(predicate, timeout=60.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if predicate():
                return True
            time.sleep(0.005)
        raise TimeoutError("decode_replica_kill condition not met")

    def run(kill: bool):
        reset_keepers()
        keeper = CheckpointKeeper(keeper_name)
        processes = []

        def make_process():
            process = Process(transport_kind="loopback")
            processes.append(process)
            return process

        replica_a = create_pipeline(make_process(),
                                    lm_definition("ck_dec0"))
        replica_b = create_pipeline(make_process(),
                                    lm_definition("ck_dec1"))
        gateway_process = make_process()
        gateway = Gateway(
            gateway_process, policy="max_inflight=32;queue=128",
            router_seed=seed, metrics_interval=60.0,
            checkpoint=f"recovery_rate=4;keeper={keeper_name}")
        # all streams pin to replica A; B joins as the warm standby
        # right before the kill, so the failover wave lands on it
        gateway.attach_replica(replica_a)
        lock = threading.Lock()
        token_times: dict = {}    # (stream, offset) -> first-seen time

        def on_out(topic, payload):
            try:
                command, parameters = parse(payload)
            except ValueError:
                return
            if command != "token_chunk" or len(parameters) < 5:
                return
            now = time.perf_counter()
            stream_id = str(parameters[0])
            offset = int(parameters[3])
            with lock:
                for j in range(len(parameters[4][0])):
                    token_times.setdefault((stream_id, offset + j),
                                           now)

        for pipe in (replica_a, replica_b):
            pipe.process.add_message_handler(
                on_out, f"{pipe.elements['lm'].topic_path}/out")
        for process in processes:
            process.run(in_thread=True)

        # warm BOTH engines (the one prompt bucket + the decode step)
        # before the measured window, so the survivor's recompile
        # count during recovery is attributable to recovery alone
        responses = queue.Queue()
        for index, (name, pipe) in enumerate(
                (("warm_a", replica_a), ("warm_b", replica_b))):
            stream = pipe.create_stream(f"{name}", grace_time=300,
                                        queue_response=responses)
            pipe.create_frame(stream, {"tokens": frames[0]})
            responses.get(timeout=120)
            pipe.destroy_stream(f"{name}")
        warm_compiles = {
            "a": replica_a.elements["lm"].engine_stats()["compiles"],
            "b": replica_b.elements["lm"].engine_stats()["compiles"]}

        # frame=0: the kill fires on the plan's FIRST consult for this
        # node (the harness consults once, at the seeded mid-storm
        # point: every stream checkpointed, none finished)
        injector = create_injector(
            f"seed={seed};process_kill:node=ck_dec0:frame=0"
        ) if kill else None
        results = queue.Queue()
        for index, frame in enumerate(frames):
            gateway.submit_stream(f"s{index}", {},
                                  queue_response=results)
            gateway.submit_frame(f"s{index}", {"tokens": frame},
                                 frame_id=0)
        kill_at = None
        migrated = []
        if kill:
            # mid-storm: every stream checkpointed, none finished
            wait(lambda: keeper.flush(timeout=0.1)
                 and keeper.kept_count() >= streams_n)
            gateway.attach_replica(replica_b)
            if injector.process_kill("ck_dec0"):
                migrated = sorted(
                    gateway.replicas[replica_a.topic_path].streams)
                kill_at = time.perf_counter()
                # a REAL death: sever + halt with no clean shutdown
                # (Process.crash), so replica A emits nothing after
                # kill_at and the recovery metrics measure the
                # survivor's restores, not the victim's death throes
                replica_a.process.crash()
                gateway.post_message("_replica_lost", [
                    replica_a.topic_path, "injected decode_replica_kill"])
        outputs = {}
        deadline = time.monotonic() + (120 if SMOKE else 300)
        while len(outputs) < streams_n:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                break
            try:
                stream_id, _frame_id, out, status = results.get(
                    timeout=remaining)
            except queue.Empty:
                break
            if status == "ok":
                outputs[stream_id] = np.asarray(
                    out["generated"]).tolist()
        survivor = replica_b.elements["lm"]
        engine = survivor.engine_stats() or {}
        recovery_ttft_ms = []
        if kill_at is not None:
            with lock:
                times = dict(token_times)
            for stream_id in migrated:
                post = [t for (s, _o), t in times.items()
                        if s == stream_id and t > kill_at]
                if post:
                    recovery_ttft_ms.append(
                        (min(post) - kill_at) * 1000.0)
        summary = gateway.telemetry.summary()
        block = {
            "outputs": outputs,
            "frames_lost": streams_n - len(outputs),
            "migrated_streams": len(migrated),
            "restores": engine.get("restores", 0),
            "restore_fallbacks": engine.get("restore_fallbacks", 0),
            "restore_replayed_tokens": engine.get(
                "restore_replayed_tokens", 0),
            "recovery_paced": summary.get("recovery_paced", 0),
            "compiles_in_window": (
                (replica_b.elements["lm"].engine_stats()["compiles"]
                 - warm_compiles["b"]) if kill else 0),
            "checkpoints": (survivor.checkpoint_stats()
                            or {}).get("checkpoints", 0),
            "keeper": keeper.stats(),
            "recovery_ttft_ms": sorted(recovery_ttft_ms),
        }
        for process in processes:
            try:
                process.terminate()
            except Exception:
                pass
        reset_keepers()
        reset_brokers()
        return block

    reference = run(kill=False)
    chaotic = run(kill=True)
    restores = chaotic["restores"]
    fallbacks = chaotic["restore_fallbacks"]
    ttft = chaotic["recovery_ttft_ms"]
    block = {
        "seed": seed,
        "streams": streams_n,
        "max_new_tokens": max_new,
        "checkpoint_spec": checkpoint_spec,
        "frames_lost": chaotic["frames_lost"],
        "frames_lost_reference": reference["frames_lost"],
        "bit_identical": chaotic["outputs"] == reference["outputs"],
        "migrated_streams": chaotic["migrated_streams"],
        "restores": restores,
        "restore_fallbacks": fallbacks,
        # the headline: migrated streams resumed from checkpoints
        # instead of re-running their (compute-bound) prompt prefill
        "reprefill_avoided_frac": round(
            restores / max(restores + fallbacks, 1), 4),
        "restore_replayed_tokens": chaotic["restore_replayed_tokens"],
        "recovery_paced": chaotic["recovery_paced"],
        "compiles_in_window": chaotic["compiles_in_window"],
        "keeper": chaotic["keeper"],
        "recovery_ttft_p50_ms": (round(ttft[len(ttft) // 2], 2)
                                 if ttft else None),
        "recovery_ttft_p99_ms": (round(ttft[min(
            int(len(ttft) * 0.99), len(ttft) - 1)], 2)
            if ttft else None),
        "topology": ("2 checkpointed continuous decode replicas + "
                     "standby keeper + paced gateway, loopback"),
    }
    return block


def _chaos_region_partition(seed: int):
    """Region loss under a continuous-batching storm: a two-region
    federated tier (`groups=us:a,eu:c`, one checkpointed decode
    replica per region, a SHARED CheckpointKeeper) loses the eu
    region at a seeded `region_partition` point mid-storm.  The
    surviving us gateway warms the lost group's journal mirror,
    adopts exactly its rendezvous share of the eu streams
    (region-aware owner_of over the survivors), and the client's
    resubmitted frames carry the one-shot warm-restore hint -- the us
    decode replica restores each adopted stream's checkpointed KV and
    re-decodes only the post-snapshot tail instead of cold
    re-prefilling.  Both arms (partition vs lossless) must be
    BIT-IDENTICAL with frames_lost == 0 and reprefill_avoided_frac >
    0: journal failover (round 13) x warm checkpoints (round 17) x
    federation (round 19) composed into one robustness proof."""
    import threading

    from aiko_services_tpu.decode import CheckpointKeeper, reset_keepers
    from aiko_services_tpu.faults import create_injector
    from aiko_services_tpu.pipeline import create_pipeline
    from aiko_services_tpu.pipeline.tensors import encode_frame_data
    from aiko_services_tpu.runtime import Process
    from aiko_services_tpu.serve import FederationRouter, Gateway
    from aiko_services_tpu.transport import reset_brokers
    from aiko_services_tpu.utils import generate, parse

    import numpy as np

    streams_n = 6 if SMOKE else 12
    max_new = 24 if SMOKE else 48
    prompt_len = 6
    keeper_name = "bench_region_keeper"
    federation_groups = "groups=us:a,eu:c"
    rng = np.random.default_rng(seed + 1)
    frames = [rng.integers(1, 300, size=(1, prompt_len))
              .astype(np.int32) for _ in range(streams_n)]
    # alternate regions so BOTH gateways carry streams and the
    # partition remaps exactly the eu half
    regions = {f"r{index}": ("us" if index % 2 == 0 else "eu")
               for index in range(streams_n)}
    eu_ids = sorted(sid for sid, region in regions.items()
                    if region == "eu")

    def wait(predicate, timeout=60.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if predicate():
                return True
            time.sleep(0.005)
        raise TimeoutError("region_partition condition not met")

    def run(partition: bool):
        reset_keepers()
        keeper = CheckpointKeeper(keeper_name)
        processes = []

        def make_process():
            process = Process(transport_kind="loopback")
            processes.append(process)
            return process

        replicas = {
            "us": create_pipeline(
                make_process(), _chaos_decode_definition(
                    "rg_dec_us", max_new=max_new, slots=streams_n,
                    keeper=keeper_name)),
            "eu": create_pipeline(
                make_process(), _chaos_decode_definition(
                    "rg_dec_eu", max_new=max_new, slots=streams_n,
                    keeper=keeper_name)),
        }
        gateways = {}
        for group, region in (("a", "us"), ("c", "eu")):
            gateways[group] = Gateway(
                make_process(), name=group,
                policy="max_inflight=32;queue=128",
                router_seed=seed, metrics_interval=60.0,
                journal=_CHAOS_JOURNAL,
                federation=(f"{federation_groups};"
                            f"group={region}:{group}"),
                checkpoint=f"recovery_rate=4;keeper={keeper_name}")
            gateways[group].attach_replica(replicas[region])
        router = FederationRouter(gateways, policy=federation_groups)

        client_process = make_process()
        reply_topic = (f"{client_process.topic_path_process}/0/"
                       f"region_client")
        lock = threading.Lock()
        outputs: dict = {}

        def on_reply(topic, payload):
            try:
                command, parameters = parse(payload)
            except ValueError:
                return
            if (command != "process_frame_response"
                    or len(parameters) < 2):
                return
            reply = parameters[0]
            if not isinstance(reply, dict) or reply.get("event"):
                return
            from aiko_services_tpu.pipeline.tensors import (
                decode_frame_data)
            generated = decode_frame_data(parameters[1]).get(
                "generated")
            with lock:
                outputs.setdefault(
                    str(reply.get("stream_id")),
                    np.asarray(generated).tolist())

        client_process.add_message_handler(on_reply, reply_topic)
        for process in processes:
            process.run(in_thread=True)

        def create(stream_id):
            group = router.group_for(stream_id,
                                     region=regions[stream_id])
            client_process.publish(
                f"{gateways[group].topic_path}/in",
                generate("create_stream", [
                    stream_id,
                    json.dumps({"region": regions[stream_id]})
                    .encode("ascii"),
                    600.0, reply_topic]))

        def submit(stream_id):
            group = router.group_for(stream_id,
                                     region=regions[stream_id])
            client_process.publish(
                f"{gateways[group].topic_path}/in",
                generate("process_frame", [
                    {"stream_id": stream_id, "frame_id": 0},
                    encode_frame_data(
                        {"tokens": frames[int(stream_id[1:])]})
                    .encode("ascii")]))

        injector = create_injector(
            f"seed={seed};region_partition:node=eu:frame=0"
        ) if partition else None
        partition_at = None
        for stream_id in sorted(regions):
            create(stream_id)
            submit(stream_id)
        if partition:
            # mid-storm: every stream checkpointed, none finished,
            # and the eu group's journal holds its streams' pins
            wait(lambda: keeper.flush(timeout=0.1)
                 and keeper.kept_count() >= streams_n)
            wait(lambda: gateways["c"].journal.entry_count()
                 >= len(eu_ids))
            if injector.region_partition("eu", frame_id=0,
                                         scope="bench") != 0.0:
                partition_at = time.perf_counter()
                # the WHOLE region goes dark at once: replica and
                # gateway sever with no clean shutdown
                replicas["eu"].process.crash()
                gateways["c"].process.crash()
                router.fail_group("c")
            # adoption before resubmission: the us gateway must hold
            # the eu streams (restore hints armed) before the client's
            # replay lands, or a fresh create would cold-prefill
            wait(lambda: gateways["a"].telemetry
                 .region_migrations.value >= len(eu_ids),
                 timeout=60 if SMOKE else 120)
        deadline = time.monotonic() + (120 if SMOKE else 300)
        while time.monotonic() < deadline:
            with lock:
                missing = sorted(set(regions) - set(outputs))
            if not missing:
                break
            if partition_at is not None:
                # client replay against the surviving region: the
                # create is an idempotent re-assertion, the frame
                # dedupes against the restored floor
                for stream_id in missing:
                    create(stream_id)
                    submit(stream_id)
            time.sleep(0.4)
        with lock:
            got = dict(outputs)
        recovery_ms = None
        if partition_at is not None:
            recovery_ms = round(
                (time.perf_counter() - partition_at) * 1000, 1)
        survivor = replicas["us"].elements["lm"]
        engine = survivor.engine_stats() or {}
        summary = gateways["a"].telemetry.summary()
        block = {
            "outputs": got,
            "frames_lost": streams_n - len(got),
            "region_migrations": summary.get("region_migrations", 0),
            "region_affinity_hits": summary.get(
                "region_affinity_hits", 0),
            "region_affinity_misses": summary.get(
                "region_affinity_misses", 0),
            "restores": engine.get("restores", 0),
            "restore_fallbacks": engine.get("restore_fallbacks", 0),
            "injected": injector.stats() if injector else {},
            "recovery_ms": recovery_ms,
        }
        for process in processes:
            try:
                process.terminate()
            except Exception:
                pass
        reset_keepers()
        reset_brokers()
        return block

    reference = run(partition=False)
    partitioned = run(partition=True)
    restores = partitioned["restores"]
    fallbacks = partitioned["restore_fallbacks"]
    return {
        "seed": seed,
        "streams": streams_n,
        "regions": {"us": streams_n - len(eu_ids),
                    "eu": len(eu_ids)},
        "frames_lost": partitioned["frames_lost"],
        "frames_lost_reference": reference["frames_lost"],
        "bit_identical": partitioned["outputs"] == reference["outputs"],
        "region_migrations": partitioned["region_migrations"],
        "region_affinity_hits": partitioned["region_affinity_hits"],
        "region_affinity_misses": partitioned[
            "region_affinity_misses"],
        "restores": restores,
        "restore_fallbacks": fallbacks,
        # the headline: adopted streams resumed from the shared
        # keeper's checkpoints instead of re-running prompt prefill
        "reprefill_avoided_frac": round(
            restores / max(restores + fallbacks, 1), 4),
        "recovery_ms": partitioned["recovery_ms"],
        "injected": partitioned["injected"],
        "topology": ("two-region federated tier (us:a, eu:c), one "
                     "checkpointed decode replica per region, shared "
                     "keeper, journaled gateways, loopback"),
    }


# -- autopilot: the online SLO control loop (observe -> decide -> act) -------

# one spec, three surfaces: the gateway policy the bench arms run, the
# definition parameter `aiko lint --bench` checks (AIKO412), and the
# published config block.  interval=0: the bench drives ticks itself
# (tick_now / posted collects) instead of arming the wire timer, so
# every run is deterministic
_AUTOPILOT_POLICY = ("interval=0;apply=on;max_delta_frac=0.5;"
                     "margin=0.15;burn_threshold=0.02")
# the deliberately mis-tuned cold default the loop must walk back from,
# and the value an operator hand-tunes for a closed-loop window of 2
# (the recommender's fixed point: pow2 of the observed group occupancy)
_AUTOPILOT_COLD_MICRO = 16
_AUTOPILOT_TUNED_MICRO = 2


def _autopilot_definition(name, micro=_AUTOPILOT_COLD_MICRO,
                          work_ms=2):
    """One fixed-host-cost element (PE_Busy) behind the gateway: the
    autopilot scenario measures the CONTROL LOOP, not compute, and the
    work_ms floor makes the queue-bound classification (starved
    micro_batch groups) deterministic on any host.  Telemetry is
    FORCED on: the trace harvest is the loop's input."""
    return {
        "name": name,
        "parameters": {"telemetry": True, "metrics_interval": 60.0,
                       "autopilot_policy": _AUTOPILOT_POLICY},
        "graph": ["(busy)"],
        "elements": [
            # "any": the chaos arm feeds exact ints (bit-identical by
            # construction), the convergence arm feeds f32 arrays (only
            # array inputs coalesce under micro-batching)
            {"name": "busy",
             "input": [{"name": "number", "type": "any"}],
             "output": [{"name": "number", "type": "any"}],
             "parameters": {"micro_batch": micro,
                            "micro_batch_wait_ms": 4,
                            "work_ms": work_ms, "constant": 3},
             "deploy": {"local": {"module": ELEMENTS,
                                  "class_name": "PE_Busy"}}},
        ],
    }


def _autopilot_replica_compiles(pipeline) -> int:
    """Sum of every `pipeline.compiles_*` counter on one replica: the
    no-recompile proof reads the delta across the apply window."""
    registry = pipeline.telemetry.registry
    return sum(counter.value
               for name, counter in registry._counters.items()
               if name.startswith("pipeline.compiles_"))


def _autopilot_convergence_arm():
    """Cold mis-tuned fleet -> deterministic tick_now() loop -> the
    applied configuration must land within `margin` of the hand-tuned
    settings, with every delta clamped/journal-accounted in the
    per-tick ledger and ZERO replica recompiles in the apply window."""
    import numpy as np

    from aiko_services_tpu.pipeline import create_pipeline
    from aiko_services_tpu.runtime import Process
    from aiko_services_tpu.serve import Gateway
    from aiko_services_tpu.transport import reset_brokers

    total = 40 if SMOKE else 120
    max_ticks = 12

    def run_load(gateway, responses, start_frame, count):
        """Closed-loop window-2 session traffic: the arrival pattern
        that starves a micro_batch=16 group (median occupancy ~2).
        Frames carry small float arrays -- ONLY array inputs coalesce
        under micro-batching, and the starved-group queue wait IS the
        signal the loop tunes on."""
        submitted, done = 0, 0
        start = time.perf_counter()

        def push():
            nonlocal submitted
            gateway.submit_frame(
                "s0",
                {"number": np.full((1, 2), float(submitted),
                                   np.float32)},
                frame_id=start_frame + submitted)
            submitted += 1

        while submitted < min(2, count):
            push()
        outputs = {}
        while done < count:
            _, frame_id, out, status = responses.get(timeout=120)
            done += 1
            if status == "ok":
                outputs[int(frame_id)] = float(
                    np.asarray(out.get("number")).ravel()[0])
            if submitted < count:
                push()
        return count / max(time.perf_counter() - start, 1e-9), outputs

    def fleet(micro, autopilot):
        process = Process(transport_kind="loopback")
        pipeline = create_pipeline(
            process, _autopilot_definition("bench_autopilot",
                                           micro=micro))
        gateway_process = Process(transport_kind="loopback")
        gateway = Gateway(gateway_process,
                          policy="max_inflight=64;queue=256",
                          router_seed=7, telemetry=True,
                          metrics_interval=60.0, autopilot=autopilot)
        gateway.attach_replica(pipeline)
        process.run(in_thread=True)
        gateway_process.run(in_thread=True)
        responses = queue.Queue()
        gateway.submit_stream("s0", queue_response=responses)
        return process, pipeline, gateway_process, gateway, responses

    # arm 1: cold (mis-tuned micro_batch) + the live control loop
    process, pipeline, gateway_process, gateway, responses = fleet(
        _AUTOPILOT_COLD_MICRO, _AUTOPILOT_POLICY)
    goodput_cold, cold_outputs = run_load(gateway, responses, 0, total)
    compiles_before = _autopilot_replica_compiles(pipeline)
    pilot = gateway.autopilot
    ticks = 0
    for _ in range(max_ticks):
        pilot.tick_now()
        ticks += 1
        tick = pilot.ledger[-1] if pilot.ledger else {}
        if tick.get("converged") and not tick.get("applied"):
            break
    compiles_in_window = (_autopilot_replica_compiles(pipeline)
                          - compiles_before)
    goodput_converged, converged_outputs = run_load(
        gateway, responses, total, total)
    micro_converged = pipeline.elements["busy"].get_parameter(
        "micro_batch")
    summary = pilot.summary()
    ledger = [dict(tick) for tick in pilot.ledger]
    gateway_process.terminate()
    process.terminate()
    reset_brokers()

    # arm 2: the hand-tuned reference, no autopilot
    process, pipeline, gateway_process, gateway, responses = fleet(
        _AUTOPILOT_TUNED_MICRO, None)
    goodput_tuned, tuned_outputs = run_load(gateway, responses, 0,
                                            total)
    gateway_process.terminate()
    process.terminate()
    reset_brokers()

    return {
        "frames_per_arm": total,
        "micro_cold": _AUTOPILOT_COLD_MICRO,
        "micro_hand_tuned": _AUTOPILOT_TUNED_MICRO,
        "micro_converged": (int(micro_converged)
                            if micro_converged is not None else None),
        "ticks": ticks,
        "converged": summary.get("converged", False),
        "convergence": summary.get("convergence"),
        "margin": pilot.policy.margin,
        "deltas_applied": summary.get("deltas_applied", 0),
        "deltas_clamped": summary.get("deltas_clamped", 0),
        "deltas_skipped": summary.get("deltas_skipped", 0),
        "compiles_in_window": compiles_in_window,
        "goodput_cold_fps": round(goodput_cold, 1),
        "goodput_converged_fps": round(goodput_converged, 1),
        "goodput_hand_tuned_fps": round(goodput_tuned, 1),
        "converged_vs_hand_tuned": round(
            goodput_converged / max(goodput_tuned, 1e-9), 2),
        # outputs are micro_batch-invariant by construction: retuning
        # mid-fleet must never change WHAT is computed
        "outputs_invariant": (
            set(cold_outputs.values()) == set(tuned_outputs.values())
            == set(converged_outputs.values())),
        "ledger": ledger,
    }


def _autopilot_chaos_arm(seed: int):
    """Seeded `process_kill` of the HA gateway primary in the apply
    window: the standby promotes, adopts the retained delta journal
    (every applied delta accounted, none re-applied), and the run's
    per-frame outputs stay BIT-IDENTICAL to an unkilled reference with
    frames_lost == 0."""
    import threading

    from aiko_services_tpu.faults import create_injector
    from aiko_services_tpu.pipeline import create_pipeline
    from aiko_services_tpu.pipeline.tensors import (
        decode_frame_data, encode_frame_data)
    from aiko_services_tpu.runtime import Process, Registrar
    from aiko_services_tpu.serve import Gateway
    from aiko_services_tpu.transport import reset_brokers
    from aiko_services_tpu.utils import generate, parse

    streams_n = 2 if SMOKE else 4
    per_stream = 20 if SMOKE else 40
    total = streams_n * per_stream
    # first autopilot tick ~40%, kill in the apply window at ~70%
    tick_frames = {max(2 * total // 5, 1), max(11 * total // 20, 2)}
    kill_gateway = max(7 * total // 10, 3)
    group = "autopilot_chaos"

    def wait(predicate, timeout=30.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if predicate():
                return True
            time.sleep(0.005)
        raise TimeoutError("autopilot chaos fleet condition not met")

    def run(chaos: bool):
        processes = []

        def make_process():
            process = Process(transport_kind="loopback")
            processes.append(process)
            return process

        registrar_process = make_process()
        registrar = Registrar(registrar_process, name="reg",
                              search_timeout=0.2)
        registrar_process.run(in_thread=True)
        wait(lambda: registrar.state == "primary")
        replica_process = make_process()
        replica = create_pipeline(
            replica_process,
            _autopilot_definition("autopilot_replica", work_ms=1))
        replica_process.run(in_thread=True)

        def make_gateway():
            process = make_process()
            gateway = Gateway(
                process, policy="max_inflight=32;queue=512",
                router_seed=seed, journal=_CHAOS_JOURNAL, ha=group,
                autopilot=_AUTOPILOT_POLICY, metrics_interval=60.0)
            gateway.discover(name="autopilot_replica*")
            process.run(in_thread=True)
            return gateway

        gateway_a = make_gateway()
        wait(lambda: gateway_a.role == "primary")
        gateway_b = make_gateway()
        wait(lambda: gateway_b.election.state == "secondary")
        for gateway in (gateway_a, gateway_b):
            wait(lambda: len(gateway.replicas) == 1 and all(
                handle.consumer.last_update is not None
                for handle in gateway.replicas.values()))

        client_process = make_process()
        reply_topic = (f"{client_process.topic_path_process}/0/"
                       f"autopilot_chaos")
        lock = threading.Lock()
        responses: dict = {}
        primary = {"topic": gateway_a.topic_path}

        def on_reply(topic, payload):
            try:
                command, parameters = parse(payload)
            except ValueError:
                return
            if command != "process_frame_response" or not parameters:
                return
            reply = parameters[0]
            if not isinstance(reply, dict) or reply.get("event"):
                return
            key = (str(reply.get("stream_id")),
                   int(reply.get("frame_id", -1)))
            outputs = (decode_frame_data(parameters[1])
                       if len(parameters) > 1 else {})
            with lock:
                responses.setdefault(key, outputs.get("number"))

        def on_boot(topic, payload):
            try:
                command, parameters = parse(payload)
            except ValueError:
                return
            if (command == "primary" and parameters
                    and parameters[0] == "found"
                    and len(parameters) > 1):
                primary["topic"] = str(parameters[1])

        client_process.add_message_handler(on_reply, reply_topic)
        client_process.add_message_handler(
            on_boot, f"{client_process.namespace}/gateway/{group}")
        client_process.run(in_thread=True)
        stream_ids = [f"c{index}" for index in range(streams_n)]

        def create(stream_id):
            client_process.publish(
                f"{primary['topic']}/in",
                generate("create_stream", [
                    stream_id, json.dumps({}).encode("ascii"), 600.0,
                    reply_topic]))

        def submit(stream_id, frame_id):
            client_process.publish(
                f"{primary['topic']}/in",
                generate("process_frame", [
                    {"stream_id": stream_id, "frame_id": frame_id},
                    encode_frame_data(
                        {"number": frame_id}).encode("ascii")]))

        injector = create_injector(
            f"seed={seed};process_kill:node=gateway_a:"
            f"frame={kill_gateway}") if chaos else None
        try:
            for stream_id in stream_ids:
                create(stream_id)
            cursors = {stream_id: 0 for stream_id in stream_ids}
            for index in range(total):
                stream_id = stream_ids[index % streams_n]
                frame_id = cursors[stream_id]
                cursors[stream_id] += 1
                submit(stream_id, frame_id)
                if index in tick_frames:
                    # one wire-harvest control-loop tick on whoever is
                    # primary; the decide lands once every replica's
                    # publish_trace reply arrives (or the wait lease
                    # expires) -- deltas journal BEFORE they apply
                    gateway_a.post_message("_autopilot_collect", [])
                    time.sleep(1.0)
                if injector is not None and injector.process_kill(
                        "gateway_a"):
                    gateway_a.process.crash()
                time.sleep(0.004)
            expected = {(stream_id, frame_id)
                        for stream_id in stream_ids
                        for frame_id in range(per_stream)}
            deadline = time.monotonic() + (60 if SMOKE else 120)
            while time.monotonic() < deadline:
                with lock:
                    missing = expected - set(responses)
                if not missing:
                    break
                for stream_id in {key[0] for key in missing}:
                    create(stream_id)
                for stream_id, frame_id in sorted(missing):
                    submit(stream_id, frame_id)
                time.sleep(0.4)
            with lock:
                got = dict(responses)
            primary_pilot = gateway_a.autopilot
            standby_pilot = gateway_b.autopilot
            applied_seqs = [record["seq"]
                            for tick in primary_pilot.ledger
                            for record in tick.get("applied", [])]
            journaled = (gateway_b.journal.replay_deltas()
                         if gateway_b.journal is not None else [])

            def pilot_count(pilot, name):
                counter = pilot.registry._counters.get(name)
                return counter.value if counter is not None else 0

            return {
                "outputs": got,
                "frames_lost": len(expected) - len(got),
                "deltas_applied_primary": len(applied_seqs),
                "deltas_journaled": len(journaled),
                "deltas_adopted_standby": pilot_count(
                    standby_pilot, "autopilot.deltas_adopted"),
                "deltas_applied_standby": pilot_count(
                    standby_pilot, "autopilot.deltas_applied"),
                "config_restored": (
                    standby_pilot._applied == primary_pilot._applied
                    if chaos else None),
                "takeover_ms": (gateway_b.telemetry.last_takeover_ms
                                if chaos else None),
            }
        finally:
            for process in processes:
                try:
                    process.terminate()
                except Exception:
                    pass

    reference = run(chaos=False)
    reset_brokers()
    chaotic = run(chaos=True)
    reset_brokers()
    return {
        "seed": seed,
        "frames_total": total,
        "bit_identical_to_uncrashed": (
            chaotic["outputs"] == reference["outputs"]),
        "frames_lost": chaotic["frames_lost"],
        "frames_lost_reference": reference["frames_lost"],
        "deltas_applied_primary": chaotic["deltas_applied_primary"],
        "deltas_journaled": chaotic["deltas_journaled"],
        "deltas_adopted_standby": chaotic["deltas_adopted_standby"],
        "deltas_applied_standby": chaotic["deltas_applied_standby"],
        "config_restored": chaotic["config_restored"],
        "takeover_ms": chaotic["takeover_ms"],
        "topology": ("registrar + 1 wire-discovered replica + HA "
                     "gateway pair with retained delta journal, "
                     "loopback broker"),
    }


def bench_autopilot(peak, seed: int | None = None):
    """`autopilot` config: the online SLO control loop end to end.
    Arm 1 starts a deliberately mis-tuned fleet (micro_batch=16 for a
    closed-loop window of 2) and drives deterministic tick_now() loops:
    live trace harvest -> tune -> clamped deltas through the no-restart
    setter paths, converging to within `margin` of the hand-tuned
    reference with zero replica recompiles; the per-tick delta ledger
    is published.  Arm 2 kills the HA gateway primary in the apply
    window under seeded chaos: the standby adopts the write-ahead delta
    journal (every applied delta accounted, none re-applied) and the
    run stays bit-identical to an unkilled reference with
    frames_lost == 0.  Host-side (loopback broker): the numbers are
    control-loop quality bounds, not throughput figures."""
    seed = int(os.environ.get("AIKO_CHAOS_SEED", "11")
               if seed is None else seed)
    result = _autopilot_convergence_arm()
    result["policy"] = _AUTOPILOT_POLICY
    result["chaos"] = _autopilot_chaos_arm(seed)
    timeline_path = os.environ.get("AIKO_AUTOPILOT_TIMELINE")
    if timeline_path:
        try:
            with open(timeline_path, "w") as handle:
                json.dump(result, handle, indent=2)
            result["timeline_file"] = timeline_path
        except OSError as error:
            result["timeline_error"] = str(error)
    return result


# -- config 6b: continuous batching (decode/ engine) -------------------------

def bench_continuous(peak):
    """`continuous` config: the slot-based decode engine (decode/) vs
    the closed-batch generate() path under the SAME open-loop LLM
    traffic -- seeded ragged prompts/completion lengths arriving at 2x
    the engine's measured decode capacity.  The closed arm is the
    STRONGEST closed-batch server this repo can build (one warmed
    executable: fixed batch arity = `decode_slots` via zero-filler
    rows, one prompt bucket, fixed decode length), so the gap is the
    convoy/admission cost alone, not a compile artifact.  Published
    per arm: sustained goodput (useful tokens/sec until the backlog
    drains), TTFT p50/p99 (arrival -> first token), and -- continuous
    only -- mean/peak slot occupancy plus the compile counter across
    the measured window (must be 0: the zero-recompile guarantee)."""
    from collections import deque

    import jax
    import jax.numpy as jnp
    import numpy as np

    from aiko_services_tpu.decode import DecodeEngine
    from aiko_services_tpu.models import (
        count_params, generate_stream, init_params,
        transformer_flops_per_token)
    from aiko_services_tpu.models.configs import LLAMA32_1B, LM_TOY
    from aiko_services_tpu.utils.padding import bucket_length

    config = LM_TOY if SMOKE else LLAMA32_1B
    name = "lm_toy" if SMOKE else "llama32_1b"
    slots = 4 if SMOKE else 8
    block = 8 if SMOKE else 32
    requests_n = 24 if SMOKE else 96
    prompt_lo, prompt_hi = (4, 16) if SMOKE else (32, 128)
    new_lo, new_hi = (4, 24) if SMOKE else (16, 96)
    params = init_params(config, jax.random.PRNGKey(0))
    n_params = count_params(params)

    rng = np.random.default_rng(11)
    workload = [
        (rng.integers(1, config.vocab_size,
                      size=int(rng.integers(prompt_lo, prompt_hi + 1)))
         .astype(np.int32),
         int(rng.integers(new_lo, new_hi + 1)))
        for _ in range(requests_n)]
    mean_tokens = float(np.mean([new for _, new in workload]))
    prompt_bucket = bucket_length(prompt_hi, minimum=block)
    max_context = (-(-(prompt_bucket + new_hi) // block)) * block

    engine = DecodeEngine(params, config, decode_slots=slots,
                          kv_block_size=block, max_context=max_context)
    # engine warmup: one prompt per reachable prefill bucket + the
    # decode step, then a capacity probe with every slot busy
    length = block
    index = 0
    while length <= prompt_bucket:
        engine.submit(("warm", index), np.ones((length,), np.int32), 2)
        length, index = length * 2, index + 1
    while engine.has_work():
        engine.step()
    probe_steps = 8 if SMOKE else 32
    for index in range(slots):
        engine.submit(("probe", index),
                      np.ones((prompt_lo,), np.int32), probe_steps + 2)
    engine.step()  # admissions + first step outside the timed region
    probe_start = time.perf_counter()
    steps = 0
    while engine.has_work():
        steps += engine.step().active
    capacity_tok_s = steps / max(time.perf_counter() - probe_start, 1e-9)
    offered_req_s = 2.0 * capacity_tok_s / mean_tokens
    arrivals = np.cumsum(rng.exponential(1.0 / offered_req_s,
                                         size=requests_n))

    # -- continuous arm ----------------------------------------------------
    compiles_before = engine.compile_count
    ttft = {}
    occupancy = []
    tokens_done = 0
    next_index = 0
    start = time.perf_counter()
    while next_index < requests_n or engine.has_work():
        now = time.perf_counter() - start
        while (next_index < requests_n
               and arrivals[next_index] <= now):
            prompt, max_new = workload[next_index]
            engine.submit(next_index, prompt, max_new)
            next_index += 1
        if not engine.has_work():
            time.sleep(min(arrivals[next_index] - now, 0.01))
            continue
        report = engine.step()
        occupancy.append(report.active / slots)
        for request_id, offset, _ in report.emitted:
            if offset == 0:
                ttft[request_id] = (time.perf_counter() - start
                                    - arrivals[request_id])
        for completion in report.completions:
            tokens_done += completion.stats["tokens"]
    continuous_elapsed = time.perf_counter() - start
    continuous = {
        "goodput_tok_s": round(tokens_done / continuous_elapsed, 1),
        "ttft_p50_ms": round(float(np.percentile(
            list(ttft.values()), 50)) * 1000, 1),
        "ttft_p99_ms": round(float(np.percentile(
            list(ttft.values()), 99)) * 1000, 1),
        "slot_occupancy_mean": round(float(np.mean(occupancy)), 3),
        "slot_occupancy_peak": round(float(np.max(occupancy)), 3),
        "preempted": engine.counters["preempted"],
        "deferred_admissions": engine.counters["deferred_admissions"],
        "compiles_in_window": engine.compile_count - compiles_before,
    }

    # -- closed-batch arm --------------------------------------------------
    # one executable: batch always `slots` (zero-filler rows), prompts
    # padded to ONE bucket, decode length fixed at new_hi -- a member's
    # useful tokens stop at its own max_new, the rest of the batch's
    # steps are the convoy cost
    chunk = 4
    warm_prompt = jnp.ones((slots, prompt_bucket), jnp.int32)
    for _ in generate_stream(params, config, warm_prompt, new_hi,
                             chunk=chunk):
        pass
    waiting = deque()
    closed_ttft = {}
    tokens_done = 0
    batches = 0
    fill = []
    next_index = 0
    start = time.perf_counter()
    while next_index < requests_n or waiting:
        now = time.perf_counter() - start
        while (next_index < requests_n
               and arrivals[next_index] <= now):
            waiting.append(next_index)
            next_index += 1
        if not waiting:
            time.sleep(min(arrivals[next_index] - now, 0.01))
            continue
        members = [waiting.popleft()
                   for _ in range(min(slots, len(waiting)))]
        prompts = np.ones((slots, prompt_bucket), np.int32)
        for row, member in enumerate(members):
            prompt = workload[member][0]
            prompts[row, prompt_bucket - prompt.size:] = prompt  # left-pad
        first_block_at = None
        for _, block_tokens in generate_stream(
                params, config, jnp.asarray(prompts), new_hi,
                chunk=chunk):
            if first_block_at is None:
                np.asarray(block_tokens)  # force the prefill complete
                first_block_at = time.perf_counter() - start
        for member in members:
            closed_ttft[member] = first_block_at - arrivals[member]
            tokens_done += workload[member][1]  # useful tokens only
        batches += 1
        fill.append(len(members) / slots)
    closed_elapsed = time.perf_counter() - start
    closed = {
        "goodput_tok_s": round(tokens_done / closed_elapsed, 1),
        "ttft_p50_ms": round(float(np.percentile(
            list(closed_ttft.values()), 50)) * 1000, 1),
        "ttft_p99_ms": round(float(np.percentile(
            list(closed_ttft.values()), 99)) * 1000, 1),
        "batches": batches,
        "batch_fill_mean": round(float(np.mean(fill)), 3),
    }

    # -- mixed long-prefill arm (convoy measurability) ---------------------
    # a prompt 4x the standard bucket admitted mid-decode: without
    # chunking its monolithic prefill stalls every co-scheduled decode
    # slot for the whole kernel; with prefill_chunk_size = one bucket
    # the stall is bounded by a chunk.  Both arms must stay
    # bit-identical -- the convoy effect becomes a measured number the
    # chunked_prefill config (and ROADMAP #2 disaggregation) can be
    # judged against.
    long_len = 4 * prompt_bucket
    long_rng = np.random.default_rng(23)
    long_prompt = long_rng.integers(
        1, config.vocab_size, size=long_len).astype(np.int32)
    convoy_shorts = [
        long_rng.integers(1, config.vocab_size,
                          size=prompt_lo).astype(np.int32)
        for _ in range(slots - 1)]
    convoy_ctx = (-(-(long_len + new_hi)
                    // block)) * block
    convoy = {"long_prompt": long_len, "chunk": prompt_bucket,
              **_convoy_pair(
                  params, config, slots=slots, block=block,
                  chunk=prompt_bucket, short_prompts=convoy_shorts,
                  short_new=new_hi, long_prompt=long_prompt,
                  long_new=new_lo, max_context=convoy_ctx)}

    decode_flops = transformer_flops_per_token(config, prompt_hi)
    return {
        "model": f"{name} ({n_params / 1e6:.0f}M params)",
        "decode_slots": slots,
        "kv_block_size": block,
        "kv_blocks": engine.blocks.capacity,
        "max_context": engine.max_context,
        "requests": requests_n,
        "prompt_len": f"uniform {prompt_lo}..{prompt_hi}",
        "max_new": f"uniform {new_lo}..{new_hi}",
        "arrival": ("seeded exponential, open-loop at 2x measured "
                    "decode capacity"),
        "offered_req_s": round(offered_req_s, 2),
        "capacity_tok_s": round(capacity_tok_s, 1),
        "continuous": continuous,
        "closed_batch": closed,
        "long_prefill": convoy,
        "goodput_speedup": round(
            continuous["goodput_tok_s"]
            / max(closed["goodput_tok_s"], 1e-9), 2),
        "ttft_p99_speedup": round(
            closed["ttft_p99_ms"]
            / max(continuous["ttft_p99_ms"], 1e-9), 2),
        "decode_mfu": _mfu(continuous["goodput_tok_s"] * decode_flops,
                           peak),
    }


# -- configs 6c/6d: kernel-floor lifts (chunked prefill, spec decode) --------

def _engine_warmup(engine, lengths, max_new=2):
    """Compile every executable the measured phase will touch: one
    request per prompt bucket (which also walks the chunk buckets when
    chunking is on) plus the decode/verify steps."""
    import numpy as np

    for index, length in enumerate(lengths):
        engine.submit(("warm", index), np.ones((length,), np.int32),
                      max_new)
    while engine.has_work():
        engine.step()


def _convoy_arm(params, config, *, slots, block, chunk, short_prompts,
                short_new, long_prompt, long_new, max_context):
    """One convoy measurement: `slots-1` short requests decode in
    steady state, then one long prompt is admitted mid-flight.
    Returns (metrics, completion tokens) where decode_stall_max_ms is
    the longest wall gap between consecutive short-request token
    emissions after the long submission -- the convoy effect itself."""
    import numpy as np

    from aiko_services_tpu.decode import DecodeEngine

    engine = DecodeEngine(params, config, decode_slots=slots,
                          kv_block_size=block, max_context=max_context,
                          prefill_chunk_size=chunk)
    _engine_warmup(engine,
                   sorted({prompt.size for prompt in short_prompts}
                          | {long_prompt.size}))
    compiles_before = engine.compile_count
    outputs = {}
    for index, prompt in enumerate(short_prompts):
        engine.submit(("short", index), prompt, short_new)
    for _ in range(2):
        engine.step()  # shorts reach steady decode before the long lands
    engine.submit("long", long_prompt, long_new)
    submitted_at = time.perf_counter()
    last_short_emit = submitted_at
    max_gap = 0.0
    long_ttft = None
    while engine.has_work():
        report = engine.step()
        now = time.perf_counter()
        for request_id, offset, _token in report.emitted:
            if request_id == "long" and offset == 0:
                long_ttft = now - submitted_at
            if isinstance(request_id, tuple) and request_id[0] == "short":
                max_gap = max(max_gap, now - last_short_emit)
                last_short_emit = now
        for completion in report.completions:
            outputs[completion.request_id] = completion.tokens
    stats = engine.stats()
    return {
        "decode_stall_max_ms": round(max_gap * 1000, 2),
        "long_ttft_ms": round((long_ttft or 0.0) * 1000, 1),
        "prefill_chunks": stats["prefill_chunks"],
        "chunk_interleave_count": stats["chunk_interleaves"],
        "compiles_in_window": engine.compile_count - compiles_before,
    }, outputs


def _convoy_pair(params, config, *, chunk, **kwargs):
    """The monolithic/chunked A-B: both arms of _convoy_arm over the
    same workload, the stall ratio, and the bit-identity verdict --
    the ONE acceptance shape both the chunked_prefill config and the
    continuous config's long_prefill arm publish."""
    import numpy as np

    arms = {}
    arm_outputs = {}
    for label, chunk_size in (("monolithic", None), ("chunked", chunk)):
        arms[label], arm_outputs[label] = _convoy_arm(
            params, config, chunk=chunk_size, **kwargs)
    return {
        "monolithic": arms["monolithic"],
        "chunked": arms["chunked"],
        "stall_speedup": round(
            arms["monolithic"]["decode_stall_max_ms"]
            / max(arms["chunked"]["decode_stall_max_ms"], 1e-9), 2),
        "bit_identical": all(
            np.array_equal(arm_outputs["monolithic"][request_id],
                           arm_outputs["chunked"][request_id])
            for request_id in arm_outputs["monolithic"]),
    }


def bench_chunked_prefill(peak):
    """`chunked_prefill` config: the 16k-prefill kernel floor, engine
    view (ROADMAP #3a).  A long prompt admitted into a busy engine is
    measured twice -- monolithic paged_prefill (today's convoy: every
    decode slot stalls for the whole quadratic kernel) vs
    paged_prefill_chunk at a fixed chunk -- and the arms must be
    bit-identical.  Publishes the decode-stall bound, per-chunk cost,
    interleave counters, and zero-recompile proof; the committed
    `aiko tune` case study (reports/tune_chunked_prefill.json) carries
    the utilization-evidence shift at the recorded 16k operating
    point."""
    import jax
    import numpy as np

    from aiko_services_tpu.models import count_params, init_params
    from aiko_services_tpu.models.configs import LLAMA32_1B, LM_TOY

    config = LM_TOY if SMOKE else LLAMA32_1B
    name = "lm_toy" if SMOKE else "llama32_1b"
    slots = 4
    block = 8 if SMOKE else 32
    chunk = 32 if SMOKE else 512
    long_len = 192 if SMOKE else 3968
    short_len = 8 if SMOKE else 64
    short_new = 48 if SMOKE else 256
    long_new = 8 if SMOKE else 32
    params = init_params(config, jax.random.PRNGKey(0))
    rng = np.random.default_rng(17)
    short_prompts = [
        rng.integers(1, config.vocab_size, size=short_len)
        .astype(np.int32) for _ in range(slots - 1)]
    long_prompt = rng.integers(1, config.vocab_size,
                               size=long_len).astype(np.int32)
    max_context = (-(-(long_len + max(long_new, short_len + short_new))
                     // block)) * block
    pair = _convoy_pair(
        params, config, slots=slots, block=block, chunk=chunk,
        short_prompts=short_prompts, short_new=short_new,
        long_prompt=long_prompt, long_new=long_new,
        max_context=max_context)
    chunks_run = max(pair["chunked"]["prefill_chunks"], 1)
    return {
        "model": f"{name} ({count_params(params) / 1e6:.0f}M params)",
        "decode_slots": slots,
        "kv_block_size": block,
        "prefill_chunk_size": chunk,
        "long_prompt": long_len,
        "short_requests": f"{slots - 1} x {short_len} (+{short_new} new)",
        **pair,
        "chunk_interleave_count": pair["chunked"][
            "chunk_interleave_count"],
        # what an equal split of the monolithic kernel across the
        # chunk count would cost -- the per-call bound chunking targets
        "equiv_chunk_ms": round(
            pair["monolithic"]["long_ttft_ms"] / chunks_run, 2),
    }


def bench_spec_decode(peak):
    """`spec_decode` config: the decode weight-streaming floor, engine
    view (ROADMAP #3c).  Small-batch decode runs three arms over the
    SAME seeded workload -- plain greedy, speculative with a
    quarter-depth random-init draft (realistic overhead, low
    acceptance until a trained draft ships), and speculative with the
    target as its own draft (the acceptance CEILING: every window
    emits k+1 tokens per weight stream) -- all bit-identical.
    accepted_len_mean / draft_overhead_frac are the published
    telemetry the tune case study (reports/tune_spec_decode.json)
    turns into floor evidence."""
    import jax
    import numpy as np

    from dataclasses import replace

    from aiko_services_tpu.decode import DecodeEngine
    from aiko_services_tpu.models import count_params, init_params
    from aiko_services_tpu.models.configs import LLAMA32_1B, LM_TOY

    config = LM_TOY if SMOKE else LLAMA32_1B
    name = "lm_toy" if SMOKE else "llama32_1b"
    slots = 2 if SMOKE else 4      # batch 4 = the decode-floor row
    block = 8 if SMOKE else 32
    spec_k = 4
    requests_n = 6 if SMOKE else 24
    prompt_lo, prompt_hi = (4, 16) if SMOKE else (32, 128)
    max_new = 24 if SMOKE else 96
    params = init_params(config, jax.random.PRNGKey(0))
    draft_config = replace(config,
                           n_layers=max(1, config.n_layers // 4),
                           d_ff=max(64, config.d_ff // 2))
    draft_params = init_params(draft_config, jax.random.PRNGKey(1))
    rng = np.random.default_rng(19)
    workload = [
        rng.integers(1, config.vocab_size,
                     size=int(rng.integers(prompt_lo, prompt_hi + 1)))
        .astype(np.int32) for _ in range(requests_n)]
    warmup_lengths = sorted({prompt.size for prompt in workload})
    from aiko_services_tpu.utils.padding import bucket_length
    max_context = (-(-(bucket_length(prompt_hi, minimum=block)
                       + max_new + spec_k) // block)) * block

    def run(arm_draft_params, arm_draft_config):
        engine = DecodeEngine(
            params, config, decode_slots=slots, kv_block_size=block,
            max_context=max_context,
            draft_params=arm_draft_params,
            draft_config=arm_draft_config,
            spec_k=spec_k if arm_draft_params is not None else 0)
        _engine_warmup(engine, warmup_lengths)
        compiles_before = engine.compile_count
        outputs = {}
        tokens_done = 0
        start = time.perf_counter()
        for index, prompt in enumerate(workload):
            engine.submit(index, prompt, max_new)
        while engine.has_work():
            for completion in engine.step().completions:
                outputs[completion.request_id] = completion.tokens
                tokens_done += completion.stats["tokens"]
        elapsed = time.perf_counter() - start
        stats = engine.stats()
        block_stats = {
            "goodput_tok_s": round(tokens_done / elapsed, 1),
            "compiles_in_window":
                engine.compile_count - compiles_before,
        }
        if arm_draft_params is not None:
            block_stats["accepted_len_mean"] = stats[
                "accepted_len_mean"]
            block_stats["draft_overhead_frac"] = stats[
                "draft_overhead_frac"]
        return block_stats, outputs

    plain, plain_outputs = run(None, None)
    drafted, drafted_outputs = run(draft_params, draft_config)
    ceiling, ceiling_outputs = run(params, config)
    bit_identical = all(
        np.array_equal(plain_outputs[index], drafted_outputs[index])
        and np.array_equal(plain_outputs[index],
                           ceiling_outputs[index])
        for index in plain_outputs)
    return {
        "model": f"{name} ({count_params(params) / 1e6:.0f}M params)",
        "draft": (f"{draft_config.n_layers}L/{draft_config.d_ff}ff "
                  f"random-init "
                  f"({count_params(draft_params) / 1e6:.0f}M params)"),
        "decode_slots": slots,
        "kv_block_size": block,
        "spec_k": spec_k,
        "requests": requests_n,
        "prompt_len": f"uniform {prompt_lo}..{prompt_hi}",
        "max_new": max_new,
        "plain": plain,
        "speculative": drafted,
        "self_draft_ceiling": ceiling,
        "accepted_len_mean": drafted["accepted_len_mean"],
        "draft_overhead_frac": drafted["draft_overhead_frac"],
        "goodput_speedup": round(
            drafted["goodput_tok_s"]
            / max(plain["goodput_tok_s"], 1e-9), 2),
        "ceiling_speedup": round(
            ceiling["goodput_tok_s"]
            / max(plain["goodput_tok_s"], 1e-9), 2),
        "bit_identical": bit_identical,
    }


# -- config 6e: cross-request prefix KV reuse --------------------------------

def _prefix_cache_definition(name, max_new=16, slots=4):
    """One prefix-caching continuous decode replica: the definition the
    `prefix_cache` config exercises, also collected into the `aiko lint
    --bench` surface so its AIKO405/411 parameter set stays strict-mode
    clean."""
    return {
        "name": name,
        "parameters": {"telemetry": TELEMETRY,
                       "metrics_interval": 60.0},
        "graph": ["(lm)"],
        "elements": [
            {"name": "lm",
             "input": [{"name": "tokens", "type": "any"}],
             "output": [{"name": "generated", "type": "any"}],
             "parameters": {
                 "vocab_size": 300, "d_model": 32, "n_layers": 1,
                 "n_heads": 2, "n_kv_heads": 1, "d_ff": 64,
                 "max_seq_len": 128, "dtype": "float32",
                 "max_new_tokens": max_new, "continuous": True,
                 "decode_slots": slots, "kv_block_size": 8,
                 "stream_tokens": True, "stream_chunk": 1,
                 "prefix_policy": ("prefix_cache=on;"
                                   "min_prefix_blocks=1;"
                                   "cache_blocks=32")},
             "deploy": {"local": {"module": ELEMENTS,
                                  "class_name": "LMGenerate"}}},
        ],
    }


def bench_prefix_cache(peak):
    """`prefix_cache` config: cross-request prefix KV reuse
    (decode/prefix.py).  A shared-system-prompt storm -- every request
    is the same long prefix plus a unique fixed-length tail -- runs
    twice over the SAME seeded workload: cold (no prefix policy, every
    prompt pays the full quadratic prefill) vs warm (prefix_cache=on,
    repeat prompts borrow the cached prompt blocks and prefill only
    the tail).  Requests are submitted sequentially, so per-request
    TTFT is the prefill cost itself; the arms must be BIT-IDENTICAL
    (f32 AND int8 KV) with zero warm-arm recompiles in the measured
    window.  A third stage A/Bs the gateway's prefix-affinity routing
    (serve/gateway.py _place) over two replica caches: the on arm must
    beat hint-blind power-of-two routing on aggregate hit rate."""
    import jax
    import numpy as np

    from dataclasses import replace

    from aiko_services_tpu.decode import DecodeEngine, prefix_head
    from aiko_services_tpu.models import count_params, init_params
    from aiko_services_tpu.models.configs import LLAMA32_1B, LM_TOY
    from aiko_services_tpu.runtime import Process
    from aiko_services_tpu.serve import Gateway
    from aiko_services_tpu.serve.gateway import _Replica
    from aiko_services_tpu.transport import reset_brokers

    config = LM_TOY if SMOKE else LLAMA32_1B
    name = "lm_toy" if SMOKE else "llama32_1b"
    slots = 2 if SMOKE else 4
    block = 8 if SMOKE else 32
    prefix_len = 32 if SMOKE else 1024   # the shared system prompt
    tail_len = 8 if SMOKE else 64        # fixed: one tail chunk bucket
    requests_n = 6 if SMOKE else 16
    max_new = 8 if SMOKE else 32
    armed = "prefix_cache=on"
    params = init_params(config, jax.random.PRNGKey(0))
    rng = np.random.default_rng(29)
    system = rng.integers(1, config.vocab_size,
                          size=prefix_len).astype(np.int32)
    workload = [
        np.concatenate([system,
                        rng.integers(1, config.vocab_size,
                                     size=tail_len).astype(np.int32)])
        for _ in range(requests_n)]
    total_len = prefix_len + tail_len
    max_context = (-(-(total_len + max_new) // block)) * block

    def run_arm(arm_config, arm_params, prefix_policy):
        engine = DecodeEngine(
            arm_params, arm_config, decode_slots=slots,
            kv_block_size=block, max_context=max_context,
            prefix_policy=prefix_policy)
        # warmup compiles BOTH prefill shapes the window touches: the
        # cold monolithic bucket and (when armed) the warm tail chunk
        # -- the probe prompt repeats so the second run takes the
        # cache-hit path, then the cache is dropped so the measured
        # window starts cold
        probe = np.ones((total_len,), np.int32)
        _engine_warmup(engine, [total_len])
        engine.submit(("warm", 1), probe, 2)
        while engine.has_work():
            engine.step()
        if engine.prefix is not None:
            engine.prefix.drop()
        compiles_before = engine.compile_count
        hits_before = engine.counters["prefix_hits"]
        shared_before = engine.counters["prefix_blocks_shared"]
        outputs, ttfts = {}, []
        for index, prompt in enumerate(workload):
            engine.submit(index, prompt, max_new)
            while engine.has_work():
                for completion in engine.step().completions:
                    outputs[completion.request_id] = completion.tokens
                    ttfts.append(completion.stats["ttft_s"] * 1000)
        return {
            "ttft_p50_ms": round(float(np.median(ttfts)), 2),
            "ttft_p99_ms": round(float(np.quantile(ttfts, 0.99)), 2),
            "compiles_in_window":
                engine.compile_count - compiles_before,
            "prefix_hits": engine.counters["prefix_hits"] - hits_before,
            "blocks_shared": (engine.counters["prefix_blocks_shared"]
                              - shared_before),
            "evictions": (engine.prefix.evictions
                          if engine.prefix is not None else 0),
        }, outputs

    cold, cold_outputs = run_arm(config, params, None)
    warm, warm_outputs = run_arm(config, params, armed)
    warm["hit_rate"] = round(warm["prefix_hits"] / requests_n, 3)
    bit_identical_f32 = all(
        np.array_equal(cold_outputs[index], warm_outputs[index])
        for index in cold_outputs)

    # int8 KV: the shared blocks carry their per-block scales, so the
    # warm path must round-trip the quantized cache bit-exactly too
    int8_config = replace(config, kv_dtype="int8")
    int8_params = init_params(int8_config, jax.random.PRNGKey(0))
    int8_cold, int8_cold_outputs = run_arm(int8_config, int8_params,
                                           None)
    int8_warm, int8_warm_outputs = run_arm(int8_config, int8_params,
                                           armed)
    bit_identical_int8 = all(
        np.array_equal(int8_cold_outputs[index],
                       int8_warm_outputs[index])
        for index in int8_cold_outputs)

    def affinity_arm(use_affinity):
        """Two replica caches behind the REAL _place scoring: seeded
        per-group prompts, sequential streams, each replica mirroring
        its chain heads the way elements/ml.py publishes them."""
        reset_brokers()
        groups = 3 if SMOKE else 4
        per_group = 4 if SMOKE else 8
        arm_rng = np.random.default_rng(31)
        prefixes = [arm_rng.integers(1, 300, size=16).astype(np.int32)
                    for _ in range(groups)]
        toy = replace(LM_TOY, vocab_size=300)
        toy_params = init_params(toy, jax.random.PRNGKey(2))
        gateway = Gateway(
            Process(transport_kind="loopback"),
            policy="max_inflight=8;queue=32", router_seed=23,
            prefix=("prefix_cache=on;affinity_weight=2"
                    if use_affinity else None))
        engines, mirrors = {}, {}
        for replica_name in ("r0", "r1"):
            engines[replica_name] = DecodeEngine(
                toy_params, toy, decode_slots=2, kv_block_size=8,
                prefix_policy=armed)
            mirror = _Replica(f"bench/{replica_name}", replica_name,
                              cache={"inflight": 0, "prefix_heads": ""})
            mirrors[replica_name] = mirror
            gateway.replicas[mirror.topic_path] = mirror
        placed, hits = 0, 0
        for round_index in range(per_group):
            for group, prefix in enumerate(prefixes):
                prompt = np.concatenate([
                    prefix, arm_rng.integers(1, 300, size=8)
                    .astype(np.int32)])
                hint = prefix_head(prompt, 8)
                chosen = gateway._place(
                    0.0, prefix_hint=hint if use_affinity else None)
                engine = engines[chosen.name]
                before = engine.counters["prefix_hits"]
                engine.submit((group, round_index), prompt, 2)
                while engine.has_work():
                    engine.step()
                hits += engine.counters["prefix_hits"] - before
                placed += 1
                mirrors[chosen.name].cache["prefix_heads"] = ",".join(
                    engine.prefix_heads())
        return round(hits / placed, 3)

    affinity_on = affinity_arm(True)
    affinity_off = affinity_arm(False)

    return {
        "model": f"{name} ({count_params(params) / 1e6:.0f}M params)",
        "decode_slots": slots,
        "kv_block_size": block,
        "shared_prefix_len": prefix_len,
        "tail_len": tail_len,
        "requests": requests_n,
        "max_new": max_new,
        "cold": cold,
        "warm": warm,
        "int8": {"cold_ttft_p50_ms": int8_cold["ttft_p50_ms"],
                 "warm_ttft_p50_ms": int8_warm["ttft_p50_ms"],
                 "prefix_hits": int8_warm["prefix_hits"]},
        "prefix_hits": warm["prefix_hits"],
        "hit_rate": warm["hit_rate"],
        "blocks_shared": warm["blocks_shared"],
        "ttft_collapse": round(
            cold["ttft_p50_ms"] / max(warm["ttft_p50_ms"], 1e-9), 2),
        "compiles_in_window": warm["compiles_in_window"],
        "bit_identical": bit_identical_f32 and bit_identical_int8,
        "bit_identical_f32": bit_identical_f32,
        "bit_identical_int8": bit_identical_int8,
        "affinity": {
            "on_hit_rate": affinity_on,
            "off_hit_rate": affinity_off,
            "advantage": round(affinity_on - affinity_off, 3),
        },
    }


# -- config 6f: prefill/decode disaggregation --------------------------------

def bench_disagg(peak):
    """`disagg` config: prefill/decode disaggregation (ROADMAP #2,
    decode/disagg.py) vs colocation under a MIXED long-prefill +
    long-decode storm.

    Four arms over one seeded workload of short decode-heavy requests,
    with periodic LONG prompts landing mid-run:

      unloaded    decode requests only -- the TTFT baseline disagg is
                  judged against
      colocated   long prompts prefill ON the decode engine: each
                  monolithic prefill kernel convoys every co-scheduled
                  decode slot (the measured cost of colocation)
      disagg      long prompts prefill on a PrefillEngine running on
                  its own thread (the prefill replica); the finished
                  prompt's KV blocks migrate over the transfer plane
                  and the decode engine ADOPTS them mid-flight
      disagg_2x   the same split with the decode load DOUBLED -- the
                  acceptance shape: decode TTFT p99 stays flat
                  (<= 1.2x unloaded) as decode load doubles

    Every arm's tokens must be bit-identical to the co-located
    continuous engine, zero requests lost, zero decode-engine
    recompiles in the measured window; the disagg arms publish KV
    migration bytes and the adopt-latency histogram."""
    import threading
    import queue as queue_module

    import jax
    import numpy as np

    from aiko_services_tpu.decode import DecodeEngine, PrefillEngine
    from aiko_services_tpu.models import (
        count_params, init_params, transformer_flops_per_token)
    from aiko_services_tpu.models.configs import LLAMA32_1B, LM_TOY
    from aiko_services_tpu.observe.metrics import MetricsRegistry
    from aiko_services_tpu.utils.padding import bucket_length

    config = LM_TOY if SMOKE else LLAMA32_1B
    name = "lm_toy" if SMOKE else "llama32_1b"
    slots = 4 if SMOKE else 8
    block = 8 if SMOKE else 32
    decode_n = 16 if SMOKE else 64
    prompt_lo, prompt_hi = (4, 8) if SMOKE else (16, 48)
    new_lo, new_hi = (8, 16) if SMOKE else (32, 96)
    longs_n = 3 if SMOKE else 8
    params = init_params(config, jax.random.PRNGKey(0))
    prompt_bucket = bucket_length(prompt_hi, minimum=block)
    long_len = 4 * prompt_bucket
    long_new = new_lo
    max_context = (-(-(long_len + new_hi) // block)) * block

    rng = np.random.default_rng(17)
    decode_work = [
        (rng.integers(1, config.vocab_size,
                      size=int(rng.integers(prompt_lo, prompt_hi + 1)))
         .astype(np.int32),
         int(rng.integers(new_lo, new_hi + 1)))
        for _ in range(2 * decode_n)]   # the 2x arm uses the full list
    long_prompts = [
        rng.integers(1, config.vocab_size,
                     size=long_len).astype(np.int32)
        for _ in range(longs_n)]
    mean_tokens = float(np.mean([new for _, new in decode_work]))

    warm_lengths = []
    length = block
    while length <= bucket_length(long_len, minimum=block):
        warm_lengths.append(length)
        length *= 2

    def build_engine(registry=None):
        engine = DecodeEngine(params, config, decode_slots=slots,
                              kv_block_size=block,
                              max_context=max_context,
                              registry=registry)
        _engine_warmup(engine, warm_lengths)
        return engine

    # capacity probe (throwaway engine): sets the open-loop offered
    # rate so the 1x arm runs AT capacity and the 2x arm at twice it
    probe = build_engine()
    for index in range(slots):
        probe.submit(("probe", index),
                     np.ones((prompt_lo,), np.int32), 10)
    probe.step()
    probe_start = time.perf_counter()
    steps = 0
    while probe.has_work():
        steps += probe.step().active
    capacity_tok_s = steps / max(time.perf_counter() - probe_start,
                                 1e-9)
    # base load at 0.4x measured capacity: the acceptance shape doubles
    # the decode load, and flat TTFT is only a meaningful claim while
    # the doubled pool is still below saturation (at/over capacity the
    # backlog itself -- not prefill convoying -- owns the p99)
    offered_req_s = 0.4 * capacity_tok_s / mean_tokens

    def run_arm(load: int, with_longs: bool, disagg: bool):
        registry = MetricsRegistry()
        engine = build_engine(registry)
        count = decode_n * load
        arrivals = np.cumsum(np.random.default_rng(29).exponential(
            1.0 / (offered_req_s * load), size=count))
        span = float(arrivals[-1])
        long_arrivals = [span * (index + 1) / (longs_n + 1)
                         for index in range(longs_n)] if with_longs \
            else []
        prefill_engine = None
        handoffs: queue_module.Queue = queue_module.Queue()
        stop = threading.Event()
        worker = None
        if disagg:
            prefill_engine = PrefillEngine(
                params, config, kv_block_size=block,
                max_context=max_context, registry=registry)
            # warm BOTH halves of the migration outside the window:
            # the prefill executables, the batched fetch, and the
            # decode pool's adopt scatter all compile here, not on the
            # first measured long prompt
            prefill_engine.submit(("warm", 0),
                                  np.ones((long_len,), np.int32), 2)
            while prefill_engine.has_work():
                for warm_handoff in prefill_engine.step():
                    engine.adopt_request(("warm", "adopt"),
                                         warm_handoff, timeout=5)
            while engine.has_work():
                engine.step()

            def pump():
                # the prefill REPLICA: its own thread, its own pool --
                # prompt kernels never touch the decode engine's slots
                while not stop.is_set():
                    if prefill_engine.has_work():
                        for handoff in prefill_engine.step():
                            handoffs.put(handoff)
                    else:
                        time.sleep(0.0005)

            worker = threading.Thread(target=pump, daemon=True)
            worker.start()
        compiles_before = engine.compile_count
        ttft = {}
        outputs = {}
        submitted = set()
        next_decode = 0
        next_long = 0
        start = time.perf_counter()

        def pending_longs():
            return (next_long < len(long_arrivals)
                    or (prefill_engine is not None
                        and (prefill_engine.has_work()
                             or not handoffs.empty())))

        while (next_decode < count or pending_longs()
               or engine.has_work()):
            now = time.perf_counter() - start
            while next_decode < count and arrivals[next_decode] <= now:
                prompt, max_new = decode_work[next_decode]
                engine.submit(("d", next_decode), prompt, max_new)
                submitted.add(("d", next_decode))
                next_decode += 1
            while (next_long < len(long_arrivals)
                   and long_arrivals[next_long] <= now):
                request_id = ("long", next_long)
                submitted.add(request_id)
                if disagg:
                    prefill_engine.submit(request_id,
                                          long_prompts[next_long],
                                          long_new)
                else:
                    engine.submit(request_id,
                                  long_prompts[next_long], long_new)
                next_long += 1
            if disagg:
                # adopt only INTO free slots: a saturated engine holds
                # the handoff (the transfer server keeps the blocks
                # fetchable) instead of burning a fallback re-prefill
                while any(slot is None for slot in engine.slots):
                    try:
                        handoff = handoffs.get_nowait()
                    except queue_module.Empty:
                        break
                    report = engine.adopt_request(
                        handoff["request_id"], handoff, timeout=5)
                    for request_id, offset, _token in report.emitted:
                        if offset == 0:
                            ttft[request_id] = (
                                time.perf_counter() - start)
                    for completion in report.completions:
                        outputs[completion.request_id] = \
                            completion.tokens
            if not engine.has_work():
                time.sleep(0.001)
                continue
            report = engine.step()
            now = time.perf_counter() - start
            for request_id, offset, _token in report.emitted:
                if offset == 0:
                    ttft[request_id] = now
            for completion in report.completions:
                outputs[completion.request_id] = completion.tokens
        elapsed = time.perf_counter() - start
        stop.set()
        if worker is not None:
            worker.join(timeout=5)
        # TTFT relative to each request's ARRIVAL, decode requests only
        decode_ttft = [
            ttft[("d", index)] - arrivals[index]
            for index in range(count) if ("d", index) in ttft]
        stats = {
            "requests": count,
            "completed": len(outputs),
            "lost": len(submitted) - len(outputs),
            "elapsed_s": round(elapsed, 2),
            "ttft_p50_ms": round(float(np.percentile(
                decode_ttft, 50)) * 1000, 1),
            "ttft_p99_ms": round(float(np.percentile(
                decode_ttft, 99)) * 1000, 1),
            "compiles_in_window": engine.compile_count
            - compiles_before,
        }
        if disagg:
            adopt = registry.histogram("decode.adopt_ms")
            stats["adopted"] = engine.counters["adopted"]
            stats["adopt_fallbacks"] = engine.counters[
                "adopt_fallbacks"]
            stats["kv_migrated_bytes"] = engine.counters[
                "kv_migrated_bytes"]
            if adopt.count:
                stats["adopt_ms_p50"] = round(adopt.quantile(0.5), 3)
                stats["adopt_ms_p99"] = round(adopt.quantile(0.99), 3)
            stats["prefill_exports"] = prefill_engine.counters[
                "exported"]
        return stats, outputs

    unloaded, _ = run_arm(1, with_longs=False, disagg=False)
    unloaded_2x, _ = run_arm(2, with_longs=False, disagg=False)
    colocated, colocated_out = run_arm(1, with_longs=True,
                                       disagg=False)
    disagg_1x, disagg_out = run_arm(1, with_longs=True, disagg=True)
    disagg_2x, disagg_2x_out = run_arm(2, with_longs=True, disagg=True)
    bit_identical = all(
        np.array_equal(colocated_out[request_id],
                       disagg_out[request_id])
        for request_id in colocated_out) and all(
        np.array_equal(disagg_2x_out[request_id],
                       colocated_out[request_id])
        for request_id in colocated_out)
    frames_lost = (colocated["lost"] + disagg_1x["lost"]
                   + disagg_2x["lost"] + unloaded["lost"])
    decode_flops = transformer_flops_per_token(config, prompt_hi)
    return {
        "model": f"{name} ({count_params(params) / 1e6:.0f}M params)",
        "decode_slots": slots,
        "kv_block_size": block,
        "max_context": max_context,
        "decode_requests": decode_n,
        "long_prefills": longs_n,
        "long_prompt": long_len,
        "prompt_len": f"uniform {prompt_lo}..{prompt_hi}",
        "max_new": f"uniform {new_lo}..{new_hi}",
        "arrival": ("seeded exponential, open-loop at measured decode "
                    "capacity (2x in the disagg_2x arm)"),
        "offered_req_s": round(offered_req_s, 2),
        "capacity_tok_s": round(capacity_tok_s, 1),
        "unloaded": unloaded,
        "unloaded_2x": unloaded_2x,
        "colocated": colocated,
        "disagg": disagg_1x,
        "disagg_2x": disagg_2x,
        "bit_identical": bit_identical,
        "frames_lost": frames_lost,
        "kv_migrated_bytes": disagg_1x.get("kv_migrated_bytes", 0)
        + disagg_2x.get("kv_migrated_bytes", 0),
        "adopt_ms_p50": disagg_1x.get("adopt_ms_p50"),
        "adopt_ms_p99": disagg_1x.get("adopt_ms_p99"),
        # the acceptance shape: the long-prefill storm must not move
        # decode TTFT p99 off its SAME-LOAD unloaded baseline as the
        # decode load doubles -- queueing from decode load itself
        # appears on both sides of each ratio, so what remains is the
        # prefill convoy, which is exactly what disaggregation removes
        # (the colocated ratio measures that convoy uncorrected)
        "ttft_p99_vs_unloaded_1x": round(
            disagg_1x["ttft_p99_ms"]
            / max(unloaded["ttft_p99_ms"], 1e-9), 2),
        "ttft_p99_vs_unloaded_2x": round(
            disagg_2x["ttft_p99_ms"]
            / max(unloaded_2x["ttft_p99_ms"], 1e-9), 2),
        "colocated_ttft_p99_ratio": round(
            colocated["ttft_p99_ms"]
            / max(unloaded["ttft_p99_ms"], 1e-9), 2),
        "ttft_p99_flat": (
            disagg_2x["ttft_p99_ms"]
            <= 1.2 * max(unloaded_2x["ttft_p99_ms"], 1e-9)),
        "decode_mfu": _mfu(capacity_tok_s * decode_flops, peak),
    }


# -- config 7: TTS -----------------------------------------------------------

def _tts_definition(phrase, batch, count):
    return {
        "name": "bench_tts",
        "graph": ["(source (tts))"],
        "elements": [
            {"name": "source",
             "output": [{"name": "text", "type": "str"},
                        {"name": "t0", "type": "float"}],
             "parameters": {"data_sources": [phrase],
                            "data_batch_size": batch,
                            "timestamps": True,
                            "count": count},
             "deploy": _local("TextSource")},
            {"name": "tts",
             "input": [{"name": "text", "type": "str"}],
             # waveform length depends on the phrase's char bucket:
             # rank+dtype are the provable contract, the sample axis
             # stays a wildcard
             "output": [{"name": "audio", "type": "f32[b,*]"},
                        {"name": "sample_rate", "type": "int"}],
             "deploy": _local("TextToSpeech")},
        ],
    }


# -- scale: ten-thousand-stream control-plane scale-out ----------------------

# one spec, three surfaces: the running gateways, the definition
# parameter `aiko lint --bench` checks (AIKO403/AIKO410), and the
# published config block.  max_inflight is sized so the storm never
# parks (the bounded parked queue's linear scans are the OLD ceiling
# this config exists to measure past); the queue is a backstop only.
_SCALE_POLICY = "max_inflight=16384;queue=2048"
_SCALE_GROUPS = ("g0", "g1", "g2", "g3")
_SCALE_FEDERATION = f"groups={','.join(_SCALE_GROUPS)}"


class _ControlPlaneMeter:
    """Control-plane cost window around one config's run: broker
    message rate, registrar registration qps, and EC share sync rate
    from the process-global counter deltas -- published as the
    `control_plane` sub-block of every pipeline-running config so
    future `aiko tune` work can see the control plane's share of each
    workload."""

    def __init__(self):
        from aiko_services_tpu.observe.metrics import get_registry
        self._registry = get_registry()
        self._start = time.perf_counter()
        self._before = dict(self._registry.snapshot()["counters"])

    def block(self) -> dict:
        counters = self._registry.snapshot()["counters"]
        elapsed = max(time.perf_counter() - self._start, 1e-9)

        def delta(name):
            return counters.get(name, 0) - self._before.get(name, 0)

        broker_msgs = delta("broker.messages")
        registrar_ops = delta("registrar.adds") + delta(
            "registrar.removes")
        ec_syncs = delta("share.publishes")
        return {
            "window_s": round(elapsed, 3),
            "broker_msgs": broker_msgs,
            "broker_msgs_per_s": round(broker_msgs / elapsed, 1),
            "broker_fanout_avoided": delta("broker.fanout_avoided"),
            "registrar_ops": registrar_ops,
            "registrar_qps": round(registrar_ops / elapsed, 1),
            "ec_syncs": ec_syncs,
            "ec_syncs_per_s": round(ec_syncs / elapsed, 1),
            "ec_updates_coalesced": delta("share.updates_coalesced"),
            "ec_delta_publishes": delta("share.delta_publishes"),
        }


def _with_control_plane(bench_fn, *args):
    """Run one config with a control-plane cost window around it."""
    meter = _ControlPlaneMeter()
    block = bench_fn(*args)
    if isinstance(block, dict):
        block["control_plane"] = meter.block()
    return block


def _scale_definition(name):
    """Device-light echo element: the scale storm measures the CONTROL
    plane (broker matching, gateway routing, EC syncs), so the data
    plane is one integer add per frame."""
    return {
        "name": name,
        "parameters": {"telemetry": False,
                       "gateway_policy": _SCALE_POLICY,
                       "federation_policy":
                           f"{_SCALE_FEDERATION};group=g0"},
        "graph": ["(echo)"],
        "elements": [
            {"name": "echo",
             "input": [{"name": "number", "type": "int"}],
             "output": [{"name": "number", "type": "int"}],
             "parameters": {"constant": 1},
             "deploy": _local("PE_Add")},
        ],
    }


def _scale_ab_arm(mode: str, subscriptions, messages):
    """One trie-vs-linear A/B arm: a dedicated loopback broker in
    `mode`, C clients with deterministic wildcard subscription sets,
    K deterministic publishes.  Returns (per-client delivery lists,
    mean per-message match seconds from the broker.match_s delta)."""
    from aiko_services_tpu.observe.metrics import get_registry
    from aiko_services_tpu.transport.loopback import (
        LoopbackTransport, get_broker)

    broker = get_broker(f"scale_ab_{mode}")
    broker.match_mode = mode
    clients = []
    for patterns in subscriptions:
        received = []
        transport = LoopbackTransport(
            on_message=(lambda topic, payload, received=received:
                        received.append((topic, payload))),
            broker=f"scale_ab_{mode}")
        for pattern in patterns:
            transport.subscribe(pattern)
        transport.connect()
        clients.append(received)
    histogram = get_registry().histogram("broker.match_s")
    count_before, sum_before = histogram.count, histogram.total
    start = time.perf_counter()
    for topic, payload in messages:
        broker.publish(topic, payload)
    broker.drain(timeout=60)
    elapsed = max(time.perf_counter() - start, 1e-9)
    matched = max(histogram.count - count_before, 1)
    mean_match_s = (histogram.total - sum_before) / matched
    return ([list(received) for received in clients], mean_match_s,
            len(messages) / elapsed)


def bench_scale(peak):
    """`scale` config (ROADMAP #5): O(10k) lightweight open-loop
    streams through a FEDERATED gateway tier -- multiple gateway
    groups, streams assigned by consistent hash of stream id, one
    shared device-light replica fleet -- with the broker and registrar
    measured as the control-plane ceiling.  Publishes goodput / shed /
    p99 (frames_lost must be 0: every offered frame answers exactly
    once), the new `broker.*` counters (messages, matched-fanout
    ratio, match latency), and a trie-vs-linear A/B arm proving the
    broker match fast path is FASTER and delivery-identical (same
    messages, same per-topic order)."""
    import threading

    import numpy as np

    from aiko_services_tpu.observe.metrics import (
        get_registry, snapshot_quantile)
    from aiko_services_tpu.pipeline import create_pipeline
    from aiko_services_tpu.runtime import Process
    from aiko_services_tpu.serve import FederationRouter, Gateway
    from aiko_services_tpu.transport import TopicTrie, topic_matches

    streams_n = int(os.environ.get(
        "AIKO_SCALE_STREAMS", "1500" if SMOKE else "6000"))
    frames_per_stream = 2
    groups = list(_SCALE_GROUPS[:2 if SMOKE else len(_SCALE_GROUPS)])
    replicas_n = 2
    offered = streams_n * frames_per_stream
    # broker counters window: the WHOLE config (A/B arms included --
    # the storm itself rides the in-process fast paths, so the arms
    # supply the broker's own matching traffic)
    registry = get_registry()
    before = dict(registry.snapshot()["counters"])
    match_before = registry.histogram("broker.match_s").snapshot()

    # -- trie-vs-linear A/B (deterministic corpus, dedicated brokers) --
    rng = random.Random(23)
    corpus = ([f"t/{index}" for index in range(64)]
              + [f"t/{index}/+" for index in range(16)]
              + [f"grp/{index}/#" for index in range(16)]
              + ["t/#", "+/0", "grp/+/state"])
    subscriptions = [rng.sample(corpus, 6) for _ in range(48)]
    topics = ([f"t/{rng.randrange(64)}" for _ in range(1500)]
              + [f"grp/{rng.randrange(16)}/state" for _ in range(500)])
    messages = [(topic, f"m{index}")
                for index, topic in enumerate(topics)]
    trie_deliveries, trie_match_s, trie_msgs_per_s = _scale_ab_arm(
        "trie", subscriptions, messages)
    linear_deliveries, linear_match_s, linear_msgs_per_s = (
        _scale_ab_arm("linear", subscriptions, messages))
    ab_identical = trie_deliveries == linear_deliveries
    # direct matcher micro-bench over the same corpus: one trie walk
    # vs the full linear pattern scan per message
    flat = [(pattern, (client, pattern))
            for client, patterns in enumerate(subscriptions)
            for pattern in patterns]
    trie = TopicTrie()
    for pattern, value in flat:
        trie.add(pattern, value)
    start = time.perf_counter()
    for topic, _ in messages:
        trie.match(topic)
    micro_trie_s = (time.perf_counter() - start) / len(messages)
    start = time.perf_counter()
    for topic, _ in messages:
        [value for pattern, value in flat
         if topic_matches(pattern, topic)]
    micro_linear_s = (time.perf_counter() - start) / len(messages)

    # -- the federated storm -------------------------------------------
    processes, replicas = [], []
    for index in range(replicas_n):
        process = Process(transport_kind="loopback")
        processes.append(process)
        replicas.append(create_pipeline(
            process, _scale_definition(f"scale_replica{index}")))
    gateways = {}
    for group in groups:
        process = Process(transport_kind="loopback")
        processes.append(process)
        gateways[group] = Gateway(
            process, name=f"gw_{group}", policy=_SCALE_POLICY,
            federation=f"groups={','.join(groups)};group={group}",
            telemetry=False)
        for replica in replicas:
            gateways[group].attach_replica(replica)
    router = FederationRouter(gateways)
    for process in processes:
        process.run(in_thread=True)

    responses = queue.Queue()
    submit_times = {}
    latencies = []
    counts = {"ok": 0, "shed": 0, "overloaded": 0, "error": 0}
    done = threading.Event()

    def drain():
        for _ in range(offered):
            stream_id, frame_id, _outputs, status = responses.get(
                timeout=900)
            if status == "ok":
                submitted = submit_times.pop((stream_id, frame_id),
                                             None)
                if submitted is not None:
                    latencies.append(time.perf_counter() - submitted)
            counts[status if status in counts else "error"] += 1
        done.set()

    start = time.perf_counter()
    for index in range(streams_n):
        router.submit_stream(f"s{index}", queue_response=responses,
                             grace_time=1800)
    drainer = threading.Thread(target=drain, daemon=True)
    drainer.start()
    # open loop: every frame submitted without waiting on completions
    for frame_id in range(frames_per_stream):
        for index in range(streams_n):
            stream_id = f"s{index}"
            submit_times[(stream_id, frame_id)] = time.perf_counter()
            router.submit_frame(stream_id, {"number": index},
                                frame_id=frame_id)
    done.wait(timeout=900)
    elapsed = time.perf_counter() - start
    # streams are never destroyed mid-storm: the live count at drain
    # time IS the concurrency the config claims
    streams_live = sum(
        len(gateway.streams) for gateway in gateways.values())
    counters = registry.snapshot()["counters"]
    match_after = registry.histogram("broker.match_s").snapshot()

    def delta(name):
        return counters.get(name, 0) - before.get(name, 0)

    match_delta = {
        "count": match_after["count"] - match_before["count"],
        "sum": match_after["sum"] - match_before["sum"],
        "min": match_after["min"], "max": match_after["max"],
        "buckets": [late - early for late, early in zip(
            match_after["buckets"], match_before["buckets"])],
    }
    delivered = delta("broker.fanout_delivered")
    avoided = delta("broker.fanout_avoided")
    shed = counts["shed"] + counts["overloaded"]
    frames_lost = offered - counts["ok"] - shed - counts["error"]
    for process in processes:
        process.terminate()
    return {
        "streams": streams_n,
        "streams_live_peak": streams_live,
        "gateway_groups": len(groups),
        "replicas": replicas_n,
        "topology": (f"federated tier: {len(groups)} gateway groups "
                     f"(consistent-hash stream->group) over one "
                     f"shared {replicas_n}-replica fleet, loopback"),
        "policy": _SCALE_POLICY,
        "offered_frames": offered,
        "completed": counts["ok"],
        "shed": shed,
        "errors": counts["error"],
        "frames_lost": frames_lost,
        "goodput_fps": round(counts["ok"] / max(elapsed, 1e-9), 1),
        # subset-run headline alias: goodput IS the config's frame rate
        "frames_per_sec_total": round(
            counts["ok"] / max(elapsed, 1e-9), 1),
        "p50_ms": (round(float(np.percentile(latencies, 50)) * 1000, 2)
                   if latencies else None),
        "p99_ms": (round(float(np.percentile(latencies, 99)) * 1000, 2)
                   if latencies else None),
        "broker": {
            "messages": delta("broker.messages"),
            "msgs_per_s": round(
                delta("broker.messages") / max(elapsed, 1e-9), 1),
            "matched_fanout_ratio": round(
                delivered / max(delivered + avoided, 1), 4),
            "fanout_avoided": avoided,
            "match_p50_us": round(snapshot_quantile(
                match_delta, 0.5) * 1e6, 2),
            "match_p99_us": round(snapshot_quantile(
                match_delta, 0.99) * 1e6, 2),
        },
        "trie_vs_linear": {
            "ab_identical": ab_identical,
            "clients": len(subscriptions),
            "messages": len(messages),
            "broker_match_trie_us": round(trie_match_s * 1e6, 3),
            "broker_match_linear_us": round(linear_match_s * 1e6, 3),
            "broker_trie_msgs_per_s": round(trie_msgs_per_s, 1),
            "broker_linear_msgs_per_s": round(linear_msgs_per_s, 1),
            "match_trie_us": round(micro_trie_s * 1e6, 3),
            "match_linear_us": round(micro_linear_s * 1e6, 3),
            "match_speedup": round(
                micro_linear_s / max(micro_trie_s, 1e-12), 2),
        },
    }


def bench_soak(peak):
    """`soak` config: the federated `scale` topology held under
    SUSTAINED stream-churn load (waves of create -> frames -> destroy)
    with a drift ledger -- periodic invariant probes that catch the
    slow leaks a 5-second window never sees.  Probes per wave, at
    quiescence: RSS, open fds, paged-pool block conservation
    (free + cached == capacity on the decode lane), journal size
    after compaction (destroyed streams must leave ZERO entries), and
    telemetry counter reconciliation (per-wave frame conservation,
    admitted+shed == offered streams, share.delta_publishes <=
    share.updates_coalesced).  End-of-window drift assertions: RSS
    slope (mean of last third vs first third) and fd growth bounded.
    `AIKO_SOAK_SECONDS` sets the window (CI runs a bounded slice; the
    full window rides the slow lane); `AIKO_SOAK_LEDGER` names a JSON
    artifact path for the full ledger.  Region-failover correctness
    that only holds for 5-second windows is not robustness -- this
    config is the proof it holds for the long haul."""
    import threading

    from aiko_services_tpu.decode import CheckpointKeeper, reset_keepers
    from aiko_services_tpu.observe.metrics import get_registry
    from aiko_services_tpu.pipeline import create_pipeline
    from aiko_services_tpu.runtime import Process
    from aiko_services_tpu.serve import FederationRouter, Gateway
    from aiko_services_tpu.transport import reset_brokers

    window_s = float(os.environ.get(
        "AIKO_SOAK_SECONDS", "45" if SMOKE else "300"))
    echo_streams = 80 if SMOKE else 200
    frames_per_stream = 2
    decode_streams = 3
    keeper_name = "bench_soak_keeper"
    groups = ("g0", "g1")
    journal_spec = "backend=retained;interval=0.05;search_timeout=0.5"
    policy = "max_inflight=2048;queue=1024"
    registry = get_registry()
    share_before = dict(registry.snapshot()["counters"])

    reset_keepers()
    keeper = CheckpointKeeper(keeper_name)
    processes = []

    def make_process():
        process = Process(transport_kind="loopback")
        processes.append(process)
        return process

    echo_replicas = [create_pipeline(
        make_process(), _scale_definition(f"soak_replica{index}"))
        for index in range(2)]
    decode_replica = create_pipeline(
        make_process(), _chaos_decode_definition(
            "soak_decode", max_new=8, slots=decode_streams + 1,
            keeper=keeper_name))
    gateways = {}
    for group in groups:
        gateways[group] = Gateway(
            make_process(), name=f"soak_{group}", policy=policy,
            federation=f"groups={','.join(groups)};group={group}",
            journal=journal_spec, metrics_interval=3600.0)
        for replica in echo_replicas:
            gateways[group].attach_replica(replica)
    router = FederationRouter(gateways)
    decode_gateway = Gateway(
        make_process(), name="soak_dec", policy="max_inflight=8;queue=32",
        metrics_interval=3600.0,
        checkpoint=f"recovery_rate=4;keeper={keeper_name}")
    decode_gateway.attach_replica(decode_replica)
    for process in processes:
        process.run(in_thread=True)

    import numpy as np
    rng = np.random.default_rng(7)
    page_kb = os.sysconf("SC_PAGE_SIZE") // 1024

    def rss_kb():
        try:
            with open("/proc/self/statm") as handle:
                return int(handle.read().split()[1]) * page_kb
        except (OSError, IndexError, ValueError):
            return None

    def open_fds():
        try:
            return len(os.listdir("/proc/self/fd"))
        except OSError:
            return None

    def wait(predicate, timeout=60.0, what="soak condition"):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if predicate():
                return True
            time.sleep(0.005)
        raise TimeoutError(f"{what} not met within {timeout}s")

    ledger: list = []
    findings: list = []
    streams_total = 0
    frames_total = 0
    wave = 0
    start = time.perf_counter()
    deadline = time.monotonic() + window_s
    while time.monotonic() < deadline:
        wave += 1
        offered = echo_streams * frames_per_stream + decode_streams
        responses = queue.Queue()
        answered = {"ok": 0, "shed": 0, "error": 0}
        # -- the churn wave: echo storm through the federated tier,
        #    a few checkpointed decode streams on the side
        echo_ids = [f"w{wave}s{index}" for index in range(echo_streams)]
        for stream_id in echo_ids:
            router.submit_stream(stream_id, queue_response=responses,
                                 grace_time=600)
        for frame_id in range(frames_per_stream):
            for index, stream_id in enumerate(echo_ids):
                router.submit_frame(stream_id, {"number": index},
                                    frame_id=frame_id)
        decode_ids = [f"w{wave}d{index}"
                      for index in range(decode_streams)]
        for stream_id in decode_ids:
            decode_gateway.submit_stream(
                stream_id, {}, queue_response=responses,
                grace_time=600)
            decode_gateway.submit_frame(
                stream_id,
                {"tokens": rng.integers(1, 300, size=(1, 6))
                 .astype(np.int32)},
                frame_id=0)
        for _ in range(offered):
            try:
                _sid, _fid, _out, status = responses.get(timeout=120)
            except queue.Empty:
                break
            answered[status if status in answered else "error"] += 1
        streams_total += echo_streams + decode_streams
        frames_total += offered
        # -- drain to quiescence: destroy everything, then probe
        for stream_id in echo_ids:
            router.destroy_stream(stream_id)
        for stream_id in decode_ids:
            decode_gateway.post_message("destroy_stream", [stream_id])
        try:
            wait(lambda: not any(gateway.streams for gateway in
                                 gateways.values())
                 and not decode_gateway.streams,
                 what=f"wave {wave} stream teardown")
            wait(lambda: (decode_replica.elements["lm"]
                          .engine_stats() or {}).get("active_slots",
                                                     -1) == 0,
                 what=f"wave {wave} decode slot release")
            for gateway in gateways.values():
                wait(lambda g=gateway: g.journal.entry_count() == 0
                     or g.journal.compact() >= 0
                     and g.journal.entry_count() == 0,
                     timeout=15,
                     what=f"wave {wave} journal drain")
        except TimeoutError as error:
            findings.append(str(error))
        # -- the drift probes
        delivered = answered["ok"] + answered["shed"] + answered["error"]
        if delivered != offered:
            findings.append(
                f"wave {wave}: frame conservation broke -- "
                f"{delivered}/{offered} answered")
        admitted = sum(gateway.telemetry.admitted.value
                       + gateway.telemetry.shed_streams.value
                       for gateway in gateways.values())
        admitted += (decode_gateway.telemetry.admitted.value
                     + decode_gateway.telemetry.shed_streams.value)
        if admitted != streams_total:
            findings.append(
                f"wave {wave}: admission reconciliation broke -- "
                f"admitted+shed {admitted} != offered {streams_total}")
        engine = decode_replica.elements["lm"].engine_stats() or {}
        pool_free = engine.get("free_blocks", 0)
        pool_cached = engine.get("prefix_cached_blocks", 0)
        pool_capacity = engine.get("blocks", 0)
        if pool_free + pool_cached != pool_capacity:
            findings.append(
                f"wave {wave}: paged-pool leak -- free {pool_free} + "
                f"cached {pool_cached} != capacity {pool_capacity}")
        journal_entries = sum(gateway.journal.entry_count()
                              for gateway in gateways.values())
        if journal_entries:
            findings.append(
                f"wave {wave}: journal kept {journal_entries} "
                f"entr(ies) after compaction at quiescence")
        counters = registry.snapshot()["counters"]

        def share_delta(name):
            return (counters.get(name, 0)
                    - share_before.get(name, 0))

        if (share_delta("share.delta_publishes")
                > share_delta("share.updates_coalesced")):
            findings.append(
                f"wave {wave}: share coalescing inverted -- "
                f"{share_delta('share.delta_publishes')} delta "
                f"publishes from "
                f"{share_delta('share.updates_coalesced')} staged "
                f"updates")
        ledger.append({
            "wave": wave,
            "t_s": round(time.perf_counter() - start, 2),
            "rss_kb": rss_kb(),
            "open_fds": open_fds(),
            "pool_free": pool_free,
            "pool_cached": pool_cached,
            "pool_capacity": pool_capacity,
            "journal_entries": journal_entries,
            "answered": delivered,
            "offered": offered,
            "findings_total": len(findings),
        })
    elapsed = time.perf_counter() - start
    # -- end-of-window drift assertions over the whole ledger
    rss_series = [entry["rss_kb"] for entry in ledger
                  if entry["rss_kb"] is not None]
    if len(rss_series) >= 3:
        third = max(len(rss_series) // 3, 1)
        early = sum(rss_series[:third]) / third
        late = sum(rss_series[-third:]) / third
        budget_kb = max(32768.0, early * 0.10)
        if late - early > budget_kb:
            findings.append(
                f"rss drift: {early:.0f} kB -> {late:.0f} kB "
                f"(budget {budget_kb:.0f} kB over the window)")
        rss_drift_kb = round(late - early, 1)
    else:
        rss_drift_kb = None
    fd_series = [entry["open_fds"] for entry in ledger
                 if entry["open_fds"] is not None]
    if len(fd_series) >= 2 and fd_series[-1] > fd_series[0] + 16:
        findings.append(
            f"fd drift: {fd_series[0]} -> {fd_series[-1]} open fds")
    for process in processes:
        try:
            process.terminate()
        except Exception:
            pass
    reset_keepers()
    reset_brokers()
    ledger_path = os.environ.get("AIKO_SOAK_LEDGER")
    if ledger_path:
        try:
            with open(ledger_path, "w") as handle:
                json.dump({"findings": findings, "ledger": ledger},
                          handle, indent=2)
        except OSError as error:
            findings.append(f"ledger write failed: {error}")
    return {
        "window_s": window_s,
        "elapsed_s": round(elapsed, 1),
        "waves": wave,
        "streams_total": streams_total,
        "frames_total": frames_total,
        "drift_ok": not findings,
        "findings": findings,
        "rss_drift_kb": rss_drift_kb,
        "open_fds_first": fd_series[0] if fd_series else None,
        "open_fds_last": fd_series[-1] if fd_series else None,
        "probes": len(ledger),
        # the ledger rides the block (bounded); the full artifact goes
        # to AIKO_SOAK_LEDGER for CI upload
        "ledger": ledger[-40:],
        "ledger_file": ledger_path,
        "topology": (f"federated tier ({len(groups)} journaled "
                     f"gateway groups, 2 echo replicas) + 1 "
                     f"checkpointed decode lane, loopback"),
    }


def bench_tts(peak):
    """Text -> speech through the pipeline element (chars -> mel ->
    Griffin-Lim, ONE jit per frame batch): the last model family's
    on-chip number (reference seat: Coqui TTS on CUDA,
    speech_elements.py:109-146)."""
    from aiko_services_tpu.models.configs import tts_flops_per_example
    from aiko_services_tpu.models.tts import TTSConfig

    phrase = ("the quick brown fox jumps over the lazy dog"
              if not SMOKE else "hello")
    batch = 2 if SMOKE else int(os.environ.get("AIKO_BENCH_TTS_BATCH",
                                               "8"))
    warmup, measure = (2, 4) if SMOKE else (5, 40)
    config = TTSConfig()
    definition = _tts_definition(phrase, batch,
                                 (warmup + measure + 4) * batch)
    fps, p50, drain_pf, outputs = _run_pipeline(
        definition, warmup=warmup, measure=measure, ready_key="audio")
    # REAL speech seconds: the element pads prompts to power-of-two
    # char buckets, so the waveform length covers pad-silence; count
    # only the phrase's own frames (matches the FLOPs denominator)
    seconds = (len(phrase) * config.frames_per_char * config.hop
               / config.sample_rate)
    flops = tts_flops_per_example(config, len(phrase)) * batch
    return {"frames_per_sec_chip": round(fps, 2),
            "telemetry": TELEMETRY,
            **_latency_fields(p50, drain_pf),
            "audio_seconds_per_frame": round(seconds * batch, 2),
            "speech_sec_per_sec": round(fps * batch * seconds, 1),
            "batch": batch,
            "mfu": _mfu(fps * flops, peak)}


def collect_definitions() -> dict:
    """Every pipeline definition the benchmark constructs, keyed by
    config name -- the `aiko lint --bench` / CI lint surface.  Built by
    the SAME builders the bench entry points call, so linting these
    lints exactly what runs (the analyzer's golden-corpus acceptance:
    zero strict-mode findings here)."""
    from aiko_services_tpu.models.configs import (
        DETECTOR_TOY, YOLOV8N_SHAPE)

    asr_batch = 2 if SMOKE else int(
        os.environ.get("AIKO_BENCH_ASR_BATCH", "16"))
    det_batch = 2 if SMOKE else int(
        os.environ.get("AIKO_BENCH_DET_BATCH", "16"))
    det_config = DETECTOR_TOY if SMOKE else YOLOV8N_SHAPE
    det_preset = "toy" if SMOKE else "yolov8n"
    rows = 1 if SMOKE else int(os.environ.get("AIKO_BENCH_ROWS", "16"))
    micro = 1 if SMOKE else int(os.environ.get("AIKO_BENCH_MICRO", "8"))
    max_new = 8 if SMOKE else int(os.environ.get("AIKO_BENCH_NEW", "32"))
    serving_micro = 4 if SMOKE else 16
    multimodal, _, _, _ = _multimodal_setup(
        "bench_multimodal", rows, micro, 16, max_new,
        1.0 if SMOKE else 5.0, 16)
    latency, _, _, _ = _multimodal_setup(
        "bench_latency", 1 if SMOKE else 2, 1, 16, max_new,
        1.0 if SMOKE else 5.0, 16)
    return {
        "text": _text_definition(200 if SMOKE else 2000),
        "asr": _asr_definition(
            asr_batch, 1.0 if SMOKE else 5.0, 8 if SMOKE else 32,
            "whisper_tiny" if SMOKE else "whisper_small", 16),
        "detector": _detector_definition(
            det_batch, det_config.image_size, det_preset, 16),
        "multimodal": multimodal,
        "latency": latency,
        "serving": _serving_definition(
            "bench_serving", det_config.image_size,
            {"telemetry": TELEMETRY, "metrics_interval": 60.0},
            {"preset": det_preset, "micro_batch": serving_micro,
             "dtype": "float32" if SMOKE else "bfloat16"}),
        "autoscale": _serving_definition(
            "bench_autoscale", det_config.image_size,
            {"telemetry": TELEMETRY, "metrics_interval": 60.0,
             "autoscale_policy": _AUTOSCALE_POLICY},
            {"preset": det_preset, "micro_batch": serving_micro,
             "dtype": "float32" if SMOKE else "bfloat16"}),
        "autopilot": _autopilot_definition("bench_autopilot"),
        "chaos": _chaos_definition("bench_chaos"),
        "chaos_decode": _chaos_decode_definition("bench_chaos_decode"),
        "prefix_cache": _prefix_cache_definition("bench_prefix_cache"),
        "scale": _scale_definition("bench_scale"),
        "tts": _tts_definition(
            "hello" if SMOKE else
            "the quick brown fox jumps over the lazy dog",
            2 if SMOKE else 8, 16),
    }


# Hard cap on the FINAL printed line.  The driver records only the last
# ~2000 chars of bench output; round 4's single fat JSON line outgrew
# that window and the headline metric was lost ("parsed": null in
# BENCH_r04.json).  The final line must always fit with margin.
HEADLINE_LINE_CAP = 1200

# one representative scalar per config for the compact summary:
# config name -> (field in that config's dict, short key in summary)
_SUMMARY_FIELDS = (
    ("asr", "mfu", "asr_mfu"),
    ("detector", "mfu", "det_mfu"),
    ("llm", "tokens_per_sec", "llm_tok_s"),
    ("llm", "decode_mfu", "llm_mfu"),
    ("train", "train_mfu", "train_mfu"),
    ("serving", "coalescing_speedup", "serving_speedup"),
    ("serving", "frames_per_sec_total", "serving_fps"),
    ("chunked_prefill", "stall_speedup", "chunk_stall_speedup"),
    ("spec_decode", "accepted_len_mean", "spec_accept_mean"),
    ("spec_decode", "ceiling_speedup", "spec_ceiling_speedup"),
    ("prefix_cache", "hit_rate", "prefix_hit_rate"),
    ("prefix_cache", "ttft_collapse", "prefix_ttft_collapse"),
    ("prefix_cache", "bit_identical", "prefix_bit_identical"),
    ("latency", "p50_ms", "latency_p50_ms"),
    ("autoscale", "time_to_healthy_warm_ms", "tth_warm_ms"),
    ("autoscale", "warm_vs_cold_speedup", "warm_speedup"),
    ("autopilot", "converged", "ap_converged"),
    ("autopilot", "deltas_applied", "ap_deltas"),
    ("chaos", "frames_lost", "chaos_lost"),
    ("chaos", "takeover_ms", "takeover_ms"),
    ("soak", "drift_ok", "soak_drift_ok"),
    ("soak", "waves", "soak_waves"),
    ("scale", "streams", "scale_streams"),
    ("scale", "goodput_fps", "scale_goodput"),
    ("scale", "frames_lost", "scale_lost"),
    ("tts", "mfu", "tts_mfu"),
    ("pipeline_multimodal", "mfu", "headline_mfu"),
    ("pipeline_multimodal", "audio_realtime_factor", "audio_rt"),
)


def compact_headline(detail: dict, cap: int = HEADLINE_LINE_CAP) -> str:
    """The short FINAL output line: headline metric + vs_baseline + a
    one-scalar-per-config summary, guaranteed to parse and to fit in
    `cap` chars (tested in tests/test_bench_output.py).  Full per-config
    detail lives in BENCH_DETAIL.json / the earlier detail line."""
    compact = {key: value for key, value in detail.items()
               if key != "configs"}
    configs = detail.get("configs", {})
    summary = {}
    for config_name, field, short in _SUMMARY_FIELDS:
        value = configs.get(config_name, {}).get(field)
        if value is not None:
            summary[short] = value
    compact["summary"] = summary
    compact["detail_file"] = "BENCH_DETAIL.json"
    # progressive field drops keep the guarantee even if units/summary
    # grow; never drop metric/value/vs_baseline
    for drop in (None, "trace_file", "trace_files", "trace_events",
                 "trace_frames_dropped", "summary",
                 "baseline", "unit", "peak_tflops_assumed"):
        if drop is not None:
            compact.pop(drop, None)
        line = json.dumps(compact)
        if len(line) <= cap:
            break
    parsed = json.loads(line)  # parse guard: the line IS the record
    assert len(line) <= cap and "vs_baseline" in parsed, (
        f"headline line {len(line)} chars exceeds cap {cap}")
    return line


def main() -> None:
    global _TRACE_PATH, _FAULTS_SEED
    argv = sys.argv[1:]
    usage = ("usage: bench.py [--trace <path>] [--faults <seed>] "
             "[--router <replicas>]")
    if "--trace" in argv:
        index = argv.index("--trace")
        if index + 1 >= len(argv):
            print(usage, file=sys.stderr)
            sys.exit(2)
        _TRACE_PATH = argv[index + 1]
    if "--faults" in argv:
        index = argv.index("--faults")
        if index + 1 >= len(argv):
            print(usage, file=sys.stderr)
            sys.exit(2)
        _FAULTS_SEED = int(argv[index + 1])
    router_replicas = None
    if "--router" in argv:
        index = argv.index("--router")
        if index + 1 >= len(argv):
            print(usage, file=sys.stderr)
            sys.exit(2)
        router_replicas = max(1, int(argv[index + 1]))
    import jax

    from aiko_services_tpu.runtime import enable_compile_cache

    # the device path: ONE process opens the chip, and a run that finds
    # no TPU stops here, before any configuration -- a CPU run happens
    # only when AIKO_BENCH_PLATFORM=cpu asks for one (counts and
    # correctness; its numbers are not device metrics)
    platform = os.environ.get("AIKO_BENCH_PLATFORM")
    if platform:
        jax.config.update("jax_platforms", platform)
    found = jax.devices()[0].platform
    if found != (platform or "tpu"):
        print(f"bench.py: wanted platform {platform or 'tpu'!r}, jax "
              f"found {found!r} ({jax.devices()[0].device_kind}); set "
              f"AIKO_BENCH_PLATFORM=cpu for a CPU correctness run",
              file=sys.stderr)
        sys.exit(1)
    enable_compile_cache()
    peak = _peak_flops_per_chip()
    default_configs = ("text,asr,detector,llm,llm_sharded,train,"
                       "longcontext,serving,continuous,chunked_prefill,"
                       "spec_decode,prefix_cache,disagg,autoscale,"
                       "autopilot,chaos,latency,scale,tts,pipeline")
    wanted = os.environ.get("AIKO_BENCH_CONFIGS",
                            default_configs).split(",")
    configs = {}
    if "text" in wanted:
        configs["text"] = _with_control_plane(bench_text)
    if "asr" in wanted:
        configs["asr"] = _with_control_plane(bench_asr, peak)
    if "detector" in wanted:
        configs["detector"] = _with_control_plane(bench_detector, peak)
    if "llm" in wanted:
        configs["llm"] = bench_llm(peak)
    if "llm_sharded" in wanted:
        configs["llm_sharded"] = bench_llm_sharded()
    if "train" in wanted:
        configs["train"] = bench_train(peak)
    if "longcontext" in wanted:
        configs["longcontext"] = bench_longcontext(peak)
    if "serving" in wanted:
        configs["serving"] = _with_control_plane(bench_serving, peak)
    if "continuous" in wanted:
        configs["continuous"] = bench_continuous(peak)
    if "chunked_prefill" in wanted:
        configs["chunked_prefill"] = bench_chunked_prefill(peak)
    if "spec_decode" in wanted:
        configs["spec_decode"] = bench_spec_decode(peak)
    if "prefix_cache" in wanted:
        configs["prefix_cache"] = bench_prefix_cache(peak)
    if "disagg" in wanted:
        configs["disagg"] = _with_control_plane(bench_disagg, peak)
    if router_replicas is not None or "router" in wanted:
        configs["router"] = _with_control_plane(
            bench_router, peak, router_replicas or 2)
    if "autoscale" in wanted:
        configs["autoscale"] = _with_control_plane(bench_autoscale, peak)
    if "autopilot" in wanted:
        configs["autopilot"] = _with_control_plane(bench_autopilot, peak)
    if "chaos" in wanted:
        configs["chaos"] = _with_control_plane(bench_chaos, peak)
    if "latency" in wanted:
        configs["latency"] = _with_control_plane(bench_latency, peak)
    if "scale" in wanted:
        configs["scale"] = _with_control_plane(bench_scale, peak)
    if "soak" in wanted:
        configs["soak"] = _with_control_plane(bench_soak, peak)
    if "tts" in wanted:
        configs["tts"] = _with_control_plane(bench_tts, peak)
    headline_fps, headline_p50, audio_seconds = None, None, None
    headline_rows = 1
    if "pipeline" in wanted:
        meter = _ControlPlaneMeter()
        (configs["pipeline_multimodal"], headline_fps, headline_p50,
         audio_seconds, headline_rows) = bench_multimodal(peak)
        configs["pipeline_multimodal"]["control_plane"] = meter.block()
    metric = "multimodal_pipeline_frames_per_sec"
    unit = ("frames/sec end-to-end (3-stage speech+LM+vision graph, "
            "HBM-resident, 1 chip)")
    if headline_fps is None:
        # subset run (no pipeline config): label the headline with the
        # config it actually came from -- a tokens/sec number must not
        # masquerade as the multimodal frame rate
        first_name, first = next(iter(configs.items()))
        headline_fps = (first.get("frames_per_sec_chip")
                        or first.get("frames_per_sec")
                        or first.get("frames_per_sec_total")
                        or first.get("tokens_per_sec", 0.0))
        headline_p50 = first.get("p50_ms", 0.0) / 1000.0
        metric = f"{first_name}_headline_subset_run"
        unit = (f"headline scalar of the '{first_name}' config "
                f"(SUBSET run -- not the end-to-end pipeline metric)")

    result = {
        "metric": metric,
        "value": round(headline_fps, 2),
        "unit": unit,
        # apples-to-apples baseline: end-to-end audio-realtime factor vs
        # the reference speech stage on a single GPU (whisper-small = 6x
        # realtime, speech_elements.py:186-192 relative-speed table --
        # generous to the reference: its LLM + YOLO stages are free here)
        "vs_baseline": (
            round(headline_fps * headline_rows * audio_seconds
                  / REFERENCE_GPU_SPEECH_REALTIME, 2)
            if audio_seconds is not None
            else round(headline_fps / REFERENCE_FRAMES_PER_SEC, 2)),
        "baseline": (
            "reference whisper-small single-GPU speech stage at 6x "
            "realtime" if audio_seconds is not None
            else "reference multitude broker ceiling 50 frames/sec"),
        "p50_frame_latency_ms": round(headline_p50 * 1000, 2),
        "device": jax.devices()[0].device_kind,
        "peak_tflops_assumed": (round(peak / 1e12, 1) if peak else None),
        "smoke": SMOKE,
        "telemetry": TELEMETRY,
        "configs": configs,
    }
    if _FAULTS_SEED is not None:
        result["faults_seed"] = _FAULTS_SEED  # self-describing A/B arm
    if _TRACE_PATH:
        # trace artifacts ship alongside the JSON: one self-describing
        # per-config file each (path published in the config block,
        # `aiko tune` input) plus the combined legacy file with every
        # benched pipeline's spans
        from aiko_services_tpu.observe import chrome_trace_document
        combined_metadata = _write_config_traces(configs, result)
        try:
            with open(_TRACE_PATH, "w") as handle:
                json.dump(chrome_trace_document(
                    _TRACE_EVENTS, metadata=combined_metadata), handle)
            result["trace_file"] = _TRACE_PATH
            result["trace_events"] = len(_TRACE_EVENTS)
            # truncation is explicit: frames evicted from the bounded
            # per-pipeline trace rings (raise with `trace_ring`)
            result["trace_frames_dropped"] = _TRACE_DROPPED
        except OSError as error:
            result["trace_error"] = str(error)
    # full detail: a file (committed evidence) + an earlier output line;
    # the FINAL line is compact so the driver's ~2000-char tail window
    # always contains it whole (round-4 lesson: BENCH_r04 parsed null).
    # Only FULL runs write the file -- a subset run must not clobber the
    # repo's end-to-end evidence record with a partial one
    detail_line = json.dumps(result)
    if set(wanted) >= set(default_configs.split(",")):
        try:
            with open(os.path.join(
                    os.path.dirname(os.path.abspath(__file__)),
                    "BENCH_DETAIL.json"), "w") as handle:
                handle.write(detail_line + "\n")
        except OSError:
            pass  # read-only checkout: the detail line still records it
    print(detail_line)
    print(compact_headline(result))
    sys.stdout.flush()


if __name__ == "__main__":
    sys.exit(main())
