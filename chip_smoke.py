#!/usr/bin/env python3
"""Chip smoke: the quickest proof that the system still starts on the TPU.

    python chip_smoke.py               # on a machine with a TPU
    python chip_smoke.py --rehearsal   # toy sizes on whatever jax finds

One OS process, jax touched only here, no child process.  It drives the
main path once through the entry points a user calls, at the published
widths of the presets the repo ships (whisper_small, llama32_1b, yolov8n;
random weights from fixed seeds), and checks what comes out:

  train     make_train_step on the 8-layer llama32_1b cut, three steps
  kernels   flash_attention against an f32 reference at the probe
            lengths and in the served prefill's form (K/V read grouped,
            head_dim 128, the long-L tiles), forward and backward, and
            told a live length (dead query blocks exactly zero),
            paged_attention against its einsum oracle, and the Mosaic
            custom call in the compiled train step and prefill
  pipeline  the headline 3-stage graph (speech -> LM, vision ->
            detections) through create_pipeline / create_stream
  serve     Registrar + replica pipeline + Gateway + DecodeEngine:
            two waves of eight ragged-prompt streams, on a 4-layer cut
            of Mistral-7B's widths (head_dim 128: the paged-attention
            kernel serves the decode step, which is checked)
  link      three facts about host<->device (findings, not speeds)

With four or more devices the same process drives a four-device mesh
instead: the pipeline with its LM tensor-parallel over model=4,
sequence-parallel LMForward (ring attention) over seq=4, and a sharded
train step on data=2 x model=2, each compared with a one-device run.

Without a TPU the script exits non-zero before any phase.  --rehearsal
runs toy sizes on the platform jax finds, tags every result line
`rehearsal platform=<platform>`, and ends with "ok": false: it checks the
script, never the chip.  A phase that raises, times out, answers with a
non-ok status or produces a non-finite value ends the run non-zero.

The last line of stdout is one JSON object with exactly the keys "ok" and
"device" ({"platform", "kind", "count"} as jax reports them); the line
before it is the `[smoke] summary` that carries `"claim": null`.
"""

from __future__ import annotations

import argparse
import gc
import json
import queue
import statistics
import sys
import time
from dataclasses import replace
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec

from aiko_services_tpu.models import (
    forward, init_params, make_train_step, paged_decode_step, param_specs)
from aiko_services_tpu.models.configs import LLAMA32_1B, LM_TOY
from aiko_services_tpu.parallel import (
    create_mesh, filter_specs, shard_pytree)
from aiko_services_tpu.parallel.attention import (
    attention_reference, flash_attention, flash_query_block,
    paged_attention, paged_attention_reference, paged_attention_takes,
    paged_attention_writes)
from aiko_services_tpu.pipeline import create_pipeline
from aiko_services_tpu.runtime import (
    Process, Registrar, cache_stats, enable_compile_cache)
from aiko_services_tpu.serve import Gateway
from aiko_services_tpu.utils import sexpr

ELEMENTS = "aiko_services_tpu.elements"
# a response includes the first compile of every shape behind it
RESPONSE_TIMEOUT_S = 900.0
PROBE_LENGTHS = (16, 37, 250, 1024)
KERNEL_DTYPE = "bfloat16"
# flash_attention vs the f32 reference, bf16 inputs.  bf16 keeps 8
# mantissa bits (eps 2^-8 ~ 3.9e-3).  The kernel keeps f32 statistics and
# accumulators and rounds once on the way out; the reference is f32 at
# "highest" matmul precision.  2e-2 absolute + 2e-2 relative is ~5 output
# ulps: loose enough for a correct bf16 result, tight enough that a wrong
# mask, a dropped block or an unnormalised row (errors of 0.1 .. 1) fails.
FLASH_ATOL = FLASH_RTOL = 2e-2
# backward: dq/dk/dv sum O(L) bf16-rounded products, so the same relative
# bound is taken against each gradient's own full scale
FLASH_GRAD_TOL = 4e-2
# four devices vs one, bf16 model: two correct programs that block the
# attention and the matmul reductions differently round differently at
# every layer.  Logits may differ by 5e-2 of their full scale (max norm),
# a mean loss / nll by 2e-2 absolute.
MESH_LOGITS_TOL = 5e-2
MESH_LOSS_TOL = 2e-2


class Sizes:
    """Every size the phases read: the published widths, or the toys."""
    def __init__(self, rehearsal: bool):
        self.rehearsal = rehearsal
        if not rehearsal:
            self.audio_seconds = 5.0
            self.asr = {"preset": "whisper_small", "max_frames": 512,
                        "dtype": "bfloat16"}
            self.lm = {"preset": "llama32_1b", "dtype": "bfloat16"}
            self.detector = {"preset": "yolov8n", "dtype": "bfloat16"}
            self.image_size = 640
            self.max_new = 32
            self.serve = {"decode_slots": 8, "kv_block_size": 32,
                          "max_context": 2048}
            # llama32_1b's heads are 64 wide, which Mosaic cannot slice
            # a paged pool by: the served model is Mistral-7B-v0.1's
            # widths (head_dim 128) at 4 of its 32 layers
            self.serve_lm = {
                "vocab_size": 32000, "d_model": 4096, "n_layers": 4,
                "n_heads": 32, "n_kv_heads": 8, "d_ff": 14336,
                "max_seq_len": 4096, "dtype": "bfloat16"}
            self.prompt_lengths = (32, 128)
            self.train_config = replace(LLAMA32_1B, n_layers=8)
            self.train_batch, self.train_seq = 4, 1024
            config = self.train_config
            # LMForward has no preset-with-depth-cut spelling: the same
            # 8-layer cut as explicit widths
            self.long_lm = {
                "vocab_size": config.vocab_size,
                "d_model": config.d_model, "n_layers": config.n_layers,
                "n_heads": config.n_heads,
                "n_kv_heads": config.n_kv_heads, "d_ff": config.d_ff,
                "max_seq_len": config.max_seq_len, "dtype": "bfloat16"}
            self.long_tokens = 8192
            self.chain_size, self.chain_steps = 4096, 256
            self.grouped_probe_length = 2500
        else:
            self.audio_seconds = 1.0
            self.asr = {"d_model": 128, "enc_layers": 1, "dec_layers": 1,
                        "n_heads": 2, "vocab_size": 1024,
                        "max_frames": 192, "dtype": "float32"}
            self.lm = {"vocab_size": 1024, "d_model": 256, "n_layers": 2,
                       "n_heads": 4, "n_kv_heads": 2, "d_ff": 512,
                       "max_seq_len": 2048, "dtype": "float32"}
            self.detector = {"n_classes": 16, "base_channels": 8,
                             "image_size": 64, "dtype": "float32"}
            self.image_size = 64
            self.max_new = 8
            self.serve = {"decode_slots": 8, "kv_block_size": 8,
                          "max_context": 128}
            self.serve_lm = self.lm
            self.prompt_lengths = (8, 32)
            self.train_config = replace(LM_TOY, n_layers=2)
            self.train_batch, self.train_seq = 2, 64
            self.long_lm = self.lm
            self.long_tokens = 256
            self.chain_size, self.chain_steps = 256, 16
            self.grouped_probe_length = 300
        self.max_tokens = 16
        self.rows = 2


class Report:
    """Result lines on stdout; the rehearsal tag rides every one."""
    def __init__(self, tag: str):
        self.tag = tag

    def line(self, text: str) -> None:
        print(f"[smoke] {self.tag}{text}", flush=True)

    def phase(self, name: str, setup_s: float, steady_s: float,
              **facts) -> None:
        detail = " ".join(f"{key}={_fmt(value)}"
                          for key, value in facts.items())
        self.line(f"{name}: ok setup_s={setup_s:.2f} "
                  f"steady_s={steady_s:.3f} {detail}".rstrip())


def _fmt(value) -> str:
    if isinstance(value, float):
        return f"{value:.4g}"
    return str(value).replace(" ", "")


def _local(class_name: str) -> dict:
    return {"local": {"module": ELEMENTS, "class_name": class_name}}


def _require(condition, message: str) -> None:
    if not condition:
        raise AssertionError(message)


def _on_platform(value, platform: str) -> bool:
    return (isinstance(value, jax.Array)
            and all(device.platform == platform
                    for device in value.devices()))


def _finite(value) -> bool:
    return bool(np.all(np.isfinite(np.asarray(value, np.float32))))


def _sharded_bytes(tree, mesh_devices: int) -> tuple[int, int]:
    """(bytes of leaves really spread over the mesh, total bytes)."""
    leaves = jax.tree_util.tree_leaves(tree)
    spread = sum(leaf.nbytes for leaf in leaves
                 if len(leaf.sharding.device_set) == mesh_devices
                 and not leaf.sharding.is_fully_replicated)
    return spread, sum(leaf.nbytes for leaf in leaves)


def _full_scale_error(meshed, single) -> float:
    """Max abs difference as a share of the one-device result's scale."""
    meshed = np.asarray(meshed, np.float32)
    single = np.asarray(single, np.float32)
    return float(np.abs(meshed - single).max()
                 / max(1.0, np.abs(single).max()))


def _stop(processes: list, threads: list) -> None:
    """terminate() only signals the event loop; the loop thread drops
    its references (elements, weights, KV pool) when it exits.  Join it,
    so device memory is back before the next phase allocates."""
    for process in reversed(processes):
        process.terminate()
    for thread in threads:
        thread.join(timeout=30)
    gc.collect()


def _memory_line(report: Report, label: str,
                 spread_bytes: int = 0) -> None:
    """Per-device bytes in use.  With `spread_bytes` (state that should
    be sharded over every device) no device may hold less than half its
    even share: state sitting on device 0 alone fails here."""
    devices = jax.devices()
    stats = [device.memory_stats() for device in devices]
    if any(entry is None for entry in stats):
        return  # the CPU backend keeps no such statistics
    used = [entry["bytes_in_use"] for entry in stats]
    report.line(f"memory {label}: bytes_in_use_mib="
                f"{[round(value / 2**20) for value in used]}")
    _require(min(used) >= spread_bytes / len(devices) / 2,
             f"{label}: a device holds {min(used)} bytes, under half "
             f"its share of {spread_bytes} sharded bytes")


# -- pipeline ---------------------------------------------------------------

def _pipeline_definition(sizes: Sizes, frame_count: int,
                         lm_sharding: dict | None) -> dict:
    """The headline 3-stage graph, as bench.py::_multimodal_setup builds
    it.  micro_batch 2 / frame_window 2 rather than the latency point's
    1 / 1: at micro_batch 1 frames never reach the fused group path, and
    its failure counters would be zero without having run.  Partial
    groups pad to the full group, so this is still one shape per
    stage."""
    micro = 2
    samples = int(sizes.audio_seconds * 16000)
    size = sizes.image_size
    audio_t = f"f32[b,{samples}]"
    image_t = f"f32[b,3,{size},{size}]"
    tokens_t = f"i32[b,{sizes.max_tokens}]"
    generated_t = f"i32[b,{sizes.max_new}]"
    lm = {"name": "lm",
          "input": [{"name": "tokens", "type": tokens_t}],
          "output": [{"name": "generated", "type": generated_t}],
          "parameters": dict(sizes.lm, micro_batch=micro,
                             max_new_tokens=sizes.max_new),
          "deploy": _local("LMGenerate")}
    if lm_sharding is not None:
        lm["sharding"] = lm_sharding
    return {
        "name": "smoke_multimodal",
        "parameters": {"metrics_interval": 60.0},
        "graph": ["(sources (asr (text) (lm (reply))) (detector))"],
        "elements": [
            {"name": "sources",
             "output": [{"name": "audio", "type": audio_t},
                        {"name": "image", "type": image_t},
                        {"name": "t0", "type": "float"}],
             "parameters": {
                 "data_sources": [[440, sizes.audio_seconds]],
                 "image_shape": [3, size, size],
                 "data_batch_size": sizes.rows, "timestamps": True,
                 "on_device": True, "count": frame_count},
             "deploy": _local("MultiModalSource")},
            {"name": "asr",
             "input": [{"name": "audio", "type": audio_t}],
             "output": [{"name": "tokens", "type": tokens_t}],
             "parameters": dict(sizes.asr, micro_batch=micro,
                                max_tokens=sizes.max_tokens),
             "deploy": _local("SpeechToText")},
            {"name": "text",
             "input": [{"name": "tokens", "type": tokens_t}],
             "output": [{"name": "text", "type": "str"}],
             "parameters": {"workers": 4},
             "deploy": _local("TokensToText")},
            lm,
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
             "parameters": dict(sizes.detector, micro_batch=micro),
             "deploy": _local("Detector")},
        ],
    }


def phase_pipeline(sizes: Sizes, report: Report, platform: str,
                   mesh_devices: int = 0) -> None:
    warmup, measure = 2, 4
    lm_sharding = None
    if mesh_devices:
        lm_sharding = {
            "axes": {"data": 1, "fsdp": 1, "seq": 1,
                     "model": mesh_devices},
            "devices": [0, mesh_devices]}
    definition = _pipeline_definition(
        sizes, warmup + measure + 4, lm_sharding)
    setup_start = time.perf_counter()
    process = Process(transport_kind="loopback")
    threads = []
    try:
        pipeline = create_pipeline(process, definition)
        threads.append(process.run(in_thread=True))
        responses: queue.Queue = queue.Queue()
        pipeline.create_stream("smoke", queue_response=responses,
                               grace_time=1800,
                               parameters={"frame_window": 2})
        for _ in range(warmup):
            _, _, outputs = responses.get(timeout=RESPONSE_TIMEOUT_S)
        jax.block_until_ready(outputs["detections"])
        setup_s = time.perf_counter() - setup_start

        steady_start = time.perf_counter()
        frames = []
        for _ in range(measure):
            _, _, outputs = responses.get(timeout=RESPONSE_TIMEOUT_S)
            frames.append(outputs)
        jax.block_until_ready([frame["detections"] for frame in frames])
        steady_s = time.perf_counter() - steady_start
        pipeline.destroy_stream("smoke")

        vocab = pipeline.elements["lm"].config.vocab_size
        for outputs in frames:
            generated = outputs["generated"]
            _require(_on_platform(generated, platform),
                     f"generated is not a {platform} jax.Array: "
                     f"{type(generated)}")
            _require(_on_platform(outputs["tokens"], platform),
                     "asr tokens left the device between elements")
            _require(tuple(generated.shape)
                     == (sizes.rows, sizes.max_new)
                     and str(generated.dtype) == "int32",
                     f"generated is {generated.dtype}"
                     f"{tuple(generated.shape)}")
            ids = np.asarray(generated)
            _require(ids.min() >= 0 and ids.max() < vocab,
                     f"generated ids outside [0, {vocab})")
            detections = outputs["detections"]
            leaves = jax.tree_util.tree_leaves(detections)
            _require(leaves and all(_on_platform(leaf, platform)
                                    for leaf in leaves),
                     "detections missing or not on the device")
            _require(all(_finite(leaf) for leaf in leaves),
                     "non-finite detections")
            _require(isinstance(outputs.get("reply"), (list, str)),
                     "no reply text came back")
        registry = pipeline.telemetry.registry
        fused_groups = registry.counter("pipeline.fused_groups").value
        fused_failures = registry.counter(
            "pipeline.fused_failures").value
        fused_disabled = registry.counter(
            "pipeline.fused_disabled").value
        _require(fused_failures == 0 and fused_disabled == 0
                 and not pipeline._fused_rejected
                 and not pipeline._fused_disabled,
                 f"fused group path gave way: failures={fused_failures} "
                 f"disabled={sorted(pipeline._fused_disabled)} "
                 f"rejected={sorted(pipeline._fused_rejected)}")
        _require(fused_groups > 0, "no fused group ever ran")
        facts = {"frames": measure,
                 "generated": f"i32[{sizes.rows},{sizes.max_new}]",
                 "fused_groups": fused_groups,
                 "chained_groups": registry.counter(
                     "pipeline.chained_groups").value,
                 "fused_failures": fused_failures}
        if mesh_devices:
            facts.update(_check_sharded_lm(
                pipeline.elements["lm"], sizes, mesh_devices, report))
        report.phase("pipeline" + (f"[model={mesh_devices}]"
                                   if mesh_devices else ""),
                     setup_s, steady_s, **facts)
    finally:
        _stop([process], threads)


def _check_sharded_lm(element, sizes: Sizes, mesh_devices: int,
                      report: Report) -> dict:
    """The tensor-parallel LM really is spread over the mesh, and its
    prefill logits agree with the same weights on one device."""
    sharded_bytes, total_bytes = _sharded_bytes(element.state,
                                                mesh_devices)
    # param_specs shards the embedding on "fsdp" only, so under pure
    # tensor parallelism it stays replicated: most bytes, not all
    _require(sharded_bytes > 0.5 * total_bytes,
             f"only {sharded_bytes}/{total_bytes} parameter bytes are "
             f"sharded over the {mesh_devices}-device mesh")
    _memory_line(report, f"pipeline[model={mesh_devices}] live "
                         f"(asr + detector unsharded on device 0)",
                 sharded_bytes)
    tokens = jnp.asarray(np.random.default_rng(5).integers(
        1, element.config.vocab_size, (sizes.rows, sizes.max_tokens)),
        jnp.int32)
    score = jax.jit(partial(forward, config=element.config))
    with jax.set_mesh(element.mesh):
        meshed = score(element.state, tokens=tokens)[:, -1]
    device = jax.devices()[0]
    single = score(jax.device_put(element.state, device),
                   tokens=jax.device_put(tokens, device))[:, -1]
    error = _full_scale_error(meshed, single)
    _require(error <= MESH_LOGITS_TOL,
             f"model={mesh_devices} logits differ from one device by "
             f"{error:.3g} of full scale (> {MESH_LOGITS_TOL})")
    return {"sharded_param_frac": sharded_bytes / total_bytes,
            "logits_vs_one_device": error}


# -- serve ------------------------------------------------------------------

def _serve_definition(sizes: Sizes) -> dict:
    return {
        "name": "smoke_replica",
        "parameters": {"metrics_interval": 60.0},
        "graph": ["(lm)"],
        "elements": [
            {"name": "lm",
             "input": [{"name": "tokens", "type": "any"}],
             "output": [{"name": "generated", "type": "any"}],
             "parameters": dict(sizes.serve_lm, **sizes.serve,
                                continuous=True, stream_tokens=True,
                                max_new_tokens=sizes.max_new),
             "deploy": _local("LMGenerate")},
        ],
    }


def _wave_prompts(sizes: Sizes, rng, vocab: int) -> list:
    """Eight seeded ragged prompts; the first three span every prefill
    bucket between the shortest and the longest prompt, so no wave can
    meet a bucket the one before it did not compile."""
    low, high = sizes.prompt_lengths
    lengths = [int(length) for length in rng.integers(low, high + 1, 8)]
    lengths[0], lengths[1], lengths[2] = low, (low + high) // 2, high
    return [rng.integers(1, vocab, (1, length)).astype(np.int32)
            for length in lengths]


def phase_serve(sizes: Sizes, report: Report, platform: str) -> None:
    setup_start = time.perf_counter()
    processes = [Process(transport_kind="loopback") for _ in range(3)]
    threads = []
    try:
        Registrar(processes[0], search_timeout=0.05)
        replica = create_pipeline(processes[1], _serve_definition(sizes))
        gateway = Gateway(processes[2], policy="max_inflight=16;queue=64",
                          metrics_interval=60.0)
        gateway.attach_replica(replica)
        threads += [process.run(in_thread=True) for process in processes]
        element = replica.elements["lm"]
        element.configure()
        vocab = element.config.vocab_size
        rng = np.random.default_rng(21)
        results: queue.Queue = queue.Queue()

        def wave(index: int) -> float:
            start = time.perf_counter()
            prompts = _wave_prompts(sizes, rng, vocab)
            for row, prompt in enumerate(prompts):
                stream_id = f"w{index}s{row}"
                gateway.submit_stream(stream_id, {},
                                      queue_response=results)
                gateway.submit_frame(stream_id, {"tokens": prompt},
                                     frame_id=0)
            for _ in prompts:
                stream_id, _, outputs, status = results.get(
                    timeout=RESPONSE_TIMEOUT_S)
                _require(status == "ok",
                         f"{stream_id} completed {status!r}: {outputs}")
                tokens = np.asarray(outputs["generated"])
                _require(tokens.shape == (1, sizes.max_new),
                         f"{stream_id} returned {tokens.shape} tokens")
                _require(tokens.min() >= 0 and tokens.max() < vocab,
                         f"{stream_id} ids outside [0, {vocab})")
                gateway.destroy_stream(stream_id)
            return time.perf_counter() - start

        setup_s = time.perf_counter() - setup_start + wave(0)
        compiles_before = element.engine_stats()["compiles"]
        steady_s = wave(1)
        stats = element.engine_stats()
        _require(stats["admitted"] == stats["completed"] == 16,
                 f"engine admitted {stats['admitted']} / completed "
                 f"{stats['completed']} of 16")
        _require(stats["preempted"] == 0,
                 f"engine preempted {stats['preempted']} requests")
        _require(stats["compiles"] == compiles_before,
                 f"second wave compiled: {compiles_before} -> "
                 f"{stats['compiles']}")
        engine = element._engine
        pool = engine.pool
        _require(all(_on_platform(leaf, platform)
                     for leaf in pool.values()),
                 "the paged KV pool is not on the device")
        # the step the waves ran, compiled again from the engine's own
        # arguments (a cache hit): the paged-attention kernel is in it,
        # and it holds no second pool while it runs
        idle = np.zeros((engine.slots_n,), np.int32)
        step = paged_decode_step.lower(
            engine.params, engine.config, pool, engine.tables,
            engine.positions, engine.last_tokens, idle, idle).compile()
        mosaic = _has_mosaic_call(step)
        temp_bytes = step.memory_analysis().temp_size_in_bytes
        pool_bytes = sum(leaf.nbytes for leaf in pool.values())
        _require(mosaic == (platform == "tpu"),
                 f"decode step Mosaic custom call present={mosaic} on "
                 f"{platform}")
        _require(platform != "tpu" or temp_bytes < pool_bytes,
                 f"decode step needs {temp_bytes} temporary bytes, the "
                 f"pool is {pool_bytes}: the step copies the pool")
        report.phase("serve", setup_s, steady_s, completed=16,
                     tokens_each=sizes.max_new,
                     admitted=stats["admitted"],
                     preempted=stats["preempted"],
                     compiles=stats["compiles"],
                     compiles_second_wave=(stats["compiles"]
                                           - compiles_before),
                     kv_blocks=stats["blocks"],
                     live_blocks=stats["live_blocks"],
                     table_blocks=stats["table_blocks"],
                     decode_mosaic_custom_call=mosaic,
                     decode_temp_bytes=temp_bytes, pool_bytes=pool_bytes)
    finally:
        _stop(processes, threads)


# -- train + kernels --------------------------------------------------------

def _has_mosaic_call(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def phase_train(sizes: Sizes, report: Report, platform: str):
    """Three optimizer steps on one device.  Returns what the four-device
    run compares itself with: the tokens and the first step's loss."""
    config = sizes.train_config
    setup_start = time.perf_counter()
    params = init_params(config, jax.random.PRNGKey(0))
    optimizer = optax.adamw(1e-3)
    opt_state = optimizer.init(params)
    tokens = jnp.asarray(np.random.default_rng(3).integers(
        1, config.vocab_size, (sizes.train_batch, sizes.train_seq + 1)),
        jnp.int32)
    # the only path that compiles _flash_dq_kernel / _flash_dkv_kernel
    train_step = make_train_step(config, optimizer).lower(
        params, opt_state, tokens).compile()
    mosaic = _has_mosaic_call(train_step)
    params, opt_state, loss = train_step(params, opt_state, tokens)
    losses = [float(loss)]
    setup_s = time.perf_counter() - setup_start
    steady_start = time.perf_counter()
    for _ in range(2):
        params, opt_state, loss = train_step(params, opt_state, tokens)
        losses.append(float(loss))
    steady_s = time.perf_counter() - steady_start
    _require(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    _require(losses[2] < losses[0],
             f"loss did not fall over three steps: {losses}")
    _require(_on_platform(loss, platform), "loss is not on the device")
    _require(mosaic == (platform == "tpu"),
             f"train step Mosaic custom call present={mosaic} on "
             f"{platform}")
    report.phase("train", setup_s, steady_s,
                 layers=config.n_layers, d_model=config.d_model,
                 batch=sizes.train_batch, seq=sizes.train_seq,
                 losses=[round(value, 4) for value in losses],
                 mosaic_custom_call=mosaic)
    return tokens, losses[0]


def _reference_attention(q, k, v, causal: bool):
    """float32 at "highest" precision; grouped K/V repeated as repeat_kv
    lays them out."""
    repeats = q.shape[1] // k.shape[1]
    with jax.default_matmul_precision("highest"):
        return attention_reference(
            q.astype(jnp.float32),
            jnp.repeat(k.astype(jnp.float32), repeats, axis=1),
            jnp.repeat(v.astype(jnp.float32), repeats, axis=1),
            causal=causal)


def _require_close(got, expected, label: str) -> float:
    """`got` finite and within the flash tolerances of `expected`; the
    largest absolute error."""
    _require(np.all(np.isfinite(got)), f"{label}: non-finite output")
    error = float(np.abs(got - expected).max())
    excess = np.abs(got - expected) - FLASH_RTOL * np.abs(expected)
    _require(float(excess.max()) <= FLASH_ATOL,
             f"{label}: off the reference by {error:.3g}")
    return error


def _flash_probe(q, k, v, causal: bool, label: str) -> float:
    """Forward against the reference; the largest absolute error."""
    expected = np.asarray(_reference_attention(q, k, v, causal))
    got = np.asarray(flash_attention(q, k, v, causal=causal), np.float32)
    return _require_close(got, expected, label)


def _flash_live_probe(q, k, v, lives, label: str) -> float:
    """The causal forward told a whole prefill's live length (traced:
    one program for every length): rows below it against the
    reference, every row from the first dead query block on exactly
    zero; the largest absolute error."""
    expected = np.asarray(_reference_attention(q, k, v, True))
    block = flash_query_block(q.shape[1], k.shape[1], q.shape[3],
                              q.shape[2], live=True)
    attend = jax.jit(partial(flash_attention, causal=True))
    worst = 0.0
    for live in lives:
        got = np.asarray(attend(q, k, v, live=jnp.int32(live)), np.float32)
        _require(not got[:, :, -(-live // block) * block:].any(),
                 f"{label} live={live}: a dead query block is not zeros")
        worst = max(worst, _require_close(
            got[:, :, :live], expected[:, :, :live],
            f"{label} live={live}"))
    _require(attend._cache_size() == 1, f"{label}: a program a length")
    return worst


def _flash_grad_probe(q, k, v, cotangent, label: str) -> float:
    """Causal dq/dk/dv against the reference's; the largest error as a
    share of each gradient's full scale."""
    def scalar(attend, q, k, v):
        return jnp.sum(attend(q, k, v).astype(jnp.float32)
                       * cotangent.astype(jnp.float32))

    grads = jax.grad(partial(scalar, partial(flash_attention,
                                             causal=True)),
                     argnums=(0, 1, 2))(q, k, v)
    expected = jax.grad(partial(scalar, partial(_reference_attention,
                                                causal=True)),
                        argnums=(0, 1, 2))(q, k, v)
    worst = 0.0
    for name, got, want in zip(("dq", "dk", "dv"), grads, expected):
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        _require(got.shape == want.shape and np.all(np.isfinite(got)),
                 f"{label} {name}: non-finite or misshapen gradient")
        error = float(np.abs(got - want).max()
                      / max(np.abs(want).max(), 1e-6))
        worst = max(worst, error)
        _require(error <= FLASH_GRAD_TOL,
                 f"{label} {name} off the reference by {error:.3g} of "
                 f"full scale")
    return worst


def phase_kernels(sizes: Sizes, report: Report, platform: str) -> None:
    setup_start = time.perf_counter()
    dtype = jnp.dtype(KERNEL_DTYPE)
    worst = {}
    for length in PROBE_LENGTHS:
        keys = jax.random.split(jax.random.PRNGKey(length), 3)
        q, k, v = (jax.random.normal(key, (2, 4, length, 64), dtype)
                   for key in keys)
        for causal in (False, True):
            worst[(length, causal)] = _flash_probe(
                q, k, v, causal,
                f"flash_attention L={length} causal={causal}")

    # backward kernels, at the one probe length that is neither short
    # nor a multiple of the block
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    q, k, v, cotangent = (jax.random.normal(key, (2, 4, 250, 64), dtype)
                          for key in keys)
    grad_error = _flash_grad_probe(q, k, v, cotangent, "flash backward")

    # the served prefill's form: four query heads a KV head read
    # grouped, head_dim 128, long enough for several of the long-L
    # tiles and a padded tail; forward and backward
    length = sizes.grouped_probe_length
    keys = jax.random.split(jax.random.PRNGKey(13), 4)
    q, cotangent = (jax.random.normal(key, (1, 8, length, 128), dtype)
                    for key in keys[:2])
    k, v = (jax.random.normal(key, (1, 2, length, 128), dtype)
            for key in keys[2:])
    grouped_error = _flash_probe(q, k, v, True,
                                 f"grouped flash_attention L={length}")
    grad_error = max(grad_error, _flash_grad_probe(
        q, k, v, cotangent, f"grouped flash backward L={length}"))
    # and told a live length, as a whole prefill tells it: in the first
    # query block, in a middle one, the whole length
    live_error = _flash_live_probe(
        q, k, v, (1, length // 2 + 3, length),
        f"grouped flash_attention L={length} told a live length")

    # the paged-attention kernel of the served decode step against its
    # einsum oracle: 8 slots on a pool of 32-position blocks, cursors on
    # both sides of a block, of the short chunk and of a chunk, one
    # slot idle on the trash block
    positions = jnp.asarray([0, 31, 32, 127, 128, 511, 512, 2000],
                            jnp.int32)
    slots, max_blocks, kv_heads, repeats = positions.shape[0], 64, 8, 4
    keys = jax.random.split(jax.random.PRNGKey(11), 3)
    pool_shape = (2, slots * max_blocks + 1, kv_heads, 32, 128)
    pool_k, pool_v = (jax.random.normal(key, pool_shape, dtype)
                      for key in keys[:2])
    tables = 1 + jnp.arange(slots * max_blocks, dtype=jnp.int32).reshape(
        slots, max_blocks).at[0].set(0)
    paged_error = 0.0
    for window in (1, 5):
        q = jax.random.normal(
            keys[2], (slots, kv_heads * repeats, window, 128), dtype)
        _require(paged_attention_takes(q.shape[1], window, 128, dtype),
                 f"paged_attention refuses window {window}")
        # at window 1 the kernel writes the step's new rows itself:
        # against the oracle over a pool with the same rows put in,
        # which the written leaves must then equal bit for bit
        write, pools = None, (pool_k, pool_v)
        if paged_attention_writes(window):
            rows = tuple(jax.random.normal(key, (slots, kv_heads, 1, 128),
                                           dtype)
                         for key in jax.random.split(keys[2]))
            blocks = tables[jnp.arange(slots), positions // 32]
            write = (rows, blocks[:, None], (positions % 32)[:, None])
            pools = tuple(pool.at[1, blocks, :, positions % 32].set(
                new[:, :, 0]) for pool, new in zip(pools, rows))
        got, *written = paged_attention(
            q, jnp.copy(pool_k), jnp.copy(pool_v), jnp.int32(1), tables,
            positions, write=write)
        _require(all(bool(jnp.array_equal(leaf, pool))
                     for leaf, pool in zip(written, pools)),
                 f"paged_attention W={window}: the pool it hands back is "
                 f"not the pool with the told rows written")
        got, want = (np.asarray(out, np.float32) for out in (
            got, paged_attention_reference(q, *pools, jnp.int32(1), tables,
                                           positions)))
        _require(np.all(np.isfinite(got)),
                 f"paged_attention W={window}: non-finite output")
        paged_error = max(paged_error, float(np.abs(got - want).max()))
        _require(paged_error <= FLASH_ATOL,
                 f"paged_attention W={window}: off the einsum by "
                 f"{paged_error:.3g}")

    # the prefill the LM elements run: forward() with no cache
    config = sizes.train_config
    params = init_params(config, jax.random.PRNGKey(0))
    tokens = jnp.asarray(np.random.default_rng(4).integers(
        1, config.vocab_size, (1, sizes.train_seq)), jnp.int32)
    prefill = jax.jit(partial(forward, config=config)).lower(
        params, tokens=tokens).compile()
    mosaic = _has_mosaic_call(prefill)
    _require(mosaic == (platform == "tpu"),
             f"prefill Mosaic custom call present={mosaic} on {platform}")
    setup_s = time.perf_counter() - setup_start
    steady_start = time.perf_counter()
    logits = prefill(params, tokens=tokens)
    _require(_finite(logits[:, -1]), "non-finite prefill logits")
    steady_s = time.perf_counter() - steady_start
    report.phase(
        "kernels", setup_s, steady_s, head_dim=64,
        lengths=list(PROBE_LENGTHS), dtype=KERNEL_DTYPE,
        max_abs_err=max(worst.values()), tol=FLASH_ATOL,
        grouped_length=length, grouped_max_abs_err=grouped_error,
        live_max_abs_err=live_error,
        grad_err=grad_error, grad_tol=FLASH_GRAD_TOL,
        paged_max_abs_err=paged_error,
        prefill_mosaic_custom_call=mosaic)


# -- link -------------------------------------------------------------------

def phase_link(sizes: Sizes, report: Report, platform: str) -> None:
    """Findings every later "call-floor-bound" verdict hangs on.  They
    describe this host and this chip's link; they are not speeds of the
    system."""
    setup_start = time.perf_counter()
    size, steps = sizes.chain_size, sizes.chain_steps

    @jax.jit
    def chain(x):
        def body(_, carry):
            return jnp.tanh(carry @ x) * 0.5
        return jax.lax.fori_loop(0, steps, body, x)

    x = jax.random.normal(jax.random.PRNGKey(1), (size, size),
                          jnp.bfloat16) * 0.01
    np.asarray(chain(x)[0, 0])                      # compile + settle
    trivial = jax.jit(lambda value: value + 1)
    one = jnp.zeros((8, 128), jnp.float32)
    trivial(one).block_until_ready()
    np.asarray(x[0, 0])                             # compile the slice
    setup_s = time.perf_counter() - setup_start
    steady_start = time.perf_counter()

    # 1. does block_until_ready wait for the chain, or only dispatch it?
    start = time.perf_counter()
    y = chain(x)
    dispatched = time.perf_counter()
    y.block_until_ready()
    blocked = time.perf_counter()
    np.asarray(y[0, 0])
    read = time.perf_counter()
    start2 = time.perf_counter()
    np.asarray(chain(x)[0, 0])
    readback_only = time.perf_counter() - start2
    block_s, after_s = blocked - dispatched, read - blocked
    # if it returned at dispatch the whole chain would still be ahead of
    # the dependent read that follows it
    waits = block_s > 0.5 * readback_only and after_s < 0.5 * readback_only

    # 2. one small device->host readback of a value that is ready
    small = [jnp.full((8,), index, jnp.float32) for index in range(32)]
    jax.block_until_ready(small)
    readbacks = []
    for value in small:
        begin = time.perf_counter()
        np.asarray(value)
        readbacks.append(time.perf_counter() - begin)

    # 3. one trivial jitted call, dispatch to completion
    calls = []
    for _ in range(64):
        begin = time.perf_counter()
        trivial(one).block_until_ready()
        calls.append(time.perf_counter() - begin)
    steady_s = time.perf_counter() - steady_start
    report.phase(
        "link", setup_s, steady_s,
        block_until_ready_waits=waits,
        chain_dispatch_ms=(dispatched - start) * 1e3,
        chain_block_ms=block_s * 1e3,
        readback_after_block_ms=after_s * 1e3,
        chain_by_readback_only_ms=readback_only * 1e3,
        small_readback_ms_median=statistics.median(readbacks) * 1e3,
        small_readback_ms_min=min(readbacks) * 1e3,
        trivial_call_ms_median=statistics.median(calls) * 1e3,
        trivial_call_ms_min=min(calls) * 1e3)


# -- four devices -----------------------------------------------------------

def _longcontext_definition(sizes: Sizes, mesh_devices: int) -> dict:
    """examples/pipeline_longcontext.json's shape: TokenSource ->
    LMForward with sequence_parallel over a seq axis (ring attention,
    the Pallas kernel as the inner hop inside shard_map)."""
    length = sizes.long_tokens
    widths = dict(sizes.long_lm)
    widths["max_seq_len"] = max(widths["max_seq_len"], length)
    vocab = widths["vocab_size"]
    return {
        "name": "smoke_longcontext",
        "parameters": {"metrics_interval": 60.0},
        "graph": ["(tokens (lm))"],
        "elements": [
            {"name": "tokens",
             "output": [{"name": "tokens", "type": f"i32[1,{length}]"}],
             "parameters": {"data_sources": [[1, length]], "count": 2,
                            "vocab_size": vocab, "seed": 9},
             "deploy": _local("TokenSource")},
            {"name": "lm",
             "input": [{"name": "tokens", "type": f"i32[1,{length}]"}],
             "output": [{"name": "logits",
                         "type": f"f32[1,{length},{vocab}]"},
                        {"name": "nll", "type": "f32[1]"}],
             "parameters": dict(widths, sequence_parallel=True),
             "sharding": {"axes": {"seq": mesh_devices},
                          "devices": [0, mesh_devices]},
             "deploy": _local("LMForward")},
        ],
    }


def phase_longcontext(sizes: Sizes, report: Report, platform: str,
                      mesh_devices: int) -> None:
    setup_start = time.perf_counter()
    process = Process(transport_kind="loopback")
    threads = []
    try:
        pipeline = create_pipeline(
            process, _longcontext_definition(sizes, mesh_devices))
        threads.append(process.run(in_thread=True))
        responses: queue.Queue = queue.Queue()
        pipeline.create_stream("long", queue_response=responses,
                               grace_time=1800,
                               parameters={"frame_window": 1})
        _, _, outputs = responses.get(timeout=RESPONSE_TIMEOUT_S)
        jax.block_until_ready(outputs["nll"])
        setup_s = time.perf_counter() - setup_start
        steady_start = time.perf_counter()
        _, _, outputs = responses.get(timeout=RESPONSE_TIMEOUT_S)
        jax.block_until_ready(outputs["nll"])
        steady_s = time.perf_counter() - steady_start
        pipeline.destroy_stream("long")

        logits, nll = outputs["logits"], outputs["nll"]
        _require(len(logits.sharding.device_set) == mesh_devices,
                 f"logits live on {len(logits.sharding.device_set)} "
                 f"devices, not {mesh_devices}")
        _require(_finite(nll), f"non-finite nll {nll}")
        element = pipeline.elements["lm"]
        _memory_line(report, f"longcontext[seq={mesh_devices}] live "
                             f"(params replicated, activations and "
                             f"logits seq-sharded)", logits.nbytes)
        device = jax.devices()[0]
        tokens = jax.device_put(np.asarray(outputs["tokens"]), device)
        tail = 256
        single = jax.jit(
            lambda params, tokens: forward(
                params, replace(element.config, sequence_parallel=False),
                tokens)[:, -tail:])(
            jax.device_put(element.state, device), tokens)
        error = _full_scale_error(logits[:, -tail:], single)
        _require(error <= MESH_LOGITS_TOL,
                 f"seq={mesh_devices} logits differ from one device by "
                 f"{error:.3g} of full scale (> {MESH_LOGITS_TOL})")
        report.phase(f"longcontext[seq={mesh_devices}]", setup_s,
                     steady_s, tokens=sizes.long_tokens,
                     nll=float(np.asarray(nll)[0]),
                     logits_vs_one_device=error, tol=MESH_LOGITS_TOL)
    finally:
        _stop([process], threads)


def phase_train_sharded(sizes: Sizes, report: Report, platform: str,
                        tokens, single_loss: float) -> None:
    """make_train_step(sharded=True) on a real data=2 x model=2 mesh,
    from the same seed and batch as the one-device train phase."""
    config = sizes.train_config
    setup_start = time.perf_counter()
    mesh = create_mesh({"data": 2, "model": 2},
                       devices=jax.devices()[:4])
    with jax.set_mesh(mesh):
        params = shard_pytree(
            init_params(config, jax.random.PRNGKey(0)), mesh,
            filter_specs(param_specs(config), mesh))
        optimizer = optax.adamw(1e-3)
        opt_state = optimizer.init(params)
        tokens = jax.device_put(
            tokens, NamedSharding(mesh, PartitionSpec("data", None)))
        train_step = make_train_step(config, optimizer, sharded=True)
        params, opt_state, loss = train_step(params, opt_state, tokens)
        first = float(loss)
        setup_s = time.perf_counter() - setup_start
        steady_start = time.perf_counter()
        params, opt_state, loss = train_step(params, opt_state, tokens)
        second = float(loss)
        steady_s = time.perf_counter() - steady_start
    _require(np.isfinite(first) and np.isfinite(second),
             f"non-finite sharded loss: {first}, {second}")
    _require(abs(first - single_loss) <= MESH_LOSS_TOL,
             f"sharded step-1 loss {first} vs one device {single_loss}")
    spread, total = _sharded_bytes(params, 4)
    _require(spread > 0.5 * total,
             f"only {spread}/{total} parameter bytes are sharded")
    _memory_line(report, "train[data=2,model=2] live", spread)
    report.phase("train[data=2,model=2]", setup_s, steady_s,
                 losses=[round(first, 4), round(second, 4)],
                 loss_vs_one_device=abs(first - single_loss),
                 tol=MESH_LOSS_TOL, sharded_param_frac=spread / total)


# -- entry ------------------------------------------------------------------

def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument(
        "--rehearsal", action="store_true",
        help="toy sizes on whatever platform jax finds; checks this "
             "script, never the chip, and cannot end in ok=true")
    args = parser.parse_args(argv)

    device = jax.devices()[0]
    platform, count = device.platform, len(jax.devices())
    cache_dir = enable_compile_cache()
    report = Report(f"rehearsal platform={platform} "
                    if args.rehearsal else "")
    report.line(f"jax={jax.__version__} platform={platform} "
                f"device_kind={device.device_kind!r} count={count}")
    report.line(f"compile_cache_dir={cache_dir} "
                f"sexpr_codec={sexpr.CODEC}")
    if platform != "tpu" and not args.rehearsal:
        print(f"chip_smoke: jax found platform {platform!r}, not a TPU; "
              f"nothing was checked (--rehearsal runs toy sizes here)",
              file=sys.stderr)
        return 1

    sizes = Sizes(args.rehearsal)
    started = time.perf_counter()
    mesh_devices = 4 if count >= 4 else 0
    # train goes first either way: its step reserves 8.8 GB in one
    # piece, which a 16 GB chip only has while nothing has fragmented it
    if mesh_devices:
        # four devices, one process: what only a mesh can show
        report.line("four-device mode: serve, kernels and link are "
                    "unsharded and belong to the one-chip run; not run")
        tokens, single_loss = phase_train(sizes, report, platform)
        gc.collect()
        phase_train_sharded(sizes, report, platform, tokens, single_loss)
        del tokens
        gc.collect()
        phase_pipeline(sizes, report, platform, mesh_devices)
        phase_longcontext(sizes, report, platform, mesh_devices)
    else:
        for phase in (phase_train, phase_kernels, phase_pipeline,
                      phase_serve, phase_link):
            phase(sizes, report, platform)
            gc.collect()
            _memory_line(report, "after " + phase.__name__[len("phase_"):])
    stats = cache_stats()
    report.line(f"compile cache: dir={stats['dir']} hits={stats['hits']} "
                f"misses={stats['misses']} requests={stats['requests']}")
    report.line(f"all phases ok in {time.perf_counter() - started:.1f} s")
    ok = not args.rehearsal
    report.line("summary " + json.dumps({
        "rehearsal": args.rehearsal, "ok": ok, "claim": None}))
    # the last line is the whole contract with the driver: exactly the
    # keys "ok" and "device", nothing else (a rehearsal is never ok)
    print(json.dumps({
        "ok": ok,
        "device": {"platform": platform, "kind": device.device_kind,
                   "count": count}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
