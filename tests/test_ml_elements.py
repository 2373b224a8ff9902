# ML + media element tests: each model family behind a pipeline element,
# then the flagship 3-stage multi-modal pipeline (speech -> LLM, vision ->
# detections in one graph) -- tiny configs on CPU.

import queue

import numpy as np
import pytest

from aiko_services_tpu.pipeline import create_pipeline
from aiko_services_tpu.runtime import Process
from aiko_services_tpu.transport import reset_brokers

ELEMENTS = "aiko_services_tpu.elements"

TINY_ASR = {"d_model": 32, "enc_layers": 1, "dec_layers": 1, "n_heads": 2,
            "vocab_size": 300, "max_frames": 64, "dtype": "float32",
            "max_tokens": 4}
TINY_LM = {"vocab_size": 300, "d_model": 32, "n_layers": 1, "n_heads": 2,
           "n_kv_heads": 1, "d_ff": 64, "dtype": "float32"}
TINY_DET = {"n_classes": 4, "base_channels": 4, "image_size": 32,
            "max_detections": 4, "dtype": "float32"}


@pytest.fixture(autouse=True)
def clean_brokers():
    reset_brokers()
    yield
    reset_brokers()


def local(class_name):
    return {"local": {"module": ELEMENTS, "class_name": class_name}}


def run_frames(definition, count=1, timeout=120):
    process = Process(transport_kind="loopback")
    pipeline = create_pipeline(process, definition)
    process.run(in_thread=True)
    responses = queue.Queue()
    pipeline.create_stream("s", queue_response=responses, grace_time=300)
    results = [responses.get(timeout=timeout) for _ in range(count)]
    process.terminate()
    return results


def test_speech_to_text_element():
    definition = {
        "name": "asr_pipe",
        "graph": ["(tone (framing (asr (text))))"],
        "elements": [
            {"name": "tone", "output": [{"name": "audio"}],
             "parameters": {"data_sources": [[440, 0.2], [880, 0.2]]},
             "deploy": local("ToneSource")},
            {"name": "framing", "input": [{"name": "audio"}],
             "output": [{"name": "audio"}],
             "parameters": {"window_count": 2},
             "deploy": local("AudioFraming")},
            {"name": "asr", "input": [{"name": "audio"}],
             "output": [{"name": "tokens"}],
             "parameters": TINY_ASR, "deploy": local("SpeechToText")},
            {"name": "text", "input": [{"name": "tokens"}],
             "output": [{"name": "text"}],
             "deploy": local("TokensToText")},
        ],
    }
    results = run_frames(definition, count=2)
    for _, _, outputs in results:
        assert isinstance(outputs["text"], list)
        assert np.asarray(outputs["tokens"]).shape == (1, 4)


def test_detector_element_and_overlay():
    definition = {
        "name": "detect_pipe",
        "graph": ["(camera (detector (overlay)))"],
        "elements": [
            {"name": "camera", "output": [{"name": "image"}],
             "parameters": {"data_sources": [[3, 32, 32]]},
             "deploy": local("ImageSource")},
            {"name": "detector", "input": [{"name": "image"}],
             "output": [{"name": "detections"}],
             "parameters": TINY_DET, "deploy": local("Detector")},
            {"name": "overlay",
             "input": [{"name": "image"}, {"name": "detections"}],
             "output": [{"name": "image"}, {"name": "overlay"}],
             "deploy": local("ImageOverlay")},
        ],
    }
    [(_, _, outputs)] = run_frames(definition)
    assert outputs["image"].dtype == np.uint8
    assert set(outputs["overlay"]) == {"objects", "rectangles"}
    for obj in outputs["overlay"]["objects"]:
        assert obj["confidence"] > 0


def test_three_stage_multimodal_pipeline():
    """The flagship shape (BASELINE.md config 5 analogue): speech -> ASR
    tokens -> LLM scoring while vision -> detector runs in the same graph,
    everything device-resident between elements."""
    definition = {
        "name": "flagship",
        "graph": ["(sources (asr (lm)) (detector))"],
        "elements": [
            {"name": "sources",
             "output": [{"name": "audio"}, {"name": "image"}],
             "parameters": {"data_sources": [[440, 0.2]]},
             "deploy": local("MultiModalSource")},
            {"name": "asr", "input": [{"name": "audio"}],
             "output": [{"name": "tokens"}],
             "parameters": TINY_ASR, "deploy": local("SpeechToText")},
            {"name": "lm", "input": [{"name": "tokens"}],
             "output": [{"name": "logits"}, {"name": "nll"}],
             "parameters": TINY_LM, "deploy": local("LMForward")},
            {"name": "detector", "input": [{"name": "image"}],
             "output": [{"name": "detections"}],
             "parameters": TINY_DET, "deploy": local("Detector")},
        ],
    }
    [(_, frame, outputs)] = run_frames(definition)
    assert np.isfinite(np.asarray(outputs["nll"])).all()
    assert "detections" in outputs
    assert {"time_asr", "time_lm", "time_detector"} <= set(frame.metrics)


def test_image_read_write_roundtrip(tmp_path):
    from PIL import Image
    source_path = tmp_path / "in.png"
    target_path = tmp_path / "out_{}.png"
    Image.fromarray(
        (np.random.default_rng(0).random((16, 16, 3)) * 255)
        .astype(np.uint8)).save(source_path)
    definition = {
        "name": "image_pipe",
        "graph": ["(read (resize (write)))"],
        "elements": [
            {"name": "read", "output": [{"name": "image"}],
             "parameters": {"data_sources": [str(source_path)]},
             "deploy": local("ImageReadFile")},
            {"name": "resize", "input": [{"name": "image"}],
             "output": [{"name": "image"}],
             "parameters": {"resize_height": 8, "resize_width": 8},
             "deploy": local("ImageResize")},
            {"name": "write", "input": [{"name": "image"}],
             "output": [{"name": "image"}],
             "parameters": {"data_targets": [str(target_path)]},
             "deploy": local("ImageWriteFile")},
        ],
    }
    run_frames(definition)
    with Image.open(tmp_path / "out_0.png") as result:
        assert result.size == (8, 8)


def test_audio_wav_roundtrip(tmp_path):
    target = tmp_path / "tone.wav"
    definition = {
        "name": "audio_pipe",
        "graph": ["(tone (write))"],
        "elements": [
            {"name": "tone", "output": [{"name": "audio"}],
             "parameters": {"data_sources": [[440, 0.1]]},
             "deploy": local("ToneSource")},
            {"name": "write", "input": [{"name": "audio"}],
             "output": [{"name": "audio"}],
             "parameters": {"data_targets": [str(target)]},
             "deploy": local("AudioWriteFile")},
        ],
    }
    run_frames(definition)
    definition2 = {
        "name": "audio_read",
        "graph": ["(read (sample))"],
        "elements": [
            {"name": "read", "output": [{"name": "audio"}],
             "parameters": {"data_sources": [str(target)]},
             "deploy": local("AudioReadFile")},
            {"name": "sample", "input": [{"name": "audio"}],
             "output": [{"name": "audio"}],
             "deploy": local("AudioSample")},
        ],
    }
    [(_, _, outputs)] = run_frames(definition2)
    audio = np.asarray(outputs["audio"])
    assert audio.shape == (1600,)
    assert 0.5 < np.abs(audio).max() <= 1.0


def test_tokens_to_text_out_of_range_ids():
    # ADVICE round 1: ids >= 259 must be skipped, not crash bytes()
    from aiko_services_tpu.elements.ml import TokensToText
    element = TokensToText.__new__(TokensToText)
    element.get_parameter = lambda name, default=None, stream=None: default
    tokens = np.array([[0, 1, 2, 3 + ord("h"), 3 + ord("i"), 300, 1023]])
    outputs = element.process_async(None, tokens=tokens)
    assert outputs["text"] == ["hi"]


def test_text_to_tokens_to_lm_with_tokenizer_streaming():
    # real-text path: TextToTokens (BPE asset) -> LMGenerate with streamed
    # token chunks published to /out, decoded text in the response
    definition = {
        "name": "chat_pipe",
        "graph": ["(prompt (lm))"],
        "elements": [
            {"name": "prompt", "input": [{"name": "text"}],
             "output": [{"name": "tokens"}],
             "deploy": local("TextToTokens")},
            {"name": "lm", "input": [{"name": "tokens"}],
             "output": [{"name": "generated"}, {"name": "text"}],
             "parameters": {**TINY_LM, "vocab_size": 4096,
                            "tokenizer": "default", "max_new_tokens": 6,
                            "stream_tokens": True, "stream_chunk": 2},
             "deploy": local("LMGenerate")},
        ],
    }
    process = Process(transport_kind="loopback")
    pipeline = create_pipeline(process, definition)
    streamed = []
    process.add_message_handler(
        lambda topic, payload: streamed.append(payload),
        f"{pipeline.elements['lm'].topic_path}/out")
    process.run(in_thread=True)
    responses = queue.Queue()
    pipeline.create_stream("s", queue_response=responses, grace_time=300)
    pipeline.process_frame({"stream_id": "s"}, {"text": "hello pipeline"})
    _, _, outputs = responses.get(timeout=120)
    assert np.asarray(outputs["generated"]).shape == (1, 6)
    assert isinstance(outputs["text"], list)
    # 6 tokens in chunks of 2 -> 3 streamed publishes
    from helpers import wait_for
    wait_for(lambda: len([s for s in streamed if "tokens" in s]) >= 3)
    process.terminate()


def test_lm_generate_weights_parameter(tmp_path):
    # seeded random params saved to safetensors load back identically
    import jax
    from aiko_services_tpu.models import (
        TransformerConfig, generate, init_params, save_pytree)
    config = TransformerConfig(
        vocab_size=300, d_model=32, n_layers=1, n_heads=2, n_kv_heads=1,
        d_ff=64, max_seq_len=2048, dtype="float32")
    params = init_params(config, jax.random.PRNGKey(0))
    path = tmp_path / "lm.safetensors"
    save_pytree(path, params)

    definition = {
        "name": "wpipe",
        "graph": ["(lm)"],
        "elements": [
            {"name": "lm", "input": [{"name": "tokens"}],
             "output": [{"name": "generated"}],
             "parameters": {**TINY_LM, "weights": str(path),
                            "max_new_tokens": 4},
             "deploy": local("LMGenerate")},
        ],
    }
    prompt = np.array([[7, 8, 9]], np.int32)
    [(_, _, outputs)] = run_frames_with_data(definition, {"tokens": prompt})
    expected, _ = generate(params, config, prompt, 4)
    np.testing.assert_array_equal(np.asarray(outputs["generated"]),
                                  np.asarray(expected))


def run_frames_with_data(definition, frame_data, timeout=120):
    process = Process(transport_kind="loopback")
    pipeline = create_pipeline(process, definition)
    process.run(in_thread=True)
    responses = queue.Queue()
    pipeline.create_stream("s", queue_response=responses, grace_time=300)
    pipeline.process_frame({"stream_id": "s"}, frame_data)
    results = [responses.get(timeout=timeout)]
    process.terminate()
    return results


def test_lm_forward_sequence_parallel_on_element_mesh():
    """Long-context is first-class at the ELEMENT layer: an LMForward
    with sequence_parallel=true and a seq axis in its sharding block runs
    ring attention over the element's mesh and matches the dense
    element's logits."""
    import queue as queue_module
    from aiko_services_tpu.runtime import Process
    from aiko_services_tpu.pipeline import create_pipeline

    def definition(name, extra_params, sharding=None):
        element = {
            "name": "lm", "input": [{"name": "tokens"}],
            "output": [{"name": "logits"}, {"name": "nll"}],
            "parameters": dict(
                {"vocab_size": 128, "d_model": 32, "n_layers": 2,
                 "n_heads": 4, "n_kv_heads": 2, "d_ff": 64,
                 "max_seq_len": 64, "dtype": "float32"}, **extra_params),
            "deploy": {"local": {"module": "aiko_services_tpu.elements",
                                 "class_name": "LMForward"}}}
        if sharding:
            element["sharding"] = sharding
        return {
            "name": name, "graph": ["(tokens (lm))"],
            "elements": [
                {"name": "tokens", "output": [{"name": "tokens"}],
                 "parameters": {"data_sources": [[2, 32]],
                                "vocab_size": 128},
                 "deploy": {"local": {
                     "module": "aiko_services_tpu.elements",
                     "class_name": "TokenSource"}}},
                element,
            ]}

    def run(pipeline_definition):
        process = Process(transport_kind="loopback")
        pipeline = create_pipeline(process, pipeline_definition)
        process.run(in_thread=True)
        responses = queue_module.Queue()
        pipeline.create_stream("s1", queue_response=responses)
        _, _, outputs = responses.get(timeout=60)
        logits = np.asarray(outputs["logits"])
        process.terminate()
        return logits

    dense = run(definition("lm_dense", {}))
    assert np.isfinite(dense).all()  # guard: NaN==NaN parity is vacuous
    ringed = run(definition(
        "lm_sp", {"sequence_parallel": True},
        sharding={"axes": {"data": 2, "seq": 2, "model": 2},
                  "inputs": {"tokens": ["data", None]}}))
    np.testing.assert_allclose(ringed, dense, atol=2e-3, rtol=2e-3)


def test_lm_generate_sequence_parallel_matches_dense():
    """LMGenerate with sequence_parallel: ring prefill + seq-sharded KV
    decode on the element's mesh must reproduce dense greedy output.
    (Prompt lengths must divide the seq axis -- power-of-two buckets
    do.)"""
    import queue as queue_module
    from aiko_services_tpu.runtime import Process
    from aiko_services_tpu.pipeline import create_pipeline

    def definition(name, extra_params, sharding=None):
        element = {
            "name": "lm", "input": [{"name": "tokens"}],
            "output": [{"name": "generated"}],
            "parameters": dict(
                {"vocab_size": 128, "d_model": 32, "n_layers": 2,
                 "n_heads": 4, "n_kv_heads": 2, "d_ff": 64,
                 "max_seq_len": 64, "dtype": "float32",
                 "max_new_tokens": 8}, **extra_params),
            "deploy": {"local": {"module": "aiko_services_tpu.elements",
                                 "class_name": "LMGenerate"}}}
        if sharding:
            element["sharding"] = sharding
        return {
            "name": name, "graph": ["(tokens (lm))"],
            "elements": [
                {"name": "tokens", "output": [{"name": "tokens"}],
                 "parameters": {"data_sources": [[2, 16]],
                                "vocab_size": 128},
                 "deploy": {"local": {
                     "module": "aiko_services_tpu.elements",
                     "class_name": "TokenSource"}}},
                element,
            ]}

    def run(pipeline_definition):
        process = Process(transport_kind="loopback")
        pipeline = create_pipeline(process, pipeline_definition)
        process.run(in_thread=True)
        responses = queue_module.Queue()
        pipeline.create_stream("s1", queue_response=responses)
        _, _, outputs = responses.get(timeout=60)
        generated = np.asarray(outputs["generated"])
        process.terminate()
        return generated

    dense = run(definition("gen_dense", {}))
    sp = run(definition(
        "gen_sp", {"sequence_parallel": True},
        sharding={"axes": {"data": 2, "seq": 2, "model": 2},
                  "inputs": {"tokens": ["data", None]}}))
    np.testing.assert_array_equal(sp, dense)


def test_lm_generate_sp_text_pad_parity():
    """Text prompts whose width does NOT divide the seq axis: the
    sequence-parallel path left-pads to a seq-multiple with the TOKENIZER
    pad id, so output must equal the dense model run on the identically
    padded prompt (round-2 advisor: id-0 seq padding diverged from the
    batch padding's pad id)."""
    import jax
    from aiko_services_tpu.models import (
        BPETokenizer, TransformerConfig, generate, init_params)
    from aiko_services_tpu.runtime import Process
    from aiko_services_tpu.pipeline import create_pipeline

    tokenizer = BPETokenizer.default()
    prompts = ["pad parity", "pp"]
    encoded = [tokenizer.encode(p, bos=True) for p in prompts]
    width = max(len(ids) for ids in encoded)
    seq_size = 2
    assert width % seq_size != 0, (
        f"pick prompts with max width not divisible by {seq_size} "
        f"(got {width})")

    params_def = {
        "vocab_size": tokenizer.vocab_size, "d_model": 32, "n_layers": 2,
        "n_heads": 4, "n_kv_heads": 2, "d_ff": 64, "max_seq_len": 64,
        "dtype": "float32", "max_new_tokens": 6, "tokenizer": "default",
        "sequence_parallel": True}
    definition = {
        "name": "sp_pad", "graph": ["(lm)"],
        "elements": [
            {"name": "lm", "input": [{"name": "text"}],
             "output": [{"name": "generated"}],
             "parameters": params_def,
             "sharding": {"axes": {"data": 2, "seq": 2, "model": 2}},
             "deploy": {"local": {"module": "aiko_services_tpu.elements",
                                  "class_name": "LMGenerate"}}},
        ]}
    [(_, _, outputs)] = run_frames_with_data(
        definition, {"text": prompts}, timeout=180)
    sp_out = np.asarray(outputs["generated"])

    config = TransformerConfig(
        vocab_size=tokenizer.vocab_size, d_model=32, n_layers=2,
        n_heads=4, n_kv_heads=2, d_ff=64, max_seq_len=64, dtype="float32")
    params = init_params(config, jax.random.PRNGKey(0))
    pad = tokenizer.pad_id or 0
    target = ((width + seq_size - 1) // seq_size) * seq_size
    padded = np.full((len(encoded), target), pad, np.int32)
    for row, ids in enumerate(encoded):
        padded[row, target - len(ids):] = ids
    expected, _ = generate(params, config, padded, 6)
    np.testing.assert_array_equal(sp_out, np.asarray(expected))

    # batch 1 (the common serving case) on a data-sharded mesh: the
    # element pads the batch to the data-axis multiple and slices it
    # back (round-3 verify drive caught this crashing in _sp_cache)
    [(_, _, single)] = run_frames_with_data(
        definition, {"text": prompts[0]}, timeout=180)
    np.testing.assert_array_equal(
        np.asarray(single["generated"]), np.asarray(expected)[:1])


# -- LLM chat semantics + detections side-channel ----------------------------
# (reference elements_llm.py:137-210: S-expression-constrained system
# prompt; {ns}/detections subscription with a 1 s freshness window)

def _chat_lm_pipeline(process, window=30.0):
    # default window is wide: first-frame setup (tokenizer + params +
    # compile) can exceed the reference's 1 s freshness rule, which the
    # dedicated staleness test covers with a warmed model
    definition = {
        "name": "chat_lm",
        "graph": ["(lm)"],
        "elements": [
            {"name": "lm", "input": [{"name": "text"}],
             "output": [{"name": "generated"}, {"name": "text"},
                        {"name": "prompt"}],
             "parameters": {
                 "vocab_size": 300, "d_model": 32, "n_layers": 1,
                 "n_heads": 2, "n_kv_heads": 2, "d_ff": 64,
                 "max_seq_len": 256, "dtype": "float32",
                 "tokenizer": "default", "max_new_tokens": 2,
                 "detections_subscribe": True,
                 "detections_window": window,
                 "system_prompt": "You control a robot. Reply with "
                                  "(action ...) commands only.",
             },
             "deploy": local("LMGenerate")},
        ],
    }
    return create_pipeline(process, definition)


def test_lm_prompt_includes_fresh_detections_and_system_prompt():
    process = Process(transport_kind="loopback")
    pipeline = _chat_lm_pipeline(process)
    process.run(in_thread=True)
    responses = queue.Queue()
    stream = pipeline.create_stream("s", queue_response=responses)

    # cold prompt: system prompt present, no vision context yet
    pipeline.create_frame(stream, {"text": "wave hello"})
    _, _, outputs = responses.get(timeout=60)
    prompt = outputs["prompt"][0]
    assert "You control a robot" in prompt
    assert "Visible objects" not in prompt
    assert "wave hello" in prompt

    # a detections publish lands on the side-channel -> injected
    from aiko_services_tpu.transport import get_broker
    process.publish(f"{process.namespace}/detections",
                    "(detections (person dog))")
    get_broker().drain()
    pipeline.create_frame(stream, {"text": "what do you see?"})
    _, _, outputs = responses.get(timeout=60)
    prompt = outputs["prompt"][0]
    assert "Visible objects: person, dog." in prompt
    assert "what do you see?" in prompt
    process.terminate()


def test_lm_stale_detections_excluded():
    import time
    process = Process(transport_kind="loopback")
    pipeline = _chat_lm_pipeline(process, window=0.2)
    process.run(in_thread=True)
    responses = queue.Queue()
    stream = pipeline.create_stream("s", queue_response=responses)
    # prime the model compile FIRST so the staleness clock isn't racing
    # the (slow) first-frame jit
    pipeline.create_frame(stream, {"text": "warmup"})
    responses.get(timeout=60)

    from aiko_services_tpu.transport import get_broker
    process.publish(f"{process.namespace}/detections",
                    "(detections (cat))")
    get_broker().drain()
    time.sleep(0.4)  # let the 0.2 s freshness window lapse
    pipeline.create_frame(stream, {"text": "now?"})
    _, _, outputs = responses.get(timeout=60)
    assert "Visible objects" not in outputs["prompt"][0]
    process.terminate()


def test_detections_publish_element_closes_the_loop():
    """DetectionsPublish -> side-channel -> LMGenerate context."""
    process = Process(transport_kind="loopback")
    lm_pipeline = _chat_lm_pipeline(process)
    publish_definition = {
        "name": "vision_pub",
        "graph": ["(publish)"],
        "elements": [
            {"name": "publish", "input": [{"name": "detections"}],
             "output": [{"name": "detections"}],
             "parameters": {"class_names": ["car", "bike", "person"]},
             "deploy": local("DetectionsPublish")},
        ],
    }
    vision_pipeline = create_pipeline(process, publish_definition)
    process.run(in_thread=True)

    detections = {
        "boxes": np.zeros((1, 4, 4), np.float32),
        "scores": np.array([[0.9, 0.8, 0.0, 0.0]], np.float32),
        "classes": np.array([[2, 0, 0, 0]], np.int32),
        "valid": np.array([[True, True, False, False]]),
    }
    vision_responses = queue.Queue()
    vision_stream = vision_pipeline.create_stream(
        "v", queue_response=vision_responses)
    vision_pipeline.create_frame(vision_stream, {"detections": detections})
    vision_responses.get(timeout=30)  # publish completed

    from aiko_services_tpu.transport import get_broker
    get_broker().drain()
    responses = queue.Queue()
    stream = lm_pipeline.create_stream("s", queue_response=responses)
    lm_pipeline.create_frame(stream, {"text": "report"})
    _, _, outputs = responses.get(timeout=60)
    assert "Visible objects: person, car." in outputs["prompt"][0]
    process.terminate()


def test_meshed_lm_defaults_to_megatron_param_sharding():
    """A meshed LM element without an explicit sharding.state must NOT
    replicate its params (an 8B replicated over a pod blows HBM): the
    megatron param_specs tree is the default."""
    from jax.sharding import PartitionSpec as P
    definition = {
        "name": "sharded_lm",
        "graph": ["(lm)"],
        "elements": [
            {"name": "lm", "input": [{"name": "tokens"}],
             "output": [{"name": "logits"}, {"name": "nll"}],
             "parameters": {"vocab_size": 128, "d_model": 32,
                            "n_layers": 2, "n_heads": 4, "n_kv_heads": 2,
                            "d_ff": 64, "max_seq_len": 64,
                            "dtype": "float32"},
             "sharding": {"axes": {"data": 2, "fsdp": 2, "seq": 1,
                                   "model": 2}},
             "deploy": local("LMForward")},
        ],
    }
    process = Process(transport_kind="loopback")
    pipeline = create_pipeline(process, definition)
    process.run(in_thread=True)
    responses = queue.Queue()
    stream = pipeline.create_stream("s", queue_response=responses)
    pipeline.create_frame(
        stream, {"tokens": np.ones((2, 8), np.int32)})
    _, _, outputs = responses.get(timeout=60)
    assert np.asarray(outputs["logits"]).shape == (2, 8, 128)
    element = pipeline.elements["lm"]
    wq = element.state["layers"]["wq"]["w"]
    assert not wq.sharding.is_fully_replicated
    assert wq.sharding.spec == P(None, "model", "fsdp"), wq.sharding.spec
    process.terminate()


def test_restored_meshed_lm_keeps_megatron_sharding(tmp_path):
    """Checkpoint restore installs state WITHOUT running setup(): the
    configure() hook must still default the megatron state spec, or a
    restored 8B would re-shard fully replicated and blow per-chip HBM."""
    from jax.sharding import PartitionSpec as P
    from aiko_services_tpu.utils.checkpoint import Checkpointer

    def definition(name):
        return {
            "name": name,
            "graph": ["(lm)"],
            "elements": [
                {"name": "lm", "input": [{"name": "tokens"}],
                 "output": [{"name": "logits"}, {"name": "nll"}],
                 "parameters": {"vocab_size": 128, "d_model": 32,
                                "n_layers": 2, "n_heads": 4,
                                "n_kv_heads": 2, "d_ff": 64,
                                "max_seq_len": 64, "dtype": "float32"},
                 "sharding": {"axes": {"data": 2, "fsdp": 2, "seq": 1,
                                       "model": 2}},
                 "deploy": local("LMForward")},
            ],
        }

    process = Process(transport_kind="loopback")
    pipeline = create_pipeline(process, definition("ckpt_lm"))
    process.run(in_thread=True)
    responses = queue.Queue()
    stream = pipeline.create_stream("s", queue_response=responses)
    pipeline.create_frame(stream, {"tokens": np.ones((2, 8), np.int32)})
    responses.get(timeout=60)
    checkpointer = Checkpointer(tmp_path / "ckpt")
    pipeline.checkpoint(checkpointer, step=1)
    process.terminate()

    restore_process = Process(transport_kind="loopback")
    restored = create_pipeline(restore_process, definition("ckpt_lm"))
    restore_process.run(in_thread=True)
    restored.restore_checkpoint(checkpointer, step=1)
    element = restored.elements["lm"]
    wq = element.state["layers"]["wq"]["w"]
    assert wq.sharding.spec == P(None, "model", "fsdp"), wq.sharding.spec
    # and the restored element still serves frames
    rq = queue.Queue()
    restored_stream = (restored.streams.get("s")
                       or restored.create_stream("s2", queue_response=rq))
    if restored_stream.queue_response is None:
        restored_stream.queue_response = rq
    restored.create_frame(restored_stream,
                          {"tokens": np.ones((2, 8), np.int32)})
    _, _, outputs = rq.get(timeout=60)
    assert np.asarray(outputs["logits"]).shape == (2, 8, 128)
    restore_process.terminate()


def test_multimodal_batch_matches_per_item_synth():
    """read_batch's fused synthesis must match the per-item on-device
    synthesizers: images bit-exact (same fold_in), audio to f32
    rounding (XLA fuses the broadcast sin differently)."""
    import jax.numpy as jnp
    import numpy as np
    from aiko_services_tpu.elements.audio_io import (
        SAMPLE_RATE, synthesize_tone_on_device)
    from aiko_services_tpu.elements.compute import _multimodal_batch
    from aiko_services_tpu.elements.image_io import (
        synthesize_image_on_device)
    seconds, shape = 0.25, (3, 8, 8)
    audio, image = _multimodal_batch(
        jnp.asarray([440.0, 523.25], jnp.float32),
        jnp.asarray([7, 8], jnp.uint32),
        int(seconds * SAMPLE_RATE), SAMPLE_RATE, shape)
    for row, (freq, seed) in enumerate([(440.0, 7), (523.25, 8)]):
        one_audio = synthesize_tone_on_device(freq, seconds)
        one_image = synthesize_image_on_device(shape, seed)
        assert np.allclose(np.asarray(audio[row]), np.asarray(one_audio),
                           atol=1e-3)
        assert np.array_equal(np.asarray(image[row]),
                              np.asarray(one_image))


def test_lm_generate_kv_int8_parameter_matches_dense():
    """kv_dtype="int8" at the ELEMENT level: same greedy tokens as the
    full-precision cache (the serving memory knob, VERDICT r5 item 4)."""
    prompt = np.array([[7, 8, 9, 10]], np.int32)
    outs = {}
    for label, extra in (("fp", {}), ("q", {"kv_dtype": "int8"})):
        definition = {
            "name": f"kv_{label}",
            "graph": ["(lm)"],
            "elements": [
                {"name": "lm", "input": [{"name": "tokens"}],
                 "output": [{"name": "generated"}],
                 "parameters": {**TINY_LM, "max_new_tokens": 6, **extra},
                 "deploy": local("LMGenerate")},
            ],
        }
        [(_, _, outputs)] = run_frames_with_data(
            definition, {"tokens": prompt})
        outs[label] = np.asarray(outputs["generated"])
    np.testing.assert_array_equal(outs["fp"], outs["q"])


def test_lm_generate_weight_dtype_int8():
    """weight_dtype="int8" at the ELEMENT level: serving decode with
    8-bit weights produces a valid generation (numerics pinned at the
    model level in TestWeightOnlyInt8)."""
    prompt = np.array([[7, 8, 9, 10]], np.int32)
    definition = {
        "name": "w8", "graph": ["(lm)"],
        "elements": [
            {"name": "lm", "input": [{"name": "tokens"}],
             "output": [{"name": "generated"}],
             "parameters": {**TINY_LM, "max_new_tokens": 6,
                            "weight_dtype": "int8"},
             "deploy": local("LMGenerate")},
        ],
    }
    [(_, _, outputs)] = run_frames_with_data(definition, {"tokens": prompt})
    generated = np.asarray(outputs["generated"])
    assert generated.shape == (1, 6)
    assert ((generated >= 0) & (generated < TINY_LM["vocab_size"])).all()


# -- fused whole-group execution on the model stages -------------------------

def _inject_frames(definition, frames, timeout=120):
    """Queue `frames` before the event loop starts (all park in the
    micro-batch scheduler), return ({frame_id: outputs}, pipeline)."""
    process = Process(transport_kind="loopback")
    pipeline = create_pipeline(process, definition)
    responses = queue.Queue()
    stream = pipeline.create_stream("s", queue_response=responses,
                                    grace_time=300)
    for frame_data in frames:
        pipeline.create_frame(stream, frame_data)
    process.run(in_thread=True)
    got = {}
    for _ in range(len(frames)):
        _, frame, outputs = responses.get(timeout=timeout)
        got[frame.frame_id] = outputs
    process.terminate()
    return got, pipeline


def _tree_equal(left, right):
    if isinstance(left, dict):
        assert set(left) == set(right)
        for key in left:
            _tree_equal(left[key], right[key])
        return
    left = np.asarray(left)
    right = np.asarray(right)
    assert left.dtype == right.dtype and left.shape == right.shape
    np.testing.assert_array_equal(left, right)


def test_detector_fused_group_matches_chained():
    """Detector's group kernel (concat+detect+split as ONE program) must
    reproduce the chained micro-batch path's detections exactly."""

    def build(fused):
        return {
            "name": "fused_det",
            "graph": ["(detector)"],
            "elements": [
                {"name": "detector", "input": [{"name": "image"}],
                 "output": [{"name": "detections"}],
                 "parameters": {**TINY_DET, "micro_batch": 4,
                                "micro_batch_fused": fused},
                 "deploy": local("Detector")},
            ],
        }

    rng = np.random.default_rng(0)
    frames = [{"image": rng.uniform(
        0, 1, (1, 3, 32, 32)).astype(np.float32)} for _ in range(3)]
    fused_got, fused_pipe = _inject_frames(build(True), frames)
    chained_got, chained_pipe = _inject_frames(build(False), frames)
    assert fused_pipe._fused_programs and not chained_pipe._fused_programs
    assert set(fused_got) == set(chained_got)
    for frame_id in fused_got:
        _tree_equal(fused_got[frame_id]["detections"],
                    chained_got[frame_id]["detections"])


def test_speech_to_text_fused_group_matches_chained():
    def build(fused):
        return {
            "name": "fused_asr",
            "graph": ["(asr)"],
            "elements": [
                {"name": "asr", "input": [{"name": "audio"}],
                 "output": [{"name": "tokens"}],
                 "parameters": {**TINY_ASR, "micro_batch": 4,
                                "micro_batch_fused": fused},
                 "deploy": local("SpeechToText")},
            ],
        }

    rng = np.random.default_rng(1)
    frames = [{"audio": rng.standard_normal(
        (1, 1600)).astype(np.float32)} for _ in range(3)]
    fused_got, fused_pipe = _inject_frames(build(True), frames)
    chained_got, _ = _inject_frames(build(False), frames)
    assert fused_pipe._fused_programs
    for frame_id in fused_got:
        _tree_equal(fused_got[frame_id]["tokens"],
                    chained_got[frame_id]["tokens"])


def test_lm_generate_fused_group_matches_chained():
    def build(fused):
        return {
            "name": "fused_lm",
            "graph": ["(lm)"],
            "elements": [
                {"name": "lm", "input": [{"name": "tokens"}],
                 "output": [{"name": "generated"}],
                 "parameters": {**TINY_LM, "micro_batch": 4,
                                "micro_batch_fused": fused,
                                "max_new_tokens": 4},
                 "deploy": local("LMGenerate")},
            ],
        }

    rng = np.random.default_rng(2)
    frames = [{"tokens": rng.integers(
        1, 300, (1, 6), dtype=np.int32)} for _ in range(3)]
    fused_got, fused_pipe = _inject_frames(build(True), frames)
    chained_got, _ = _inject_frames(build(False), frames)
    assert fused_pipe._fused_programs
    for frame_id in fused_got:
        _tree_equal(fused_got[frame_id]["generated"],
                    chained_got[frame_id]["generated"])


def test_lm_generate_group_kernel_gated_on_host_work():
    """Configurations whose process_frame does per-frame host work
    (tokenizer decode, token streaming) must fall back to the chained
    path: group_kernel returns None."""
    definition = {
        "name": "gated_lm",
        "graph": ["(lm)"],
        "elements": [
            {"name": "lm", "input": [{"name": "text"}],
             "output": [{"name": "generated"}, {"name": "text"}],
             "parameters": {**TINY_LM, "tokenizer": "default",
                            "max_new_tokens": 2},
             "deploy": local("LMGenerate")},
        ],
    }
    process = Process(transport_kind="loopback")
    pipeline = create_pipeline(process, definition)
    process.run(in_thread=True)
    responses = queue.Queue()
    stream = pipeline.create_stream("s", queue_response=responses,
                                    grace_time=300)
    assert pipeline.elements["lm"].group_kernel(stream) is None
    process.terminate()
