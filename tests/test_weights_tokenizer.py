# Weight ingestion + tokenizer: safetensors round-trip (incl. bf16), HF
# Llama naming -> framework pytree parity, BPE train/encode/decode
# round-trips, HF tokenizer.json loading, and the streamed decode path.

import json

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from aiko_services_tpu.models import (
    BPETokenizer, TransformerConfig, forward, generate, generate_stream,
    init_params, load_llama_params, load_pytree, read_safetensors,
    save_pytree, train_bpe, write_safetensors)
from aiko_services_tpu.models.configs import (
    LLAMA3_8B, WHISPER_SMALL, YOLOV8N_SHAPE, transformer_flops_per_token)


# -- safetensors container ---------------------------------------------------

def test_safetensors_roundtrip(tmp_path):
    tensors = {
        "a": np.arange(12, dtype=np.float32).reshape(3, 4),
        "b.c": np.ones((2, 2), dtype=ml_dtypes.bfloat16),
        "ints": np.array([1, 2, 3], dtype=np.int64),
    }
    path = tmp_path / "t.safetensors"
    write_safetensors(path, tensors, metadata={"format": "pt"})
    loaded = read_safetensors(path)
    assert set(loaded) == set(tensors)
    for name in tensors:
        assert loaded[name].dtype == tensors[name].dtype
        np.testing.assert_array_equal(
            np.asarray(loaded[name], np.float64),
            np.asarray(tensors[name], np.float64))


def test_pytree_roundtrip(tmp_path):
    tree = {"layer": {"w": np.ones((2, 3), np.float32),
                      "b": np.zeros((3,), np.float32)},
            "top": np.full((4,), 2.0, np.float32)}
    path = tmp_path / "p.safetensors"
    save_pytree(path, tree)
    back = load_pytree(path)
    assert back["layer"]["w"].shape == (2, 3)
    assert back["top"][0] == 2.0
    cast = load_pytree(path, dtype="bfloat16")
    assert cast["layer"]["w"].dtype == ml_dtypes.bfloat16


def _tiny_config():
    return TransformerConfig(
        vocab_size=64, d_model=16, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=32, max_seq_len=32, dtype="float32")


def _write_hf_llama(path, config, seed=0, lm_head=False):
    """Fake HF-named checkpoint with HF (out, in) weight layout."""
    rng = np.random.default_rng(seed)
    hd = config.head_dim
    tensors = {
        "model.embed_tokens.weight": rng.standard_normal(
            (config.vocab_size, config.d_model)).astype(np.float32),
        "model.norm.weight": np.ones((config.d_model,), np.float32),
    }
    if lm_head:
        tensors["lm_head.weight"] = rng.standard_normal(
            (config.vocab_size, config.d_model)).astype(np.float32)
    for layer in range(config.n_layers):
        prefix = f"model.layers.{layer}."
        tensors.update({
            prefix + "input_layernorm.weight":
                np.ones((config.d_model,), np.float32),
            prefix + "post_attention_layernorm.weight":
                np.ones((config.d_model,), np.float32),
            prefix + "self_attn.q_proj.weight": rng.standard_normal(
                (config.n_heads * hd, config.d_model)).astype(np.float32),
            prefix + "self_attn.k_proj.weight": rng.standard_normal(
                (config.n_kv_heads * hd,
                 config.d_model)).astype(np.float32),
            prefix + "self_attn.v_proj.weight": rng.standard_normal(
                (config.n_kv_heads * hd,
                 config.d_model)).astype(np.float32),
            prefix + "self_attn.o_proj.weight": rng.standard_normal(
                (config.d_model, config.n_heads * hd)).astype(np.float32),
            prefix + "mlp.gate_proj.weight": rng.standard_normal(
                (config.d_ff, config.d_model)).astype(np.float32),
            prefix + "mlp.up_proj.weight": rng.standard_normal(
                (config.d_ff, config.d_model)).astype(np.float32),
            prefix + "mlp.down_proj.weight": rng.standard_normal(
                (config.d_model, config.d_ff)).astype(np.float32),
        })
    write_safetensors(path, tensors)
    return tensors


def test_load_llama_params_shapes_and_orientation(tmp_path):
    config = _tiny_config()
    path = tmp_path / "model.safetensors"
    tensors = _write_hf_llama(path, config)
    params = load_llama_params(path, config)
    hd = config.head_dim
    assert params["embed"]["w"].shape == (config.vocab_size, config.d_model)
    assert params["layers"]["wq"]["w"].shape == (
        config.n_layers, config.n_heads * hd, config.d_model)
    assert params["layers"]["wk"]["w"].shape == (
        config.n_layers, config.n_kv_heads * hd, config.d_model)
    assert params["layers"]["wv"]["w"].shape == (
        config.n_layers, config.d_model, config.n_kv_heads * hd)
    # loaded params run end-to-end
    logits = forward(params, config, jnp.ones((1, 4), jnp.int32))
    assert logits.shape == (1, 4, config.vocab_size)
    assert bool(jnp.isfinite(logits).all())


@pytest.mark.parametrize("ours,theirs,transposed", [
    ("wq", "self_attn.q_proj", False), ("wk", "self_attn.k_proj", False),
    ("wv", "self_attn.v_proj", True), ("wo", "self_attn.o_proj", True),
    ("w_gate", "mlp.gate_proj", True), ("w_up", "mlp.up_proj", True),
    ("w_down", "mlp.down_proj", True)])
def test_load_llama_leaf_orientation(tmp_path, ours, theirs, transposed):
    """wq and wk are held as the checkpoint holds q_proj and k_proj,
    (out, in), the layout the decode step's matmul reads; every other
    projection is its tensor's transpose, (in, out)."""
    config = _tiny_config()
    path = tmp_path / "model.safetensors"
    tensors = _write_hf_llama(path, config)
    leaf = np.asarray(load_llama_params(path, config)["layers"][ours]["w"])
    for layer in range(config.n_layers):
        tensor = tensors[f"model.layers.{layer}.{theirs}.weight"]
        np.testing.assert_array_equal(
            leaf[layer], tensor.T if transposed else tensor)


def test_load_llama_untied_head_changes_logits(tmp_path):
    config = _tiny_config()
    tied = tmp_path / "tied.safetensors"
    untied = tmp_path / "untied.safetensors"
    _write_hf_llama(tied, config, seed=1)
    _write_hf_llama(untied, config, seed=1, lm_head=True)
    params_tied = load_llama_params(tied, config)
    params_untied = load_llama_params(untied, config)
    assert "lm_head" in params_untied and "lm_head" not in params_tied
    tokens = jnp.ones((1, 4), jnp.int32)
    a = forward(params_tied, config, tokens)
    b = forward(params_untied, config, tokens)
    assert not np.allclose(np.asarray(a), np.asarray(b))


def test_load_llama_sharded_on_mesh(tmp_path):
    from aiko_services_tpu.models import param_specs
    from aiko_services_tpu.parallel.mesh import create_mesh
    config = _tiny_config()
    path = tmp_path / "model.safetensors"
    _write_hf_llama(path, config)
    mesh = create_mesh({"data": 2, "fsdp": 1, "seq": 2, "model": 2})
    params = load_llama_params(path, config, mesh=mesh,
                               specs=param_specs(config))
    wq = params["layers"]["wq"]["w"]
    assert len(wq.sharding.device_set) == 8
    with jax.set_mesh(mesh):
        logits = forward(params, config, jnp.ones((2, 4), jnp.int32))
    assert bool(jnp.isfinite(logits).all())


def test_missing_tensor_raises(tmp_path):
    config = _tiny_config()
    path = tmp_path / "broken.safetensors"
    tensors = _write_hf_llama(path, config)
    del tensors["model.layers.1.mlp.up_proj.weight"]
    write_safetensors(path, tensors)
    with pytest.raises(KeyError, match="mlp.up_proj"):
        load_llama_params(path, config)


# -- tokenizer ---------------------------------------------------------------

def test_bpe_train_roundtrip():
    corpus = ["the pipeline processes frames of tokens",
              "frames flow through the pipeline elements"] * 10
    tokenizer = train_bpe(corpus, vocab_size=300)
    for text in ["the pipeline", "unseen wørds 123!", "  spaced  out  "]:
        assert tokenizer.decode(tokenizer.encode(text)) == text
    ids = tokenizer.encode("the pipeline", bos=True, eos=True)
    assert ids[0] == tokenizer.bos_id and ids[-1] == tokenizer.eos_id


def test_default_asset_loads_and_compresses():
    tokenizer = BPETokenizer.default()
    text = "The pipeline processes frames through elements."
    ids = tokenizer.encode(text)
    assert tokenizer.decode(ids) == text
    assert len(ids) < len(text) / 2  # real merges, not bytes


def test_hf_tokenizer_json_format(tmp_path):
    base = train_bpe(["hello world hello there"], vocab_size=280)
    hf = {
        "model": {
            "vocab": base.vocab,
            "merges": [f"{a} {b}" for a, b in base.merges],
        },
        "added_tokens": [
            {"id": 0, "content": "<|begin_of_text|>"},
            {"id": 1, "content": "<|end_of_text|>"},
        ],
    }
    path = tmp_path / "tokenizer.json"
    path.write_text(json.dumps(hf))
    tokenizer = BPETokenizer.from_file(path)
    assert tokenizer.bos_id == 0 and tokenizer.eos_id == 1
    assert tokenizer.decode(tokenizer.encode("hello world")) == (
        "hello world")


# -- presets + analytics -----------------------------------------------------

def test_reference_scale_configs():
    # Llama-3-8B ~8.0B params; Whisper-small ~240M (analytic counts)
    def lm_params(c):
        hd = c.head_dim
        per_layer = (c.d_model * hd * (c.n_heads * 2 + c.n_kv_heads * 2)
                     + 3 * c.d_model * c.d_ff + 2 * c.d_model)
        return (c.vocab_size * c.d_model * 2   # embed + untied head
                + c.n_layers * per_layer + c.d_model)
    total = lm_params(LLAMA3_8B)
    assert 7.5e9 < total < 8.6e9
    assert WHISPER_SMALL.d_model == 768 and WHISPER_SMALL.enc_layers == 12
    assert YOLOV8N_SHAPE.image_size == 640
    assert YOLOV8N_SHAPE.n_classes == 80
    flops = transformer_flops_per_token(LLAMA3_8B)
    assert 1.3e10 < flops < 2.0e10  # ~2*7B matmul params


# -- streamed decode ---------------------------------------------------------

def test_generate_stream_matches_generate():
    config = _tiny_config()
    params = init_params(config, jax.random.PRNGKey(0))
    prompt = jnp.array([[5, 6, 7]], jnp.int32)
    full, _ = generate(params, config, prompt, max_new_tokens=9)
    chunks = list(generate_stream(params, config, prompt,
                                  max_new_tokens=9, chunk=4))
    # first token streams immediately after prefill (TTFT), then chunks
    assert [offset for offset, _ in chunks] == [0, 1, 5]
    assert [block.shape[1] for _, block in chunks] == [1, 4, 4]
    streamed = np.concatenate([block for _, block in chunks], axis=1)
    np.testing.assert_array_equal(np.asarray(full), streamed)


# -- whisper checkpoint ingestion --------------------------------------------

def _tiny_asr_config():
    from aiko_services_tpu.models import AsrConfig
    return AsrConfig(
        n_mels=8, d_model=16, enc_layers=2, dec_layers=2, n_heads=4,
        vocab_size=64, max_frames=16, max_text_len=12, dtype="float32")


def _write_hf_whisper(path, config, seed=0):
    """Fake HF openai/whisper-* checkpoint: HF (out, in) linear layout,
    biases on q/v/out + fc + norms, NO bias on k_proj, 30 s-sized
    positional tables (longer than the config windows)."""
    rng = np.random.default_rng(seed)
    d = config.d_model

    def t(*shape):
        return rng.standard_normal(shape).astype(np.float32) * 0.05

    tensors = {
        "model.encoder.conv1.weight": t(d, config.n_mels, 3),
        "model.encoder.conv1.bias": t(d),
        "model.encoder.conv2.weight": t(d, d, 3),
        "model.encoder.conv2.bias": t(d),
        "model.encoder.embed_positions.weight": t(config.max_frames + 8, d),
        "model.encoder.layer_norm.weight": t(d),
        "model.encoder.layer_norm.bias": t(d),
        "model.decoder.embed_tokens.weight": t(config.vocab_size, d),
        "model.decoder.embed_positions.weight": t(
            config.max_text_len + 8, d),
        "model.decoder.layer_norm.weight": t(d),
        "model.decoder.layer_norm.bias": t(d),
    }

    def attention(prefix):
        tensors[prefix + "q_proj.weight"] = t(d, d)
        tensors[prefix + "q_proj.bias"] = t(d)
        tensors[prefix + "k_proj.weight"] = t(d, d)  # no bias (HF whisper)
        tensors[prefix + "v_proj.weight"] = t(d, d)
        tensors[prefix + "v_proj.bias"] = t(d)
        tensors[prefix + "out_proj.weight"] = t(d, d)
        tensors[prefix + "out_proj.bias"] = t(d)

    for layer in range(config.enc_layers):
        prefix = f"model.encoder.layers.{layer}."
        attention(prefix + "self_attn.")
        tensors[prefix + "self_attn_layer_norm.weight"] = t(d)
        tensors[prefix + "self_attn_layer_norm.bias"] = t(d)
        tensors[prefix + "fc1.weight"] = t(4 * d, d)
        tensors[prefix + "fc1.bias"] = t(4 * d)
        tensors[prefix + "fc2.weight"] = t(d, 4 * d)
        tensors[prefix + "fc2.bias"] = t(d)
        tensors[prefix + "final_layer_norm.weight"] = t(d)
        tensors[prefix + "final_layer_norm.bias"] = t(d)
    for layer in range(config.dec_layers):
        prefix = f"model.decoder.layers.{layer}."
        attention(prefix + "self_attn.")
        attention(prefix + "encoder_attn.")
        tensors[prefix + "self_attn_layer_norm.weight"] = t(d)
        tensors[prefix + "self_attn_layer_norm.bias"] = t(d)
        tensors[prefix + "encoder_attn_layer_norm.weight"] = t(d)
        tensors[prefix + "encoder_attn_layer_norm.bias"] = t(d)
        tensors[prefix + "fc1.weight"] = t(4 * d, d)
        tensors[prefix + "fc1.bias"] = t(4 * d)
        tensors[prefix + "fc2.weight"] = t(d, 4 * d)
        tensors[prefix + "fc2.bias"] = t(d)
        tensors[prefix + "final_layer_norm.weight"] = t(d)
        tensors[prefix + "final_layer_norm.bias"] = t(d)
    write_safetensors(path, tensors)
    return tensors


def test_load_whisper_params_shapes_orientation_and_forward(tmp_path):
    from aiko_services_tpu.models import asr_forward, load_whisper_params
    config = _tiny_asr_config()
    path = tmp_path / "whisper.safetensors"
    tensors = _write_hf_whisper(path, config)
    params = load_whisper_params(path, config)
    # conv layout passes through untransposed (d, in, k)
    assert params["conv1"]["w"].shape == (config.d_model, config.n_mels, 3)
    # linear orientation: ours is HF transposed, bias carried
    np.testing.assert_allclose(
        np.asarray(params["enc_layers"]["attn"]["wq"]["w"][0]),
        tensors["model.encoder.layers.0.self_attn.q_proj.weight"].T,
        rtol=1e-6)
    assert "b" in params["enc_layers"]["attn"]["wq"]
    assert "b" not in params["enc_layers"]["attn"]["wk"]  # HF k_proj
    assert "bias" in params["dec_layers"]["cross_norm"]
    # positional tables sliced to the serving windows
    assert params["enc_positions"].shape == (config.max_frames,
                                             config.d_model)
    assert params["dec_positions"].shape == (config.max_text_len,
                                             config.d_model)
    # stacked layers run end-to-end through the jitted forward
    mel = jnp.ones((1, config.n_mels, 24), jnp.float32)
    tokens = jnp.ones((1, 4), jnp.int32)
    logits = asr_forward(params, config, mel, tokens)
    assert logits.shape == (1, 4, config.vocab_size)
    assert bool(jnp.isfinite(logits).all())


def test_whisper_biases_change_output(tmp_path):
    """The bias terms must actually flow through the forward: zeroing
    them changes logits (guards against a map that loads-but-drops)."""
    from aiko_services_tpu.models import asr_forward, load_whisper_params
    config = _tiny_asr_config()
    path = tmp_path / "whisper.safetensors"
    _write_hf_whisper(path, config, seed=3)
    params = load_whisper_params(path, config)
    mel = jnp.ones((1, config.n_mels, 24), jnp.float32)
    tokens = jnp.ones((1, 4), jnp.int32)
    base = np.asarray(asr_forward(params, config, mel, tokens))
    stripped = jax.tree_util.tree_map(lambda leaf: leaf, params)
    stripped["dec_norm"] = {
        "scale": params["dec_norm"]["scale"],
        "bias": jnp.zeros_like(params["dec_norm"]["bias"])}
    changed = np.asarray(asr_forward(stripped, config, mel, tokens))
    assert not np.allclose(base, changed)
    stripped_fc = jax.tree_util.tree_map(lambda leaf: leaf, params)
    stripped_fc["dec_layers"]["mlp"]["w1"] = {
        "w": params["dec_layers"]["mlp"]["w1"]["w"],
        "b": jnp.zeros_like(params["dec_layers"]["mlp"]["w1"]["b"])}
    changed_fc = np.asarray(asr_forward(stripped_fc, config, mel, tokens))
    assert not np.allclose(base, changed_fc)


def test_speech_to_text_element_ingests_hf_whisper(tmp_path):
    """The element probes the container and loads HF naming with no code
    changes (reference speech_elements.py:229 runs pretrained whisper)."""
    import queue
    from aiko_services_tpu.pipeline import create_pipeline
    from aiko_services_tpu.runtime import Process
    config = _tiny_asr_config()
    path = tmp_path / "whisper.safetensors"
    _write_hf_whisper(path, config)
    definition = {
        "name": "asr_hf",
        "graph": ["(tone (asr))"],
        "elements": [
            {"name": "tone", "output": [{"name": "audio"}],
             "parameters": {"data_sources": [[220, 0.2]]},
             "deploy": {"local": {"module": "aiko_services_tpu.elements",
                                  "class_name": "ToneSource"}}},
            {"name": "asr", "input": [{"name": "audio"}],
             "output": [{"name": "tokens"}],
             "parameters": {"d_model": config.d_model, "n_mels": 8,
                            "enc_layers": config.enc_layers,
                            "dec_layers": config.dec_layers,
                            "n_heads": config.n_heads,
                            "vocab_size": config.vocab_size,
                            "max_frames": config.max_frames,
                            "max_tokens": 4, "dtype": "float32",
                            "weights": str(path)},
             "deploy": {"local": {"module": "aiko_services_tpu.elements",
                                  "class_name": "SpeechToText"}}},
        ],
    }
    process = Process(transport_kind="loopback")
    pipeline = create_pipeline(process, definition)
    process.run(in_thread=True)
    responses = queue.Queue()
    pipeline.create_stream("s", queue_response=responses)
    _, _, outputs = responses.get(timeout=30)
    assert np.asarray(outputs["tokens"]).shape == (1, 4)
    process.terminate()


# -- yolov8 checkpoint ingestion ---------------------------------------------

def _tiny_yolo_config():
    from aiko_services_tpu.models import YoloV8Config
    return YoloV8Config(
        n_classes=4, width=(4, 8, 16, 32, 64), repeats=(1, 2, 2, 1),
        image_size=64, max_detections=8, score_threshold=0.01,
        dtype="float32")


def _write_ultralytics_yolo(path, config, seed=0):
    """Fake ultralytics YOLOv8 state_dict: torch (O, I, kh, kw) conv
    weights + separate BatchNorm tensors; head's final 1x1 convs are
    plain conv2d with bias."""
    rng = np.random.default_rng(seed)
    tensors = {}

    def conv_bn(stem, c_in, c_out, k):
        tensors[f"{stem}.conv.weight"] = (
            rng.standard_normal((c_out, c_in, k, k)).astype(np.float32)
            * 0.1)
        tensors[f"{stem}.bn.weight"] = rng.uniform(
            0.5, 1.5, c_out).astype(np.float32)
        tensors[f"{stem}.bn.bias"] = (
            rng.standard_normal(c_out).astype(np.float32) * 0.1)
        tensors[f"{stem}.bn.running_mean"] = (
            rng.standard_normal(c_out).astype(np.float32) * 0.1)
        tensors[f"{stem}.bn.running_var"] = rng.uniform(
            0.5, 2.0, c_out).astype(np.float32)

    def plain(stem, c_in, c_out):
        tensors[f"{stem}.weight"] = (
            rng.standard_normal((c_out, c_in, 1, 1)).astype(np.float32)
            * 0.1)
        tensors[f"{stem}.bias"] = (
            rng.standard_normal(c_out).astype(np.float32) * 0.1)

    def c2f(module, c_in, c_out, n):
        half = c_out // 2
        conv_bn(f"model.{module}.cv1", c_in, c_out, 1)
        conv_bn(f"model.{module}.cv2", (2 + n) * half, c_out, 1)
        for i in range(n):
            conv_bn(f"model.{module}.m.{i}.cv1", half, half, 3)
            conv_bn(f"model.{module}.m.{i}.cv2", half, half, 3)

    w, r = config.width, config.repeats
    conv_bn("model.0", 3, w[0], 3)
    conv_bn("model.1", w[0], w[1], 3)
    c2f(2, w[1], w[1], r[0])
    conv_bn("model.3", w[1], w[2], 3)
    c2f(4, w[2], w[2], r[1])
    conv_bn("model.5", w[2], w[3], 3)
    c2f(6, w[3], w[3], r[2])
    conv_bn("model.7", w[3], w[4], 3)
    c2f(8, w[4], w[4], r[3])
    conv_bn("model.9.cv1", w[4], w[4] // 2, 1)
    conv_bn("model.9.cv2", w[4] * 2, w[4], 1)
    c2f(12, w[4] + w[3], w[3], 1)
    c2f(15, w[3] + w[2], w[2], 1)
    conv_bn("model.16", w[2], w[2], 3)
    c2f(18, w[3] + w[2], w[3], 1)
    conv_bn("model.19", w[3], w[3], 3)
    c2f(21, w[4] + w[3], w[4], 1)
    box_c, cls_c = config.head_box_hidden, config.head_cls_hidden
    for scale, c_in in enumerate((w[2], w[3], w[4])):
        conv_bn(f"model.22.cv2.{scale}.0", c_in, box_c, 3)
        conv_bn(f"model.22.cv2.{scale}.1", box_c, box_c, 3)
        plain(f"model.22.cv2.{scale}.2", box_c, 4 * config.reg_max)
        conv_bn(f"model.22.cv3.{scale}.0", c_in, cls_c, 3)
        conv_bn(f"model.22.cv3.{scale}.1", cls_c, cls_c, 3)
        plain(f"model.22.cv3.{scale}.2", cls_c, config.n_classes)
    write_safetensors(path, tensors)
    return tensors


def test_load_yolov8_structure_matches_init(tmp_path):
    from aiko_services_tpu.models import init_yolo_params, load_yolov8_params
    config = _tiny_yolo_config()
    path = tmp_path / "yolo.safetensors"
    _write_ultralytics_yolo(path, config)
    loaded = load_yolov8_params(path, config)
    initialized = init_yolo_params(config, jax.random.PRNGKey(0))
    assert (jax.tree_util.tree_structure(loaded)
            == jax.tree_util.tree_structure(initialized))
    same_shapes = jax.tree_util.tree_map(
        lambda a, b: a.shape == b.shape, loaded, initialized)
    assert all(jax.tree_util.tree_leaves(same_shapes))


def test_yolov8_bn_folding_is_numerically_exact(tmp_path):
    """conv2d(folded_params) must equal BatchNorm(conv(x)) computed the
    torch way (eps=1e-3)."""
    from aiko_services_tpu.models import load_yolov8_params
    config = _tiny_yolo_config()
    path = tmp_path / "yolo.safetensors"
    tensors = _write_ultralytics_yolo(path, config)
    params = load_yolov8_params(path, config)
    rng = np.random.default_rng(7)
    x = rng.standard_normal((1, 6, 6, 3)).astype(np.float32)  # NHWC
    from aiko_services_tpu.models.layers import conv2d
    folded = np.asarray(conv2d(params["m0"], jnp.asarray(x), stride=2))
    # reference: plain conv then BN, torch semantics
    w = tensors["model.0.conv.weight"]  # (O, I, kh, kw)
    raw_out = np.asarray(conv2d(
        {"w": jnp.asarray(np.ascontiguousarray(w.transpose(2, 3, 1, 0)))},
        jnp.asarray(x), stride=2))
    gamma = tensors["model.0.bn.weight"]
    beta = tensors["model.0.bn.bias"]
    mean = tensors["model.0.bn.running_mean"]
    var = tensors["model.0.bn.running_var"]
    expected = (raw_out - mean) / np.sqrt(var + 1e-3) * gamma + beta
    np.testing.assert_allclose(folded, expected, rtol=2e-4, atol=2e-5)


def test_yolo_detect_end_to_end(tmp_path):
    from aiko_services_tpu.models import load_yolov8_params, yolo_detect
    config = _tiny_yolo_config()
    path = tmp_path / "yolo.safetensors"
    _write_ultralytics_yolo(path, config)
    params = load_yolov8_params(path, config)
    images = jnp.asarray(
        np.random.default_rng(1).random((2, 3, 64, 64), np.float32))
    out = yolo_detect(params, config, images)
    assert out["boxes"].shape == (2, config.max_detections, 4)
    assert out["scores"].shape == (2, config.max_detections)
    assert bool(jnp.isfinite(out["boxes"]).all())
    # DFL decode keeps boxes inside [0 - reg_max*stride, size + ...):
    # with finite inputs the xyxy ordering must hold where valid
    valid = np.asarray(out["valid"])
    boxes = np.asarray(out["boxes"])
    if valid.any():
        picked = boxes[valid]
        assert (picked[:, 2] >= picked[:, 0]).all()
        assert (picked[:, 3] >= picked[:, 1]).all()


def test_detector_element_ingests_ultralytics_yolo(tmp_path):
    import queue
    from aiko_services_tpu.pipeline import create_pipeline
    from aiko_services_tpu.runtime import Process
    config = _tiny_yolo_config()
    path = tmp_path / "yolo.safetensors"
    _write_ultralytics_yolo(path, config)
    definition = {
        "name": "det_hf",
        "graph": ["(camera (detector))"],
        "elements": [
            {"name": "camera", "output": [{"name": "image"}],
             "parameters": {"data_sources": [[3, 64, 64]]},
             "deploy": {"local": {"module": "aiko_services_tpu.elements",
                                  "class_name": "ImageSource"}}},
            {"name": "detector", "input": [{"name": "image"}],
             "output": [{"name": "detections"}],
             "parameters": {"weights": str(path), "n_classes": 4,
                            "image_size": 64, "max_detections": 8,
                            "score_threshold": 0.01, "dtype": "float32"},
             "deploy": {"local": {"module": "aiko_services_tpu.elements",
                                  "class_name": "Detector"}}},
        ],
    }
    process = Process(transport_kind="loopback")
    pipeline = create_pipeline(process, definition)
    process.run(in_thread=True)
    responses = queue.Queue()
    pipeline.create_stream("s", queue_response=responses)
    _, _, outputs = responses.get(timeout=60)
    detections = outputs["detections"]
    assert np.asarray(detections["boxes"]).shape == (1, 8, 4)
    process.terminate()


def test_infer_yolov8_config_reads_architecture_from_shapes(tmp_path):
    from aiko_services_tpu.models import infer_yolov8_config
    config = _tiny_yolo_config()
    path = tmp_path / "yolo.safetensors"
    _write_ultralytics_yolo(path, config)
    inferred = infer_yolov8_config(path, image_size=64, dtype="float32")
    assert inferred.width == config.width
    assert inferred.repeats == config.repeats
    assert inferred.neck_repeats == config.neck_repeats
    assert inferred.n_classes == config.n_classes
    assert inferred.reg_max == config.reg_max
    assert inferred.image_size == 64
