# Weight ingestion: safetensors read/write + checkpoint -> pytree mapping.
#
# The reference loads real model weights through third-party runtimes
# (reference: src/aiko_services/examples/yolo/yolo.py:51-54 ultralytics .pt,
# speech_elements.py:229 whisperx, elements_llm.py:137-179 Ollama).  This
# framework ingests weights directly: the safetensors container format is
# parsed in pure numpy (8-byte little-endian header length, JSON header of
# {name: {dtype, shape, data_offsets}}, flat data buffer) with zero-copy
# mmap reads -- no torch, no network.
#
#   - read_safetensors / write_safetensors: the container
#   - save_pytree / load_pytree: any model pytree <-> one .safetensors file
#     (dotted flat names)
#   - load_llama_params: HuggingFace Llama-family checkpoint naming ->
#     this framework's stacked-layer TransformerConfig pytree (transposed
#     to (in, out), scan-stacked, cast to config dtype, optionally
#     device_put with mesh shardings as it loads so an 8B model never
#     needs 2x host RAM)

from __future__ import annotations

import contextlib
import json
import mmap
from pathlib import Path

import numpy as np
import ml_dtypes

__all__ = [
    "read_safetensors", "write_safetensors", "SafetensorsFile",
    "save_pytree", "load_pytree", "load_llama_params", "llama_name_map",
    "load_whisper_params", "whisper_layer_map",
]

_DTYPES = {
    "F64": np.float64, "F32": np.float32, "F16": np.float16,
    "BF16": ml_dtypes.bfloat16,
    "I64": np.int64, "I32": np.int32, "I16": np.int16, "I8": np.int8,
    "U8": np.uint8, "BOOL": np.bool_,
}
_DTYPE_NAMES = {np.dtype(dtype): name for name, dtype in _DTYPES.items()}


class SafetensorsFile:
    """mmap-backed lazy reader: tensors materialize on get()."""

    def __init__(self, path):
        self.path = Path(path)
        with open(self.path, "rb") as handle:
            header_len = int.from_bytes(handle.read(8), "little")
            header = json.loads(handle.read(header_len))
            self._data_start = 8 + header_len
        self.metadata = header.pop("__metadata__", {})
        self._entries = header
        self._mmap = None

    def keys(self):
        return list(self._entries.keys())

    def __contains__(self, name):
        return name in self._entries

    def shape(self, name) -> tuple:
        return tuple(self._entries[name]["shape"])

    def get(self, name: str) -> np.ndarray:
        entry = self._entries[name]
        if self._mmap is None:
            handle = open(self.path, "rb")
            self._mmap = mmap.mmap(handle.fileno(), 0,
                                   access=mmap.ACCESS_READ)
        start, end = entry["data_offsets"]
        dtype = _DTYPES[entry["dtype"]]
        buffer = self._mmap[self._data_start + start:self._data_start + end]
        array = np.frombuffer(buffer, dtype=dtype)
        return array.reshape(entry["shape"])

    def close(self):
        if self._mmap is not None:
            self._mmap.close()
            self._mmap = None


def read_safetensors(path, names=None) -> dict:
    """Eagerly load {name: np.ndarray} (names=None loads everything)."""
    reader = SafetensorsFile(path)
    wanted = names if names is not None else reader.keys()
    tensors = {name: np.array(reader.get(name)) for name in wanted}
    reader.close()
    return tensors


def write_safetensors(path, tensors: dict, metadata: dict = None) -> None:
    header: dict = {}
    if metadata:
        header["__metadata__"] = {str(k): str(v)
                                  for k, v in metadata.items()}
    offset = 0
    arrays = {}
    for name, value in tensors.items():
        array = np.ascontiguousarray(np.asarray(value))
        if array.dtype not in _DTYPE_NAMES:
            raise TypeError(f"{name}: unsupported dtype {array.dtype}")
        arrays[name] = array
        header[name] = {
            "dtype": _DTYPE_NAMES[array.dtype],
            "shape": list(array.shape),
            "data_offsets": [offset, offset + array.nbytes],
        }
        offset += array.nbytes
    header_bytes = json.dumps(header).encode("utf-8")
    with open(path, "wb") as handle:
        handle.write(len(header_bytes).to_bytes(8, "little"))
        handle.write(header_bytes)
        for array in arrays.values():
            handle.write(array.tobytes())


# -- pytree <-> safetensors --------------------------------------------------

def save_pytree(path, tree, metadata: dict = None) -> None:
    """Persist any nested-dict pytree of arrays with dotted flat names."""
    flat: dict = {}

    def walk(node, prefix):
        if isinstance(node, dict):
            for key, value in node.items():
                walk(value, f"{prefix}.{key}" if prefix else str(key))
        else:
            flat[prefix] = np.asarray(node)

    walk(tree, "")
    write_safetensors(path, flat, metadata)


def load_pytree(path, dtype=None) -> dict:
    """Inverse of save_pytree; dtype casts every float leaf."""
    tree: dict = {}
    for name, array in read_safetensors(path).items():
        if dtype is not None and np.issubdtype(
                np.asarray(array).dtype, np.floating):
            array = array.astype(dtype)
        node = tree
        parts = name.split(".")
        for part in parts[:-1]:
            node = node.setdefault(part, {})
        node[parts[-1]] = array
    return tree


@contextlib.contextmanager
def open_checkpoint(paths):
    """Multi-shard safetensors index shared by the checkpoint loaders:
    yields (index, raw) where index maps tensor name -> reader and
    raw(name) materializes a tensor (KeyError names the missing tensor).
    Readers are closed even when a load fails partway."""
    if isinstance(paths, (str, Path)):
        paths = [paths]
    readers = [SafetensorsFile(path) for path in paths]
    index = {name: reader for reader in readers for name in reader.keys()}

    def raw(name: str) -> np.ndarray:
        reader = index.get(name)
        if reader is None:
            raise KeyError(f"Checkpoint is missing tensor: {name}")
        return reader.get(name)

    try:
        yield index, raw
    finally:
        for reader in readers:
            reader.close()


# -- HuggingFace Llama naming -> framework pytree ---------------------------

def llama_name_map(layer: int) -> dict:
    """HF tensor name -> (pytree path under layers, transpose?) for one
    decoder layer.  HF nn.Linear stores (out, in); this framework stores
    (in, out) so matmuls read x @ w (layers.py:10-12), except wq and wk,
    which it too holds (out, in) (layers.dense_heads)."""
    prefix = f"model.layers.{layer}."
    return {
        prefix + "input_layernorm.weight": (("attn_norm", "scale"), False),
        prefix + "post_attention_layernorm.weight": (
            ("mlp_norm", "scale"), False),
        prefix + "self_attn.q_proj.weight": (("wq", "w"), False),
        prefix + "self_attn.k_proj.weight": (("wk", "w"), False),
        prefix + "self_attn.v_proj.weight": (("wv", "w"), True),
        prefix + "self_attn.o_proj.weight": (("wo", "w"), True),
        prefix + "mlp.gate_proj.weight": (("w_gate", "w"), True),
        prefix + "mlp.up_proj.weight": (("w_up", "w"), True),
        prefix + "mlp.down_proj.weight": (("w_down", "w"), True),
    }


def load_llama_params(paths, config, mesh=None, specs=None):
    """Build the TransformerConfig pytree from HF Llama-family safetensors
    shard(s).

    paths: one file or a list of shards (names are disjoint across shards).
    With mesh+specs (models.transformer.param_specs), every leaf is
    device_put onto its NamedSharding as it is read, so peak host memory
    stays ~one-tensor-sized above the checkpoint mmap.
    Matches the capability of reference elements_llm.py:137-179 (llama3.1)
    with in-framework weights instead of an external runtime.
    """
    dtype = np.dtype(config.dtype)
    with open_checkpoint(paths) as (index, raw):
        return _load_llama_indexed(index, raw, config, mesh, specs, dtype)


def _load_llama_indexed(index, raw, config, mesh, specs, dtype):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    def fetch(name, transpose=False):
        array = raw(name)
        if transpose:
            array = array.T
        return np.ascontiguousarray(array).astype(dtype, copy=False)

    def spec_for(path_parts):
        if mesh is None or specs is None:
            return None
        node = specs
        for part in path_parts:
            if not isinstance(node, dict):
                return None
            node = node.get(part)
            if node is None:
                return None
        return node if isinstance(node, PartitionSpec) else None

    def place(path_parts, array):
        spec = spec_for(path_parts)
        if spec is None:
            return jnp.asarray(array)
        return jax.device_put(array, NamedSharding(mesh, spec))

    params: dict = {
        "embed": {"w": place(("embed", "w"),
                             fetch("model.embed_tokens.weight"))},
        "norm_out": {"scale": place(("norm_out", "scale"),
                                    fetch("model.norm.weight"))},
    }
    if "lm_head.weight" in index:
        # untied output head (Llama-3-8B+); same (V, D) layout as embed
        params["lm_head"] = {"w": place(("embed", "w"),
                                        fetch("lm_head.weight"))}

    per_layer: list[dict] = []
    for layer in range(config.n_layers):
        mapping = llama_name_map(layer)
        layer_params: dict = {}
        for hf_name, (path_parts, transpose) in mapping.items():
            node = layer_params
            for part in path_parts[:-1]:
                node = node.setdefault(part, {})
            node[path_parts[-1]] = fetch(hf_name, transpose)
        per_layer.append(layer_params)

    stacked_layers = jax.tree_util.tree_map(
        lambda *leaves: np.stack(leaves), *per_layer)
    if mesh is not None and specs is not None:
        stacked_layers = jax.tree_util.tree_map(
            lambda leaf, spec: jax.device_put(
                leaf, NamedSharding(mesh, spec)),
            stacked_layers, specs["layers"])
    else:
        stacked_layers = jax.tree_util.tree_map(jnp.asarray,
                                                stacked_layers)
    params["layers"] = stacked_layers
    return params


# -- HuggingFace Whisper naming -> framework ASR pytree ----------------------

def _attention_map(hf_prefix: str, ours: str) -> dict:
    """One whisper attention block: q/v/out projections carry biases,
    k_proj does not (HF WhisperAttention)."""
    return {
        hf_prefix + "q_proj.weight": ((ours, "wq", "w"), True),
        hf_prefix + "q_proj.bias": ((ours, "wq", "b"), False),
        hf_prefix + "k_proj.weight": ((ours, "wk", "w"), True),
        hf_prefix + "v_proj.weight": ((ours, "wv", "w"), True),
        hf_prefix + "v_proj.bias": ((ours, "wv", "b"), False),
        hf_prefix + "out_proj.weight": ((ours, "wo", "w"), True),
        hf_prefix + "out_proj.bias": ((ours, "wo", "b"), False),
    }


def whisper_layer_map(layer: int, decoder: bool) -> dict:
    """HF tensor name -> (pytree path under enc_layers/dec_layers,
    transpose?) for one whisper transformer layer.  Linear weights are
    (out, in) in HF and (in, out) here; layer norms carry weight+bias
    (models/asr.py pre-LN blocks apply both)."""
    side = "decoder" if decoder else "encoder"
    prefix = f"model.{side}.layers.{layer}."
    mapping = {
        prefix + "fc1.weight": (("mlp", "w1", "w"), True),
        prefix + "fc1.bias": (("mlp", "w1", "b"), False),
        prefix + "fc2.weight": (("mlp", "w2", "w"), True),
        prefix + "fc2.bias": (("mlp", "w2", "b"), False),
        prefix + "final_layer_norm.weight": (("mlp_norm", "scale"), False),
        prefix + "final_layer_norm.bias": (("mlp_norm", "bias"), False),
    }
    if decoder:
        mapping.update(_attention_map(prefix + "self_attn.", "self"))
        mapping.update(_attention_map(prefix + "encoder_attn.", "cross"))
        mapping.update({
            prefix + "self_attn_layer_norm.weight": (
                ("self_norm", "scale"), False),
            prefix + "self_attn_layer_norm.bias": (
                ("self_norm", "bias"), False),
            prefix + "encoder_attn_layer_norm.weight": (
                ("cross_norm", "scale"), False),
            prefix + "encoder_attn_layer_norm.bias": (
                ("cross_norm", "bias"), False),
        })
    else:
        mapping.update(_attention_map(prefix + "self_attn.", "attn"))
        mapping.update({
            prefix + "self_attn_layer_norm.weight": (
                ("attn_norm", "scale"), False),
            prefix + "self_attn_layer_norm.bias": (
                ("attn_norm", "bias"), False),
        })
    return mapping


def load_whisper_params(paths, config) -> dict:
    """Build the AsrConfig pytree from HuggingFace openai/whisper-*
    safetensors naming (capability parity with the reference's pretrained
    WhisperX element, reference speech_elements.py:229-262 -- here the
    checkpoint feeds the in-framework encoder-decoder, models/asr.py).

    Layout notes: HF conv1/conv2 weights are (d_model, in, kernel),
    exactly this framework's _conv1d layout; positional tables are sliced
    to config.max_frames / config.max_text_len (shorter serving windows
    read a prefix of the 30 s table); the output head is tied to
    model.decoder.embed_tokens (HF WhisperForConditionalGeneration ties
    proj_out the same way)."""
    dtype = np.dtype(config.dtype)
    with open_checkpoint(paths) as (_index, raw):
        return _load_whisper_indexed(raw, config, dtype)


def _load_whisper_indexed(raw, config, dtype):
    import jax
    import jax.numpy as jnp

    def fetch(name, transpose=False):
        array = raw(name)
        if transpose:
            array = array.T
        return np.ascontiguousarray(array).astype(dtype, copy=False)

    def build_layers(count, decoder):
        per_layer = []
        for layer in range(count):
            layer_params: dict = {}
            for hf_name, (parts, transpose) in whisper_layer_map(
                    layer, decoder).items():
                node = layer_params
                for part in parts[:-1]:
                    node = node.setdefault(part, {})
                node[parts[-1]] = fetch(hf_name, transpose)
            per_layer.append(layer_params)
        return jax.tree_util.tree_map(
            lambda *leaves: jnp.asarray(np.stack(leaves)), *per_layer)

    params = {
        "conv1": {"w": fetch("model.encoder.conv1.weight"),
                  "b": fetch("model.encoder.conv1.bias")},
        "conv2": {"w": fetch("model.encoder.conv2.weight"),
                  "b": fetch("model.encoder.conv2.bias")},
        "enc_positions": fetch(
            "model.encoder.embed_positions.weight")[:config.max_frames],
        "enc_layers": build_layers(config.enc_layers, decoder=False),
        "enc_norm": {
            "scale": fetch("model.encoder.layer_norm.weight"),
            "bias": fetch("model.encoder.layer_norm.bias")},
        "token_embed": {"w": fetch("model.decoder.embed_tokens.weight")},
        "dec_positions": fetch(
            "model.decoder.embed_positions.weight")[:config.max_text_len],
        "dec_layers": build_layers(config.dec_layers, decoder=True),
        "dec_norm": {
            "scale": fetch("model.decoder.layer_norm.weight"),
            "bias": fetch("model.decoder.layer_norm.bias")},
    }
    return jax.tree_util.tree_map(jnp.asarray, params)
