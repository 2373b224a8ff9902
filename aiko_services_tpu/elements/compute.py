# JAX compute toy elements: the smallest real ComputeElements, used by
# tests and as templates for user elements.  No reference counterpart --
# the reference's compute lives in torch/CUDA user code (reference:
# src/aiko_services/examples/yolo/yolo.py:51-87); here it is jit-compiled
# JAX running on whatever mesh the definition names.

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..pipeline import ComputeElement, StreamEvent
from .common_io import DataSource

__all__ = ["ArraySource", "TokenSource", "MultiModalSource", "JaxScale",
           "JaxMLP", "ToHost"]


class ArraySource(DataSource):
    """Emits {"tensor": ndarray} frames; data_sources items give shapes,
    e.g. [[8, 16], [8, 16]] emits two 8x16 arrays (seeded, deterministic)."""

    def read_item(self, stream, item) -> dict:
        shape = tuple(int(size) for size in item)
        rng = np.random.default_rng(
            int(self.get_parameter("seed", 0, stream))
            + self.emission_index(stream))
        return {"tensor": rng.standard_normal(shape, dtype=np.float32)}


class TokenSource(DataSource):
    """Emits {"tokens": (B, L) int32} frames: data_sources [[batch, seq]],
    repeated `count` times (load-generator for LM pipelines/benchmarks)."""

    def start_stream(self, stream, stream_id):
        items = self.get_parameter("data_sources", [[8, 128]], stream)
        shapes = [tuple(int(size) for size in item) for item in items]
        count = int(self.get_parameter("count", 1, stream))
        name = self.definition.name
        stream.variables[f"{name}.shapes"] = shapes
        stream.variables[f"{name}.remaining"] = count
        rate = self.get_parameter("rate", None, stream)
        self.create_frames(stream, self._generate,
                           rate=float(rate) if rate else None)
        return StreamEvent.OKAY, None

    def _generate(self, stream, frame_id):
        import time
        name = self.definition.name
        remaining = stream.variables[f"{name}.remaining"]
        if remaining <= 0:
            return StreamEvent.STOP, {"diagnostic": "count exhausted"}
        stream.variables[f"{name}.remaining"] = remaining - 1
        shapes = stream.variables[f"{name}.shapes"]
        index = self.emission_index(stream)
        shape = shapes[index % len(shapes)]  # cycle all configured shapes
        vocab = int(self.get_parameter("vocab_size", 8192, stream))
        rng = np.random.default_rng(
            int(self.get_parameter("seed", 0, stream)) + index)
        # t0 rides the swag so consumers can measure true frame latency
        # (declare a "t0" output port to propagate it)
        return StreamEvent.OKAY, {
            "tokens": rng.integers(0, vocab, shape, dtype=np.int32),
            "t0": time.time()}


class MultiModalSource(DataSource):
    """Emits {"audio", "image"} frames: items are [frequency_hz, seconds]
    tone specs plus a synthetic image (parameter image_shape, default
    [3, 32, 32]) -- the hermetic driver for multi-modal pipelines.
    Composes audio_io.synthesize_tone + image_io.synthesize_image."""

    def read_item(self, stream, item) -> dict:
        from .audio_io import SAMPLE_RATE, synthesize_tone
        from .image_io import synthesize_image
        shape = self.get_parameter("image_shape", [3, 32, 32], stream)
        seed = (int(self.get_parameter("seed", 0, stream))
                + self.emission_index(stream))
        if self.get_parameter("on_device", False, stream):
            # synthesize directly in HBM: no host->device transfer rides
            # the frame path (the HBM-resident design property; bench
            # measures model compute, not host ingest bandwidth)
            from .audio_io import synthesize_tone_on_device
            from .image_io import synthesize_image_on_device
            return {
                "audio": synthesize_tone_on_device(
                    float(item[0]), float(item[1])),
                "image": synthesize_image_on_device(shape, seed),
            }
        return {
            "audio": synthesize_tone(float(item[0]), float(item[1])),
            "image": synthesize_image(shape, seed),
        }

    def read_batch(self, stream, items) -> dict | None:
        """Whole-row-batch synthesis as ONE device program (B tones + B
        images in a single dispatch -- the per-item path costs ~10
        dispatches per frame).  Host path and
        ragged tone lengths fall back to per-item reads."""
        from .audio_io import SAMPLE_RATE
        if not self.get_parameter("on_device", False, stream):
            return None
        seconds = float(items[0][1])
        if any(float(item[1]) != seconds for item in items):
            return None  # ragged lengths cannot stack
        shape = tuple(int(size) for size in self.get_parameter(
            "image_shape", [3, 32, 32], stream))
        base_seed = int(self.get_parameter("seed", 0, stream))
        seeds = np.asarray(
            [base_seed + self.emission_index(stream) for _ in items],
            np.uint32)
        freqs = np.asarray([float(item[0]) for item in items], np.float32)
        audio, image = _multimodal_batch(
            jnp.asarray(freqs), jnp.asarray(seeds),
            int(seconds * SAMPLE_RATE), SAMPLE_RATE, shape)
        return {"audio": audio, "image": image}


@functools.partial(jax.jit,
                   static_argnames=("samples", "sample_rate", "shape"))
def _multimodal_batch(freqs, seeds, samples, sample_rate, shape):
    """(B,) tone frequencies + (B,) seeds -> ((B, samples) audio,
    (B, *shape) images): the whole multi-modal batch in one dispatch.
    Same formulas and fold_in as the per-item synthesize_tone_on_device /
    synthesize_image_on_device; images are bit-exact, audio agrees to
    f32 rounding (~1e-4 -- XLA fuses the broadcast sin differently)."""
    t = jnp.arange(samples) / sample_rate
    audio = jnp.sin(2 * jnp.pi * freqs[:, None] * t[None, :])
    keys = jax.vmap(
        lambda seed: jax.random.fold_in(jax.random.PRNGKey(0), seed))(seeds)
    image = jax.vmap(
        lambda key: jax.random.uniform(key, shape, jnp.float32))(keys)
    return audio, image


class JaxScale(ComputeElement):
    """tensor -> tensor * scale + offset: stateless pure-JAX element.
    scale/offset are dynamic parameters, so live updates (dashboard, EC
    share, stream overrides) apply without recompiling."""

    def dynamic_parameters(self, stream):
        return {"scale": float(self.get_parameter("scale", 2.0, stream)),
                "offset": float(self.get_parameter("offset", 0.0, stream))}

    def compute(self, state, tensor, scale, offset):
        return {"tensor": tensor * scale + offset}


class JaxMLP(ComputeElement):
    """Two-layer MLP over the last axis: a stateful ComputeElement whose
    params live on the element's mesh (definition "sharding" block)."""

    def setup(self):
        features = int(self.get_parameter("features", 16))
        hidden = int(self.get_parameter("hidden", 32))
        key = jax.random.PRNGKey(int(self.get_parameter("seed", 0)))
        k1, k2 = jax.random.split(key)
        return {
            "w1": jax.random.normal(k1, (features, hidden),
                                    jnp.float32) / np.sqrt(features),
            "b1": jnp.zeros((hidden,), jnp.float32),
            "w2": jax.random.normal(k2, (hidden, features),
                                    jnp.float32) / np.sqrt(hidden),
            "b2": jnp.zeros((features,), jnp.float32),
        }

    def compute(self, state, tensor):
        hidden = jax.nn.gelu(tensor @ state["w1"] + state["b1"])
        return {"tensor": hidden @ state["w2"] + state["b2"]}


class ToHost(ComputeElement):
    """Device -> host boundary: returns the tensor as numpy (the explicit
    Sink-side transfer point; everything upstream stays on device)."""

    def process_frame(self, stream, tensor):
        return StreamEvent.OKAY, {"tensor": np.asarray(tensor)}
