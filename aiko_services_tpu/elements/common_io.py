# DataSource / DataTarget base elements.
#
# Capability parity with the reference media I/O bases (reference:
# src/aiko_services/elements/media/common_io.py:22-151): a DataSource turns a
# "data_sources" parameter (file path(s), glob patterns, or in-memory items)
# into a stream of frames -- single item goes through the no-thread fast path
# (create_frame), multiple items run on a frame-generator thread with
# optional rate throttling and batching; a DataTarget consumes frames into
# "data_targets" (templated file paths).

from __future__ import annotations

import glob as globlib
from pathlib import Path

from ..pipeline import PipelineElement, StreamEvent

__all__ = ["DataSource", "DataTarget", "Sample", "expand_data_sources"]


def expand_data_sources(data_sources) -> list:
    """Expand path patterns: "file://path" prefixes, globs, lists."""
    if data_sources is None:
        return []
    if isinstance(data_sources, (str, Path)):
        data_sources = [data_sources]
    expanded = []
    for source in data_sources:
        if not isinstance(source, str):
            expanded.append(source)
            continue
        path = source[len("file://"):] if source.startswith("file://") else (
            source)
        if any(character in path for character in "*?["):
            expanded.extend(sorted(globlib.glob(path)))
        else:
            expanded.append(path)
    return expanded


class Sample(PipelineElement):
    """Pass every sample_rate-th frame, DROP_FRAME otherwise -- the
    drop-frame test pattern, name-agnostic over its input ports
    (reference: text_io.py:108-115; Text/Audio/VideoSample are aliases)."""

    def process_frame(self, stream, **inputs):
        sample_rate = int(self.get_parameter("sample_rate", 1, stream))
        counter_key = f"{self.definition.name}.counter"
        counter = stream.variables.get(counter_key, 0)
        stream.variables[counter_key] = counter + 1
        if sample_rate > 1 and counter % sample_rate != 0:
            return StreamEvent.DROP_FRAME, {}
        return StreamEvent.OKAY, inputs


class DataSource(PipelineElement):
    """Subclasses implement read_item(stream, item) -> frame_data dict.

    Parameters (all stream-overridable):
      data_sources     items / paths / globs
      rate             frames per second throttle
      count            total frames to emit, cycling items (default: one
                       pass over the items)
      data_batch_size  stack N read_item results per frame (reference
                       common_io.py data_batch_size); ndarray values get a
                       leading batch axis
      timestamps       add "t0" (time.time()) to every frame -- declare a
                       "t0" output port to propagate it (latency probes)
    """

    def emission_index(self, stream) -> int:
        """Monotonic per-stream emission counter.  Use this (not
        stream.frame_id) to seed synthetic sources: frame_id only advances
        when the pipeline mailbox drains, so a fast generator would reuse
        the same value across in-flight frames."""
        key = f"{self.definition.name}.emitted"
        index = stream.variables.get(key, 0)
        stream.variables[key] = index + 1
        return index

    # path-like sources expand "file://" prefixes and glob patterns;
    # literal-content sources (TextSource: prompts may contain ? or *)
    # override with False
    expand_sources = True

    def start_stream(self, stream, stream_id):
        data_sources = self.get_parameter("data_sources", None, stream)
        if self.expand_sources:
            items = expand_data_sources(data_sources)
        elif data_sources is None:
            items = []
        elif isinstance(data_sources, (str, Path)):
            items = [data_sources]
        else:
            items = list(data_sources)
        if not items:
            return StreamEvent.ERROR, {"diagnostic": "no data_sources"}
        rate = self.get_parameter("rate", None, stream)
        rate = float(rate) if rate else None
        count = self.get_parameter("count", None, stream)
        batch = int(self.get_parameter("data_batch_size", 1, stream))
        name = self.definition.name
        stream.variables[f"{name}.items"] = items
        stream.variables[f"{name}.remaining"] = (
            int(count) if count is not None
            else max(1, len(items) // max(batch, 1)))
        if (len(items) == 1 and rate is None and batch == 1
                and count is None):
            # fast path: single item, no generator thread
            # (reference common_io.py:96-102)
            try:
                frame_data = self._read_frame(stream)
            except Exception as error:
                return StreamEvent.ERROR, {"diagnostic": str(error)}
            self.create_frame(stream, frame_data)
            return StreamEvent.OKAY, None
        self.create_frames(stream, self._frame_generator, rate=rate)
        return StreamEvent.OKAY, None

    def _read_frame(self, stream) -> dict:
        """One frame's data: `data_batch_size` read_item()s stacked."""
        import time

        import numpy as np

        name = self.definition.name
        items = stream.variables[f"{name}.items"]
        batch = int(self.get_parameter("data_batch_size", 1, stream))
        cursor_key = f"{name}.cursor"
        batch_items = []
        for _ in range(max(batch, 1)):
            cursor = stream.variables.get(cursor_key, 0)
            stream.variables[cursor_key] = cursor + 1
            batch_items.append(items[cursor % len(items)])
        if batch > 1:
            # one fused call for the whole row batch when the source
            # supports it (per-row synthesis pays a dispatch EACH; a
            # batched source is one launch per frame)
            batched = self.read_batch(stream, batch_items)
            if batched is not None:
                if self.get_parameter("timestamps", False, stream):
                    batched["t0"] = time.time()
                return batched
        parts = [self.read_item(stream, item) for item in batch_items]
        if batch <= 1:
            frame_data = parts[0]
        else:
            frame_data = {}
            for key in parts[0]:
                values = [part[key] for part in parts]
                if isinstance(values[0], np.ndarray):
                    frame_data[key] = np.stack(values)
                else:
                    try:  # device arrays stack ON DEVICE (jnp.stack) --
                        # never a host round-trip for on_device sources
                        import jax
                        import jax.numpy as jnp
                        if isinstance(values[0], jax.Array):
                            frame_data[key] = jnp.stack(values)
                        else:
                            frame_data[key] = values
                    except ImportError:  # pragma: no cover
                        frame_data[key] = values
        if self.get_parameter("timestamps", False, stream):
            frame_data["t0"] = time.time()
        return frame_data

    def _frame_generator(self, stream, frame_id):
        name = self.definition.name
        remaining_key = f"{name}.remaining"
        remaining = stream.variables.get(remaining_key, 0)
        if remaining <= 0:
            return StreamEvent.STOP, {"diagnostic": "data sources exhausted"}
        stream.variables[remaining_key] = remaining - 1
        return StreamEvent.OKAY, self._read_frame(stream)

    def read_item(self, stream, item) -> dict:
        raise NotImplementedError

    def read_batch(self, stream, items) -> dict | None:
        """Optional whole-batch read: return {key: (B, ...) stacked} for
        `items`, or None to fall back to per-item read_item() + stack.
        Sources that can synthesize/load a batch in one device program
        should implement this (dispatch-latency economy)."""
        return None

    def process_frame(self, stream, **inputs):
        # sources inject frames; a frame passing through is forwarded as-is
        return StreamEvent.OKAY, inputs


class DataTarget(PipelineElement):
    """Subclasses implement write_item(stream, path, **inputs)."""

    def start_stream(self, stream, stream_id):
        data_targets = self.get_parameter("data_targets", None, stream)
        targets = expand_data_sources(data_targets)
        if not targets:
            return StreamEvent.ERROR, {"diagnostic": "no data_targets"}
        stream.variables[f"{self.definition.name}.target"] = targets[0]
        stream.variables[f"{self.definition.name}.count"] = 0
        return StreamEvent.OKAY, None

    def next_target_path(self, stream) -> str:
        """Template "{}" in the target expands to the write counter."""
        template = stream.variables[f"{self.definition.name}.target"]
        count_key = f"{self.definition.name}.count"
        count = stream.variables[count_key]
        stream.variables[count_key] = count + 1
        return (template.format(count) if "{" in str(template)
                else str(template))
