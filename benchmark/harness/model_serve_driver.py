"""Drive one cell of a served model whose configuration file holds its
published config.json keys: the clients, the window and the last line are
`lm_serve_driver`'s, run as they are.

That driver fixes three things to the six dense-Llama keys: the element's
parameters (`definition`), the sizes it reads from the file
(`roofline.lm_shape`), and the reference `checks.py` imports (`ROADMAP.md`
D14).  None of the three files may be edited by a PR that adds a cell, so
this driver puts others in their place for the length of one run, as
`dsv2_serve_driver` does, and takes them from the configuration file, so
that the next configuration brings data and no driver:

    "system": "model_serve"
    "reference": "<module>"   benchmark/reference/<module>.py, with
                              `shape_of(file)` and `logits_at(shape, seed,
                              tokens, positions, precision=)`
    "counts": "<module>"      benchmark/harness/<module>.py, with
                              `shape(file)` -> at least {"vocab": ...}
    "model_type": ...         and every other key that is not the
                              harness's: handed to the program whole, as
                              LMGenerate's `model`

A fourth, `common.check_served`, is wrapped to empty the device first.
"""

from __future__ import annotations

import importlib
from contextlib import ExitStack
from unittest import mock

from . import checks, common, lm_serve_driver
# common.check_served once the device is empty: the program's weights and
# pool, which lm_serve_driver's sampler keeps alive, leave the reference no
# room
from .dsv2_serve_driver import check_served

# what the configuration file holds beside the model's own keys
_NOT_THE_MODEL = ("name", "system", "source", "why", "reference", "counts",
                  "reduced", "reduced_note", "assumed", "serve",
                  "deployment")


def model_keys(config: dict) -> dict:
    """The file's published keys, as the program is given them."""
    return {key: value for key, value in config.items()
            if key not in _NOT_THE_MODEL}


def definition(config: dict, seed: int, max_new: int) -> dict:
    """The replica's pipeline: one LMGenerate given the file's model keys
    whole, served as `serve` says."""
    serve = config["serve"]
    parameters = {
        "model": model_keys(config),
        "max_seq_len": serve["max_context"],
        "dtype": config.get("torch_dtype", "bfloat16"), "seed": seed,
        "decode_slots": serve["decode_slots"],
        "kv_block_size": serve["kv_block_size"],
        "kv_blocks": serve["kv_blocks"],
        "max_context": serve["max_context"],
        "continuous": True, "stream_tokens": True,
        "max_new_tokens": max_new,
        # compiled when the replica is configured, not under the first
        # requests: a stream's lease at the gateway is 60 s
        "warm_buckets": serve.get("warm_buckets", []),
    }
    if serve.get("prefill_chunk_size"):
        parameters["prefill_chunk_size"] = serve["prefill_chunk_size"]
    return {
        "name": "bench_replica",
        "parameters": {"metrics_interval": 60.0},
        "graph": ["(lm)"],
        "elements": [{
            "name": "lm",
            "input": [{"name": "tokens", "type": "any"}],
            "output": [{"name": "generated", "type": "any"}],
            "parameters": parameters,
            "deploy": {"local": {"module": lm_serve_driver.ELEMENTS,
                                 "class_name": "LMGenerate"}}}],
    }


def has_reader(model_type: str) -> bool:
    """Whether the program can read a published config of `model_type`:
    its table of readers where it has one, else a reader by name."""
    from aiko_services_tpu.models import configs
    readers = getattr(configs, "PUBLISHED_READERS", None)
    if readers is not None:
        return model_type in readers
    return hasattr(configs, f"{model_type}_config")


def run(cell, manifest: dict, **keywords) -> str:
    """One run of one serving cell; returns the result line.  A program
    that cannot read the configuration (the parent of the PR that brought
    it: it would refuse the `model` under its first request, minutes
    later, or serve another model and be judged not correct) is refused
    at once."""
    config = cell.config
    model_type = str(config.get("model_type"))
    if not has_reader(model_type):
        raise SystemExit(
            f"benchmark: this program has no reader for model_type "
            f"{model_type} (models/configs.py): it cannot run "
            f"{cell.name}; nothing was run")
    reference = importlib.import_module(
        f"benchmark.reference.{config['reference']}")
    counts = importlib.import_module(
        f"benchmark.harness.{config['counts']}")

    class Sizes:
        """Stands where lm_serve_driver reads `roofline.lm_shape`."""
        lm_shape = staticmethod(counts.shape)

    with ExitStack() as replaced:
        for module, name, ours in (
                (lm_serve_driver, "definition", definition),
                (lm_serve_driver, "roofline", Sizes),
                (common, "check_served", check_served),
                (checks, "reference", reference)):
            replaced.enter_context(mock.patch.object(module, name, ours))
        return lm_serve_driver.run(cell, manifest, **keywords)
