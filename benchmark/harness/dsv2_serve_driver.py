"""Drive one cell of a served DeepSeek-V2 share: the clients, the window
and the last line are `lm_serve_driver`'s, run as they are.

That driver fixes three things to the six dense-Llama keys: the element's
parameters (`definition`), the sizes it reads from the file
(`roofline.lm_shape`), and the reference `checks.py` imports.  None of
the three files may be edited by a PR that adds a cell, so this driver
puts its own in their place for the length of one run and calls
`lm_serve_driver.run`: the published keys go to the program whole
(`model`), the sizes come from `dsv2_counts.shape`, and the served tokens
are judged by `checks.served_gaps` against `reference/deepseek_v2.py`,
which has `reference/transformer.py`'s two entry points.  A fourth,
`common.check_served`, is wrapped to empty the device first.
"""

from __future__ import annotations

from contextlib import ExitStack
from unittest import mock

from ..reference import deepseek_v2
from . import checks, common, dsv2_counts, lm_serve_driver

# what the configuration file holds beside the model's own keys
_NOT_THE_MODEL = ("name", "system", "source", "why", "reduced", "assumed",
                  "serve", "deployment")


def definition(config: dict, seed: int, max_new: int) -> dict:
    """The replica's pipeline: one LMGenerate given the file's model keys
    whole, served as `serve` says."""
    serve = config["serve"]
    parameters = {
        "model": {key: value for key, value in config.items()
                  if key not in _NOT_THE_MODEL},
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


class _Sizes:
    """Stands where lm_serve_driver reads `roofline.lm_shape`."""
    lm_shape = staticmethod(dsv2_counts.shape)


_check_served = common.check_served


def check_served(*arguments) -> bool:
    """`common.check_served`, once the device is empty.  lm_serve_driver's
    run keeps its occupancy sampler until it returns, and through it the
    element, the engine, the weights and the pool: 10.9 GB here, beside
    which one row of the reference's attention at 7936 positions (3.9 GB)
    has no room.  The program is stopped by now and nothing of it runs
    again, so what it left on the device is deleted."""
    import jax
    for array in jax.live_arrays():
        array.delete()
    return _check_served(*arguments)


def run(cell, manifest: dict, **keywords) -> str:
    """One run of one DeepSeek-V2 serving cell; returns the result line.
    A program that cannot read the configuration (the parent of the PR
    that brought it: it would serve its default toy model and be judged
    not correct a few minutes later) is refused at once."""
    from aiko_services_tpu.models import configs
    if not hasattr(configs, "deepseek_v2_config"):
        raise SystemExit(
            "benchmark: this program has no reader for model_type "
            "deepseek_v2 (models/configs.py deepseek_v2_config): it "
            f"cannot run {cell.name}; nothing was run")
    with ExitStack() as replaced:
        for module, name, ours in (
                (lm_serve_driver, "definition", definition),
                (lm_serve_driver, "roofline", _Sizes),
                (common, "check_served", check_served),
                (checks, "reference", deepseek_v2)):
            replaced.enter_context(mock.patch.object(module, name, ours))
        return lm_serve_driver.run(cell, manifest, **keywords)
