# Test configuration: force JAX onto a virtual 8-device CPU mesh so
# sharding/collective tests run without TPU hardware.  XLA_FLAGS is set
# before jax is imported; the platform is pinned through jax.config so a
# shell without JAX_PLATFORMS=cpu still never opens an accelerator.

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("AIKO_NAMESPACE", "aiko_test")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
assert len(jax.devices()) == 8, (
    "tests need the virtual 8-device CPU mesh; got "
    f"{jax.devices()}")
