# Device-path guards (ISSUE 21): chip_smoke.py's rehearsal and its
# refusal to pass without a TPU, the Pallas interpret gate, and the
# launchers that must never open a device (a chip belongs to ONE OS
# process: a parent that touched jax starves the child that needs it).

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def _smoke(args, tmp_path, devices: int = 1):
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               XLA_FLAGS=f"--xla_force_host_platform_device_count="
                         f"{devices}")
    return subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py"), *args], env=env,
        cwd=str(tmp_path), capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("devices,phases", [
    (1, ("pipeline", "serve", "train", "kernels", "link")),
    (4, ("pipeline[model=4]", "longcontext[seq=4]", "train",
         "train[data=2,model=2]")),
])
def test_rehearsal_passes_and_can_never_read_as_a_pass(
        tmp_path, devices, phases):
    result = _smoke(["--rehearsal"], tmp_path, devices)
    assert result.returncode == 0, result.stderr[-3000:]
    lines = result.stdout.strip().splitlines()
    results = [line for line in lines if line.startswith("[smoke]")]
    assert results and all(
        line.startswith("[smoke] rehearsal platform=cpu ")
        for line in results)
    for phase in phases:
        assert any(f" {phase}: ok setup_s=" in line for line in results)
    # the driver's contract for the last line: exactly these keys
    assert json.loads(lines[-1]) == {
        "ok": False,
        "device": {"platform": "cpu", "kind": "cpu", "count": devices}}
    summary = json.loads(lines[-2].split(" summary ", 1)[1])
    assert summary["rehearsal"] is True and summary["ok"] is False
    assert list(summary)[-1] == "claim" and summary["claim"] is None
    # the cache went where JAX_COMPILATION_CACHE_DIR put it, and
    # nowhere else: the script ran from an empty directory
    assert f"compile_cache_dir={tmp_path / 'cache'} " in results[1]
    assert os.listdir(tmp_path / "cache")
    assert os.listdir(tmp_path) == ["cache"]


def test_without_a_tpu_and_without_the_flag_it_fails(tmp_path):
    result = _smoke([], tmp_path)
    assert result.returncode != 0
    assert "not a TPU" in result.stderr
    lines = result.stdout.strip().splitlines()
    assert not any(line.startswith("{") for line in lines)
    assert not any(": ok " in line for line in lines)


def test_interpret_mode_only_on_cpu(monkeypatch):
    import jax

    from aiko_services_tpu.parallel import attention
    assert attention._interpret() is True          # the test platform
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert attention._interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        attention._interpret()


def test_flash_attention_partitions_itself_under_an_ambient_mesh():
    """Mosaic refuses automatic partitioning ("wrap the call in a
    shard_map"), which only a real multi-chip mesh shows; the CPU
    interpreter lowers to plain XLA and never complained.  So the
    contract is pinned structurally: under an ambient multi-device mesh
    the kernel call sits inside a shard_map over batch and heads, the
    numbers do not change, and without a mesh nothing is wrapped."""
    from functools import partial

    import jax
    import numpy as np

    from aiko_services_tpu.parallel import create_mesh
    from aiko_services_tpu.parallel.attention import (
        attention_reference, flash_attention)

    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(key, (2, 8, 64, 32)) for key in keys)
    attend = partial(flash_attention, causal=True)
    expected = np.asarray(attention_reference(q, k, v, causal=True))
    assert "shard_map" not in str(jax.make_jaxpr(attend)(q, k, v))
    with jax.set_mesh(create_mesh({"data": 2, "model": 4})):
        assert "shard_map" in str(jax.make_jaxpr(attend)(q, k, v))
        meshed = jax.jit(attend)(q, k, v)
        grads = jax.jit(jax.grad(
            lambda q, k, v: attend(q, k, v).sum(), argnums=(0, 1, 2)))(
                q, k, v)
    np.testing.assert_allclose(np.asarray(meshed), expected, atol=1e-5)
    wanted = jax.grad(lambda q, k, v: attention_reference(
        q, k, v, causal=True).sum(), argnums=(0, 1, 2))(q, k, v)
    for got, want in zip(grads, wanted):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4)


def test_launchers_never_initialise_a_backend():
    """Importing the CLI and constructing a Registrar (what the `aiko
    system start` parent and its registrar child do) must leave jax's
    backend table empty.  Runs in a fresh interpreter: the test
    process itself opened the CPU backend long ago."""
    script = (
        "import aiko_services_tpu.cli\n"
        "from aiko_services_tpu.runtime import Process, Registrar\n"
        "process = Process(transport_kind='loopback')\n"
        "Registrar(process, search_timeout=0.05)\n"
        "process.run(in_thread=True)\n"
        "import time; time.sleep(0.3)\n"
        "process.terminate()\n"
        "from jax._src import xla_bridge\n"
        "assert xla_bridge._backends == {}, xla_bridge._backends\n"
        "print('no backend')\n")
    env = dict(os.environ, PYTHONPATH=str(REPO))
    result = subprocess.run([sys.executable, "-c", script], env=env,
                            capture_output=True, text=True, timeout=120)
    assert result.returncode == 0, result.stderr[-2000:]
    assert "no backend" in result.stdout
