# Multi-host runtime: jax.distributed initialization + global meshes.
#
# The reference's only cross-host fabric is the MQTT broker (reference:
# src/aiko_services/main/message/mqtt.py; SURVEY.md 2.4 "Distributed comm
# backend" -- no NCCL/MPI/Gloo anywhere).  The TPU-native equivalent keeps
# the broker for CONTROL traffic and runs the DATA plane over the runtime
# fabric XLA already owns: jax.distributed connects every host's runtime to
# a coordinator, after which jax.devices() spans the whole pod/slice and
# meshes built here generate ICI/DCN collectives (psum/ppermute/all_gather)
# directly between chips -- no broker hop, no serialization.
#
# Deployment contract (mirrors TPU pod env conventions):
#   AIKO_COORDINATOR   host:port of process 0 (also JAX auto-detects on
#                      Cloud TPU -- leave everything unset there)
#   AIKO_NUM_PROCESSES total framework Processes in the job
#   AIKO_PROCESS_ID    this process's rank
#
# Works on CPU backends too (Gloo), which is how the tests exercise a
# 2-process global mesh without TPU hardware.

from __future__ import annotations

import os
import threading

import jax

from .mesh import create_mesh

__all__ = [
    "initialize_distributed", "shutdown_distributed", "is_distributed",
    "global_mesh", "process_index", "process_count",
]

_LOCK = threading.Lock()
_INITIALIZED = False


def initialize_distributed(coordinator_address: str | None = None,
                           num_processes: int | None = None,
                           process_id: int | None = None,
                           local_device_ids=None) -> bool:
    """Connect this process to the job's JAX runtime fabric.

    Arguments default to the AIKO_* env contract above; with nothing set
    anywhere (single-process deployment) this is a no-op returning False.
    Idempotent: repeated calls after a successful init return True.
    """
    global _INITIALIZED
    with _LOCK:
        if _INITIALIZED:
            return True
        coordinator_address = (coordinator_address
                               or os.environ.get("AIKO_COORDINATOR"))
        if num_processes is None and "AIKO_NUM_PROCESSES" in os.environ:
            num_processes = int(os.environ["AIKO_NUM_PROCESSES"])
        if process_id is None and "AIKO_PROCESS_ID" in os.environ:
            process_id = int(os.environ["AIKO_PROCESS_ID"])
        if coordinator_address is None and num_processes is None:
            return False  # single-process deployment
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
            local_device_ids=local_device_ids)
        _INITIALIZED = True
        return True


def shutdown_distributed():
    global _INITIALIZED
    with _LOCK:
        if _INITIALIZED:
            jax.distributed.shutdown()
            _INITIALIZED = False


def is_distributed() -> bool:
    """True once this process has joined a multi-process job.  Must NOT
    touch jax.process_count()/jax.devices() here: those initialize the
    local backend, after which jax.distributed.initialize refuses to run
    -- the `if not is_distributed(): initialize_distributed()` idiom has
    to stay safe."""
    return _INITIALIZED or jax.distributed.is_initialized()


def process_index() -> int:
    return jax.process_index()


def process_count() -> int:
    return jax.process_count()


def global_mesh(axes: dict | None = None):
    """A mesh over the JOB's devices (all hosts), not just this host's.

    After initialize_distributed, jax.devices() already spans every
    process; axis sizes follow the same conventions as create_mesh
    ({"data": -1, "model": 4}, one -1 fills).  Computations jit over this
    mesh move data between hosts via XLA collectives -- the cross-host
    data plane (SURVEY.md 5).
    """
    return create_mesh(axes, devices=jax.devices())
