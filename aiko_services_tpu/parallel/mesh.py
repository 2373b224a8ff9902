# Device-mesh management: the TPU-native placement layer.
#
# The reference has no counterpart (SURVEY.md 2.4: TP/SP "absent") -- its
# only parallelism is process-level replication over MQTT.  Here the mesh is
# the first-class primitive: every ComputeElement may name mesh axes in its
# definition's "sharding" block and the engine places its state and batch
# math with jax.sharding.NamedSharding over a shared jax.sharding.Mesh.
#
# Axis convention (the "How to Scale Your Model" recipe):
#   data  -- batch-axis data parallelism (gradients psum here)
#   fsdp  -- parameter sharding axis (zero-style, all-gather on use)
#   model -- tensor parallelism (megatron-style matmul sharding)
#   seq   -- sequence/context parallelism (ring attention / Ulysses)
#   expert - expert parallelism for MoE layers
#
# Meshes are cached by (axes, device fingerprint) so every element naming the
# same topology shares one Mesh object (and therefore one XLA compilation
# environment).

from __future__ import annotations

import threading

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh, NamedSharding, PartitionSpec

__all__ = [
    "MESH_AXIS_ORDER", "create_mesh", "get_mesh", "named_sharding",
    "partition_spec", "shard_pytree", "filter_specs",
]

# Ordering matters for ICI locality: innermost (fastest-varying) axes get
# the most tightly coupled devices.  model/seq want maximum ICI bandwidth,
# so they are last (minor-most) in the device grid.
MESH_AXIS_ORDER = ("data", "fsdp", "expert", "pipeline", "seq", "model")

_MESH_CACHE: dict = {}
_MESH_LOCK = threading.Lock()


def _canonical_axes(axes: dict, device_count: int) -> tuple:
    """Order axes by MESH_AXIS_ORDER (unknown names keep given order at the
    end) and resolve a single -1 entry to fill the remaining devices."""
    known = [name for name in MESH_AXIS_ORDER if name in axes]
    unknown = [name for name in axes if name not in MESH_AXIS_ORDER]
    ordered = known + unknown
    sizes = {name: int(axes[name]) for name in ordered}
    fill = [name for name, size in sizes.items() if size == -1]
    if len(fill) > 1:
        raise ValueError(f"Only one mesh axis may be -1, got {fill}")
    if fill:
        fixed = 1
        for name, size in sizes.items():
            if size != -1:
                fixed *= size
        if device_count % fixed != 0:
            raise ValueError(
                f"{device_count} devices not divisible by fixed axes "
                f"{sizes} (product {fixed})")
        sizes[fill[0]] = device_count // fixed
    return tuple((name, sizes[name]) for name in ordered)


def create_mesh(axes: dict | None = None, devices=None) -> Mesh:
    """Build a Mesh from an axis-size mapping, e.g. {"data": -1, "model": 4}.

    With no axes, the whole device set becomes a 1-D "data" mesh.  Device
    grids come from mesh_utils.create_device_mesh so multi-chip TPU slices
    get an ICI-aware layout; on CPU (tests) this degenerates to a reshape.
    """
    devices = list(devices if devices is not None else jax.devices())
    axes = dict(axes or {"data": -1})
    canonical = _canonical_axes(axes, len(devices))
    shape = tuple(size for _, size in canonical)
    names = tuple(name for name, _ in canonical)
    total = int(np.prod(shape))
    if total != len(devices):
        raise ValueError(
            f"Mesh axes {dict(canonical)} need {total} devices, "
            f"have {len(devices)}")
    if devices[0].platform == "cpu":
        # virtual CPU devices (tests) have no topology to honor
        grid = np.asarray(devices).reshape(shape)
    else:
        # on an accelerator a grid the ICI-aware layout cannot build is
        # an error, never a silent fall to an arbitrary device order
        grid = mesh_utils.create_device_mesh(shape, devices=devices)
    return Mesh(grid, names)


def get_mesh(axes: dict | None = None, devices=None) -> Mesh:
    """Cached create_mesh: elements naming the same topology share a Mesh."""
    devices_list = list(devices if devices is not None else jax.devices())
    axes = dict(axes or {"data": -1})
    key = (tuple(sorted(axes.items())),
           tuple(id(device) for device in devices_list))
    with _MESH_LOCK:
        mesh = _MESH_CACHE.get(key)
        if mesh is None:
            mesh = create_mesh(axes, devices_list)
            _MESH_CACHE[key] = mesh
        return mesh


def partition_spec(spec) -> PartitionSpec:
    """Coerce a user-level spec into a PartitionSpec.

    Accepts: PartitionSpec (passthrough), None (replicated), a single axis
    name ("data" == shard dim 0 on data), or a list whose entries are axis
    names, None, or tuples/lists of axis names, e.g. ["data", None, "model"]
    or [["data", "fsdp"], None].
    """
    if isinstance(spec, PartitionSpec):
        return spec
    if spec is None:
        return PartitionSpec()
    if isinstance(spec, str):  # bare name, NOT iterated per-character
        return PartitionSpec(spec)
    entries = []
    for entry in spec:
        if isinstance(entry, (list, tuple)):
            entries.append(tuple(entry))
        else:
            entries.append(entry)
    return PartitionSpec(*entries)


def named_sharding(mesh: Mesh, spec) -> NamedSharding:
    return NamedSharding(mesh, partition_spec(spec))


def shard_pytree(tree, mesh: Mesh, specs):
    """device_put a pytree with per-leaf PartitionSpecs.

    specs may be a single spec applied to every leaf, or a (possibly
    PARTIAL) pytree: leaves present in `tree` but absent from `specs`
    replicate.  Partial trees matter for checkpoint ingestion -- an HF
    whisper pytree carries bias leaves the published asr_param_specs
    doesn't name, and under global-view SPMD a replicated bias is
    correct (XLA still partitions the matmuls it feeds)."""
    if isinstance(specs, (PartitionSpec, list, tuple)) or specs is None:
        shardings = jax.tree_util.tree_map(
            lambda _: named_sharding(mesh, specs), tree)
    else:
        def spec_leaf(entry):
            """Is `entry` one whole-array spec (vs a structural subtree)?
            Axis lists like ["data", None] or [("data", "fsdp"), None]
            count -- they BROADCAST over a subtree (the old device_put
            prefix-tree semantics); per-item spec lists must therefore
            use PartitionSpec objects to stay unambiguous."""
            if entry is None or isinstance(entry, (PartitionSpec, str)):
                return True
            if isinstance(entry, (list, tuple)):
                return all(
                    axis is None or isinstance(axis, str)
                    or (isinstance(axis, (list, tuple))
                        and all(isinstance(name, str) for name in axis))
                    for axis in entry)
            return False

        def build(node, spec_node):
            if isinstance(node, dict):
                if isinstance(spec_node, dict):
                    return {key: build(value, spec_node.get(key))
                            for key, value in node.items()}
                broadcast = spec_node if spec_leaf(spec_node) else None
                return {key: build(value, broadcast)
                        for key, value in node.items()}
            if isinstance(node, (list, tuple)):
                if (isinstance(spec_node, (list, tuple))
                        and not spec_leaf(spec_node)
                        and len(spec_node) == len(node)):
                    built = [build(value, spec)
                             for value, spec in zip(node, spec_node)]
                else:
                    broadcast = spec_node if spec_leaf(spec_node) else None
                    built = [build(value, broadcast) for value in node]
                if isinstance(node, tuple):
                    # namedtuples (e.g. optax opt_state) take positional
                    # fields, not an iterable
                    return (type(node)(*built) if hasattr(node, "_fields")
                            else type(node)(built))
                return built
            return named_sharding(
                mesh, spec_node if spec_leaf(spec_node) else None)

        shardings = build(tree, specs)
    return jax.device_put(tree, shardings)


def filter_specs(specs, mesh: Mesh):
    """Drop axis names a mesh doesn't have from a pytree of PartitionSpecs.

    Model code publishes specs over the full axis vocabulary (data/fsdp/
    seq/model); a deployment that collapses an axis (e.g. no FSDP on a
    single host) filters rather than rewriting every spec.
    """
    names = set(mesh.axis_names)

    def _filter_entry(entry):
        if entry is None:
            return None
        if isinstance(entry, (tuple, list)):
            kept = tuple(name for name in entry if name in names)
            return kept if kept else None
        return entry if entry in names else None

    def _filter(spec):
        spec = partition_spec(spec)
        return PartitionSpec(*(_filter_entry(entry) for entry in spec))

    return jax.tree_util.tree_map(
        _filter, specs,
        is_leaf=lambda leaf: (leaf is None
                              or isinstance(leaf, (PartitionSpec, list,
                                                   str))))
