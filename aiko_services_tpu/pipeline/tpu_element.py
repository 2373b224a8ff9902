# ComputeElement: the TPU compute contract for pipeline elements.
#
# This layer has no reference counterpart -- the reference's elements call
# torch/CUDA libraries ad hoc inside process_frame (reference:
# src/aiko_services/examples/yolo/yolo.py:51-87,
# examples/speech/speech_elements.py:229-262).  Here element math is a PURE
# JAX function compiled once per shape bucket:
#
#   class MyElement(ComputeElement):
#       def setup(self) -> state:            # build params (pytree) once
#       def compute(self, state, **inputs):  # pure jax fn -> outputs dict
#       def dynamic_parameters(self, stream) -> dict   # optional: traced
#           # per-frame values (live-updatable without recompiling)
#
# The engine: places state on the element's mesh (definition "sharding"
# block) with NamedSharding; jits compute; pads variable axes to
# power-of-two buckets so jit's shape-keyed cache stays small and un-pads
# matching output axes afterwards; keeps outputs on device (jax.Array in
# the swag) so a downstream ComputeElement never touches the host.
#
# Parameter semantics: plain get_parameter() reads inside compute() are
# baked in at trace time (cheap, but live updates need a recompile); values
# returned from dynamic_parameters() are fed as traced 0-d arrays each
# frame, so dashboard/EC updates apply immediately at zero recompile cost.

from __future__ import annotations

import contextlib
import inspect
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..observe.trace import NO_SPANS
from ..parallel.mesh import get_mesh, named_sharding, shard_pytree
from ..runtime.compile_cache import compile_bracket, setup_interval
from ..utils import get_logger
from ..utils.padding import bucket_length, pad_axis_to  # noqa: F401
from .element import PipelineElement
from .stream import Stream, StreamEvent

__all__ = ["ComputeElement", "bucket_length", "pad_axis_to"]

_LOGGER = get_logger("tpu_element")


class ComputeElement(PipelineElement):
    """PipelineElement whose math is a pure, jit-compiled JAX function.

    Definition parameters understood by the engine:
      sharding:        {"axes": {"data": -1, ...},
                        "state": <spec or pytree of specs>,
                        "inputs": {input_name: spec}}
      bucket_axes:     {input_name: axis_index} -- pad that axis to a bucket
      bucket_min:      minimum bucket size (default 16)
      buckets:         explicit bucket ladder, e.g. [128, 512, 2048]
      unpad_outputs:   slice bucket padding off outputs whose bucketed axis
                       matches the padded input size (default True)
      blocking_metrics: bool -- block_until_ready inside the timing window

    If compute() declares a `lengths` keyword, the engine passes a dict
    {input_name: int32 scalar} of pre-padding lengths so kernels can mask
    padded positions.
    """

    def __init__(self, process, pipeline, definition):
        super().__init__(process, pipeline, definition)
        sharding = dict(definition.sharding or {})
        if sharding:
            # "devices": [start, end) pins this element to a mesh
            # SUB-SLICE -- pipeline stages partition the pod (stage-level
            # pipeline parallelism, SURVEY.md 2.4 PP equivalent)
            devices = None
            device_range = sharding.get("devices")
            if device_range:
                start, end = int(device_range[0]), int(device_range[1])
                devices = jax.devices()[start:end]
            self.mesh = get_mesh(sharding.get("axes"), devices)
        else:
            self.mesh = None
        self._state_spec = sharding.get("state")
        self._input_specs = dict(sharding.get("inputs", {}))
        self._bucket_axes = dict(
            self.get_parameter("bucket_axes", {}) or {})
        self._bucket_min = int(self.get_parameter("bucket_min", 16))
        self._buckets = self.get_parameter("buckets", None)
        self._unpad_outputs = bool(
            self.get_parameter("unpad_outputs", True))
        self._blocking_metrics = bool(
            self.get_parameter("blocking_metrics", False))
        self.state = None
        self._compiled = None
        self._accepts_lengths = False
        self._replicated_warned: set = set()
        self._group_kernel_fn = None

    # -- the compute contract (override these) -----------------------------

    def setup(self):
        """Build the element's device state (params pytree); called lazily
        before the first frame.  Return None for stateless elements."""
        return None

    def compute(self, state, **inputs) -> dict:
        """PURE function: jax in, jax out.  No side effects, no Python
        branching on traced values."""
        raise NotImplementedError

    def dynamic_parameters(self, stream: Stream) -> dict:
        """Per-frame parameter values to pass as TRACED kwargs to compute.
        Read get_parameter(...) here (not inside compute) for live-updatable
        values: they enter the compiled fn as 0-d arrays, so updates apply
        without recompilation."""
        return {}

    # -- engine ------------------------------------------------------------

    def configure(self) -> None:
        """Idempotent pre-state configuration hook: build self.config /
        default self._state_spec here (NOT in setup) so the checkpoint
        RESTORE path -- which installs state without calling setup() --
        still configures the element before sharding or compute."""

    def _seam(self):
        """The pipeline's telemetry as the seam program spans go through
        (observe/trace.py); NO_SPANS where there is none."""
        telemetry = getattr(self.pipeline, "telemetry", None)
        return telemetry if telemetry is not None else NO_SPANS

    def _weights_interval(self, source: str, node: str | None = None):
        """The `aiko:setup.weights` interval around the making of this
        element's state (or of a second model `node` names, a draft);
        `source` is init | load | restore."""
        return setup_interval("weights", self._seam().span(
            "setup.weights", node=node or self.definition.name,
            source=source))

    def _ensure_ready(self):
        if self._compiled is not None:
            return
        self.configure()
        if self.state is None:  # restore_state may have installed it
            source = "load" if self.get_parameter("weights") else "init"
            with self._weights_interval(source) as interval:
                state = self.setup()
                if state is not None and self.mesh is not None:
                    state = shard_pytree(state, self.mesh,
                                         self._state_spec)
                self.state = state
                interval.holds(state)
        signature = inspect.signature(self.compute)
        self._accepts_lengths = "lengths" in signature.parameters

        def _call(state, dynamic, kwargs):
            outputs = self.compute(state, **dynamic, **kwargs)
            if not isinstance(outputs, dict):
                raise TypeError(
                    f"{self.definition.name}.compute must return a dict")
            return outputs

        self._compiled = jax.jit(_call)

    def _note_compile(self, waited_s: float, programs: int,
                      args: dict) -> None:
        """This element's own jitted call compiled (a new signature of
        its inputs): the `aiko:compile` mark that closes it."""
        self._seam().mark("compile", waited_s,
                           node=self.definition.name, what="element",
                           **args)

    def _place_inputs(self, inputs: dict) -> tuple[dict, dict]:
        """Returns (placed inputs, padding info {name: (axis, original)})."""
        placed, padding = {}, {}
        for name, value in inputs.items():
            if isinstance(value, (np.ndarray, jnp.ndarray)) or hasattr(
                    value, "__jax_array__"):
                axis = self._bucket_axes.get(name)
                if axis is not None:
                    original = value.shape[int(axis)]
                    target = bucket_length(
                        original, self._bucket_min, self._buckets)
                    if target != original:
                        value = pad_axis_to(value, int(axis), target)
                        padding[name] = (int(axis), original)
                spec = self._input_specs.get(name)
                if self.mesh is not None and spec is not None:
                    sharding = named_sharding(self.mesh, spec)
                    try:
                        sharding.shard_shape(tuple(value.shape))
                    except ValueError:
                        # dim not divisible by its mesh axis: replicate
                        # rather than fail the frame -- but say so, this
                        # forfeits the parallelism the definition asked for
                        if name not in self._replicated_warned:
                            self._replicated_warned.add(name)
                            _LOGGER.warning(
                                "%s: input '%s' shape %s not divisible by "
                                "mesh axes %s; running REPLICATED",
                                self.definition.name, name,
                                tuple(value.shape), sharding.spec)
                        value = jnp.asarray(value)
                    else:
                        value = jax.device_put(value, sharding)
                elif isinstance(value, np.ndarray):
                    value = jnp.asarray(value)
            placed[name] = value
        return placed, padding

    def _unpad(self, outputs: dict, inputs: dict, padding: dict) -> dict:
        """Slice bucket padding back off: any output array whose bucketed
        axis has exactly the padded input's size is restored to the
        original length (opt out with unpad_outputs=false)."""
        if not padding or not self._unpad_outputs:
            return outputs
        result = {}
        for name, value in outputs.items():
            # every padded axis is restored (an output may carry several
            # bucketed axes, e.g. an outer product of two padded inputs)
            sliced_axes: set = set()
            for input_name, (axis, original) in padding.items():
                padded_size = inputs[input_name].shape[axis]
                if (hasattr(value, "shape") and value.ndim > axis
                        and axis not in sliced_axes
                        and value.shape[axis] == padded_size):
                    index = [slice(None)] * value.ndim
                    index[axis] = slice(0, original)
                    value = value[tuple(index)]
                    sliced_axes.add(axis)
            result[name] = value
        return result

    def group_kernel(self, stream: Stream):
        """Fused whole-group execution for free: compute() exposed as a
        batch-in/batch-out kernel so the micro-batch scheduler traces
        concat+pad+compute+split as ONE program (PipelineElement
        .group_kernel contract).  State and dynamic parameters ride the
        traced `context` -- never baked-in constants -- so checkpoint
        restores and live parameter updates apply without a stale
        executable.  Elements whose engine path does host-side per-frame
        work fall back to the chained path: bucket padding and `lengths`
        masks depend on pre-padding sizes, meshed inputs need NamedSharding
        placement, blocking_metrics promises an in-window
        block_until_ready, and a custom process_frame override means
        compute() alone would not reproduce the element's behavior."""
        if (self._bucket_axes or self.mesh is not None
                or self._blocking_metrics):
            return None
        if (type(self).process_frame is not ComputeElement.process_frame
                or type(self).compute is ComputeElement.compute):
            return None
        self._ensure_ready()
        if self._accepts_lengths:
            return None
        if self._group_kernel_fn is None:
            def kernel(context, **batch):
                state, dynamic = context
                outputs = self.compute(state, **dynamic, **batch)
                if not isinstance(outputs, dict):
                    raise TypeError(
                        f"{self.definition.name}.compute must return "
                        f"a dict")
                return outputs

            self._group_kernel_fn = kernel
        dynamic = {
            key: jnp.asarray(value)
            for key, value in self.dynamic_parameters(stream).items()}
        return self._group_kernel_fn, (self.state, dynamic)

    def eval_kernel(self):
        """Abstract-interpretation hook for the static analyzer
        (PipelineElement.eval_kernel contract): compute() exposed with
        its state BUILDER so the analyzer can dry-run
        setup-then-compute entirely under jax.eval_shape -- no
        parameter allocation, no compile, no device.  Elements whose
        engine path depends on runtime sizes (bucket padding, `lengths`
        masks) or a custom process_frame fall out: compute() alone
        would not reproduce their behavior."""
        if (type(self).compute is ComputeElement.compute
                or type(self).process_frame
                is not ComputeElement.process_frame):
            return None
        if self._bucket_axes or "lengths" in inspect.signature(
                self.compute).parameters:
            return None
        self.configure()

        def kernel(state, **batch):
            dynamic = {
                key: jnp.asarray(value)
                for key, value in self.dynamic_parameters(None).items()}
            return self.compute(state, **dynamic, **batch)

        return kernel, self.setup

    def _cached_group_kernel(self, key, build):
        """Per-static-parameter-value kernel cache for group_kernel
        overrides (e.g. one kernel per max_tokens): a STABLE kernel
        identity per value keeps the scheduler's compiled fused program
        (and every executable under it) cached across groups."""
        kernels = getattr(self, "_group_kernels", None)
        if kernels is None:
            kernels = self._group_kernels = {}
        kernel = kernels.get(key)
        if kernel is None:
            kernel = kernels[key] = build()
        return kernel

    def restore_state(self, state) -> None:
        """Install checkpointed state (numpy pytree from Checkpointer),
        re-placing it on the element's mesh.  Installed BEFORE
        _ensure_ready so setup() never allocates a fresh params pytree
        that would double peak HBM on the restore path."""
        self.configure()  # state specs / config must exist before placing
        if state is not None:
            with self._weights_interval("restore") as interval:
                if self.mesh is not None:
                    state = shard_pytree(state, self.mesh,
                                         self._state_spec)
                else:
                    state = jax.tree_util.tree_map(jnp.asarray, state)
                self.state = state
                interval.holds(state)
        self._ensure_ready()

    def process_frame(self, stream: Stream, **inputs) -> tuple:
        self._ensure_ready()
        host_start = time.perf_counter()
        placed, padding = self._place_inputs(inputs)
        dynamic = {
            key: jnp.asarray(value)
            for key, value in self.dynamic_parameters(stream).items()}
        if self._accepts_lengths:
            dynamic["lengths"] = {
                name: jnp.int32(inputs[name].shape[int(axis)])
                for name, axis in self._bucket_axes.items()
                if name in inputs}
        try:
            # TraceAnnotation: per-element spans in jax.profiler traces
            # (SURVEY.md section 5 tracing parity).  The element's mesh
            # becomes the AMBIENT mesh for the compiled call, so compute
            # bodies may use shard_map collectives with mesh=None (ring
            # attention, sp decode -- the long-context path).
            mesh_scope = (jax.set_mesh(self.mesh)
                          if self.mesh is not None
                          else contextlib.nullcontext())
            with mesh_scope, jax.profiler.TraceAnnotation(
                    f"element:{self.definition.name}"), \
                    compile_bracket(self._note_compile):
                outputs = self._compiled(self.state, dynamic, placed)
        except TypeError as error:
            bad = {name: type(value).__name__
                   for name, value in placed.items()
                   if not hasattr(value, "shape")
                   and not isinstance(value, (bool, int, float, complex,
                                              list, tuple))}
            if bad:
                raise TypeError(
                    f"{self.definition.name}: inputs {bad} are not JAX "
                    f"types; ComputeElement inputs must be arrays or "
                    f"numbers (route strings/objects around compute "
                    f"elements with map_in/map_out)") from error
            raise
        outputs = self._unpad(outputs, placed, padding)
        block_elapsed = None
        if self._blocking_metrics:
            block_start = time.perf_counter()
            outputs = jax.block_until_ready(outputs)
            block_elapsed = time.perf_counter() - block_start
        elapsed = time.perf_counter() - host_start
        telemetry = getattr(self.pipeline, "telemetry", None)
        if telemetry is None or telemetry.enabled:
            stream.variables.setdefault("compute_seconds", {})[
                self.definition.name] = elapsed
        if telemetry is not None:
            telemetry.record_device(self.definition.name, elapsed,
                                    block_elapsed)
        return StreamEvent.OKAY, outputs
