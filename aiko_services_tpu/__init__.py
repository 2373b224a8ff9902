# aiko_services_tpu: a TPU-native distributed ML pipeline framework.
#
# Brand-new implementation with the capabilities of the reference
# aiko_services (distributed actor-model services, registrar discovery,
# eventually-consistent state shares, streaming ML pipelines), redesigned
# around a JAX/XLA data plane: element compute runs as jit-compiled JAX
# functions on TPU, inter-element tensors stay HBM-resident as jax.Array,
# multi-stage graphs shard over a jax.sharding.Mesh, and the S-expression
# control plane rides a pluggable transport (in-process loopback broker by
# default; MQTT when available).
#
# Layering (see SURVEY.md section 1 for the reference layer map):
#   utils/     L0 kernel utilities (sexpr codec, graph, config, logging)
#   transport/ L1 message transports (loopback broker, MQTT, null)
#   runtime/   L2-L8 event engine, process, service, actor, share, registrar
#   observe/   telemetry: metrics registry, frame tracer, live export
#   analyze/   definition-time static analysis: typed tensor ports,
#              shape-flow verification, actor-safety lint (aiko lint)
#   pipeline/  L9 pipeline engine: streams, frames, elements, graphs
#   serve/     L10 serving tier: gateway (admission, routing, failover)
#   ops/       TPU ops: attention, mel spectrogram, image, pallas kernels
#   parallel/  mesh management, sharding specs, collectives, ring attention
#   models/    flagship model families: LLM (Llama-style), Whisper, YOLO
#   elements/  pipeline elements: media I/O + ML elements over models/

import time as _time

# The process epoch: the zero of the start-up gauges (`setup.boot_s`,
# `setup.ready_s`; runtime/compile_cache.py).  First statement of the
# package, which imports nothing else, so it lies within the
# interpreter's own start of whatever imported it.
PROCESS_EPOCH = _time.perf_counter()

__version__ = "0.1.0"
