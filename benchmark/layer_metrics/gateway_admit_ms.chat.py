"""Mean time a request spends in the gateway before its replica has it:
the `admit`, `route` and `queue` stages of the gateway's own per-stream
decomposition (`GatewayTelemetry.stream_decomposition`), summed over the
run's load and divided by the streams it offered, ms.  (The histogram
`gateway.admit_latency_s` is not this: it runs from admission to the
frame's completion, so it is the whole request.)"""


def read(run):
    stages = run.gateway_stage_ms
    if not stages or not run.streams_closed:
        return None
    return sum(stages.get(stage, 0.0)
               for stage in ("admit", "route", "queue")) / run.streams_closed
