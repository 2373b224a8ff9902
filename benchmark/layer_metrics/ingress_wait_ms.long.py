"""Mean time a request waits between the gateway's dispatch and
`DecodeEngine.submit`, where the engine's own `queue_wait` clock starts:
the replica's mailbox while its loop sits in a decode step (what the
`aiko:ingress` + `aiko:engine.submit` marks measured, carried as
`ingress_us` on the request's `aiko:engine.chunk` marks), over the
requests that published a chunk inside the traced window, ms.  None under
3 of them."""
from benchmark.harness.program_spans import ingress_wait_ms as read  # noqa: F401
