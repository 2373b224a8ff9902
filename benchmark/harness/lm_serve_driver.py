"""Drive one cell of a served LM: Gateway -> pipeline replica ->
LMGenerate(continuous, stream_tokens) -> DecodeEngine -> paged KV pool.

The harness is the clients: it sends each request when it is due (open
loop) or when a caller is free (closed loop), listens to the replica's
`token_chunk` stream from a process of its own, and times everything from
when a request was due.
"""

from __future__ import annotations

import gc
import queue
import statistics
import threading
import time

import numpy as np

from . import common, estimators, roofline, traffic as traffic_mod
from .common import log, span

ELEMENTS = "aiko_services_tpu.elements"
RESPONSE_TIMEOUT_S = 300.0
# tokens asked of a warm-up request: one full chunk of the stream and one
# more, so the chunk publisher and the final flush have both run
WARM_TOKENS = 9


def prompt_bucket(length: int, block: int, max_context: int) -> int:
    """The engine's prefill bucket for a prompt (decode/engine.py
    `_bucket`): the block size doubled until it covers the prompt,
    clamped to the context."""
    padded = block
    while padded < length:
        padded *= 2
    return min(-(-padded // block) * block, max_context)


def definition(config: dict, seed: int, max_new: int) -> dict:
    shape = roofline.lm_shape(config)
    serve = config["serve"]
    parameters = {
        "vocab_size": shape["vocab"], "d_model": shape["d"],
        "n_layers": shape["layers"], "n_heads": shape["heads"],
        "n_kv_heads": shape["kv_heads"], "d_ff": shape["ff"],
        "max_seq_len": serve["max_context"],
        "dtype": config.get("torch_dtype", "bfloat16"), "seed": seed,
        "decode_slots": serve["decode_slots"],
        "kv_block_size": serve["kv_block_size"],
        "kv_blocks": serve["kv_blocks"],
        "max_context": serve["max_context"],
        "continuous": True, "stream_tokens": True,
        "max_new_tokens": max_new,
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
            "deploy": {"local": {"module": ELEMENTS,
                                 "class_name": "LMGenerate"}}}],
    }


class _Clients:
    """The sending and listening side of a run."""

    def __init__(self, gateway, vocab: int):
        self.gateway = gateway
        self.vocab = vocab
        self.records: dict = {}
        self.results: queue.Queue = queue.Queue()
        self.freed: queue.Queue = queue.Queue()   # closed loop: a caller is free
        self.done = threading.Condition()
        self._collector = threading.Thread(
            target=self._collect, daemon=True, name="bench-collector")
        self._collector.start()

    def on_out(self, _topic, payload) -> None:
        """`(token_chunk stream frame row offset (tokens))` from the
        replica's /out: one arrival of `len(tokens)` tokens."""
        from aiko_services_tpu.utils import parse
        now = time.perf_counter()
        try:
            command, parameters = parse(payload)
        except ValueError:
            return
        if command != "token_chunk" or len(parameters) < 5:
            return
        record = self.records.get(str(parameters[0]))
        if record is not None:
            ids = [int(token) for token in parameters[4][0]]
            record["chunks"].append((now, len(ids)))
            if ids and not 0 <= min(ids) <= max(ids) < self.vocab:
                record["bad_chunk"] = True

    def send(self, stream_id: str, request, due: float, phase: str) -> None:
        record = {"stream_id": stream_id, "index": request.index,
                  "due": due, "prompt": request.prompt,
                  "answer": request.answer_tokens, "chunks": [],
                  "phase": phase, "status": None, "tokens": None,
                  "done_at": None, "bad_chunk": False}
        self.records[stream_id] = record
        with span("bench:submit"):
            record["sent_at"] = time.perf_counter()
            self.gateway.submit_stream(
                stream_id, {"max_new_tokens": request.answer_tokens},
                queue_response=self.results)
            self.gateway.submit_frame(
                stream_id, {"tokens": request.prompt[None]}, frame_id=0)

    def _collect(self) -> None:
        while True:
            item = self.results.get()
            if item is None:
                return
            now = time.perf_counter()
            stream_id, _, outputs, status = item
            record = self.records.get(str(stream_id))
            if record is None:
                continue
            with span("bench:readback"):
                tokens = (np.asarray(outputs["generated"])
                          if status == "ok" and isinstance(outputs, dict)
                          and "generated" in outputs else None)
            record["tokens"], record["status"] = tokens, status
            record["done_at"] = now
            # through the gateway's mailbox, like every other client call
            self.gateway.post_message("destroy_stream", [str(stream_id)])
            self.freed.put(now)
            with self.done:
                self.done.notify_all()

    def wait_for(self, stream_ids, timeout: float) -> bool:
        deadline = time.perf_counter() + timeout
        with self.done:
            while any(self.records[sid]["done_at"] is None
                      for sid in stream_ids):
                left = deadline - time.perf_counter()
                if left <= 0:
                    return False
                self.done.wait(timeout=min(left, 0.5))
        return True

    def close(self) -> None:
        self.results.put(None)
        self._collector.join(timeout=10)

    def answer_ok(self, record) -> bool:
        """A finished request answered in full and in range; one still
        streaming when the run stopped waiting has streamed in range."""
        if not record["chunks"] or record["bad_chunk"]:
            return False
        if record["done_at"] is None:
            return True
        tokens = record["tokens"]
        return (record["status"] == "ok" and tokens is not None
                and tokens.shape == (1, record["answer"])
                and int(tokens.min()) >= 0
                and int(tokens.max()) < self.vocab)

    def wait_first_chunks(self, stream_ids, timeout: float) -> bool:
        deadline = time.perf_counter() + timeout
        while any(not self.records[sid]["chunks"] for sid in stream_ids):
            if time.perf_counter() >= deadline:
                return False
            time.sleep(0.05)
        return True


def _sleep_until(moment: float) -> None:
    left = moment - time.perf_counter()
    if left > 0:
        with span("bench:wait_next_arrival"):
            time.sleep(left)


class _Sampler:
    """Reads the engine's occupancy every 50 ms from a thread of its
    own (plain Python ints, read across threads)."""

    def __init__(self, element):
        self.element = element
        self.samples = []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="bench-sampler")
        self._thread.start()

    def _run(self) -> None:
        while not self._stop.wait(0.05):
            stats = self.element.engine_stats()
            if stats is not None:
                self.samples.append((time.perf_counter(),
                                     stats["waiting"],
                                     stats["active_slots"],
                                     stats["free_blocks"]))

    def stop(self) -> list:
        self._stop.set()
        self._thread.join(timeout=5)
        return self.samples


def _live_positions(records, moments) -> float:
    """Mean over `moments` of the cached positions the live requests
    hold: prompt plus what each has generated by then."""
    totals = []
    for moment in moments:
        total = 0.0
        for record in records:
            if not record["chunks"] or record["done_at"] is None:
                continue
            first = record["chunks"][0][0]
            if not first <= moment < record["done_at"]:
                continue
            share = (moment - first) / max(record["done_at"] - first, 1e-9)
            total += len(record["prompt"]) + record["answer"] * share
        totals.append(total)
    return statistics.fmean(totals) if totals else 0.0


def _build_system(config: dict, seed: int, max_new: int, vocab: int):
    """Registrar, replica pipeline, gateway and a client process that
    listens to the replica's /out, each with an event loop of its own."""
    from aiko_services_tpu.pipeline import create_pipeline
    from aiko_services_tpu.runtime import Process, Registrar
    from aiko_services_tpu.serve import Gateway

    processes = [Process(transport_kind="loopback") for _ in range(4)]
    Registrar(processes[0], search_timeout=0.05)
    replica = create_pipeline(processes[1],
                              definition(config, seed, max_new))
    gateway = Gateway(processes[2],
                      policy=config["serve"]["gateway_policy"],
                      metrics_interval=60.0)
    gateway.attach_replica(replica)
    element = replica.elements["lm"]
    clients = _Clients(gateway, vocab)
    processes[3].add_message_handler(clients.on_out,
                                     f"{element.topic_path}/out")
    threads = [process.run(in_thread=True) for process in processes]
    element.configure()
    return processes, threads, gateway, replica, element, clients


def _warm_up(clients: _Clients, requests: list, serve: dict) -> list:
    """The longest prompt of every prefill bucket this run's requests
    fall into, all at once, so the decode step has run with several
    slots live too; nothing else is compiled."""
    block, context = serve["kv_block_size"], serve["max_context"]
    by_bucket: dict = {}
    for request in requests:
        bucket = prompt_bucket(len(request.prompt), block, context)
        if bucket not in by_bucket \
                or len(request.prompt) > len(by_bucket[bucket].prompt):
            by_bucket[bucket] = request
    warm_ids = []
    for bucket, request in sorted(by_bucket.items()):
        stream_id = f"warm{bucket}"
        clients.send(stream_id, traffic_mod.Request(
            -1, None, request.prompt,
            min(WARM_TOKENS, context - len(request.prompt))),
            time.perf_counter(), "warm")
        warm_ids.append(stream_id)
    if not clients.wait_for(warm_ids, RESPONSE_TIMEOUT_S):
        raise SystemExit("benchmark: warm-up requests did not finish")
    bad = [sid for sid in warm_ids
           if not clients.answer_ok(clients.records[sid])]
    if bad:
        raise SystemExit(f"benchmark: warm-up requests failed: {bad}")
    log(f"warmed prefill buckets {sorted(by_bucket)} and the decode step")
    while not clients.freed.empty():
        clients.freed.get()
    return warm_ids


def _offer_closed(clients: _Clients, requests: list, arrivals: dict,
                  load_start: float, window_start: float,
                  window_end: float) -> list:
    """`callers` callers, each sending its next request when its reply
    has come; a request is due when its caller became free."""
    for _ in range(int(arrivals["callers"])):
        clients.freed.put(load_start)
    measured, sent = [], 0
    while True:
        freed_at = clients.freed.get(timeout=RESPONSE_TIMEOUT_S)
        if max(freed_at, time.perf_counter()) >= window_end:
            return measured
        in_window = freed_at >= window_start
        stream_id = f"r{sent}"
        clients.send(stream_id, requests[sent % len(requests)], freed_at,
                     "window" if in_window else "outside")
        if in_window:
            measured.append(stream_id)
        sent += 1


def _offer_open(clients: _Clients, requests: list, arrivals: dict,
                load_start: float, window_start: float,
                window_end: float) -> list:
    """Every request at its own due time, whatever has come back; load
    goes on past the window until the measured requests are under way."""
    measured = []
    for request in requests:
        due = load_start + request.due_s
        if due >= window_end and clients.wait_for(measured, 0.0):
            break  # every measured request is done: stop offering
        _sleep_until(due)
        stream_id = f"r{request.index}"
        in_window = window_start <= due < window_end
        clients.send(stream_id, request, due,
                     "window" if in_window else "outside")
        if in_window:
            measured.append(stream_id)
    return measured


def run(cell, manifest: dict, *, seed: int, seconds: float, trace: bool,
        started_at: float, out_dir: str, require_tpu: bool = True) -> str:
    """One run of one LM serving cell; returns the result line."""
    from aiko_services_tpu.runtime import enable_compile_cache

    device = common.device_facts(cell.chips, require_tpu)
    log(f"compile cache at {enable_compile_cache()}")
    config, mix = cell.config, cell.traffic
    serve = config["serve"]
    shape = roofline.lm_shape(config)
    arrivals = mix["arrivals"]
    closed = arrivals["process"] == "closed"
    warm_in = float(mix.get("warm_in_s", 0.0))
    drain = float(mix.get("drain_s", 10.0))
    requests = traffic_mod.requests(
        mix, seed, warm_in + seconds + drain, shape["vocab"])
    max_new = int(max(request.answer_tokens for request in requests))

    (processes, threads, gateway, replica, element,
     clients) = _build_system(config, seed, max_new, shape["vocab"])
    warm_ids = _warm_up(clients, requests, serve)

    registry = replica.telemetry.registry
    engine_before = dict(element.engine_stats())
    hist_names = ("decode.queue_wait_s", "decode.prefill_s")
    hist_before = {name: common.histogram_totals(registry, name)
                   for name in hist_names}
    stages_before = gateway.telemetry.stream_decomposition()["_total"]
    compiles_before = common.compile_requests()
    sampler = _Sampler(element)

    load_start = time.perf_counter()
    window_start = load_start + warm_in
    window_end = window_start + seconds
    tracer = common.start_tracer(trace, out_dir, cell,
                                 warm_in + 0.4 * seconds, seconds)

    offer = _offer_closed if closed else _offer_open
    measured = offer(clients, requests, arrivals, load_start, window_start,
                     window_end)
    setup_s = window_start - started_at
    # an answer of 256 tokens streams for tens of seconds: the window's
    # tokens are what arrived inside it.  Wait only for the measured
    # requests' first chunks (a closed loop's few callers: their replies)
    if closed:
        finished = clients.wait_for(measured, drain)
    else:
        finished = clients.wait_first_chunks(measured, drain)
    samples = sampler.stop()
    compiles_in_window = common.compile_requests() - compiles_before
    engine_after = dict(element.engine_stats())
    hist = {}
    for name in hist_names:
        count, total = common.histogram_totals(registry, name)
        hist[name] = (count - hist_before[name][0],
                      total - hist_before[name][1])
    # the gateway's own decomposition of where streams' time went, ms
    stages_after = gateway.telemetry.stream_decomposition()["_total"]
    gateway_stage_ms = {stage: stages_after.get(stage, 0.0)
                        - stages_before.get(stage, 0.0)
                        for stage in stages_after}
    trace_path = tracer.finish() if tracer else None
    peak = common.memory_peak_bytes(cell.chips)

    records = [clients.records[sid] for sid in measured]
    good = [record for record in records if clients.answer_ok(record)]
    attempted, failed = len(records), len(records) - len(good)
    log(f"window: attempted={attempted} failed={failed} "
        f"all_finished={finished} compiles_in_window={compiles_in_window} "
        f"engine_compiles={engine_after['compiles'] - engine_before['compiles']} "
        f"preempted={engine_after['preempted'] - engine_before['preempted']}")

    ttft = [record["chunks"][0][0] - record["due"] for record in good]
    # per-token gaps of every chunk that arrived inside the window, of
    # any request the load offered (those sent while it warmed in too)
    gaps = []
    for record in clients.records.values():
        if record["phase"] == "warm":
            continue
        chunks = list(record["chunks"])
        for (arrival, _), gap in zip(chunks[1:],
                                     estimators.token_gaps(chunks)):
            if window_start <= arrival <= window_end:
                gaps.append(gap)
    late = [record["sent_at"] - record["due"] for record in records]
    values = {"setup_s": setup_s}
    # the median first-chunk time goes by the name the mix gives it: a
    # mix of long prompts is judged apart from chat's
    ttft_name = mix.get("ttft_metric", "ttft_p50_ms")
    if ttft:
        values[ttft_name] = statistics.median(ttft) * 1e3
    if gaps:
        values["token_gap_p50_ms"] = statistics.median(gaps) * 1e3
        log(f"token gap per token: p50={values['token_gap_p50_ms']:.4f} "
            f"ms p95={estimators.percentile(gaps, 95) * 1e3:.4f} ms over "
            f"{len(gaps)} chunks; first-chunk p50="
            f"{values[ttft_name]:.3f} ms p95="
            f"{estimators.percentile(ttft, 95) * 1e3:.3f} ms over "
            f"{len(ttft)} requests")
    growth = estimators.queue_growth(
        [(at, waiting) for at, waiting, _, _ in samples],
        window_start, seconds)
    if growth:
        log(f"engine queue: first third mean={growth[0]:.3f} "
            f"last sixth mean={growth[1]:.3f}")

    offered = len(clients.records) - len(warm_ids)
    # requests still streaming are cancelled through the gateway, and the
    # engine is let run dry, before anything is torn down under it
    for stream_id, record in clients.records.items():
        if record["done_at"] is None:
            gateway.post_message("destroy_stream", [stream_id])
    quiet_by = time.perf_counter() + 15.0
    while time.perf_counter() < quiet_by:
        stats = element.engine_stats()
        if not stats["active_slots"] and not stats["waiting"]:
            break
        time.sleep(0.1)
    # the program's state goes before the reference comes
    clients.close()
    common.stop_processes(processes, threads)
    finished_ok = [record for record in clients.records.values()
                   if record["phase"] != "warm"
                   and record["done_at"] is not None
                   and clients.answer_ok(record)]
    sample = _check_sample(finished_ok, seed,
                           int(mix.get("check_requests", 4)))
    del clients, element, replica, gateway, processes, threads
    gc.collect()
    correct = common.check_served(
        cell, config, seed,
        [(record["prompt"], record["tokens"][0]) for record in sample],
        _reference_length(mix), f"{len(sample)} requests")
    if compiles_in_window or engine_after["compiles"] \
            != engine_before["compiles"]:
        log("a program compiled inside the window: the run is not correct")
        correct = False

    traced = [moment for moment, *_ in samples
              if tracer.started_at <= moment <= tracer.stopped_at] \
        if tracer and tracer.started_at else []
    return common.report(
        manifest, cell, tracer=tracer, trace_path=trace_path,
        correct=correct, attempted=attempted, failed=failed,
        device=dict(device, memory_peak_bytes=peak), end_to_end=values,
        recorded=dict(
            seconds=seconds, records=records, good=good, late_s=late,
            ttft_s=ttft, gaps_s=gaps, samples=samples,
            window=(window_start, window_end), hist=hist,
            gateway_stage_ms=gateway_stage_ms, streams_closed=offered,
            engine_before=engine_before, engine_after=engine_after,
            slots=serve["decode_slots"], shape=shape,
            live_positions=_live_positions(records, traced)))


def _reference_length(mix: dict) -> int:
    """One fixed length per mix for the reference's pass: the longest
    prompt and answer the mix can make, rounded up to 256."""
    longest = (int(mix["prompt_tokens"].get(
        "max", mix["prompt_tokens"].get("value", 0)))
        + int(mix["answer_tokens"].get(
            "max", mix["answer_tokens"].get("value", 0))))
    return -(-longest // 256) * 256


def _check_sample(good: list, seed: int, count: int) -> list:
    """A seeded sample of finished requests, the longest among them."""
    if not good:
        return []
    longest = max(good, key=lambda record: len(record["prompt"])
                  + record["answer"])
    others = [record for record in good if record is not longest]
    rng = np.random.default_rng(seed + 2)
    picked = [others[index] for index in rng.permutation(
        len(others))[:max(count - 1, 0)]]
    return [longest] + picked
