# Prefill/decode disaggregation: the prefill half of the split fleet.
#
# Production serving splits prefill and decode into separate replica
# pools (DistServe OSDI'24, Splitwise ISCA'24) because a long prompt's
# compute-bound prefill kernel convoys every co-scheduled decode slot
# -- the longcontext roofline on record is 1.94 s of kernel time for a
# 16k prompt.  Chunked prefill (PR 10) bounds the stall but still
# spends decode-replica cycles on prompt compute; disaggregation moves
# the prompt compute onto a PREFILL pool entirely and streams the
# finished prompt's paged KV blocks to a decode replica over the
# transfer plane (pipeline/transfer.py).
#
#   PrefillEngine   runs paged_prefill / paged_prefill_chunk into its
#                   own paged pool, one request at a time (a prefill
#                   replica's whole job is the prompt kernel; there is
#                   no co-scheduled decode to protect), and EXPORTS the
#                   finished prompt's KV blocks as a `__tensorref__`
#                   descriptor tree -- one descriptor per (block, pool
#                   leaf), so int8 KV (codes + scales) carries through
#                   unchanged
#   fetch_kv_blocks the decode-side half: pulls a handoff's whole
#                   descriptor tree through fetch_many (ONE connection
#                   per producing peer, not one TCP handshake per
#                   block) and restacks it into per-leaf arrays shaped
#                   for a pool scatter
#
# DecodeEngine.adopt_request (engine.py) consumes the handoff: blocks
# fetched into a free slot, block table rewritten, greedy decode
# continues from the prompt end BIT-IDENTICALLY to the co-located
# engine -- the transferred K/V are exact copies of what a local
# prefill would have written, and the writes-before-gather invariant
# covers the garbage tail of the last prompt block exactly as it
# covers local prefill's bucket padding.
#
# The handoff record is JSON-safe end to end (prompt token list +
# descriptor dicts), so it rides the ordinary frame codec between
# gateway, prefill replica, and decode replica.

from __future__ import annotations

import time

from collections import deque

import numpy as np

from ..models import (
    RECORD_COUNTERS, init_paged_pool, paged_prefill, paged_prefill_chunk,
    prefill_record)
from ..observe.trace import NO_SPANS
from ..pipeline.transfer import fetch_many, get_transfer_server
from ..runtime.compile_cache import compile_bracket, setup_interval
from ..utils import get_logger
from ..utils.padding import bucket_length
from .blocks import TRASH_BLOCK, BlockManager

__all__ = ["HANDOFF_SCHEMA", "PrefillEngine", "fetch_kv_blocks",
           "offer_pool_blocks"]

_LOGGER = get_logger("prefill_engine")

HANDOFF_SCHEMA = "aiko.kv_handoff/1"


def offer_pool_blocks(pool: dict, block_ids) -> tuple:
    """Offer `block_ids`' slices of every pool leaf on this process's
    transfer server as RAW descriptors (never `{__tensorref__: ...}`
    marker nodes -- see fetch_kv_blocks); returns (kv_blocks, bytes)
    where kv_blocks is one {leaf_name: descriptor} dict per block.
    Shared by PrefillEngine's handoff export and the decode-state
    checkpointer (decode/checkpoint.py): one device->host gather per
    leaf, then per-block host views."""
    server = get_transfer_server()
    block_ids = np.asarray(block_ids)
    host = {name: np.asarray(leaf[:, block_ids])
            for name, leaf in pool.items()}
    kv_blocks = []
    total_bytes = 0
    for index in range(len(block_ids)):
        entry = {}
        for name in sorted(host):
            view = host[name][:, index]
            total_bytes += view.nbytes
            entry[name] = server.offer(view)
        kv_blocks.append(entry)
    return kv_blocks, total_bytes


def fetch_kv_blocks(handoff: dict, timeout: float | None = None) -> dict:
    """Fetch a handoff's KV blocks in ONE batched round trip per peer
    and restack them for the pool scatter: returns {leaf_name: array of
    shape (n_layers, n_blocks, ...)} matching init_paged_pool's leaf
    layout.  Raises KeyError/TransferError exactly like fetch_many --
    the adopting engine turns either into a local re-prefill.

    The handoff carries RAW transfer descriptors (the {host, port,
    key, dtype, shape} dicts fetch() consumes), deliberately NOT
    `{__tensorref__: ...}` marker nodes: the frame codec eagerly
    materializes marker nodes one fetch at a time on the consumer's
    event loop, which would both serialize the migration and strip
    the descriptors before adopt_request ever saw them."""
    blocks = handoff["kv_blocks"]
    if not blocks:
        raise ValueError("handoff carries no KV blocks")
    names = sorted(blocks[0])
    descriptors = [block[name] for block in blocks for name in names]
    arrays = fetch_many(descriptors, timeout=timeout)
    leaves = {}
    for offset, name in enumerate(names):
        per_block = arrays[offset::len(names)]
        # (n_blocks, n_layers, heads, block, depth) -> pool layout
        # (n_layers, n_blocks, heads, block, depth)
        leaves[name] = np.stack(per_block, axis=1)
    return leaves


class _PrefillJob:
    __slots__ = ("request_id", "prompt", "max_new", "true_len",
                 "bucket", "padded", "blocks", "prefill_pos",
                 "submitted_at", "started_at")

    def __init__(self, request_id, prompt, max_new):
        self.request_id = request_id
        self.prompt = prompt
        self.max_new = int(max_new)
        self.true_len = int(prompt.size)
        self.bucket = 0
        self.padded = None
        self.blocks: list = []
        self.prefill_pos = 0
        self.submitted_at = time.perf_counter()
        self.started_at: float | None = None


class PrefillEngine:
    """Single-flight prompt prefill over a private paged pool.

    Shapes fixed at construction like DecodeEngine's (one block table
    row wide enough for max_context), so a warmed prefill replica
    never recompiles.  step() advances the active job by one chunk
    (or the whole prompt when chunking is off) and returns the list of
    handoff records that finished this tick -- each with the prompt's
    KV blocks ALREADY offered on the transfer plane and the job's
    blocks returned to the free list (the transfer server holds host
    copies for the offer ttl; a handoff nobody adopts costs linger
    memory, never pool capacity)."""

    def __init__(self, params, config, *, kv_block_size: int = 16,
                 kv_blocks: int | None = None,
                 max_context: int | None = None,
                 prefill_chunk_size: int | None = None, registry=None,
                 spans=None, node: str = "prefill"):
        from .engine import refuse_recurrent
        refuse_recurrent(config, "a disaggregated prefill (its hand-off "
                         "carries K/V blocks, not the state)")
        self.params = params
        self.config = config
        max_context = int(max_context or config.max_seq_len)
        self.max_blocks = -(-max_context // int(kv_block_size))
        self.max_context = self.max_blocks * int(kv_block_size)
        if kv_blocks is None:
            kv_blocks = self.max_blocks + 1
        self.blocks = BlockManager(int(kv_blocks), int(kv_block_size))
        # the owning pipeline's telemetry seam and this engine's name
        # on its start-up spans and `aiko:compile` marks, as
        # DecodeEngine's
        self._spans = spans if spans is not None else NO_SPANS
        self._node = node
        with setup_interval("state", self._spans.span(
                "setup.state", node=node, what="pool",
                blocks=self.blocks.num_blocks)) as interval:
            self.pool = init_paged_pool(config, self.blocks.num_blocks,
                                        self.blocks.block_size)
            self.table = np.full((self.max_blocks,), TRASH_BLOCK,
                                 np.int32)
            interval.holds(self.pool)
        self.waiting: deque[_PrefillJob] = deque()
        self._active: _PrefillJob | None = None
        self._registry = registry
        if prefill_chunk_size is not None:
            chunk = bucket_length(int(prefill_chunk_size),
                                  minimum=self.blocks.block_size)
            self.prefill_chunk = int(min(chunk, self.max_context))
        else:
            self.prefill_chunk = None
        self.counters = {"submitted": 0, "exported": 0, "chunks": 0,
                         "compiles": 0, "exported_bytes": 0,
                         **RECORD_COUNTERS}

    @property
    def compile_count(self) -> int:
        return self.counters["compiles"]

    def _compiling(self, what: str):
        return compile_bracket(self._note_compiles, what)

    def _note_compiles(self, waited_s: float, programs: int, args: dict,
                       what: str) -> None:
        self.counters["compiles"] += programs
        self._bump("prefill.compiles", programs)
        self._spans.mark("compile", waited_s, node=self._node, what=what,
                         **args)

    def _bump(self, name: str, amount) -> None:
        if self._registry is not None:
            self._registry.counter(name).inc(amount)

    # -- submission --------------------------------------------------------

    def _bucket(self, true_len: int) -> int:
        block = self.blocks.block_size
        padded = bucket_length(true_len, minimum=block)
        return min(-(-padded // block) * block, self.max_context)

    def submit(self, request_id, prompt_tokens, max_new_tokens: int):
        prompt = np.asarray(prompt_tokens, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError(f"{request_id}: empty prompt")
        if int(max_new_tokens) < 1:
            raise ValueError(
                f"{request_id}: max_new_tokens must be >= 1")
        if prompt.size + int(max_new_tokens) > self.max_context:
            raise ValueError(
                f"{request_id}: prompt {prompt.size} + max_new "
                f"{int(max_new_tokens)} exceeds max_context "
                f"{self.max_context} (the ADOPTING pool's contract)")
        self.waiting.append(
            _PrefillJob(request_id, prompt, max_new_tokens))
        self.counters["submitted"] += 1

    def cancel(self, predicate) -> int:
        """Drop every job whose request_id satisfies `predicate`; a
        cancelled ACTIVE job's blocks return to the free list.
        Returns the number cancelled."""
        cancelled = 0
        kept = deque()
        for job in self.waiting:
            if predicate(job.request_id):
                cancelled += 1
            else:
                kept.append(job)
        self.waiting = kept
        if (self._active is not None
                and predicate(self._active.request_id)):
            self.blocks.free(self._active.blocks)
            self._active = None
            cancelled += 1
        return cancelled

    def has_work(self) -> bool:
        return self._active is not None or bool(self.waiting)

    @property
    def queue_depth(self) -> int:
        """Jobs not yet finished -- the signal the prefill pool's
        autoscaler watches (queue wait, not slot occupancy)."""
        return len(self.waiting) + (1 if self._active else 0)

    # -- the engine step ---------------------------------------------------

    def step(self) -> list:
        """Advance the active prefill by one chunk (or run it whole);
        returns the handoff records that finished this tick."""
        if self._active is None:
            if not self.waiting:
                return []
            job = self.waiting.popleft()
            job.started_at = time.perf_counter()
            job.bucket = self._bucket(job.true_len)
            granted = self.blocks.allocate(
                self.blocks.blocks_for(job.bucket))
            # the pool is sized for max_context and jobs run one at a
            # time, so a grant can never fail here
            job.blocks = granted
            job.padded = np.zeros((job.bucket,), np.int32)
            job.padded[:job.true_len] = job.prompt
            self.table[:] = TRASH_BLOCK
            self.table[:len(granted)] = granted
            self._active = job
        job = self._active
        if (self.prefill_chunk is None
                or self.prefill_chunk >= job.bucket):
            # as DecodeEngine's span of a whole prefill: what the model
            # says of the call, its counts onto the running counters
            fields, counts = prefill_record(
                self.config, self.pool, job.bucket, job.true_len)
            for name, count in counts.items():
                self.counters[name] = self.counters.get(name, 0) + count
            with self._spans.span(
                    "engine.prefill", job.request_id, bucket=job.bucket,
                    true_len=job.true_len, **fields):
                with self._compiling("paged_prefill"):
                    self.pool, first = paged_prefill(
                        self.params, self.config, self.pool,
                        job.padded[None], self.table,
                        np.int32(job.true_len))
                first = int(first)  # the readback waits for the prefill
            job.prefill_pos = job.bucket
            return [self._finish(job, first)]
        return self._step_chunk(job)

    def _step_chunk(self, job: _PrefillJob) -> list:
        block_size = self.blocks.block_size
        start = job.prefill_pos
        remaining = job.true_len - start
        size = min(self.prefill_chunk,
                   bucket_length(remaining, minimum=block_size))
        take = min(size, remaining)
        chunk = np.zeros((1, size), np.int32)
        chunk[0, :take] = job.padded[start:start + take]
        write_blocks = np.full((size,), TRASH_BLOCK, np.int32)
        write_offsets = np.zeros((size,), np.int32)
        for offset in range(size):
            position = start + offset
            if position < job.true_len:
                write_blocks[offset] = job.blocks[position // block_size]
            write_offsets[offset] = position % block_size
        with self._compiling("paged_prefill_chunk"):
            self.pool, greedy = paged_prefill_chunk(
                self.params, self.config, self.pool, chunk, self.table,
                np.int32(start), write_blocks, write_offsets)
        self.counters["chunks"] += 1
        self._bump("prefill.chunks", 1)
        job.prefill_pos = start + take
        if job.prefill_pos < job.true_len:
            return []
        first = int(np.asarray(greedy)[job.true_len - 1 - start])
        return [self._finish(job, first)]

    # -- export ------------------------------------------------------------

    def _finish(self, job: _PrefillJob, first: int) -> dict:
        """Offer the prompt's KV blocks on the transfer plane and build
        the handoff record.  Only blocks holding TRUE prompt positions
        travel: the bucket-padding tail past true_len is garbage the
        adopting engine overwrites before its gather reaches it, and
        whole blocks past the prompt hold nothing at all."""
        used = self.blocks.blocks_for(job.true_len)
        # RAW descriptors, not {TENSOR_REF_KEY: ...} markers: see
        # fetch_kv_blocks -- the frame codec must carry these inert so
        # the ADOPTING engine batch-fetches
        kv_blocks, total_bytes = offer_pool_blocks(
            self.pool, job.blocks[:used])
        self.blocks.free(job.blocks)
        job.blocks = []
        self._active = None
        now = time.perf_counter()
        self.counters["exported"] += 1
        self.counters["exported_bytes"] += total_bytes
        self._bump("prefill.exports", 1)
        self._bump("prefill.exported_bytes", total_bytes)
        if self._registry is not None:
            self._registry.histogram("prefill.queue_wait_s").record(
                (job.started_at or now) - job.submitted_at)
            self._registry.histogram("prefill.prefill_s").record(
                now - (job.started_at or now))
        return {
            "schema": HANDOFF_SCHEMA,
            "request_id": job.request_id,
            "prompt": [int(token) for token in job.prompt],
            "max_new": job.max_new,
            "true_len": job.true_len,
            "first_token": int(first),
            "block_size": self.blocks.block_size,
            "kv_dtype": self.config.kv_dtype or "",
            "kv_bytes": int(total_bytes),
            "queue_wait_s": round(
                (job.started_at or now) - job.submitted_at, 6),
            "prefill_s": round(now - (job.started_at or now), 6),
            "kv_blocks": kv_blocks,
        }

    def stats(self) -> dict:
        stats = {
            "waiting": len(self.waiting),
            "active": 1 if self._active else 0,
            "block_size": self.blocks.block_size,
            "free_blocks": self.blocks.free_count,
            **self.counters,
        }
        if self.prefill_chunk is not None:
            stats["prefill_chunk_size"] = self.prefill_chunk
        return stats
