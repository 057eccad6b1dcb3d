"""Iteration-level scheduler: the continuous-batching decode loop.

Reference: Yu et al., "Orca: A Distributed Serving System for
Transformer-Based Generative Models" (OSDI'22) — scheduling decisions
are made per *iteration* (one decode step), not per batch: newly-arrived
requests join the running batch between steps, finished sequences retire
immediately, and no request ever waits for a batch-mate to finish. Under
cache pressure the engine preempts the lowest-priority sequence
(freeing its blocks, requeueing it for recompute — vLLM's recompute
preemption mode) instead of crashing or deadlocking the loop.

The engine is deliberately split so the unit tier can drive it without
threads: `step()` executes exactly one scheduler iteration (admissions →
capacity check/preemption → one decode step → retirements) and is what
`tests/test_unit_engine.py` calls in a plain loop; `start()` merely runs
`step()` on a daemon thread with an idle-event park, which is how a
Serve replica hosts it.

`policy="static"` runs the SAME loop but only admits into an empty
batch (the `@serve.batch` shape: form once, hold to completion) — the
honest baseline the `llm_serve` bench compares continuous batching
against, paying identical per-step bookkeeping.

One loop drives every model, through three calls (`model.py` states
the protocol): `prefill` -> `cache.write_range` for a prompt,
`decode_paged` inside `cache.paged_step` for a step, `prefill_paged`
for a prompt whose head is already cached (and `prefill_chunk`, where
a model offers it: below). The model reads the KV pool
itself, through block tables; the engine never gathers a sequence's KV.
Where the pool lives is the model's to say (`kv_pool_ns`: numpy for
`TinyLM`, `jax.numpy` for `TransformerEngineModel`).

Prefix sharing (on by default): admission consults a radix prefix
index (`prefix_index.PrefixIndex`) and ADOPTS the longest cached prefix
by reference — matched blocks cost a refcount bump instead of prefill
compute and duplicate cache capacity; only the unmatched tail is
prefilled (`model.prefill_paged` reads the matched head out of the
pool). A prompt that is fully cached skips the prefill pass entirely:
its first token is one read-only `decode_paged` step over the adopted
blocks. Preemption frees only a sequence's private tail (shared blocks
survive and stay indexed), and cold prefixes are LRU-evicted under
block pressure instead of admissions being rejected.

A model with per-sequence state (`state_shapes`: a recurrent or
linear-attention layer's state) gets a slot a sequence in the cache
beside its blocks, sized here from `max_batch_size`, and no prefix
sharing: blocks of KV do not restore such a sequence's prefix, so no
index is built, every prompt is prefilled from position 0, and a
preempted row's slot is freed and its state recomputed by prefill (as is
a prompt's that was cancelled, failed or requeued in flight: `free`
gives the slot back with the blocks). Decided from what the model
declares; no option.

A prefill hands its KV over in two calls, with or without a prefix hit:
the model's prefill returns the logits and the KV (`len()` rows), and
`cache.write_range` stores them. Where the model computed them on the
device (`TransformerEngineModel`), the KV is the jit's padded output
(`model.PromptKV`) and one donated scatter writes it: it never crosses
to the host. A numpy pool (`TinyLM`) writes on the host.

A decode step's sampling adapts to what the model's step returned.
`TransformerEngineModel.decode_paged` crosses the host boundary once
each way with a few integers: one packed int32 array in (tokens,
positions, write slots, block tables; the jitted call uploads it), the
greedy ids its program sampled out (`model.DecodeStep`); the `[b, V]`
logits stay on the device and `engine.sample` takes the ids. A model
that returns host logits (`TinyLM`) has their argmax taken: the same
tokens. Who may ask a `DecodeStep` for logits, through `np.asarray`: a
fully cached prompt's first token below, a reference check, a test;
never the steady step.

A decode step's tokens are delivered while the device runs the next
step. What the next step needs of them is done at once (the token
joins its sequence, a sequence that ended leaves the batch and
frees its blocks); what only the client needs (`stream._push` and the
consumers it wakes, a finished stream's `_finish`, the gauges) waits in
`_pending` and is handed over from inside the next `decode_paged`,
between its dispatch and its wait for the ids (`meanwhile`, which the
model runs there: `model.py`), when this thread would otherwise sleep.
Nothing pending waits behind anything but that one dispatch: it is
flushed at once where no decode step follows (nothing left running),
before a prefill's call, and before anything else ends a stream (a
cancellation, a failed step, `stop`), so a stream sees its tokens in
order and never its end before a token generated for it.

A full batch's next decode step is dispatched before the last one's
ids are read, where the model takes them on the device (`ahead`:
`model.py`). A decode step may be *in flight*: dispatched, its ids not
read (`_ahead`; the PROMPT in flight below is another thing). At the
next iteration, iff every row of the batch is taken and no prompt is in
flight in chunks (so nothing waiting could be admitted in the turn this
skips), no row of the step in flight is known to end with it
(`generated + 1 >= max_new_tokens`), none is cancelled and every row has
room for one more position without a preemption, the scheduler builds
the next step from what it knows without the tokens (every row a
position further, its token the row's place in the step in flight's
ids) and calls `decode_paged` with it: the program takes the tokens on
the device, and only once it is dispatched does the call wait for the
step in flight's ids. Those tokens are sampled, appended and checked
for their end at once, and handed to their streams from the next call's
`meanwhile` as every step's are, beside a device that is busy either
way. In every other case the loop first
reads the step in flight and goes on as it always has: admit, prefill, a
chunk, a batch that is not full, a retirement, a cancellation, a
preemption, `stop`, a failed step. An end the host cannot foresee (the
model's `eos_token` among the ids) is found a step late: the row's token
of the step already dispatched is dropped, never emitted, and its blocks
go back once that step is read (`_take`). No option decides any of it;
a model without the keyword runs the loop as it was.
`decode_steps_ahead` and `decode_ends_found_late` count both.

A long prompt is prefilled in chunks, a decode step between two of
them, where the model offers the call (`prefill_chunk`, with its
`prefill_chunk_tokens`: `layer_groups_model.py`): a prompt longer than a
chunk is admitted as any other (FIFO, the cache's estimate for the
whole prompt) and is then *in flight*, neither waiting nor running: an
iteration runs its next chunk (the model reads the positions before it
out of the pools, the cache then grows by the chunk and takes its
rows), then the decode step of the running batch, so no running row
waits behind more than one chunk. One prompt is in flight at a time;
nothing else is admitted until its last chunk has given its first token
and it has joined the batch. With nothing running the chunks follow
each other at once. A chunk's call takes `meanwhile` as a decode step's
does. A chunk that is not its prompt's last hands the host nothing and
is not waited for (the model's rule, `sparse_model._prompt_logits`): its
rows' write and the iteration's decode step are dispatched behind it,
and the step's ids are the next thing the loop reads, so the device runs
chunk, write, step back to back and the host's turn passes beside it.
Over a model with state (`gigachat_model.py`) the chunk is also
handed the sequence's state slot, begins from what it holds, and its
payload carries the state it ended on back into the slot. A shorter
prompt, one with a prefix hit, and every prompt of a model without the
call are prefilled whole.
"""

from __future__ import annotations

import inspect
import itertools
import logging
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from ray_tpu.core import flight
from ray_tpu.serve.engine.kv_cache import CacheOverflowError, KVCacheManager
from ray_tpu.serve.engine.prefix_index import PrefixIndex
from ray_tpu.util import tracing


logger = logging.getLogger(__name__)

# Where the loop's wall time goes, as `stats()["phase.<name>_s"]`: every
# instant of the loop lies in one of these. All but `park` and `other`
# are leaf spans inside `engine.step`; `other` is what a step spent in no
# leaf. `tables` is the `kv_gather_s` clock (its name in `stats()`).
_STEP_PHASES = ("reap", "admit", "capacity", "prefill_match",
                "prefill_kv_write", "prefill_seal", "sample", "emit",
                "gauges")
# The model's host side of a call, kept by the model in its own `phase`
# dict (`TransformerEngineModel`); a model without one reads 0.0.
_MODEL_PHASES = ("prefill_prep", "prefill_dispatch", "prefill_wait",
                 "prefill_kv_d2h", "decode_prep", "decode_dispatch",
                 "decode_wait")
# Clocks the engine had before the phases, still read under these names.
_LEGACY_CLOCKS = ("prefill_s", "kv_gather_s", "model_step_s")


class EngineOverloadedError(RuntimeError):
    """The waiting queue is full — the caller should shed, not enqueue.

    `retry_after_s` (set at raise time from the engine's observed queue
    drain rate) tells the shedding edge how long a well-behaved client
    should back off — the proxy surfaces it as an HTTP `Retry-After`
    header so overload backpressure is actionable, not just a 503."""

    retry_after_s: Optional[float] = None


@dataclass
class EngineConfig:
    max_batch_size: int = 8
    block_size: int = 16
    num_blocks: int = 64
    max_queue: int = 64            # waiting-queue bound (backpressure)
    max_new_tokens_default: int = 64
    policy: str = "continuous"     # "continuous" | "static"
    prefix_sharing: bool = True    # adopt cached prompt prefixes
    replica_tag: str = ""          # fleet identity (metrics/digests)
    # Blocks of each further layer group of a model that declares some
    # (`kv_groups`, e.g. {"window": 640}); `num_blocks` is the global
    # group's.
    group_blocks: Optional[Dict[str, int]] = None
    # Selects nothing: the paged step is the engine. The name stays, to
    # accept `True`, because the benchmark's cell files pass it; it goes
    # with the next `benchmark` issue (ROADMAP S6).
    paged_decode: bool = True

    def __post_init__(self):
        if self.paged_decode is not True:
            raise ValueError(
                "paged_decode accepts only True: the host-gather decode "
                "loop is gone and every model runs the paged step")


class TokenStream:
    """Per-request token channel: the engine pushes one token per
    iteration; consumers iterate synchronously (`for tok in stream`) or
    asynchronously (`async for tok in stream`) — both see tokens as they
    are produced, so time-to-first-token decouples from completion."""

    def __init__(self, request_id: str,
                 clocks: Optional[Dict[str, float]] = None):
        self.request_id = request_id
        self._lock = threading.Lock()
        self._tokens: List[int] = []
        # Push stamp of each token (perf_counter; 0.0 with the flight
        # recorder off), and the engine's clocks that the consumer adds
        # pickup - push to: the engine -> replica half of the stream hop.
        self._stamps: List[float] = []
        self._clocks = clocks
        self._done = False
        self._error: Optional[BaseException] = None
        self._waiters: List = []   # threading.Event | (loop, aio.Event)
        self.cancelled = False
        self.finished_at: Optional[float] = None  # perf_counter stamp

    # -- producer (engine loop) ----------------------------------------
    def _push(self, token: int) -> None:
        stamp = time.perf_counter() if flight.enabled else 0.0
        with self._lock:
            self._tokens.append(token)
            self._stamps.append(stamp)
            waiters, self._waiters = self._waiters, []
        self._wake(waiters)

    def _finish(self, error: Optional[BaseException] = None) -> None:
        with self._lock:
            if self._done:
                return
            self._error = error
            self._done = True
            self.finished_at = time.perf_counter()
            waiters, self._waiters = self._waiters, []
        self._wake(waiters)

    @staticmethod
    def _wake(waiters) -> None:
        for w in waiters:
            if isinstance(w, tuple):
                loop, ev = w
                try:
                    loop.call_soon_threadsafe(ev.set)
                except RuntimeError:
                    pass  # consumer loop already closed
            else:
                w.set()

    # -- consumer ------------------------------------------------------
    @property
    def finished(self) -> bool:
        with self._lock:
            return self._done

    def cancel(self) -> None:
        """Ask the engine to retire this sequence at the next iteration
        boundary; already-produced tokens stay readable."""
        self.cancelled = True

    def tokens_so_far(self) -> List[int]:
        with self._lock:
            return list(self._tokens)

    def _picked_up(self, stamp: float) -> None:
        """A consumer took the token pushed at `stamp`. Racing consumer
        threads can lose an increment, as `flight.record` can."""
        clocks = self._clocks
        if stamp and clocks is not None:
            clocks["stream_wake_s"] += time.perf_counter() - stamp
            clocks["stream_wake_tokens"] += 1

    def __iter__(self):
        idx = 0
        while True:
            with self._lock:
                if idx < len(self._tokens):
                    tok = self._tokens[idx]
                    stamp = self._stamps[idx]
                    idx += 1
                elif self._done:
                    if self._error is not None:
                        raise self._error
                    return
                else:
                    ev = threading.Event()
                    self._waiters.append(ev)
                    tok = None
            if tok is None:
                ev.wait()
                continue
            self._picked_up(stamp)
            yield tok

    async def __aiter__(self):
        import asyncio

        loop = asyncio.get_running_loop()
        idx = 0
        while True:
            with self._lock:
                if idx < len(self._tokens):
                    tok = self._tokens[idx]
                    stamp = self._stamps[idx]
                    idx += 1
                elif self._done:
                    if self._error is not None:
                        raise self._error
                    return
                else:
                    ev = asyncio.Event()
                    self._waiters.append((loop, ev))
                    tok = None
            if tok is None:
                await ev.wait()
                continue
            self._picked_up(stamp)
            yield tok


@dataclass
class _Sequence:
    seq_id: str
    prompt: List[int]
    all_tokens: List[int]          # prompt + generated so far
    max_new_tokens: int
    priority: int                  # higher = more important
    arrival: float
    stream: TokenStream
    submitted_at: float = field(default_factory=time.perf_counter)
    first_token_at: Optional[float] = None
    preemptions: int = 0
    # When it last entered the waiting queue (submission, or preemption).
    queued_at: float = field(default_factory=time.perf_counter)
    # Trace id of the `serve.replica` span that submitted it, if any.
    trace_id: Optional[str] = None
    # Positions whose KV the chunks run so far have put in the cache,
    # while the sequence is in flight.
    prefilled: int = 0
    # A decode step's token was its last (a preempted sequence never
    # is one: it ends where it ends).
    ended: bool = False

    @property
    def generated(self) -> int:
        return len(self.all_tokens) - len(self.prompt)


class InferenceEngine:
    """Continuous-batching engine around one model + one KV cache.

    Invariant between iterations: for every running sequence, the cache
    holds KV for `all_tokens[:-1]` (the last token is the decode input
    that the NEXT step will both consume and cache)."""

    def __init__(self, model, config: Optional[EngineConfig] = None):
        self.model = model
        self.config = config or EngineConfig()
        missing = [name for name in ("prefill", "decode_paged",
                                     "prefill_paged")
                   if not callable(getattr(model, name, None))]
        if missing:
            raise ValueError(
                f"{type(model).__name__} lacks {', '.join(missing)}: the "
                f"engine drives a model through prefill, decode_paged and "
                f"prefill_paged (serve/engine/model.py)")
        # A model may declare a state it keeps a sequence beside its KV
        # rows (`state_shapes`): the cache then holds a slot a sequence,
        # one for each row of the batch.
        state_shapes = getattr(model, "state_shapes", None)
        # A model may declare further layer groups (`kv_groups`: layers
        # of sliding-window attention keep a window's rows, in a pool of
        # their own); the config says how many blocks each gets.
        kv_groups = getattr(model, "kv_groups", None) or {}
        # And which of its groups' own pools are held by planes
        # (`kv_planes`: group -> bool, from the group's key/value heads:
        # `ops.paged_attention.held_by_planes`; `kv_cache.py`, storage).
        kv_planes = getattr(model, "kv_planes", None) or {}
        sizes = self.config.group_blocks or {}
        # A pool that rides the global group's blocks has none of its
        # own to size (`kv_cache.py`).
        riding = {name for name, g in kv_groups.items() if g.get("rides")}
        if set(kv_groups) - riding != set(sizes):
            raise ValueError(
                f"the model's layer groups {sorted(kv_groups)} and the "
                f"config's group_blocks {sorted(sizes)} differ")
        self.cache = KVCacheManager(
            self.config.num_blocks, self.config.block_size,
            kv_shape=tuple(getattr(model, "kv_token_shape", ())),
            planes=kv_planes.get(KVCacheManager.GLOBAL, False),
            dtype=getattr(model, "kv_dtype", np.float32),
            array_ns=getattr(model, "kv_pool_ns", None),
            state_shapes=state_shapes,
            state_slots=self.config.max_batch_size if state_shapes else 0,
            groups={name: (group if name in riding
                           else dict(group, num_blocks=sizes[name],
                                     planes=kv_planes.get(name, False)))
                    for name, group in kv_groups.items()})
        self.prefix_index: Optional[PrefixIndex] = None
        # Adopting blocks of KV restores a prefix only where KV is all a
        # sequence keeps: over a model with per-sequence state no index
        # is built, nothing is adopted, exported or imported, and every
        # prompt is prefilled whole. Nor over a model with a window
        # group: an adopted prefix would need the window layers' rows at
        # its end, which the blocks of the global group do not hold.
        if (self.config.prefix_sharing and not state_shapes
                and not kv_groups):
            self.prefix_index = PrefixIndex(self.cache,
                                            self.config.block_size)
            self.cache.set_reclaimer(self.prefix_index.evict,
                                     self.prefix_index.evictable_blocks)
        self._waiting: deque = deque()
        self._running: List[_Sequence] = []
        # The prompt being prefilled in chunks, if any: neither waiting
        # nor running. Only the loop's thread sets it.
        self._in_flight: Optional[_Sequence] = None
        # The decode step on the device whose ids the host has not read,
        # with its batch: (the model's step, the rows). While there is
        # one, the running batch is its rows, less those whose end the
        # step before's ids brought. Only the loop's thread sets it.
        self._ahead: Optional[tuple] = None
        # Whether the model's `decode_paged` takes `ahead` (`model.py`);
        # one that does not is never called with it and runs no step
        # ahead.
        self._takes_ahead = "ahead" in inspect.signature(
            model.decode_paged).parameters
        # A prompt longer than this many tokens is prefilled in chunks
        # of as many, where the model offers the call.
        self._chunk = (int(model.prefill_chunk_tokens)
                       if callable(getattr(model, "prefill_chunk", None))
                       else None)
        self._lock = threading.Lock()
        self._work = threading.Event()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._ids = itertools.count()
        self.replica_tag = self.config.replica_tag or "replica-0"
        # Counters (exported as serve_engine_* through stats()/metrics).
        self.steps = 0
        self.prefills = 0
        self.preemptions = 0
        self.tokens_generated = 0
        self.prefix_hit_tokens = 0
        self.prefix_exports = 0
        self.prefix_imports = 0
        self.prefix_import_tokens = 0
        self.finished = 0
        self.paged_steps = 0
        self.decode_steps_ahead = 0
        self.decode_ends_found_late = 0
        self.prefill_chunks = 0
        self.prefill_chunk_tokens = 0
        # Every clock of the loop, in seconds, each fed by one
        # `flight.span` (so all of them stand still while the flight
        # recorder is off): the phases, the older clocks (`prefill_s`;
        # the decode step's split into block tables / compiled step),
        # `step_s` (all of `engine.step`), and what is no
        # phase: `thread_cpu_s`, `queue_wait_s`, `stream_wake_*`,
        # `pending_wait_*`.
        self._clocks: Dict[str, float] = dict.fromkeys(
            _LEGACY_CLOCKS + tuple(f"{p}_s" for p in _STEP_PHASES)
            + ("window_release_s", "park_s", "other_s", "step_s",
               "thread_cpu_s",
               "queue_wait_s", "stream_wake_s", "stream_wake_tokens",
               "pending_wait_s", "pending_wait_tokens"), 0.0)
        self._cpu_thread: Optional[int] = None
        self._cpu_at = 0.0
        self._ttfts: List[float] = []
        self._pushed: Dict[str, float] = {}
        # Retirement stamps feeding the queue-drain-rate estimate behind
        # EngineOverloadedError.retry_after_s.
        self._finish_stamps: deque = deque(maxlen=64)
        # Decode tokens not yet handed to their streams, in order:
        # (sequence, token, whether it was the sequence's last, its
        # step's stamp on joining: perf_counter, 0.0 with the flight
        # recorder off). Only the loop's thread touches it.
        self._pending: List[tuple] = []
        self.tokens_delivered_overlapped = 0

    # -- submission ----------------------------------------------------
    def submit(self, prompt_tokens: Sequence[int],
               max_new_tokens: Optional[int] = None,
               priority: int = 0) -> TokenStream:
        """Enqueue a request; returns its TokenStream immediately.
        Raises EngineOverloadedError when the waiting queue is full and
        CacheOverflowError when the request can never fit the cache."""
        prompt = [int(t) for t in prompt_tokens]
        if not prompt:
            raise ValueError("empty prompt")
        max_new = (self.config.max_new_tokens_default
                   if max_new_tokens is None else int(max_new_tokens))
        # Worst-case footprint must fit the cache at all, or no amount
        # of preemption ever admits it — reject at the door.
        worst = len(prompt) + max_new
        if worst > self.cache.capacity_tokens:
            raise CacheOverflowError(
                f"prompt+max_new_tokens={worst} exceeds cache capacity "
                f"{self.cache.capacity_tokens}")
        seq_id = f"seq-{next(self._ids)}"
        stream = TokenStream(seq_id, self._clocks)
        seq = _Sequence(seq_id=seq_id, prompt=prompt,
                        all_tokens=list(prompt), max_new_tokens=max_new,
                        priority=priority, arrival=time.monotonic(),
                        stream=stream,
                        trace_id=tracing.current_trace_id())
        with self._lock:
            if len(self._waiting) >= self.config.max_queue:
                err = EngineOverloadedError(
                    f"waiting queue full ({self.config.max_queue})")
                err.retry_after_s = self._retry_after_locked()
                raise err
            self._waiting.append(seq)
        self._work.set()
        return stream

    def queue_depth(self) -> int:
        with self._lock:
            return len(self._waiting)

    def batch_occupancy(self) -> int:
        with self._lock:
            return len(self._running)

    # -- overload backpressure -----------------------------------------
    def drain_rate(self) -> float:
        """Sequences retired per second over the recent window (0.0
        until two retirements have been observed)."""
        stamps = list(self._finish_stamps)
        if len(stamps) < 2:
            return 0.0
        dt = stamps[-1] - stamps[0]
        return (len(stamps) - 1) / dt if dt > 0 else 0.0

    def retry_after_s(self) -> float:
        """How long a shed client should wait before retrying: the time
        for the current waiting queue to drain one slot at the observed
        retirement rate, clamped to [0.05, 30] so a cold engine still
        hints something sane."""
        with self._lock:
            return self._retry_after_locked()

    def _retry_after_locked(self) -> float:
        rate = self.drain_rate()
        depth = len(self._waiting) + 1
        if rate <= 0.0:
            return 1.0
        return min(30.0, max(0.05, depth / rate))

    # -- cross-replica prefix shipping (PR 19) -------------------------
    def export_prefix(self, tokens: Sequence[int]):
        """The cached FULL-block prefix of `tokens` as
        (chunks, kv_arrays) — the holding side of cross-replica prefix
        shipping. kv_arrays[i] is a copy of the block holding
        chunks[i]; a block evicted between the index walk and the read
        truncates the chain there (shipping is best-effort)."""
        if self.prefix_index is None:
            return [], []
        chain = self.prefix_index.export_chain(tokens)
        chunks: List = []
        kvs: List = []
        for chunk, block in chain:
            try:
                kvs.append(self.cache.read_block(block))
            except ValueError:
                break   # evicted under us: ship the intact head only
            chunks.append(chunk)
        if chunks:
            self.prefix_exports += 1
        return chunks, kvs

    def import_prefix(self, chunks, kv_blocks) -> int:
        """Adopt shipped sealed blocks into the LOCAL cache + prefix
        index by reference-semantics insert: each block is installed
        once, the index takes its usual single reference, and the next
        admission matching this prefix adopts it exactly like a
        locally-prefilled one. Chunks already indexed here keep the
        first-indexed block (the duplicate import frees immediately).
        Returns tokens now covered by the imported chain."""
        if self.prefix_index is None:
            return 0
        installed: List[int] = []
        flat: List[int] = []
        for chunk, kv in zip(chunks, kv_blocks):
            b = self.cache.install_block(kv)
            if b is None:
                break   # no capacity even after reclaim: partial adopt
            installed.append(b)
            flat.extend(int(t) for t in chunk)
        if not installed:
            return 0
        self.prefix_index.insert(flat, installed)
        for b in installed:
            # Drop the installer's reference: newly indexed blocks stay
            # held by the index; duplicates go straight back free.
            self.cache.release(b)
        adopted = len(installed) * self.config.block_size
        self.prefix_imports += 1
        self.prefix_import_tokens += adopted
        return adopted

    def prefix_digest(self, max_entries: int = 4096):
        """The radix index summary the fleet router keys on (None when
        prefix sharing is off)."""
        if self.prefix_index is None:
            return None
        return self.prefix_index.digest(max_entries)

    # -- the iteration loop --------------------------------------------
    def step(self) -> bool:
        """One scheduler iteration. Returns False when idle (nothing
        running and nothing admittable). Never raises for per-sequence
        failures — a poisoned sequence finishes its stream with the
        error; the loop survives."""
        clocks = self._clocks
        leaves = self._leaf_seconds()
        with flight.span("engine", "step", None, clocks, "step_s") as sp:
            sp.arg = self._step()
        if flight.enabled:
            # What the step spent in no leaf; never negative, though a
            # model called from another thread meanwhile can add to the
            # leaves (the benchmark's set-up does).
            clocks["other_s"] += max(
                0.0, sp.dur - (self._leaf_seconds() - leaves))
            # CPU time of this thread since its last step, park included.
            cpu, thread = time.thread_time(), threading.get_ident()
            if thread == self._cpu_thread:
                clocks["thread_cpu_s"] += cpu - self._cpu_at
            self._cpu_thread, self._cpu_at = thread, cpu
        return sp.arg is not None

    def _leaf_seconds(self) -> float:
        """Sum of the leaf phases inside `engine.step`."""
        clocks = self._clocks
        total = clocks["kv_gather_s"]
        for name in _STEP_PHASES:
            total += clocks[f"{name}_s"]
        model = getattr(self.model, "phase", None)
        if model:
            total += sum(model.values())
        return total

    def _step(self) -> Optional[int]:
        """The iteration itself: the decode batch's size, None when
        idle."""
        clocks = self._clocks
        if self._ahead is not None:
            # The batch is as the step in flight left it: where the rule
            # allows, the next step goes out before its ids are read and
            # that is the iteration. Else they are read first, and the
            # iteration is what it always was.
            rows = self._step_ahead()
            if rows is not None:
                return rows
        with flight.span("engine", "reap", None, clocks, "reap_s"):
            self._reap_cancelled()
        prefilled = clocks["prefill_s"]
        with flight.span("engine", "admit") as sp:
            self._admit()
        # Self time: the prefills inside it have their own phases.
        clocks["admit_s"] += max(
            0.0, sp.dur - (clocks["prefill_s"] - prefilled))
        with self._lock:
            batch = list(self._running)
        if not batch:
            self._settle_idle()
            return None
        with flight.span("engine", "capacity", None, clocks, "capacity_s"):
            if self.cache.grouped:
                self._release_expired(batch, 0)
            self._ensure_capacity()
        with self._lock:
            batch = list(self._running)
        if not batch:
            self._settle_idle()
            return None
        return self._decode_step(batch)

    def _decode_step(self, batch: List[_Sequence]) -> int:
        """The batch's decode step and what an iteration does behind
        it."""
        try:
            self._decode_once(batch)
        except Exception as e:  # noqa: BLE001 — the loop must survive
            logger.exception("decode step failed; failing %d stream(s)",
                             len(batch))
            # (What only the device can raise behind a chunk of a
            # prompt nobody waited for surfaces here too, at the ids'
            # read.) The step before's tokens come first: those of a step
            # in flight that can still be read, and what is pending where
            # the step failed before its `meanwhile`.
            self._land()
            self._deliver()
            for seq in batch:
                if not seq.ended:   # else its last token came just now
                    self._retire(seq, error=e)
        self.steps += 1
        with self._lock:
            follows = bool(self._running)
        if not follows:
            # Else the next step's `meanwhile` delivers this step's
            # tokens and updates the gauges, beside a busy device.
            self._settle_idle()
        return len(batch)

    # -- a decode step in flight ---------------------------------------
    def _full_after(self, batch: List[_Sequence]) -> bool:
        """Whether the step of `batch` that is about to go out, or is on
        the device unread, can be followed by the next one before its
        ids are read, by what the loop can see: every row of the batch
        is taken and no prompt is in flight in chunks, so nothing that
        waits could be admitted in the turn the step ahead skips; no row
        is known to end with this step, so the batch is as full after
        it; none is cancelled. (An end the host cannot foresee, the
        model's `eos_token` among the ids, is found a step late:
        `_take`.) No option: a batch that is not full runs the loop as
        it always was."""
        if not self._takes_ahead or self._in_flight is not None:
            return False
        with self._lock:
            if not (len(batch) == len(self._running)
                    == self.config.max_batch_size):
                return False
        return not any(s.stream.cancelled
                       or s.generated + 1 >= s.max_new_tokens
                       for s in batch)

    def _step_ahead(self) -> Optional[int]:
        """An iteration that begins with a decode step in flight. Where
        the batch is still full behind it and every row has room for one
        more position without a preemption, the next step is dispatched
        at once, over the same rows a position further, and the step in
        flight is read while the device runs it. Else the step in flight
        is read here (None), and the iteration goes on as one without."""
        _, batch = self._ahead
        clocks = self._clocks
        if self._full_after(batch):
            with flight.span("engine", "capacity", None, clocks,
                             "capacity_s"):
                if self.cache.grouped:
                    self._release_expired(batch, 1)
                room = self._ensure_capacity(preempt=False, unread=1)
            if room:
                return self._decode_step(batch)
        self._land()
        return None

    def _land(self) -> None:
        """Read the decode step in flight, if there is one, and take its
        tokens: nothing follows it on the device. Its streams fail
        where its ids do not come."""
        if self._ahead is None:
            return
        step, batch = self._ahead
        self._ahead = None
        try:
            with flight.span("engine", "model_step", len(batch),
                             self._clocks, "model_step_s"):
                step.ids     # the wait: `model.decode.logits_wait`
        except Exception as e:  # noqa: BLE001 — the loop must survive
            logger.exception("the decode step in flight failed; failing "
                             "%d stream(s)", len(batch))
            self._deliver()
            for seq in batch:
                if seq.ended:
                    self.cache.free(seq.seq_id)
                else:
                    seq.ended = True
                    self._retire(seq, error=e)
            return
        self._take(step, batch)

    def _release_expired(self, batch: List[_Sequence], unread: int) -> None:
        """Blocks every position of which has left a window group's
        window go back to its free list before the tables grow. A span
        and a clock of its own inside `capacity` (whose clock holds it
        too: the phases stay a partition), so that its host time has a
        name in the idle attribution."""
        with flight.span("engine", "window_release", None, self._clocks,
                         "window_release_s"):
            for seq in batch:
                self.cache.release_expired(
                    seq.seq_id, len(seq.all_tokens) + unread)

    def _settle_idle(self) -> None:
        """No decode step follows in whose shadow to deliver: hand over
        what is pending and bring the gauges up to date, at once."""
        self._deliver()
        self._update_gauges()

    def _in_shadow(self) -> None:
        """A decode step's `meanwhile`: the device runs the step just
        dispatched, and the step before's tokens and gauges go out. Under
        the cache's lock (`paged_step`), so nothing here may wait for
        another thread that wants the cache."""
        self.tokens_delivered_overlapped += self._deliver()
        self._update_gauges()

    def _deliver(self) -> int:
        """Hand the pending decode tokens to their streams, in order,
        and end the streams of the sequences they ended. Returns how
        many there were."""
        pending = self._pending
        if not pending:
            return 0
        self._pending = []
        clocks = self._clocks
        if flight.enabled:
            # How long the tokens waited here: one clock read a flush,
            # against the one their step took when they joined.
            now = time.perf_counter()
            joined = [at for _, _, _, at in pending if at]
            clocks["pending_wait_s"] += now * len(joined) - sum(joined)
            clocks["pending_wait_tokens"] += len(joined)
        with flight.span("engine", "emit", len(pending), clocks, "emit_s"):
            for seq, tok, last, _ in pending:
                seq.stream._push(tok)
                if last:
                    self._close(seq)
        return len(pending)

    def _reap_cancelled(self) -> None:
        with self._lock:
            cancelled = [s for s in self._running if s.stream.cancelled]
            waiting_cancelled = [s for s in self._waiting
                                 if s.stream.cancelled]
            for s in waiting_cancelled:
                self._waiting.remove(s)
            flying = self._in_flight
            if flying is not None and flying.stream.cancelled:
                self._in_flight = None
                cancelled.append(flying)
        if cancelled or waiting_cancelled:
            self._deliver()   # a pending token precedes its stream's end
        for s in cancelled + waiting_cancelled:
            self._retire(s)

    def _admit(self) -> None:
        """Pull waiting requests into the running batch (prefill). The
        static policy only forms a batch when the previous one fully
        retired — the `@serve.batch` behavior the bench compares
        against. The in-flight check happens ONCE per pass (not per
        admitted sequence: the first prefill populates `_running`, and
        re-checking would cap static batches at size one — serial
        decoding, not static batching). A prompt in flight comes first:
        its next chunk, and beside a running batch nothing more in this
        iteration (the static policy forms its batch in one pass, so
        there the chunks follow each other).

        A chunk that is not its prompt's last is not waited for, and
        this is what bounds how far the host runs ahead of the device:
        beside a running batch the iteration's decode step, whose ids
        the loop reads before it comes here again (one chunk, one write,
        one step in the device's queue at most); with no batch running
        the loop below dispatches a prompt's chunks one behind the other
        up to its last, whose logits it reads."""
        with self._lock:
            if self.config.policy == "static" and self._running:
                # A batch is in flight: hold admissions until it
                # completes; the loop below then drains the queue into
                # a full batch.
                return
        while True:
            seq, chunks = self._in_flight, self.prefill_chunks
            if seq is None:
                with self._lock:
                    if not self._waiting:
                        return
                    if len(self._running) >= self.config.max_batch_size:
                        return
                    seq = self._waiting[0]
                    # Admission needs the prompt cached (len-1 after the
                    # invariant) plus the first decode write — i.e.
                    # blocks covering len(prompt) positions, +1 for
                    # growth.
                    need = len(seq.all_tokens)
                    if not self.cache.can_allocate(seq.seq_id, need):
                        return
                    self._waiting.popleft()
            try:
                if not self._prefill(seq):
                    return   # allocation lost after the estimate: the
                             # seq is requeued; let the batch make
                             # progress before re-trying admission
            except Exception as e:  # noqa: BLE001
                # What the host raised, at dispatch or before, or a last
                # chunk's read: this prompt's alone. (What only the
                # device raises behind a chunk nobody waited for comes
                # with the decode step's ids: `_decode_step`.)
                logger.exception("prefill of %s failed", seq.seq_id)
                if self._in_flight is seq:
                    self._in_flight = None
                    # Where a chunk failed before its `meanwhile`.
                    self._deliver()
                self.cache.free(seq.seq_id)
                seq.stream._finish(e)
            if (self.prefill_chunks > chunks
                    and self.config.policy != "static"):
                # One chunk an iteration beside a running batch: its
                # decode step comes before the next chunk, and before
                # anything else is admitted.
                with self._lock:
                    if self._running:
                        return

    def _prefill(self, seq: _Sequence) -> bool:
        # Engine steps in the flight ring: a decode-latency spike lines
        # up against GC pauses / loop stalls in the merged timeline
        # instead of being its own mystery; prefix_hit makes
        # shared-prefill savings visible per admission in /api/timeline.
        with flight.span("engine", "prefill", None, self._clocks,
                         "prefill_s") as sp:
            return self._prefill_inner(seq, sp)

    def _prefill_inner(self, seq: _Sequence, sp: flight.span) -> bool:
        if seq is self._in_flight:
            return self._prefill_chunk(seq, sp)
        clocks = self._clocks
        tokens = list(seq.all_tokens)
        n = len(tokens)
        # In chunks: a prompt longer than one, unless a prefix of it is
        # cached already (the whole path reads that out of the pool).
        chunked = self._chunk is not None and n > self._chunk
        # A whole prefill is milliseconds to tenths of a second of
        # device time: no token waits behind it; a chunk delivers from
        # its `meanwhile`. (Nor may this sequence's own first token
        # overtake what a preempted run of it left pending.)
        if not chunked:
            self._deliver()
        admission = time.perf_counter()
        hit = 0
        with flight.span("engine", "prefill.match", None, clocks,
                         "prefill_match_s"):
            if self.prefix_index is not None:
                blocks, hit = self.prefix_index.match(tokens)
                if hit:
                    self.cache.adopt(seq.seq_id, blocks, hit)
                    if chunked:
                        chunked = False
                        self._deliver()
            # Privatize from the first position this prefill writes: a
            # partially-adopted shared block COWs here, planned into the
            # same atomic free-block arithmetic as table growth. A
            # chunk allocates its own positions when it has run.
            ok = chunked or self.cache.allocate(seq.seq_id, n,
                                                writable_from=hit)
        if not ok:   # lost capacity since the admission check: requeue
            self._requeue_at_head(seq)
            return False
        sp.arg = f"tokens={n} prefix_hit={hit}"
        if flight.enabled:
            # The wait this admission ends, as an event of its own that
            # closes where the prefill opens; it carries the request's
            # identifiers (the prefill's arg is read as it stands).
            wait = admission - seq.queued_at
            clocks["queue_wait_s"] += wait
            queued_ago = time.perf_counter() - seq.queued_at
            flight.record(
                "engine", "queue_wait", dur_us=int(wait * 1e6),
                arg=(seq.seq_id if seq.trace_id is None
                     else f"{seq.seq_id} trace={seq.trace_id}"),
                t=time.monotonic() - queued_ago)
        if chunked:
            self._in_flight = seq
            seq.prefilled = 0
            return self._prefill_chunk(seq, sp)
        if hit == n:
            # Full prefix hit: every prompt position is already cached.
            # The first generated token is ONE decode step over the
            # adopted blocks — no prefill pass at all. The write list
            # is empty (the shared block already holds this KV; writing
            # it would force a pointless COW): a read-only fused step,
            # and mutate_pool re-binds the buffer the donating jit
            # returns. The table goes in as wide as the sequence's next
            # step will have it (position n: one more column where the
            # prompt ends on a block's edge; the column names block 0,
            # which the position masks), so that a model that compiles
            # a program a table width runs this step in that step's
            # program and never compiles one for it alone.
            table = self.cache.block_table(seq.seq_id)
            table += [0] * (n // self.config.block_size + 1 - len(table))
            logits = self.cache.mutate_pool(
                lambda pool: self.model.decode_paged(
                    pool, [table], [tokens[-1]], [n - 1], [], [],
                    self.config.block_size))
            logits = np.asarray(logits)[0]
        elif hit:
            # Prefill-from-offset: the model reads the adopted prefix
            # out of the pool through the block table.
            table = self.cache.block_table(seq.seq_id)
            logits, tail_kv = self.cache.with_pool(
                lambda pool: self.model.prefill_paged(
                    tokens, pool, table, hit, self.config.block_size))
            with flight.span("engine", "prefill.kv_write", None, clocks,
                             "prefill_kv_write_s"):
                self.cache.write_range(seq.seq_id, hit, tail_kv)
        else:
            logits, kv = self.model.prefill(tokens)
            with flight.span("engine", "prefill.kv_write", None, clocks,
                             "prefill_kv_write_s"):
                self.cache.write_range(seq.seq_id, 0, kv)
        self._first_token(seq, tokens, logits, hit)
        return True

    def _requeue_at_head(self, seq: _Sequence) -> None:
        """A prefill lost the blocks the admission check saw: what it
        holds goes back and it is the next to be admitted."""
        self.cache.free(seq.seq_id)
        with self._lock:
            self._waiting.appendleft(seq)

    def _prefill_chunk(self, seq: _Sequence, sp: flight.span) -> bool:
        """The next chunk of the prompt in flight: the model runs it
        over the positions before it as the pools hold them, then the
        cache grows by the chunk (a window group gives back what the
        chunk's end no longer sees: hence behind the model's dispatch)
        and takes its rows. Only the last chunk is read; any other is on
        the device, or in front of it, when the call returns, and the
        rows' write goes out behind it as a donated program like any
        other. Nothing here needs the chunk finished: the device runs
        programs in dispatch order, so a block given back below and
        handed to another sequence is written only behind this chunk's
        read of it (the sentence `_take` rests on). After the last chunk
        the sequence joins the batch with its first token. False: the
        chunk lost its blocks, and the sequence is requeued at the head
        to begin again."""
        clocks = self._clocks
        tokens = seq.all_tokens      # nothing joins it while in flight
        n, start = len(tokens), seq.prefilled
        end = min(n, start + self._chunk)
        sp.arg = f"tokens={end - start} prefix_hit=0 chunk_at={start}"
        tables = self.cache.step_tables(seq.seq_id)
        # Over a model with state the chunk begins from the sequence's
        # slot (None before its first chunk is stored: that one begins
        # from nothing) and its payload carries the state it ended on,
        # which `write_range` below puts back.
        carried = ({"slot": self.cache.slot_of(seq.seq_id)}
                   if self.cache.state_slots else {})
        logits, kv = self.cache.with_pools(
            lambda pools: self.model.prefill_chunk(
                tokens, pools, tables, start, self.config.block_size,
                meanwhile=self._in_shadow, **carried))
        if not self.cache.allocate(seq.seq_id, end, writable_from=start):
            self._in_flight = None
            self._requeue_at_head(seq)
            return False
        with flight.span("engine", "prefill.kv_write", None, clocks,
                         "prefill_kv_write_s"):
            self.cache.write_range(seq.seq_id, start, kv)
        seq.prefilled = end
        self.prefill_chunks += 1
        self.prefill_chunk_tokens += end - start
        if end == n:
            self._in_flight = None
            self._first_token(seq, tokens, logits, 0)
        return True

    def _first_token(self, seq: _Sequence, tokens: List[int], logits,
                     hit: int) -> None:
        """The end of a prefill, whole or in chunks: the prompt's blocks
        sealed, its first token sampled and emitted, the sequence in the
        batch (or ended by that token)."""
        clocks = self._clocks
        if self.prefix_index is not None:
            # Seal: every full prompt block becomes adoptable.
            with flight.span("engine", "prefill.seal", None, clocks,
                             "prefill_seal_s"):
                self.prefix_index.insert(
                    tokens, self.cache.block_table(seq.seq_id))
        with flight.span("engine", "sample", None, clocks, "sample_s"):
            tok = int(np.argmax(np.asarray(logits)))
        self.prefills += 1
        self.prefix_hit_tokens += hit
        with flight.span("engine", "emit", None, clocks, "emit_s"):
            self._emit(seq, tok)
            if self._ended(seq):
                self._retire(seq)
            else:
                with self._lock:
                    self._running.append(seq)

    def _ensure_capacity(self, preempt: bool = True,
                         unread: int = 0) -> bool:
        """Every running sequence needs a cache slot for the token the
        next decode step writes. Deterministic OOM: preempt the
        lowest-priority / youngest sequence and requeue it for
        recompute; never crash, never stall the rest of the batch.
        `unread`: a row's tokens on the device that the host has not
        read (one, behind a decode step in flight), each a position.
        Without `preempt`, False where a row is short (what the rows
        before it took they need a step later anyway)."""
        while True:
            with self._lock:
                running = list(self._running)
            short = None
            for seq in running:
                # Next write position = len(all_tokens) - 1 + 1 slots;
                # writable_from additionally COWs that slot's block if
                # it is shared (a fully-adopted prompt ending mid-block
                # faults here on its first generated token).
                n = len(seq.all_tokens) + unread
                if not self.cache.allocate(seq.seq_id, n,
                                           writable_from=n - 1):
                    short = seq
                    break
            if short is None:
                return True
            if not preempt:
                return False
            victim = self._pick_victim()
            if victim is None or victim is short:
                # Nothing lower-priority to evict: preempt `short`
                # itself back to the queue; it re-admits when space
                # frees (or, if it is ALONE and still does not fit,
                # grows block-by-block as retirement frees space —
                # capacity_tokens was checked at submit).
                victim = short
            self._preempt(victim)

    def _pick_victim(self) -> Optional[_Sequence]:
        with self._lock:
            if not self._running:
                return None
            # Lowest priority first; then youngest (latest arrival) —
            # the sequence that has consumed the least service.
            return min(self._running,
                       key=lambda s: (s.priority, -s.arrival))

    def _preempt(self, seq: _Sequence) -> None:
        with self._lock:
            if seq in self._running:
                self._running.remove(seq)
            # Requeue at the FRONT: a preempted sequence re-admits
            # before fresh arrivals (no starvation).
            self._waiting.appendleft(seq)
        self.cache.free(seq.seq_id)
        seq.queued_at = time.perf_counter()
        seq.preemptions += 1
        self.preemptions += 1

    def _decode_once(self, batch: List[_Sequence]) -> None:
        with flight.span("engine", "decode", len(batch)):
            self._decode_inner(batch)

    def _decode_inner(self, batch: List[_Sequence]) -> None:
        clocks = self._clocks
        b = len(batch)
        # Hand the model the POOL + block tables + write slots: the read
        # through the tables, the step AND the new tokens' KV write-back
        # are the model's one call (`TransformerEngineModel`: one
        # donated jit). The engine's own work is the int32 tables, which
        # `kv_gather_s` times; no KV payload passes through here.
        # With a step in flight (`before`: its rows are this batch, in
        # this order) every row is a position further than the host has
        # tokens for, and its token is where the step in flight left it:
        # row i's place in that step's ids, on the device.
        before = self._ahead[0] if self._ahead is not None else None
        unread = int(before is not None)
        with flight.span("engine", "tables", b, clocks, "kv_gather_s"):
            lasts = ([0] * b if unread
                     else [s.all_tokens[-1] for s in batch])
            poss = [len(s.all_tokens) - 1 + unread for s in batch]
            tables = [self.cache.step_tables(s.seq_id) for s in batch]
            entries = [(s.seq_id, poss[i]) for i, s in enumerate(batch)]
        keywords = {"meanwhile": self._in_shadow}
        if unread:
            keywords["ahead"] = (before, list(range(b)))
        elif self._full_after(batch):
            # The first of a run: it takes the host's tokens and stays
            # unread, for the next step to go out ahead of its ids.
            keywords["ahead"] = (None, [-1] * b)
        with flight.span("engine", "model_step", b, clocks,
                         "model_step_s"):
            # `state`: the state pool and the rows' slots, where the
            # model keeps a state a sequence; nothing otherwise.
            step = self.cache.paged_step(
                entries,
                lambda pool, blocks, offs, *state: self.model.decode_paged(
                    pool, tables, lasts, poss, blocks, offs,
                    self.config.block_size, *state, **keywords))
        self.paged_steps += 1
        # (A model that takes `ahead` and still hands back host logits
        # has nothing a later step could take on the device: read.)
        self._ahead = ((step, batch) if "ahead" in keywords
                       and hasattr(step, "on_device") else None)
        if unread:
            # The call has read the step before; this one runs.
            self.decode_steps_ahead += 1
            self._take(before, batch)
        if self._ahead is None:
            self._take(step, batch)

    def _take(self, step, batch: List[_Sequence]) -> None:
        """A decode step's tokens, its ids read or its logits here: what
        the next step needs of them at once (the token joins its
        sequence, a sequence that ended leaves the batch); the streams
        get them from the next call's `meanwhile`, whether that step
        goes out ahead or not: behind its dispatch and in front of its
        wait, where this thread sleeps next and the consumers it wakes
        find the interpreter free. (Handing them over right here, with a
        later step on the device already, put the consumers' wake-up
        between two `decode_paged` calls: 1.3 ms a step of this thread
        waiting for the interpreter, PR 60's first traced run.)

        A row whose end these ids bring while a later step holds it (the
        model's `eos_token`: no end the host could foresee) leaves the
        batch now and keeps its blocks until that step is read: its
        token there is dropped, never emitted, and its write there went
        to a slot it still owned (the device runs programs in dispatch
        order, so a later owner's write comes after it)."""
        clocks = self._clocks
        held_later = self._ahead is not None
        with flight.span("engine", "sample", len(batch), clocks,
                         "sample_s"):
            toks = self._greedy(step)
        joined = time.perf_counter() if flight.enabled else 0.0
        for seq, tok in zip(batch, toks):
            if seq.ended:
                self.cache.free(seq.seq_id)
                continue
            seq.all_tokens.append(tok)
            self.tokens_generated += 1
            seq.ended = self._ended(seq)
            if seq.ended and held_later:
                self.decode_ends_found_late += 1
                with self._lock:
                    self._running.remove(seq)
            elif seq.ended:
                self._release(seq)
            self._pending.append((seq, tok, seq.ended, joined))

    @staticmethod
    def _greedy(step) -> List[int]:
        """A decode step's greedy tokens: the ids its program sampled
        where the model's result carries them (`model.DecodeStep`: no
        logits cross to the host), else the argmax over the logits it
        returned; ties go to the lowest index either way."""
        ids = getattr(step, "ids", None)
        if ids is None:
            ids = np.argmax(np.asarray(step), axis=-1)
        return ids.tolist()

    def _emit(self, seq: _Sequence, tok: int) -> None:
        """A prefill's token, the sequence's first: to its stream at
        once."""
        seq.all_tokens.append(tok)
        if seq.first_token_at is None:
            seq.first_token_at = time.perf_counter()
            ttft = seq.first_token_at - seq.submitted_at
            self._ttfts.append(ttft)
            del self._ttfts[:-1024]
            try:
                from ray_tpu.serve._private.metrics import engine_metrics

                engine_metrics()["ttft"].observe(ttft)
            except Exception:
                pass
        self.tokens_generated += 1
        seq.stream._push(tok)

    def _ended(self, seq: _Sequence) -> bool:
        """Whether the token just appended was the sequence's last."""
        eos = getattr(self.model, "eos_token", None)
        return (seq.generated >= seq.max_new_tokens
                or (eos is not None and seq.all_tokens[-1] == eos))

    def _release(self, seq: _Sequence) -> None:
        """The engine's half of a retirement: out of the batch, its
        blocks free for the next step."""
        with self._lock:
            if seq in self._running:
                self._running.remove(seq)
        self.cache.free(seq.seq_id)

    def _close(self, seq: _Sequence,
               error: Optional[BaseException] = None) -> None:
        """The client's half: the stream ends."""
        self.finished += 1
        self._finish_stamps.append(time.perf_counter())
        seq.stream._finish(error)

    def _retire(self, seq: _Sequence,
                error: Optional[BaseException] = None) -> None:
        self._release(seq)
        self._close(seq, error)

    # -- hosting -------------------------------------------------------
    def start(self) -> None:
        """Run the loop on a daemon thread (how a Serve replica hosts
        the engine); idles on an event when there is no work."""
        if self._thread is not None and self._thread.is_alive():
            return
        self._stop.clear()

        def loop():
            while not self._stop.is_set():
                try:
                    worked = self.step()
                except Exception as e:  # noqa: BLE001 — loop must survive
                    logger.exception("engine step failed; failing "
                                     "in-flight streams")
                    self._fail_in_flight(e)
                    worked = False
                if not worked:
                    with flight.span("engine", "park", None, self._clocks,
                                     "park_s"):
                        self._work.wait(timeout=0.05)
                    self._work.clear()

        self._thread = threading.Thread(target=loop, daemon=True,
                                        name="inference-engine")
        self._thread.start()

    def stop(self, timeout_s: float = 5.0) -> None:
        self._stop.set()
        self._work.set()
        if self._thread is not None:
            self._thread.join(timeout=timeout_s)
            self._thread = None
        self._fail_in_flight(EngineStoppedError("engine stopped"))

    def _fail_in_flight(self, error: BaseException) -> None:
        """Finish every running and waiting stream with `error`, so
        consumers unblock and see it; the tokens of a decode step in
        flight and what is pending come first."""
        self._land()
        self._deliver()
        with self._lock:
            leftovers = list(self._running) + list(self._waiting)
            if self._in_flight is not None:
                leftovers.append(self._in_flight)
                self._in_flight = None
            self._running.clear()
            self._waiting.clear()
        for seq in leftovers:
            self.cache.free(seq.seq_id)
            seq.stream._finish(error)

    def drain(self, timeout_s: float = 30.0) -> bool:
        """Block until no work remains (tests / graceful shutdown)."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            with self._lock:
                if not (self._running or self._waiting or self._pending
                        or self._in_flight or self._ahead):
                    return True
            time.sleep(0.005)
        return False

    # -- observability -------------------------------------------------
    @property
    def cow_copies(self) -> int:
        return self.cache.cow_copies

    # The older clocks as attributes (`ray_tpu/perf.py` reads them so).
    @property
    def prefill_s(self) -> float:
        return self._clocks["prefill_s"]

    @property
    def kv_gather_s(self) -> float:
        return self._clocks["kv_gather_s"]

    @property
    def model_step_s(self) -> float:
        return self._clocks["model_step_s"]

    @property
    def decode_s(self) -> float:
        """A decode step up to its result on the host (the sampled ids,
        or logits): the block tables + the model's step."""
        clocks = self._clocks
        return clocks["kv_gather_s"] + clocks["model_step_s"]

    def phase_seconds(self) -> Dict[str, float]:
        """Where the loop's wall time went, by phase: every instant of
        the loop (`loop_s`) lies in exactly one. The model's phases are
        the model's own (`model.phase`)."""
        clocks = self._clocks
        model = getattr(self.model, "phase", None) or {}
        out = {"park": clocks["park_s"], "tables": clocks["kv_gather_s"],
               "other": clocks["other_s"]}
        out.update((name, clocks[f"{name}_s"]) for name in _STEP_PHASES)
        out.update((f"model_{name}", model.get(f"{name}_s", 0.0))
                   for name in _MODEL_PHASES)
        return out

    def stats(self) -> Dict[str, Any]:
        """Counts, gauges and clocks of the engine. Every top-level
        number but `running`, `waiting` and `ttft_p50_ms` only grows, and
        all are there from construction, so two snapshots subtract key
        by key.

        Clocks (seconds; all fed by `flight.span`, so they stand still
        while the flight recorder is off): `phase.<name>_s` partition
        `loop_s`, the loop's wall time (`engine.step` spans plus the
        park); `thread_cpu_s` is the loop thread's CPU time over the
        same iterations; `queue_wait_s` sums, over admissions, the time
        from entering the waiting queue to the start of the prefill;
        `stream_wake_s` / `stream_wake_tokens` sum, over tokens handed
        to a consumer, the time from `TokenStream._push` to pickup;
        `pending_wait_s` / `pending_wait_tokens`, over decode tokens,
        the time from the end of the token's step (where it joins
        `_pending`) to the flush that hands it to its stream.
        `prefill_s` is all of `_prefill`, `decode_s` a decode step up to
        its result on the host = `kv_gather_s` (the block-table build:
        no KV is gathered on the host) + `model_step_s` (the model's
        step, which also writes the new tokens' KV).
        `paged` reads True and `paged_steps` counts the decode steps:
        every step is the paged one. `decode_steps_ahead` counts those
        of them dispatched before the step before's ids were read (a
        full batch none of whose rows was known to end: the module's
        text), `decode_ends_found_late` the rows whose end such a step's
        ids brought when their next step was already dispatched: that
        step's token of theirs was dropped, never emitted.
        `tokens_delivered_overlapped` counts the decode tokens handed to
        their streams from inside the next step's `meanwhile`, beside a
        busy device (behind a step dispatched ahead too: two steps are
        then on the device); `tokens_generated` less `prefills` (a
        prefill's token goes out at once) less it, those flushed
        early.
        `prefill_chunks` counts the chunks of prompts prefilled in
        chunks (the model's `prefill_chunk` calls whose rows reached the
        cache), `prefill_chunk_tokens` the prompt tokens they ran: over
        the model's `prefill_tokens`, the share of prefilled tokens that
        went in chunks. `prefill_chunks_unwaited` counts, in the model
        where the wait is skipped, the chunks that handed the host
        nothing and were not waited for (every chunk but a prompt's
        last); 0 for a model without the call. `prefills` and
        `queue_wait_s` count a prompt in chunks once, `prefill_s` holds
        every chunk's host side.
        `prefill_kv_device_writes` and `prefill_kv_host_writes` count
        the prompt-KV writes into the pool (`write_range`) that stayed
        on the device, and those that passed through host memory.
        `decode_h2d_arrays` and `decode_d2h_bytes` are what the model's
        paged decode steps moved across the host boundary: host arrays
        among the arguments of its jitted calls, which the call uploads
        (one a step), and bytes brought back (a step's sampled
        ids, 4 a row of the model's largest batch bucket, whatever the
        step's rows, and three counters more where the model has
        experts; its `[b_pad, V]` logits only where somebody fetched
        them); 0 for a model that keeps no such count.
        `decode_attn_inplace_steps` counts the paged steps whose
        attention read the pool's pages in place through the Pallas
        kernel (every step on the chip at heads of 128; none on the CPU,
        at other head sizes, or for `TinyLM`), `decode_kv_pages_read`
        the live pages the block tables of those steps named
        (`position // block_size + 1` a row): what a layer of such a
        step reads of the pool; `decode_kv_page_groups_read` the groups
        of pages the kernel fetched them in (a row's pages that hold a
        cached position it sees, from the first such page on, in groups
        of at most `ops.paged_attention.pages_per_step` pages: 32 of
        64 KB, 8 of 256 KB, no more than the table's width; a row's
        groups are of one size). Pages ÷ groups is what a
        fetch brings: near the group size at long contexts, near 1
        where tables are a column or two wide.
        `moe_local_assignments`, `moe_expert_touches` and
        `moe_max_expert_load` are a sparse-expert model's own counts
        over its paged decode steps, summed over layers: (token, expert)
        pairs that fell on experts held here, (layer, expert) pairs with
        at least one token, and the largest count any held expert took
        in a layer; computed inside the step and fetched with its
        sampled ids; 0 for a model without experts.
        `moe_steps_kernel` and `moe_steps_scan` count such a model's
        programs run, prefills and decode steps (each runs every expert
        layer once), by the body their expert layers were traced with:
        `ops.experts`' Pallas kernel or its scan.
        `dense_steps_kernel` and `dense_steps_xla` count the dense
        model's programs run (each runs every layer's four weight
        products once) by the body those products were traced with:
        `ops.weight_matmul`'s Pallas kernel or XLA's product; 0 for a
        model that has no such products.
        `state_slot_steps_in_use` and `state_slot_steps` sum, over paged
        steps, the state slots in use and the slots there are (a model
        with per-sequence state; 0 otherwise); `cache` has the gauges
        `state_slots`, `state_slots_in_use` and `state_bytes`.
        `kv_<group>_block_steps_in_use` / `kv_<group>_block_steps` sum,
        over paged steps, a layer group's blocks in use and the blocks
        it has, and `kv_<group>_window_blocks_released` counts the blocks
        a window group gave back when they left the window
        (`cache["groups"]` has the gauges); `decode_kv_pages_read_global`
        and `decode_kv_pages_read_window` split `decode_kv_pages_read`
        for a model with a window group (0 otherwise), and
        `decode_kv_page_groups_read_global` / `_window` its groups, and
        `decode_kv_bytes_read_held` / `_model` those pages' bytes as the
        pools hold a position and as the model counts it (keys wider
        than values are held in whole slots);
        `window_release_s` is the host time of that release (the span
        `engine.window_release`, inside `engine.capacity`, whose
        `phase.capacity_s` holds it too)."""
        with self._lock:
            running = len(self._running)
            waiting = len(self._waiting)
        ttfts = sorted(self._ttfts)
        clocks = self._clocks
        cache = self.cache.stats()
        return {
            "steps": self.steps,
            "prefills": self.prefills,
            "preemptions": self.preemptions,
            "tokens_generated": self.tokens_generated,
            "tokens_delivered_overlapped": self.tokens_delivered_overlapped,
            "prefix_hit_tokens": self.prefix_hit_tokens,
            "prefix_exports": self.prefix_exports,
            "prefix_imports": self.prefix_imports,
            "prefix_import_tokens": self.prefix_import_tokens,
            "cow_copies": self.cache.cow_copies,
            "finished": self.finished,
            "running": running,
            "waiting": waiting,
            "cache": cache,
            "prefix_index": (self.prefix_index.stats()
                             if self.prefix_index is not None else None),
            "paged": True,
            "paged_steps": self.paged_steps,
            "decode_steps_ahead": self.decode_steps_ahead,
            "decode_ends_found_late": self.decode_ends_found_late,
            "prefill_chunks": self.prefill_chunks,
            "prefill_chunk_tokens": self.prefill_chunk_tokens,
            "prefill_chunks_unwaited": getattr(
                self.model, "prefill_chunks_unwaited", 0),
            "prefill_kv_device_writes": self.cache.range_writes_device,
            "prefill_kv_host_writes": self.cache.range_writes_host,
            "decode_h2d_arrays": getattr(
                self.model, "decode_h2d_arrays", 0),
            "decode_d2h_bytes": getattr(self.model, "decode_d2h_bytes", 0),
            "decode_attn_inplace_steps": getattr(
                self.model, "decode_attn_inplace_steps", 0),
            "decode_kv_pages_read": getattr(
                self.model, "decode_kv_pages_read", 0),
            "decode_kv_page_groups_read": getattr(
                self.model, "decode_kv_page_groups_read", 0),
            "decode_kv_pages_read_planes": getattr(
                self.model, "decode_kv_pages_read_planes", 0),
            "moe_local_assignments": getattr(
                self.model, "moe_local_assignments", 0),
            "moe_expert_touches": getattr(
                self.model, "moe_expert_touches", 0),
            "moe_max_expert_load": getattr(
                self.model, "moe_max_expert_load", 0),
            "moe_steps_kernel": getattr(self.model, "moe_steps_kernel", 0),
            "moe_steps_scan": getattr(self.model, "moe_steps_scan", 0),
            "dense_steps_kernel": getattr(
                self.model, "dense_steps_kernel", 0),
            "dense_steps_xla": getattr(self.model, "dense_steps_xla", 0),
            "state_slot_steps_in_use": self.cache.state_slot_steps_in_use,
            "state_slot_steps": self.cache.state_slot_steps,
            "decode_kv_pages_read_global": getattr(
                self.model, "decode_kv_pages_read_global", 0),
            "decode_kv_pages_read_window": getattr(
                self.model, "decode_kv_pages_read_window", 0),
            "decode_kv_page_groups_read_global": getattr(
                self.model, "decode_kv_page_groups_read_global", 0),
            "decode_kv_page_groups_read_window": getattr(
                self.model, "decode_kv_page_groups_read_window", 0),
            "decode_kv_bytes_read_held": getattr(
                self.model, "decode_kv_bytes_read_held", 0),
            "decode_kv_bytes_read_model": getattr(
                self.model, "decode_kv_bytes_read_model", 0),
            **self._group_counters(cache["groups"]),
            # What a model counts of its own beside all of the above.
            **{name: getattr(self.model, name)
               for name in getattr(self.model, "own_counters", ())},
            "jit_bucket_evictions": getattr(
                self.model, "jit_cache_evictions", 0),
            "prefill_s": round(self.prefill_s, 6),
            "decode_s": round(self.decode_s, 6),
            "kv_gather_s": round(self.kv_gather_s, 6),
            "model_step_s": round(self.model_step_s, 6),
            "ttft_p50_ms": (round(ttfts[len(ttfts) // 2] * 1e3, 3)
                            if ttfts else None),
            **{f"phase.{name}_s": seconds
               for name, seconds in self.phase_seconds().items()},
            "window_release_s": clocks["window_release_s"],
            "loop_s": clocks["step_s"] + clocks["park_s"],
            "thread_cpu_s": clocks["thread_cpu_s"],
            "queue_wait_s": clocks["queue_wait_s"],
            "stream_wake_s": clocks["stream_wake_s"],
            "stream_wake_tokens": int(clocks["stream_wake_tokens"]),
            "pending_wait_s": clocks["pending_wait_s"],
            "pending_wait_tokens": int(clocks["pending_wait_tokens"]),
        }

    @staticmethod
    def _group_counters(groups: Dict[str, dict]) -> Dict[str, int]:
        """The cache's counters a layer group, flat (`kv_<group>_...`),
        so that two snapshots subtract: blocks in use and blocks there
        are, summed over paged steps, and the blocks a window group gave
        back. A model without groups has the `global` group alone."""
        out = {}
        for name, g in groups.items():
            for key in ("block_steps_in_use", "block_steps",
                        "window_blocks_released"):
                out[f"kv_{name}_{key}"] = g[key]
        return out

    def _update_gauges(self) -> None:
        with flight.span("engine", "gauges", None, self._clocks,
                         "gauges_s"):
            self._push_gauges()

    def _push_gauges(self) -> None:
        try:
            from ray_tpu.serve._private.metrics import engine_metrics

            m = engine_metrics()
            m["batch_occupancy"].set(float(self.batch_occupancy()))
            m["cache_utilization"].set(self.cache.utilization())
            m["queue_depth"].set(float(self.queue_depth()))
            # Counters take deltas since the last push (the registry
            # instruments are cumulative; the engine's own fields are
            # the source of truth for stats()).
            for attr, key in (("preemptions", "preemptions"),
                              ("tokens_generated", "tokens"),
                              ("prefix_hit_tokens", "prefix_hit_tokens"),
                              ("cow_copies", "cow")):
                cur = getattr(self, attr)
                last = self._pushed.get(attr, 0)
                if cur > last:
                    m[key].inc(cur - last)
                    self._pushed[attr] = cur
            # The loop's phases ride the same counter as further
            # `phase` tags, every 64th step: a registry call each, on
            # the loop they time.
            phases = self.phase_seconds() if self.steps % 64 == 0 else {}
            for attr in ("prefill", "decode", "kv_gather", "model_step"):
                phases[attr] = getattr(self, f"{attr}_s")
            for phase, cur in phases.items():
                last = self._pushed.get(f"phase.{phase}", 0.0)
                if cur > last:
                    m["step_phase"].inc(cur - last,
                                        tags={"phase": phase})
                    self._pushed[f"phase.{phase}"] = cur
            m["kv_pool_bytes"].set(
                float(self.cache.pool_bytes),
                tags={"replica": self.replica_tag,
                      "residency": self.cache.pool_residency})
            evs = int(getattr(self.model, "jit_cache_evictions", 0))
            last_ev = self._pushed.get("jit_evictions", 0)
            if evs > last_ev:
                m["jit_evictions"].inc(evs - last_ev)
                self._pushed["jit_evictions"] = evs
            if self.prefix_index is not None:
                # Per-replica radix-index state on the scrape path —
                # the dashboard's /api/serve `prefix` section and the
                # fleet router's digest freshness both ride this.
                pst = self.prefix_index.stats()
                tags = {"replica": self.replica_tag}
                m["prefix_nodes"].set(float(pst["nodes"]), tags=tags)
                m["prefix_sealed"].set(
                    float(self.prefix_index.held_blocks()), tags=tags)
                m["prefix_hits_state"].set(float(pst["hits"]), tags=tags)
                m["prefix_evictions_state"].set(
                    float(pst["evictions"]), tags=tags)
        except Exception:
            pass  # metrics must never fail the decode loop


class EngineStoppedError(RuntimeError):
    pass
