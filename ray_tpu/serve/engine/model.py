"""Decode-step model shims for the continuous-batching engine.

The iteration scheduler drives any model through three calls. The model
reads the KV pool (``[num_blocks, block_size, *kv_token_shape]``) itself,
through block tables; the engine never gathers a sequence's KV for it:

- ``prefill(tokens) -> (next_token_logits [V], kv [S, *kv_token_shape])``
  — run the prompt once, return the logits that predict the first
  generated token plus the per-position KV entries to cache. The KV
  result goes to `KVCacheManager.write_range`; what that needs of it is
  `len()` (the rows to write) and `np.asarray()` (`PromptKV` adds its
  device rows);
- ``prefill_paged(tokens, pool, block_table, prefix_len, block_size)
  -> (logits [V], kv [S-P, *kv_token_shape])`` — the same for a prompt
  whose first ``prefix_len`` positions the engine adopted from a shared
  prefix: the model reads them out of ``pool`` through ``block_table``
  and computes (and returns) KV for the unmatched tail only —
  prefill-from-offset, the compute half of prefix sharing;
- ``decode_paged(pool, block_tables, last_tokens, positions,
  write_blocks, write_offs, block_size) -> (step, new_pool)`` — one
  incremental step for a batch of sequences: row i reads its cached
  positions ``[0, positions[i])`` through ``block_tables[i]``,
  ``last_tokens[i]`` is its most recent token (not yet cached), and its
  KV lands at ``(write_blocks[i], write_offs[i])`` (a write list shorter
  than the batch writes only those rows; empty = read-only). ``step`` is
  host logits ``[B, V]`` or a `DecodeStep`. It takes one keyword beside
  them, ``meanwhile``: a callable that the model runs exactly once in the
  call, where it has started the step and has nothing to do until the
  step's result is there (between the ``decode.dispatch`` and the
  ``decode.logits_wait`` span of a model that dispatches to a device;
  before it computes, for one that computes on the host). It is the one
  point of a step at which the host knows the device is busy, and the
  model owns it; the scheduler delivers the step before's tokens there.
  Keyword-only, because the families' further arguments (``state``,
  ``slots``) trail the positional ones. None runs nothing.

  A model whose step runs on a device may take a second keyword,
  ``ahead``, by which the scheduler dispatches a step BEFORE it has read
  the step before's ids (`scheduler.py`: behind a full batch none of
  whose rows is known to end). ``ahead = (before, sources)``: ``before``
  is the `DecodeStep` an earlier call returned and nobody has read, or
  None where there is none (the first of a run); ``sources[i]`` is row
  i's place in ``before``'s ids, or -1 for ``last_tokens[i]``. With it
  the call (a) takes a row's token from ``before``'s ids where they lie
  on the device wherever a place is given, in the bucket's ONE program
  (it always takes an array of the step before's ids, zeros where every
  token is the host's, and always returns its own at that width: no
  second program, nothing to compile in a serving window); (b) once
  dispatched, and after ``meanwhile``, waits for ``before``'s ids where
  it would have waited for its own (the same ``decode.logits_wait``
  span; no wait where ``before`` is None); (c) returns its own step
  UNREAD: a `DecodeStep` whose ``ids`` wait for the device when first
  looked at, and whose ``on_device`` the next call takes as
  ``before``. The model holds nothing between two calls: the scheduler
  holds the step in flight and hands it back. Without the keyword a
  call does what it always did and its step comes back read. A model
  whose ``decode_paged`` has no parameter of that name (`TinyLM`, a
  user's own) is never called with it, and its every step is read
  before the next is dispatched.

Beside them, three attributes: ``kv_token_shape`` and ``kv_dtype`` (a
pool row) and ``kv_pool_ns``, the array namespace the model's step reads
its pool in (numpy or `jax.numpy`; absent means numpy). It is no option:
each model class has one value and the engine builds its pool from it.

A model may declare a fourth, ``state_shapes``: what it keeps a SEQUENCE
beside its KV rows, ``{name: (shape, dtype)}`` (a recurrent or
linear-attention layer's state, a convolution's tail). The cache manager
then holds a slot a sequence beside the blocks and the contract grows by
two things, for that model alone: the KV result of ``prefill`` carries
``state``, the arrays the prompt ended on (`hybrid_model.PromptState`),
which `write_range` stores in the sequence's slot; and ``decode_paged``
takes two more arguments, ``state`` (the pools, ``[slots, *shape]`` a
name) and ``slots`` (row i's slot), and returns ``(step, new_pool,
new_state)``. Blocks of KV do not restore such a sequence's prefix, so
the engine builds no prefix index over a model that declares state,
never calls its ``prefill_paged`` with an offset, and recomputes a
preempted row by ``prefill``, as for any model.

A model may declare a fifth, ``kv_groups``: further layer groups of
its KV beside the one `kv_token_shape` describes, ``{name: {"kv_shape":
..., "window": ...}}`` (layers of sliding-window attention keep a
window's rows, in a pool of their own whose blocks the cache manager
gives back as they leave the window: `kv_cache.py`). The contract then
changes in three places, for that model alone: the KV result of
``prefill`` carries ``groups``, a `PromptKV` a further group
(`layer_groups_model.PromptGroups`); ``decode_paged`` takes ``pool``,
``write_blocks`` and ``write_offs`` as dicts a group (the first group is
``"global"``) and ``block_tables[i]`` as ``{group: (base, table)}``, a
window group's table compact from logical block ``base``, and returns
``(step, new_pools)``; and the engine builds no prefix index (an adopted
prefix would need the window layers' rows at its end). Such a model may
also declare ``kv_planes``, ``{group: bool}``: which of its groups' own
pools the cache holds by planes (`kv_cache.py`, storage;
`ops.paged_attention.held_by_planes` says from the group's key/value
heads). The payloads stay a position a row; the pools the model's
programs take and hand back are of the layout declared, and they tell
the two by the pool's rank (`ops.paged_attention.by_planes`).

A model may offer a fourth call, with the attribute
``prefill_chunk_tokens`` (``C``): ``prefill_chunk(tokens, pools, tables,
start, block_size, *, meanwhile=None) -> (logits [V] or None, kv)``
runs positions ``[start, start + C)`` of the prompt ``tokens`` (what it
has of them; ``start`` a multiple of ``C``), whose positions before
``start`` it reads out of ``pools`` through ``tables`` (the sequence's
`KVCacheManager.step_tables`, in `with_pools`: a pool and a table, or
with ``kv_groups`` a dict a group of each), and returns the chunk's KV
for ``write_range(seq, start, kv)`` and, from the chunk that holds the
prompt's last token alone, the logits ``prefill`` would return. The
scheduler then prefills a prompt longer than ``C`` a chunk an
iteration, a decode step of the running batch between two chunks
(`scheduler.py`); ``meanwhile`` as in ``decode_paged``, behind the
dispatch. Only the chunk that yields logits is waited for: any other
returns once dispatched, its ``kv`` unfinished device values that
``write_range`` and the next decode step take as they are (the device
runs programs in dispatch order; `sparse_model._prompt_logits`).

Four implementations:

- **TinyLM** — a deterministic pure-numpy model whose next token is a
  fixed function of the *cached* KV contents, so every block-table bug
  (wrong block, stale entry, bad gather order) changes the output. This
  is what makes the scheduler fully testable under ``JAX_PLATFORMS=cpu``
  in the seconds-fast unit tier.
- **TransformerEngineModel** — incremental KV decoding over the
  flagship ``models/transformer.py`` weights (same params pytree, same
  rmsnorm/rotary/attention math as `plain_attention`), jit-compiled once
  per (batch, seq) *bucket*: inputs are padded up to power-of-two
  bucket sizes so the number of distinct compiled shapes stays
  O(log max_batch * log max_seq) instead of one per request mix.
- **HybridEngineModel** (`hybrid_model.py`) — the hybrid sparse decoder
  of `models/hybrid_moe.py`: grouped-query attention over the pool in
  one layer of four, delta-rule linear attention over a declared
  per-sequence state in the others, a dropless expert layer that holds a
  range of the routed experts; the same buckets, packed upload and
  sampled-ids return.
- **LagunaEngineModel** (`laguna_model.py`) — the window-and-global
  sparse decoder of `models/laguna.py`: full and sliding-window layers
  of different head counts over two layer groups of the cache, a gate a
  head, two rotary schemes, a dense first layer and held experts behind
  a softmax router. What it shares with the hybrid model (norm, product
  helper, expert layer, counters, the host side of a call) is
  `sparse_model.SparseEngineModel`.
- **MimoEngineModel** (`mimo_model.py`) — the sparse decoder of
  `models/mimo_v2.py`: global layers on 4 key/value heads beside window
  layers on 8, keys of 192 values over values of 128, a sink logit a
  head in the window layers' softmax, a sigmoid router without a shared
  expert. What it shares with the Laguna model (the two layer groups'
  programs, packed step and counters) is
  `layer_groups_model.LayerGroupsEngineModel`.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Dict, List, Sequence, Tuple

import numpy as np

from ray_tpu.core import flight


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


class _JitLRU(OrderedDict):
    """Bounded LRU of compiled shape buckets. Buckets accumulate over a
    replica's lifetime (a prompt length for prefill, (batch, blocks,
    block size) for the paged step, (tail, prefix blocks, block size)
    for the paged prefill) — unbounded dicts would pin every compiled
    executable forever. `get` refreshes recency; inserting past `cap`
    drops the coldest bucket (the executable is re-built on next use)
    and counts the eviction."""

    def __init__(self, cap: int):
        super().__init__()
        self.cap = max(1, int(cap))
        self.evictions = 0

    def get(self, key, default=None):
        if key in self:
            self.move_to_end(key)
            return super().__getitem__(key)
        return default

    def __setitem__(self, key, value):
        super().__setitem__(key, value)
        self.move_to_end(key)
        while len(self) > self.cap:
            self.popitem(last=False)
            self.evictions += 1


class PromptKV:
    """A prefill's KV as its jit returned it: `padded` is the device
    array `[rows_pad, *kv_token_shape]` of the whole shape bucket, of
    which the first `len()` rows are the prompt's (or its tail's) KV and
    the rest whatever the padded positions computed.

    A device pool scatters `padded` as it stands (`write_range` points
    the rows past `len()` outside the pool, where they drop), so the KV
    never visits the host and no device program's shape depends on the
    prompt's length. `np.asarray()` is the `[len(), *kv_token_shape]`
    host array, for a host pool or a test: one copy of the bucket, cut
    on the host."""

    __slots__ = ("padded", "_n")

    def __init__(self, padded, n: int):
        self.padded = padded
        self._n = n

    def __len__(self) -> int:
        return self._n

    def __array__(self, dtype=None, copy=None):
        host = np.asarray(self.padded)[:self._n]
        return host if dtype is None else host.astype(dtype, copy=False)


class DecodeStep:
    """A paged decode step's result as its jit returned it: the int32
    array its program sampled on the device (the greedy ids at a fixed
    width, `jnp.argmax`, ties to the lowest index as in `np.argmax`;
    behind them whatever else the model's program put there) and the
    step's `[b_pad, V]` float32 logits, both still on the device.

    `ids` is the `[len()]` greedy tokens on the host. A plain
    `decode_paged` call has read them before it returns; a call with
    `ahead` hands its step back UNREAD, and the first look at `ids`
    waits for the device and fetches them (`model._fetch_ids`: the
    `decode.logits_wait` span, the bytes and the counters the program
    sent along are counted there, once, when the step is read).
    `on_device` is the array itself, which the next step's program takes
    its tokens from without the host's having seen them.

    The scheduler's sampler takes `ids` and nothing else crosses.
    `np.asarray()` is the `[len(), V]` host logits for whoever asks (a
    check against a reference, the scheduler's fully-cached prompt, a
    test): one fetch of the padded bucket, counted in the model's
    `decode_d2h_bytes`, cut on the host and kept."""

    __slots__ = ("on_device", "_ids", "_n", "_logits", "_model")

    def __init__(self, on_device, n: int, logits, model):
        self.on_device = on_device
        self._ids = None
        self._n = n
        self._logits = logits
        self._model = model

    @property
    def ids(self) -> np.ndarray:
        if self._ids is None:
            self._ids = self._model._fetch_ids(self.on_device)[:self._n]
        return self._ids

    def __len__(self) -> int:
        return self._n

    def __array__(self, dtype=None, copy=None):
        if not isinstance(self._logits, np.ndarray):
            padded = np.asarray(self._logits)
            self._model.decode_d2h_bytes += padded.nbytes
            self._logits = padded[:self._n]
        host = self._logits
        return host if dtype is None else host.astype(dtype, copy=False)


def step_tokens(packed, before):
    """Inside a decode program: the rows' input tokens. `packed`'s first
    column is a row's token as the host knew it, its LAST column the
    row's place in `before` (the int32 array the step before's program
    returned, `DecodeStep.on_device`) or -1: where a place is given the
    token comes from there, and the host has not seen it."""
    import jax.numpy as jnp

    src = packed[:, -1]
    return jnp.where(src >= 0, before[jnp.maximum(src, 0)], packed[:, 0])


def place_sources(packed: np.ndarray, ahead) -> None:
    """The host's side of `step_tokens`: `packed`'s last column from the
    call's `ahead` (`(before, sources)` or None)."""
    packed[:, -1] = -1
    if ahead is not None and ahead[0] is not None:
        packed[:len(ahead[1]), -1] = ahead[1]


class StepIds:
    """What the device models share of a decode step's int32 result
    (the base of `TransformerEngineModel` and of `SparseEngineModel`,
    which set `_max_batch` and `_jnp`): how wide a bucket's program
    returns its ids, and what it takes as the step before's."""

    # What a program returns behind its ids (expert counters).
    _ids_trail = 0

    def _ids_width(self, b_pad: int) -> int:
        """How wide a bucket's program returns its ids, and takes the
        step before's: the model's largest batch bucket (a bucket of more
        rows than the model was built for, its own)."""
        return max(_next_pow2(self._max_batch), b_pad)

    def _before(self, ahead, b_pad: int):
        """What a step's program takes as the step before's result: the
        one `ahead` names, where it lies on the device; for a step that
        takes every token from the host zeros, on the device, made once
        a width."""
        if ahead is not None and ahead[0] is not None:
            return ahead[0].on_device
        width = self._ids_width(b_pad) + self._ids_trail
        zeros = getattr(self, "_zeros", None)
        if zeros is None:
            zeros = self._zeros = {}
        if width not in zeros:
            zeros[width] = self._jnp.zeros((width,), self._jnp.int32)
        return zeros[width]


def read_after_dispatch(step: DecodeStep, ahead) -> None:
    """What a `decode_paged` call waits for once its step is on the
    device: its own step's ids, or with `ahead` the step before's (none
    where there is no step before: the call returns at once)."""
    if ahead is None:
        step.ids
    elif ahead[0] is not None:
        ahead[0].ids


class TinyLM:
    """Deterministic cache-exercising toy LM.

    KV entry per token = ``float(token)`` (shape ``(1,)``). The next
    token is ``2 + (sum(cached) + 7*last + 3*pos) % (vocab-2)`` — a pure
    function of the full token history, but computed FROM THE CACHE, so
    the engine only reproduces the oracle (`TinyLM.oracle`) if block
    allocation, writes, gathers, preemption-requeue and re-prefill are
    all correct. Token ids 0 (pad) and 1 (eos) are reserved; when
    ``eos_period`` is set, the hash landing on a multiple emits eos.
    """

    kv_token_shape: Tuple[int, ...] = (1,)
    kv_dtype = np.float32
    kv_pool_ns = np

    def __init__(self, vocab_size: int = 32, eos_period: int = 0,
                 step_delay_s: float = 0.0,
                 prefill_token_delay_s: float = 0.0):
        assert vocab_size >= 4
        self.vocab_size = vocab_size
        self.eos_token = 1
        self.eos_period = eos_period
        self.step_delay_s = step_delay_s
        # Simulated per-token prefill cost: makes shared-prefill compute
        # savings measurable in the prefix-workload bench (a prefix hit
        # pays only the tail).
        self.prefill_token_delay_s = prefill_token_delay_s
        self.prefill_calls = 0
        self.prefill_tokens = 0
        self.decode_calls = 0

    def _next(self, cached_sum: float, last: int, pos: int) -> int:
        h = int(round(cached_sum)) + 7 * int(last) + 3 * int(pos)
        if self.eos_period and h % self.eos_period == 0:
            return self.eos_token
        return 2 + h % (self.vocab_size - 2)

    def prefill(self, tokens: Sequence[int], prefix_kv=None):
        self.prefill_calls += 1
        toks = np.asarray(tokens, np.int64)
        p = 0 if prefix_kv is None else int(np.asarray(prefix_kv).shape[0])
        self.prefill_tokens += len(toks) - p
        if self.prefill_token_delay_s:
            import time

            time.sleep(self.prefill_token_delay_s * (len(toks) - p))
        kv = toks[p:].astype(np.float32)[:, None]      # [S-P, 1]
        # The hash reads the CACHED prefix kv values, not the token
        # ids — an adoption bug (wrong block, stale COW source) changes
        # this sequence's very first token.
        cached = float(np.asarray(prefix_kv).sum()) if p else 0.0
        if len(toks) - 1 > p:
            cached += float(toks[p:-1].sum())
        nxt = self._next(cached, int(toks[-1]), len(toks) - 1)
        logits = np.full((self.vocab_size,), -1e30, np.float32)
        logits[nxt] = 0.0
        return logits, kv

    def decode(self, kvs: List[np.ndarray], last_tokens: Sequence[int],
               positions: Sequence[int]):
        """TinyLM's own arithmetic over each row's gathered KV: what
        `decode_paged` calls once it has read the pool."""
        self.decode_calls += 1
        if self.step_delay_s:
            import time

            time.sleep(self.step_delay_s)
        b = len(last_tokens)
        logits = np.full((b, self.vocab_size), -1e30, np.float32)
        new_kv = np.zeros((b,) + self.kv_token_shape, np.float32)
        for i in range(b):
            nxt = self._next(float(np.asarray(kvs[i]).sum()),
                             int(last_tokens[i]), int(positions[i]))
            logits[i, nxt] = 0.0
            new_kv[i, 0] = float(last_tokens[i])
        return logits, new_kv

    def _pool_gather(self, pool, table: Sequence[int], n: int,
                     block_size: int) -> np.ndarray:
        """Host gather of positions [0, n) straight from the pool via a
        block table — the toy model's paged path. TinyLM is the oracle,
        not the perf subject, so reading the pool to host here is fine;
        what matters is that the BLOCK TABLE (not a pre-gathered view)
        drives the read, so table bugs still change tokens."""
        if n == 0:
            return np.zeros((0,) + self.kv_token_shape, np.float32)
        nb = (n + block_size - 1) // block_size
        pool_np = np.asarray(pool, np.float32)
        idx = np.asarray(list(table)[:nb], np.int64)
        return pool_np[idx].reshape((-1,) + self.kv_token_shape)[:n]

    def decode_paged(self, pool, block_tables: List[Sequence[int]],
                     last_tokens: Sequence[int],
                     positions: Sequence[int],
                     write_blocks: Sequence[int],
                     write_offs: Sequence[int], block_size: int, *,
                     meanwhile=None):
        """Fused paged step: read through the block tables, decode,
        write each new token's KV into its (block, off) slot, and
        return ``(logits, new_pool)``. `write_blocks` may be shorter
        than the batch (empty = read-only step, e.g. a full prefix
        hit). The oracle keeps everything on host; only the write-back
        shape matters here. It has no device to wait for, so `meanwhile`
        runs before it computes: the same order as a model that has."""
        if meanwhile is not None:
            meanwhile()
        kvs = [self._pool_gather(pool, block_tables[i],
                                 int(positions[i]), block_size)
               for i in range(len(last_tokens))]
        logits, new_kv = self.decode(kvs, last_tokens, positions)
        k = min(len(write_blocks), len(last_tokens))
        if isinstance(pool, np.ndarray):
            for i in range(k):
                pool[write_blocks[i], write_offs[i]] = new_kv[i]
        elif k:
            pool = pool.at[np.asarray(write_blocks[:k], np.int32),
                           np.asarray(write_offs[:k], np.int32)].set(
                np.asarray(new_kv[:k], dtype=pool.dtype))
        return logits, pool

    def prefill_paged(self, tokens: Sequence[int], pool,
                      block_table: Sequence[int], prefix_len: int,
                      block_size: int):
        prefix_kv = (self._pool_gather(pool, block_table, prefix_len,
                                       block_size)
                     if prefix_len else None)
        return self.prefill(tokens, prefix_kv)

    def oracle(self, prompt: Sequence[int], max_new_tokens: int
               ) -> List[int]:
        """Reference generation, no cache: what the engine MUST emit."""
        toks = list(prompt)
        out: List[int] = []
        while len(out) < max_new_tokens:
            nxt = self._next(float(sum(toks[:-1])), toks[-1],
                             len(toks) - 1)
            out.append(nxt)
            if nxt == self.eos_token:
                break
            toks.append(nxt)
        return out


class TransformerEngineModel(StepIds):
    """Incremental KV decoding over `models/transformer.py` weights.

    KV entry per token: ``[n_layers, 2, n_heads, head_dim]`` float32.
    Prefill runs a full causal forward (same math as the training
    model's CPU path — rmsnorm, fused qkv, rotary, `plain_attention`
    scaling, silu-gated FFN, tied embeddings) while collecting K/V;
    decode attends one query token a row against that row's pages of
    the pool, read where they lie through the block table, one layer
    at a time (`ops/paged_attention.py`: a Pallas kernel on the chip at
    a multiple of 8 heads of 128, a per-layer XLA gather elsewhere;
    the choice is made from backend and widths, no option). No dense
    copy of the batch's cache is ever built. Both are jit-compiled per
    shape bucket: sequence lengths pad to the next power of two (>=
    block multiple), batches pad with masked dummy rows, so compiles
    are bounded by the bucket count, not the request mix. It declares
    no per-sequence state: KV rows are all a sequence keeps, and the
    engine shares prefixes over it. The training model's capacity-drop
    MoE (`cfg.is_moe`, `ops/moe.py`) is refused here; sparse experts are
    served by `HybridEngineModel` through `ops/experts.py`.

    A layer's four weight products (`qkv`, `wo`, `w13`, `w2`) take the
    whole stack of the layers' matrices and the layer's index
    (`ops/weight_matmul.py`: on the chip a Pallas kernel that streams
    that layer's float32 tiles from HBM once and rounds them to bf16 in
    VMEM; XLA's product elsewhere, chosen from backend, widths and
    rows, no option). The programs' layer scans close over the stacks
    and scan over the layer index and the norms alone, so nothing copies
    a layer out of a stack. The model holds the weights in the dtype it
    was given, in a tree of its own: `wqkv` and `w13` laid out once, at
    construction, as 3-D ``[L, d, 3 d]`` and ``[L, d, 2 f]`` (the chip
    keeps the training tree's 4-D ``[L, d, 3, d]`` in tiles a kernel
    cannot read in place and would re-lay them in front of every call);
    `wo`, `w2`, the norms and the embedding are the caller's own arrays.
    A caller that keeps its tree (a benchmark's reference check) so
    holds `wqkv` and `w13` twice: 3.0 GB more at `olmo-1b`'s widths,
    where the programs used to hold a 2.15 GB bf16 copy of the stacks
    while they ran.
    """

    def __init__(self, params, cfg, max_batch_size: int = 8,
                 jit_cache_cap: int = 32):
        import jax
        import jax.numpy as jnp

        from ray_tpu.ops.paged_attention import (kernel_eligible,
                                                 page_groups)

        if cfg.is_moe:
            raise ValueError("TransformerEngineModel supports dense "
                             "configs only (num_experts == 0)")
        # `wqkv [L, d, 3, d]` and `w13 [L, d, 2, f]` as `[L, d, 3 d]` and
        # `[L, d, 2 f]`, once (one pass over the two): the shape
        # `ops.weight_matmul`'s kernel reads in place.
        lay = jax.jit(lambda w: w.reshape(w.shape[0], w.shape[1], -1))
        layers = dict(params["layers"])
        layers.update(wqkv=lay(layers["wqkv"]), w13=lay(layers["w13"]))
        self._params = dict(params, layers=layers)
        self._cfg = cfg
        self.vocab_size = cfg.vocab_size
        self.eos_token = 1
        self.kv_token_shape = (cfg.n_layers, 2, cfg.n_heads, cfg.head_dim)
        self.kv_dtype = np.float32
        self._max_batch = max_batch_size
        self._prefill_jit = _JitLRU(jit_cache_cap)   # S_pad -> fn
        self._decode_paged_jit = _JitLRU(jit_cache_cap)
        self._prefill_paged_jit = _JitLRU(jit_cache_cap)
        self.prefill_calls = 0
        self.prefill_tokens = 0
        self.decode_calls = 0
        self.jit_compiles = 0
        # What a paged decode step moves across the host boundary: host
        # arrays among the jitted call's arguments, which it uploads
        # (one a step), and bytes brought back (the
        # step's ids; its logits only where somebody asked for them).
        # `InferenceEngine.stats()` reads both under these names.
        self.decode_h2d_arrays = 0
        self.decode_d2h_bytes = 0
        # How a paged decode step reads the pool: the steps whose
        # attention read pages in place through the Pallas kernel (all
        # of them on the chip at 8k heads of 128, none elsewhere: fixed
        # by backend and widths, `ops.paged_attention.kernel_eligible`),
        # and the live pages the tables of those steps named
        # (`position // block_size + 1` a row, the page the new token
        # lands in included); a layer reads each once, in the groups of
        # pages the kernel fetches together (`page_groups`: a row's live
        # pages ÷ `pages_per_step`, rounded up).
        self._attn_inplace = kernel_eligible(cfg.n_heads, cfg.head_dim)
        self._page_groups = page_groups
        self.decode_attn_inplace_steps = 0
        self.decode_kv_pages_read = 0
        self.decode_kv_page_groups_read = 0
        # Programs run (a prefill, a decode step: each runs every layer's
        # four weight products once) by the body those products got when
        # the program was traced, `ops.weight_matmul`'s kernel or XLA's
        # product, found by their rows.
        self.dense_steps_kernel = 0
        self.dense_steps_xla = 0
        self._products_kernel_at: Dict[int, bool] = {}
        # Host side of the calls, in seconds, each fed by its
        # `flight.span`: input padding and upload (`prep`), the call of
        # the jitted function (`dispatch`; a paged decode step's one
        # host buffer goes up inside it), the result's arrival on the
        # host (`wait`: the device's compute shows here; a prefill waits
        # for its logits, a paged decode step for its sampled ids).
        # `prefill_kv_d2h_s` timed the prompt KV's trip to the host
        # while `prefill` made it; it stands still now, and stays for
        # the readers of `phase.model_prefill_kv_d2h_s`.
        # `InferenceEngine.stats()` reads them as `phase.model_<name>`.
        self.phase: Dict[str, float] = dict.fromkeys(
            ("prefill_prep_s", "prefill_dispatch_s", "prefill_wait_s",
             "prefill_kv_d2h_s", "decode_prep_s", "decode_dispatch_s",
             "decode_wait_s"), 0.0)
        self._jnp = jnp
        self._tree_leaves = jax.tree_util.tree_leaves

    @property
    def kv_pool_ns(self):
        """The pool is a `jax.numpy` array: the jitted steps read it
        through block tables and the donated scatter writes it."""
        return self._jnp

    @property
    def jit_cache_evictions(self) -> int:
        """Compiled shape buckets dropped by the LRU caps (the
        `serve_engine_jit_bucket_evictions` counter)."""
        return (self._prefill_jit.evictions
                + self._decode_paged_jit.evictions
                + self._prefill_paged_jit.evictions)

    # -- shared math ---------------------------------------------------
    @staticmethod
    def _rot1(x, cos, sin, positions):
        """Rotary for one token per row: x [B, H, D], positions [B]."""
        import jax.numpy as jnp

        c = cos[positions][:, None, :]   # [B, 1, D/2]
        s = sin[positions][:, None, :]
        x1, x2 = jnp.split(x, 2, axis=-1)
        return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c],
                               axis=-1).astype(x.dtype)

    def _products(self, layers, rows: int, phase: str):
        """The weight products of a program of `rows` rows, while it is
        traced: ``product(y, key, li)`` is ``y [rows, K]`` against layer
        `li` of the stack ``layers[key]``, float32. The layer scan's
        body closes over the stacks through it. In a device trace the
        kernel's calls are `stacked_weight_matmul_<phase>`. Notes which
        body the rows get, for `_count_products`."""
        from ray_tpu.ops.weight_matmul import (kernel_eligible,
                                               stacked_weight_matmul)

        stacks = {key: layers[key] for key in ("wqkv", "wo", "w13", "w2")}
        self._products_kernel_at[rows] = all(
            kernel_eligible(rows, *w.shape[1:], w.dtype)
            for w in stacks.values())
        name = f"stacked_weight_matmul_{phase}"

        def product(y, key, li):
            return stacked_weight_matmul(y, stacks[key], li, name)

        return product

    def _count_products(self, rows: int) -> None:
        """A program of `rows` rows has been dispatched (so traced)."""
        if self._products_kernel_at.get(rows):
            self.dense_steps_kernel += 1
        else:
            self.dense_steps_xla += 1

    def _layer_xs(self, layers):
        """What the layer scans scan over: the two norms' scales and the
        layer's index. The four matrix stacks are closed over."""
        return (layers["ln1"], layers["ln2"],
                self._jnp.arange(self._cfg.n_layers, dtype=self._jnp.int32))

    def _build_prefill(self, s_pad: int):
        import jax
        import jax.numpy as jnp

        from ray_tpu.models.transformer import _rmsnorm
        from ray_tpu.ops.rotary import apply_rotary, rotary_freqs

        self.jit_compiles += 1
        cfg = self._cfg
        h, hd = cfg.n_heads, cfg.head_dim
        d, f = h * hd, cfg.d_ff

        def prefill(params, tokens, length):
            # tokens [S_pad] int32 (zero-padded), length scalar int32.
            act = jnp.float32
            with jax.named_scope("embed"):
                x = params["embed"][tokens].astype(act)[None]   # [1,S,D]
            cos, sin = rotary_freqs(hd, cfg.max_seq_len, cfg.rope_theta)
            pos = jnp.arange(s_pad)
            valid = pos < length
            causal = (pos[:, None] >= pos[None, :]) & valid[None, :]
            product = self._products(params["layers"], s_pad, "prefill")

            def layer(x, inputs):
                ln1, ln2, li = inputs
                with jax.named_scope("attn"):
                    y = _rmsnorm(x, ln1)
                    qkv = product(y[0], "wqkv", li)       # [S, 3 d]
                    q, k, v = (qkv[:, i * d:(i + 1) * d].reshape(
                        1, s_pad, h, hd) for i in range(3))
                    q = apply_rotary(q, cos, sin, pos)
                    k = apply_rotary(k, cos, sin, pos)
                    scale = hd ** -0.5
                    scores = jnp.einsum(
                        "bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
                    scores = jnp.where(causal[None, None], scores, -1e30)
                    probs = jax.nn.softmax(scores, axis=-1).astype(act)
                    o = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
                    x = x + product(o.reshape(s_pad, d), "wo", li)[None]
                with jax.named_scope("mlp"):
                    y = _rmsnorm(x, ln2)
                    gu = product(y[0], "w13", li)         # [S, 2 f]
                    x = x + product(jax.nn.silu(gu[:, :f]) * gu[:, f:],
                                    "w2", li)[None]
                kv = jnp.stack([k[0], v[0]], axis=1)  # [S, 2, H, hd]
                return x, kv

            x, kvs = jax.lax.scan(layer, x,
                                  self._layer_xs(params["layers"]))
            with jax.named_scope("lm_head"):
                x = _rmsnorm(x, params["ln_f"])
                last = x[0, length - 1]
                logits = jnp.einsum("d,vd->v", last,
                                    params["embed"].astype(act))
            # kvs [L, S, 2, H, hd] -> [S, L, 2, H, hd]
            return logits, kvs.transpose(1, 0, 2, 3, 4)

        # The function's name is the device program's: `jit_prefill` in
        # a profile's module line.
        return jax.jit(prefill)

    def _prefill_cached_math(self, params, tail_tokens, p_len, t_len,
                             prefix, t_pad: int, p_pad: int):
        """Traced body of prefill-from-offset: tail queries attend over
        the prefix KV plus the tail's own keys — the prompt's matched
        head is never recomputed. `prefix` rows beyond `p_len` are
        masked out of attention (`pref_valid`): the paged gather hands
        in whatever the gathered blocks hold there."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.models.transformer import _rmsnorm
        from ray_tpu.ops.rotary import apply_rotary, rotary_freqs

        cfg = self._cfg
        h, hd = cfg.n_heads, cfg.head_dim
        d, f = h * hd, cfg.d_ff
        product = self._products(params["layers"], t_pad, "prefill")

        act = jnp.float32
        with jax.named_scope("embed"):
            x = params["embed"][tail_tokens].astype(act)[None]  # [1,T,D]
        cos, sin = rotary_freqs(hd, cfg.max_seq_len, cfg.rope_theta)
        tpos = p_len + jnp.arange(t_pad)      # absolute positions
        tail_valid = jnp.arange(t_pad) < t_len
        pref_valid = jnp.arange(p_pad) < p_len
        causal_tt = ((jnp.arange(t_pad)[:, None]
                      >= jnp.arange(t_pad)[None, :])
                     & tail_valid[None, :])
        prefix_l = prefix.transpose(1, 0, 2, 3, 4)  # [L,P,2,H,hd]

        def layer(x, inputs):
            (ln1, ln2, li), pkv = inputs   # pkv [P, 2, H, hd]
            with jax.named_scope("attn"):
                y = _rmsnorm(x, ln1)
                qkv = product(y[0], "wqkv", li)           # [T, 3 d]
                q, k, v = (qkv[:, i * d:(i + 1) * d].reshape(
                    1, t_pad, h, hd) for i in range(3))
                q = apply_rotary(q, cos, sin, tpos)
                k = apply_rotary(k, cos, sin, tpos)
                pk = pkv[None, :, 0]           # [1, P, H, hd]
                pv = pkv[None, :, 1]
                scale = hd ** -0.5
                sc_p = jnp.einsum(
                    "bqhd,bkhd->bhqk", q, pk,
                    preferred_element_type=jnp.float32) * scale
                sc_t = jnp.einsum(
                    "bqhd,bkhd->bhqk", q, k,
                    preferred_element_type=jnp.float32) * scale
                sc_p = jnp.where(pref_valid[None, None, None, :],
                                 sc_p, -1e30)
                sc_t = jnp.where(causal_tt[None, None], sc_t, -1e30)
                probs = jax.nn.softmax(
                    jnp.concatenate([sc_p, sc_t], axis=-1),
                    axis=-1).astype(act)
                o = (jnp.einsum("bhqk,bkhd->bqhd",
                                probs[..., :p_pad], pv)
                     + jnp.einsum("bhqk,bkhd->bqhd",
                                  probs[..., p_pad:], v))
                x = x + product(o.reshape(t_pad, d), "wo", li)[None]
            with jax.named_scope("mlp"):
                y = _rmsnorm(x, ln2)
                gu = product(y[0], "w13", li)             # [T, 2 f]
                x = x + product(jax.nn.silu(gu[:, :f]) * gu[:, f:],
                                "w2", li)[None]
            kv = jnp.stack([k[0], v[0]], axis=1)   # [T, 2, H, hd]
            return x, kv

        x, kvs = jax.lax.scan(
            layer, x, (self._layer_xs(params["layers"]), prefix_l))
        with jax.named_scope("lm_head"):
            x = _rmsnorm(x, params["ln_f"])
            last = x[0, t_len - 1]
            logits = jnp.einsum("d,vd->v", last,
                                params["embed"].astype(act))
        # kvs [L, T, 2, H, hd] -> [T, L, 2, H, hd]
        return logits, kvs.transpose(1, 0, 2, 3, 4)

    def _build_prefill_paged(self, t_pad: int, nbp_pad: int,
                             block_size: int):
        """Paged prefill-from-offset: the prefix is gathered from the
        device pool INSIDE the jit via the block table — no host
        materialization of the adopted prefix. Rows past `p_len` hold
        whatever the gathered blocks contain (stale reused-block data
        included); `pref_valid` masks them out of attention."""
        import jax
        import jax.numpy as jnp

        self.jit_compiles += 1
        p_pad = nbp_pad * block_size
        kv_shape = self.kv_token_shape

        def prefill_paged(params, tail_tokens, p_len, t_len, pool, table):
            # table [nbp_pad] int32, zero-padded (block 0 gathers are
            # masked by pref_valid). pool [N, bs, L, 2, H, hd].
            with jax.named_scope("kv_gather"):
                prefix = jnp.take(pool, table, axis=0).reshape(
                    (p_pad,) + kv_shape).astype(jnp.float32)
            return self._prefill_cached_math(
                params, tail_tokens, p_len, t_len, prefix, t_pad, p_pad)

        return jax.jit(prefill_paged)

    def _decode_math(self, params, tokens, positions, pool, tables,
                     b_pad: int):
        """Traced body of one incremental step. Each layer's attention
        reads the row's cached positions `[0, position)` out of `pool`
        through `tables`, for that layer alone
        (`ops.paged_attention.paged_decode_attention`: pages in place
        on the chip, a per-layer XLA gather elsewhere), and takes the
        new token's K/V as the step computed them: they reach the pool
        after the scan, in the caller's one scatter. Pool rows at or
        past a row's `position` may hold ANYTHING (stale reused-block
        data, block 0 behind a padded table entry): attention masks
        them."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.models.transformer import _rmsnorm
        from ray_tpu.ops.paged_attention import paged_decode_attention
        from ray_tpu.ops.rotary import rotary_freqs

        cfg = self._cfg
        h, hd = cfg.n_heads, cfg.head_dim
        d, f = h * hd, cfg.d_ff
        rot1 = self._rot1
        product = self._products(params["layers"], b_pad, "decode")

        # tokens [B], positions [B], tables [B, nb_pad],
        # pool [N, bs, L, 2, H, hd].
        act = jnp.float32
        with jax.named_scope("embed"):
            x = params["embed"][tokens].astype(act)       # [B, D]
        cos, sin = rotary_freqs(hd, cfg.max_seq_len, cfg.rope_theta)

        def layer(x, inputs):
            # li: this layer's index, in the pool and in the stacks.
            ln1, ln2, li = inputs
            with jax.named_scope("attn"):
                y = _rmsnorm(x, ln1)
                qkv = product(y, "wqkv", li)              # [B, 3 d]
                q, k, v = (qkv[:, i * d:(i + 1) * d].reshape(b_pad, h, hd)
                           for i in range(3))
                q = rot1(q, cos, sin, positions)
                k = rot1(k, cos, sin, positions)
                with jax.named_scope("kv_gather"):
                    o = paged_decode_attention(q, k, v, pool, tables,
                                               positions, li)
                x = x + product(o.reshape(b_pad, d), "wo", li)
            with jax.named_scope("mlp"):
                y = _rmsnorm(x, ln2)
                gu = product(y, "w13", li)                # [B, 2 f]
                x = x + product(jax.nn.silu(gu[:, :f]) * gu[:, f:],
                                "w2", li)
            return x, jnp.stack([k, v], axis=1)   # [B, 2, H, hd]

        x, new_kv = jax.lax.scan(layer, x,
                                 self._layer_xs(params["layers"]))
        with jax.named_scope("lm_head"):
            x = _rmsnorm(x, params["ln_f"])
            logits = jnp.einsum("bd,vd->bv", x,
                                params["embed"].astype(act))
        # new_kv [L, B, 2, H, hd] -> [B, L, 2, H, hd]
        return logits, new_kv.transpose(1, 0, 2, 3, 4)

    def _build_decode_paged(self, b_pad: int, nb_pad: int,
                            block_size: int):
        """Fused paged decode step: attend, write back AND sample in
        one compiled call. No copy of the batch's cache is built: each
        layer's attention reads the rows' pages out of the device pool
        where they lie, through the padded block tables (`_decode_math`),
        and each new token's K/V is scattered into its (block, off) slot
        before returning. The pool is DONATED: XLA aliases input to
        output, so steady-state decode is one dispatch with no pool copy
        and no KV payload crossing the host boundary in either
        direction.

        What does cross: in, ONE int32 array `packed` `[b_pad, 5 +
        nb_pad]`, a row a sequence: token, position, write block, write
        offset, then its block table, then the row's place in `before`
        or -1. Out, the `[width]` int32 greedy ids (`width` the model's
        largest batch bucket, whatever this bucket's rows: what the next
        step's program takes as `before`, so a bucket has ONE program
        whether its tokens come from the host or from the step before's
        ids on the device: `step_tokens`). The `[b_pad, V]` float32
        logits are an output too, and stay on the device unless fetched
        (`DecodeStep`)."""
        import jax
        import jax.numpy as jnp

        self.jit_compiles += 1
        width = self._ids_width(b_pad)

        def decode_paged(pool, params, packed, before):
            # tables [b_pad, nb_pad], zero-padded (rows past the batch
            # and blocks past a row's coverage name block 0; attention
            # masks by position, so nothing of it is read). wblocks
            # padding rows point past the pool, so mode="drop" skips
            # them — dummy batch rows never touch real blocks.
            tokens, positions = step_tokens(packed, before), packed[:, 1]
            wblocks, woffs = packed[:, 2], packed[:, 3]
            tables = packed[:, 4:-1]
            logits, new_kv = self._decode_math(
                params, tokens, positions, pool, tables, b_pad)
            with jax.named_scope("kv_write"):
                new_pool = pool.at[wblocks, woffs].set(
                    new_kv.astype(pool.dtype), mode="drop")
            with jax.named_scope("sample"):
                ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return jnp.pad(ids, (0, width - b_pad)), logits, new_pool

        return jax.jit(decode_paged, donate_argnums=0)

    def _fetch_ids(self, on_device) -> np.ndarray:
        """A decode step is read (`DecodeStep.ids`): the wait for the
        device and the ids' trip to the host."""
        with flight.span("model", "decode.logits_wait", None, self.phase,
                         "decode_wait_s"):
            ids = np.asarray(on_device)
            self.decode_d2h_bytes += ids.nbytes
        return ids

    # -- engine interface ----------------------------------------------
    def prefill(self, tokens: Sequence[int]):
        """Run the prompt. Returns the host logits that predict the
        next token and the prompt's KV as a `PromptKV`: still on the
        device, in the jit's padded bucket."""
        with flight.span("model", "prefill", len(tokens)):
            return self._prefill(tokens)

    def _prefill(self, tokens: Sequence[int]):
        jnp, phase = self._jnp, self.phase
        self.prefill_calls += 1
        n = len(tokens)
        self.prefill_tokens += n
        with flight.span("model", "prefill.prep", None, phase,
                         "prefill_prep_s"):
            s_pad = _next_pow2(max(n, 8))
            fn = self._prefill_jit.get(s_pad)
            if fn is None:
                fn = self._prefill_jit[s_pad] = self._build_prefill(s_pad)
            padded = np.zeros((s_pad,), np.int32)
            padded[:n] = np.asarray(tokens, np.int32)
            args = (jnp.asarray(padded), jnp.int32(n))
        with flight.span("model", "prefill.dispatch", None, phase,
                         "prefill_dispatch_s"):
            logits, kv = fn(self._params, *args)
        self._count_products(s_pad)
        with flight.span("model", "prefill.logits_wait", None, phase,
                         "prefill_wait_s"):
            logits = np.asarray(logits)
        return logits, PromptKV(kv, n)

    def decode_paged(self, pool, block_tables: List[Sequence[int]],
                     last_tokens: Sequence[int],
                     positions: Sequence[int],
                     write_blocks: Sequence[int],
                     write_offs: Sequence[int], block_size: int, *,
                     meanwhile=None, ahead=None):
        """One fused incremental step reading KV straight out of the
        device pool and writing the new tokens' KV back in-place. The
        step crosses the host boundary once each way with a few
        integers: host work is one pass that writes tokens, positions,
        write slots and block tables into one int32 buffer, which the
        jitted call uploads; what comes back (and what this call waits
        for) is the `[b]` int32 greedy ids the same program sampled.
        Neither the KV payload nor the logits touch the host.

        Returns ``(step, new_pool)``: `step` is a `DecodeStep` (`.ids`
        for the sampler; `np.asarray(step)` fetches the `[b, V]`
        float32 logits for whoever needs them), `new_pool` the
        post-write pool (the input pool was donated — the caller MUST
        re-bind, e.g. via `KVCacheManager.paged_step`). `write_blocks`
        may be shorter than the batch; missing rows (and batch padding
        rows) scatter past the pool and are dropped, so an empty write
        list is a read-only step. `meanwhile` runs once the step is
        dispatched, before the wait for ids: beside a busy device.

        `ahead` (the protocol's: the module's text), ``(before,
        sources)``: the step comes back unread, row i's token is taken
        on the device from `before`'s ids at ``sources[i]`` where that
        is not -1, and the call waits for `before`'s ids where it would
        have waited for its own. The bucket's one program serves both."""
        with flight.span("model", "decode", len(last_tokens)):
            return self._decode_paged(pool, block_tables, last_tokens,
                                      positions, write_blocks, write_offs,
                                      block_size, meanwhile, ahead)

    def _decode_paged(self, pool, block_tables, last_tokens, positions,
                      write_blocks, write_offs, block_size: int,
                      meanwhile, ahead):
        phase = self.phase
        b = len(last_tokens)
        self.decode_calls += 1
        with flight.span("model", "decode.prep", None, phase,
                         "decode_prep_s"):
            b_pad = _next_pow2(max(b, 1))
            pages = [int(p) // block_size + 1 for p in positions]
            # A table may come wider than its row's live pages (a fully
            # cached prompt's one read-only step comes as wide as its
            # next step: `scheduler._prefill_inner`); the bucket holds
            # the widest.
            nb = max(max(pages), max(len(t) for t in block_tables))
            nb_pad = _next_pow2(nb)
            if self._attn_inplace:
                self.decode_attn_inplace_steps += 1
                self.decode_kv_pages_read += sum(pages)
                self.decode_kv_page_groups_read += self._page_groups(
                    pool, nb_pad, positions)
            key = (b_pad, nb_pad, block_size)
            fn = self._decode_paged_jit.get(key)
            if fn is None:
                fn = self._decode_paged_jit[key] = \
                    self._build_decode_paged(*key)
            # One host buffer, a row a sequence: token, position,
            # write block (default past the pool: dropped), write
            # offset, block table, place in the step before's ids.
            packed = np.zeros((b_pad, 5 + nb_pad), np.int32)
            packed[:, 2] = int(pool.shape[0])
            for i in range(b):
                table = block_tables[i][:nb_pad]
                packed[i, 0] = last_tokens[i]
                packed[i, 1] = positions[i]
                packed[i, 4:4 + len(table)] = table
            k = min(len(write_blocks), b)
            packed[:k, 2] = write_blocks[:k]
            packed[:k, 3] = write_offs[:k]
            place_sources(packed, ahead)
            # The call's arguments that are still host arrays: what the
            # jitted call below uploads for this step.
            args = (pool, self._params, packed, self._before(ahead, b_pad))
            self.decode_h2d_arrays += sum(
                isinstance(leaf, np.ndarray)
                for leaf in self._tree_leaves(args))
        with flight.span("model", "decode.dispatch", None, phase,
                         "decode_dispatch_s"):
            # The jitted call uploads the host buffer itself: one
            # transfer, and no separate call that lets other threads in
            # before the step is on the device.
            ids, logits, new_pool = fn(*args)
        self._count_products(b_pad)
        step = DecodeStep(ids, b, logits, self)
        if meanwhile is not None:
            meanwhile()
        read_after_dispatch(step, ahead)
        return step, new_pool

    def prefill_paged(self, tokens: Sequence[int], pool,
                      block_table: Sequence[int], prefix_len: int,
                      block_size: int):
        """Prefill-from-offset with the adopted prefix gathered from
        the device pool inside the jit. Returns host logits plus the
        tail KV as a `PromptKV` for `write_range`, as `prefill` does."""
        with flight.span("model", "prefill", len(tokens)):
            return self._prefill_paged(tokens, pool, block_table,
                                       int(prefix_len), block_size)

    def _prefill_paged(self, tokens, pool, block_table, p: int,
                       block_size: int):
        jnp, phase = self._jnp, self.phase
        self.prefill_calls += 1
        t = len(tokens) - p
        self.prefill_tokens += t
        with flight.span("model", "prefill.prep", None, phase,
                         "prefill_prep_s"):
            t_pad = _next_pow2(max(t, 8))
            nbp = (p + block_size - 1) // block_size
            nbp_pad = _next_pow2(max(nbp, 1))
            key = (t_pad, nbp_pad, block_size)
            fn = self._prefill_paged_jit.get(key)
            if fn is None:
                fn = self._prefill_paged_jit[key] = \
                    self._build_prefill_paged(*key)
            tail = np.zeros((t_pad,), np.int32)
            tail[:t] = np.asarray(tokens[p:], np.int32)
            table = np.zeros((nbp_pad,), np.int32)
            table[:nbp] = np.asarray(block_table[:nbp], np.int32)
            args = (jnp.asarray(tail), jnp.int32(p), jnp.int32(t))
            table = jnp.asarray(table)
        with flight.span("model", "prefill.dispatch", None, phase,
                         "prefill_dispatch_s"):
            logits, kv = fn(self._params, *args, pool, table)
        self._count_products(t_pad)
        with flight.span("model", "prefill.logits_wait", None, phase,
                         "prefill_wait_s"):
            logits = np.asarray(logits)
        return logits, PromptKV(kv, t)
