"""Block-granular KV-cache manager (the vLLM PagedAttention idea).

Reference: Kwon et al., "Efficient Memory Management for Large Language
Model Serving with PagedAttention" (SOSP'23) — the KV cache is carved
into fixed-size blocks in ONE preallocated buffer; each sequence owns an
ordered block list instead of a contiguous max-length slab, so cache
memory is committed token-by-token and freed the moment a sequence
retires. Fragmentation is bounded to less than one block per sequence,
and admission/preemption decisions reduce to free-block arithmetic.

Blocks are **refcounted and shareable** (prefix sharing, PR 13): a full
block holding a common prompt prefix can appear in many sequences'
tables at once — `adopt` extends a table by reference (refcount bump,
no copy, no recompute), `free` decrements and only reclaims a block at
refcount zero, and a write into a block whose refcount is above one
first copies it into a private block (**copy-on-write** — the writer
gets its own block, every other holder keeps reading the original).
`utilization` therefore counts *physical* blocks once no matter how
many tables reference them. The prefix index (`prefix_index.py`) holds
one reference on every block it indexes via `retain`/`release`, and the
manager calls an optional *reclaimer* under block pressure so cold
indexed prefixes are evicted instead of admissions being rejected.

The manager owns two things:

- **accounting**: the free-block list, per-block refcounts, per-sequence
  block tables and written lengths — `can_allocate` / `allocate` /
  `adopt` / `free` are what the iteration scheduler calls between
  decode steps;
- **storage**: the preallocated buffer itself, in one of two layouts.
  *Rows*, `[num_blocks, block_size, *kv_shape]`: a position's row in one
  piece. *Planes* (a group declared so: the constructor's `planes`, a
  further group's ``"planes": True``; device pools only), for a row
  ``kv_shape = (L, ..., dv)``: `[num_blocks, L, planes, block_size, dv]`,
  ``planes`` the product of the row's middle axes (a KV row ``(L, S,
  Hkv, dv)`` has ``S * Hkv``), a layer's page in one piece and a block's
  positions side by side in every plane: the layout of a pool that
  rides, below, for a group's own pool. A model declares it for a group
  whose key/value heads do not fill a float32 tile
  (`ops.paged_attention.held_by_planes`: the engine reads the model's
  `kv_planes`, group -> bool), and its programs tell the two by the
  pool's rank. Nothing of the accounting differs, and a payload is a
  position a row in both (`write_range`, `gather`); what differs is how
  a row reaches the pool: by slots `(block, layer, plane, offset)`, the
  indexed axes side by side, and a prefill's block-aligned range a whole
  block at a time (`_write_planes`). Prefix shipping (`read_block`,
  `install_block`) meets pools of rows alone and raises on the other:
  the models that declare planes keep a window group or a pool that
  rides, and the engine builds no prefix index over either. The
  engine's model reads the buffer through block tables
  inside its own step, and where they lie: the transformer's decode
  attention fetches a row's blocks of one layer straight out of this
  buffer (`ops/paged_attention.py`; on the chip a Pallas kernel whose
  block index is `table[row, page]`, so the layouts here are that
  kernel's contract), and no step gathers a dense
  copy of a batch's cache. `paged_step` (a decode step: slots resolved,
  the donated pool re-bound), `mutate_pool` (a read-only step) and
  `with_pool` (the paged prefill) hand the live buffer to the dispatch
  under the lock, and `write_range` stores a prefill's rows. `write` /
  `gather` are the manager's own by-position write and read (tests of
  tables, refcounts and COW use them; the engine calls neither, so the
  `host_gathers` counter stays 0 over a served run and the benchmark
  asserts it). The buffer lives in the array namespace the engine read
  off its model (`array_ns`): numpy (`TinyLM`: views, exact, no XLA
  compile under `JAX_PLATFORMS=cpu`) or `jax.numpy`, a
  **device-resident pool** whose every mutation (`write`,
  `write_range`, COW privatize, `install_block`) goes through a
  donated-argument jitted update — the pool is threaded through the
  jit and donated back, so XLA aliases input to output and steady-state
  decode neither copies the pool nor allocates a second one. A
  prefill's KV that is still on the device (`model.PromptKV`) is that
  update's payload as it stands: the prompt KV never visits the host
  on its way into a device pool.

**State slots** (beside the blocks, for a model that declares
`state_shapes`): a recurrent or linear-attention layer keeps a state of
fixed size a sequence, whatever its length, where an attention layer
keeps a row a token. The manager then holds a second pool, one slot a
sequence: `allocate` takes a slot with a sequence's first block, `free`
returns it, `can_allocate` and `stats()` count both, `write_range`
stores the state a prefill ended on (its payload's `state`) and
`paged_step` resolves each row's slot with its write block and donates
both pools to the model's one step. A slot cannot be shared or adopted:
blocks of KV restore a prefix, a state does not (the engine builds no
prefix index over such a model). A model that declares no state gets no
second pool and the calls it has always had.

**Layer groups** (for a model whose layers do not all keep the same
positions): a *layer group* is a set of layers that share one pool, one
free list and one block table a sequence. A model declares them
(`kv_groups`: name -> the group's row shape and, for layers of
sliding-window attention, the window's length); the first, `global`,
is this manager itself and keeps every position, and each further group
is a manager of its own inside it, with the same block size. A group with
a **window** of `w` positions keeps, of a sequence whose next query
stands at position `p`, only the blocks that hold a position `j` with
``p - j < w``: a block every position of which has left the window goes
back to that group's free list in the step that leaves it
(`release_expired`, which the scheduler calls before it grows the
tables; `allocate` trims too, so the bound holds whoever calls), and a
sequence never holds more than ``ceil(w / block_size) + 1`` of the
group's blocks whatever its length. Its table is compact: entry 0 is
logical block `base`, which `step_tables` hands the model beside the
table and `ops/paged_attention.py` takes as a row's `starts`.
`can_allocate` / `allocate` / `free` count every group and stay atomic
over them; `write_range` stores every row of a prefill in the global
group and only the rows the window still reaches in a window group (the
payload's `groups`); `paged_step` resolves a write slot a group and
hands the model dicts, ``{group: pool}``, ``{group: blocks}``, ``{group:
offs}``, all pools donated and re-bound. `stats()` counts a group
(`groups`): `blocks`, `blocks_in_use`, `block_steps_in_use` /
`block_steps` (summed over paged steps: their quotient is the pool's
occupancy while it decodes) and `window_blocks_released`. No prefix is
adopted beside a window group (an adopted prefix would need the window
layers' rows at its end), and groups are not combined with state slots.
A model that declares no groups is one global group, with the calls and
answers it has always had.

**A pool that rides** (a group declared with ``"rides": True``): a
second kind of row a position keeps beside its keys and values, in a
pool of its own that has no free list and no table: block ``b`` of it
belongs to block ``b`` of the global group, so it is allocated, freed,
copied on a write and preempted with that block, and a step reads it
through the global group's table. Its layout is ``[num_blocks, L,
block_size, *rest]`` for a row ``(L, *rest)`` a position: a layer's page
in one piece, because a model reads such a pool whole a layer (every
live position, every step: an indexer's keys, `ops/sparse_attention.py`)
where it reads the KV in part. `write_range` stores the payload's
``groups[name]`` rows at the same slots as the KV's, `paged_step` and
`with_pools` hand it over by name beside the groups' pools (donated and
re-bound like them, its write slots the global group's), `step_tables`
names no table for it, and `stats()` counts it under `groups` with the
global group's blocks in use and its own bytes.

Determinism contract (the scheduler's loop must never crash on OOM):
`allocate` is atomic — it either extends the table (and privatizes the
requested write range) or changes nothing and returns False; the
scheduler converts False into preempt-and-requeue of the lowest-priority
sequence. COW faults never surprise the decode loop: the scheduler
passes `writable_from` so the copy is planned into the same atomic
free-block arithmetic as table growth.
"""

from __future__ import annotations

import math
import threading
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np


class CacheOverflowError(RuntimeError):
    """A single sequence needs more tokens than the whole cache holds —
    the one OOM shape that cannot be fixed by preempting someone else."""


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


def _device_rows(values):
    """The device array behind a `write_range` payload, or None for a
    host payload: a jax array itself, or the `padded` rows of a model's
    prefill result (`model.PromptKV`: at least `len(values)` rows, the
    rest the shape bucket's padding)."""
    rows = getattr(values, "padded", values)
    return rows if hasattr(rows, "block_until_ready") else None


class _DevicePoolOps:
    """Donated-arg jitted mutations over the device pool, compiled once
    per pool shape (token writes go through `scatter`, whose row count
    pads to pow2 buckets — a handful of compiles covers every range
    length and batch size, no per-offset churn).

    Every op takes the pool as argument 0 with `donate_argnums=0`: XLA
    aliases the input buffer to the output, the update happens in place
    on the accelerator, and the caller re-binds `self._buffer` to the
    returned handle. The previous handle is invalidated by donation —
    which is exactly why all pool access goes through the manager's
    lock (`with_pool` for in-jit readers)."""

    def __init__(self, block_size: int, kv_shape: Tuple[int, ...]):
        import jax

        from ray_tpu.ops.paged_attention import write_rows

        def copy_block(pool, dst, src):
            return pool.at[dst].set(pool[src])

        def set_block(pool, block, vals):
            return pool.at[block].set(vals)

        def scatter(pool, blocks, offs, vals):
            # Batched token write: one (block, off) slot per row —
            # a whole prefill range in a single dispatch. Padding rows
            # carry block == num_blocks (out of range) and are dropped,
            # so one compile per pow2 row bucket suffices.
            return pool.at[blocks, offs].set(vals, mode="drop")

        def scatter_rider(pool, blocks, offs, vals):
            # The same write into a pool that rides, `[N, L, bs, ...]`:
            # a slot a (row, layer). (`pool.at[blocks, :, offs]`, two
            # indexed axes with one between them, XLA lowers through
            # three transposed copies of the whole pool.)
            return pool.at[rider_slots(pool, blocks, offs)].set(
                vals.astype(pool.dtype), mode="drop")

        def scatter_planes(pool, blocks, offs, vals):
            # The same write into a pool held by planes, `[N, L, P, bs,
            # dv]`: a slot a (row, layer, plane).
            return write_rows(pool, blocks, offs, vals.astype(pool.dtype))

        def set_blocks_planes(pool, blocks, vals, tail_blocks, tail_offs,
                              tail_from):
            # A block-aligned range into a pool held by planes: payload
            # block ``j`` (rows ``[j * bs, (j + 1) * bs)`` of `vals`)
            # whole at ``blocks[j]`` (past the pool: dropped), then the
            # `bs` rows from `tail_from` on by their slots (the ragged
            # tail; a row past it names a block past the pool).
            n, layers, planes, bs, dv = pool.shape
            whole = vals.astype(pool.dtype).reshape(
                -1, bs, layers, planes, dv).transpose(0, 2, 3, 1, 4)
            pool = pool.at[blocks].set(whole, mode="drop")
            tail = jax.lax.dynamic_slice_in_dim(vals, tail_from, bs)
            return scatter_planes(pool, tail_blocks, tail_offs, tail)

        self.copy_block = jax.jit(copy_block, donate_argnums=0)
        self.set_block = jax.jit(set_block, donate_argnums=0)
        self.scatter = jax.jit(scatter, donate_argnums=0)
        self.scatter_rider = jax.jit(scatter_rider, donate_argnums=0)
        self.scatter_planes = jax.jit(scatter_planes, donate_argnums=0)
        self.set_blocks_planes = jax.jit(set_blocks_planes,
                                         donate_argnums=0)


def rider_slots(pool, blocks, offs):
    """The index of rows' slots in a pool that rides, ``[N, L, bs,
    ...]``: ``pool.at[rider_slots(...)]`` is ``[rows, L, ...]``, the
    three indexed axes side by side, which the compiler writes in
    place."""
    layers = np.arange(pool.shape[1])
    return blocks[:, None], layers[None, :], offs[:, None]


_POOL_OPS: Dict[Tuple[int, Tuple[int, ...]], _DevicePoolOps] = {}
_POOL_OPS_LOCK = threading.Lock()


def _pool_ops(block_size: int,
              kv_shape: Tuple[int, ...]) -> _DevicePoolOps:
    """Process-wide ops cache: the jitted mutations close over nothing
    but shapes, so every manager with the same block geometry shares
    one set of compiled executables — a fresh engine must not re-pay
    XLA compiles for the same pool shape (jit caches live on the
    function object, and per-manager ops would make every cache cold)."""
    key = (block_size, kv_shape)
    with _POOL_OPS_LOCK:
        ops = _POOL_OPS.get(key)
        if ops is None:
            ops = _POOL_OPS[key] = _DevicePoolOps(block_size, kv_shape)
        return ops


_STATE_WRITER = None


def _state_writer():
    """The donated update that stores one sequence's state in its slot
    (a dict of arrays into a dict of pools); one jitted function a
    process, compiled per pool shapes."""
    global _STATE_WRITER
    if _STATE_WRITER is None:
        import jax

        _STATE_WRITER = jax.jit(
            lambda pools, slot, state: {
                name: pool.at[slot].set(state[name].astype(pool.dtype))
                for name, pool in pools.items()},
            donate_argnums=0)
    return _STATE_WRITER


class KVCacheManager:
    """Fixed-size refcounted blocks in one preallocated buffer +
    per-sequence block tables. Thread-safe (the engine loop and
    `stats()` callers race)."""

    GLOBAL = "global"       # the name of the group this manager is
    STATE = "state"         # and of the state pool, where `with_pools`
                            # hands a model with state both by name

    def __init__(self, num_blocks: int, block_size: int,
                 kv_shape: Tuple[int, ...] = (), dtype=np.float32,
                 array_ns=None, state_shapes: Optional[dict] = None,
                 state_slots: int = 0, window: Optional[int] = None,
                 groups: Optional[dict] = None, planes: bool = False):
        """`state_shapes`: what the model keeps a sequence beside its KV
        rows, ``{name: (shape, dtype)}``; with it, `state_slots` slots
        of each, zeroed, in the pool's namespace. `groups`: the further
        layer groups, ``{name: {"num_blocks", "kv_shape", "window"}}``,
        each a manager of its own with this one's block size, dtype and
        namespace; `window`: this group's own (a sub-manager's). A group
        with ``"rides": True`` and a row ``kv_shape`` ``(L, *rest)`` is a
        pool that rides this one's blocks (module docstring). `planes`
        (a further group's ``"planes": True``): this group's own pool is
        held a block, a layer and a plane at a time (module docstring:
        storage)."""
        riders = {name: g for name, g in (groups or {}).items()
                  if g.get("rides")}
        groups = {name: g for name, g in (groups or {}).items()
                  if name not in riders}
        if num_blocks <= 0 or block_size <= 0:
            raise ValueError("num_blocks and block_size must be positive")
        if state_shapes and state_slots <= 0:
            raise ValueError("a model with per-sequence state needs "
                             "state_slots > 0")
        if groups and state_shapes:
            raise ValueError("layer groups beside state slots: no model "
                             "of the tree needs both")
        if window is not None and num_blocks < math.ceil(
                window / block_size) + 1:
            raise ValueError(
                f"a window of {window} needs "
                f"{math.ceil(window / block_size) + 1} blocks a sequence; "
                f"the group has {num_blocks}")
        self.window = window
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.kv_shape = tuple(kv_shape)
        self.planes = bool(planes)
        self._ns = array_ns if array_ns is not None else np
        self._device = self._ns is not np
        if self.planes and not (self._device and len(self.kv_shape) >= 2):
            raise ValueError(
                "a pool held by planes needs a device pool and a row "
                "(layers, ..., values): no host model declares one")
        self._dtype = dtype
        self._ops: Optional[_DevicePoolOps] = None
        if self._device:
            self._ops = _pool_ops(self.block_size, self.kv_shape)
        # THE preallocated cache: every sequence's KV lives here.
        self._buffer = self._ns.zeros(self._pool_shape(), dtype)
        # Data-movement honesty counters: `host_gathers` counts calls
        # that materialize per-sequence KV for host-side consumption
        # (`gather`: the engine makes none, and the benchmark asserts
        # this stays 0 across a run); `pool_updates` counts donated
        # in-place pool mutations on the device path.
        self.host_gathers = 0
        self.pool_updates = 0
        # `write_range` calls whose payload went from the device into a
        # device pool, and those that passed through host memory.
        self.range_writes_device = 0
        self.range_writes_host = 0
        # LIFO free list: recently-freed blocks are cache-warm.
        self._free: List[int] = list(range(self.num_blocks - 1, -1, -1))
        self._refs: Dict[int, int] = {}          # block -> holder count
        self._tables: Dict[str, List[int]] = {}
        self._lens: Dict[str, int] = {}
        # A window group's table is compact: entry 0 is logical block
        # `_base[seq]` (absent: 0, as in every group without a window).
        self._base: Dict[str, int] = {}
        self.window_blocks_released = 0
        # Blocks in use and blocks there are, summed over paged steps.
        self.block_steps_in_use = 0
        self.block_steps = 0
        self._groups: Dict[str, "KVCacheManager"] = {
            name: KVCacheManager(
                g["num_blocks"], block_size, tuple(g["kv_shape"]), dtype,
                array_ns, window=g.get("window"),
                planes=g.get("planes", False))
            for name, g in (groups or {}).items()}
        if riders and not self._device:
            raise ValueError("a pool that rides needs a device pool: no "
                             "host model keeps a second kind of row")
        # Pools that ride this group's blocks, by name.
        self._riders = {
            name: self._ns.zeros(
                (self.num_blocks, g["kv_shape"][0], self.block_size)
                + tuple(g["kv_shape"][1:]), dtype)
            for name, g in riders.items()}
        # Reentrant: `with_pool` callbacks legitimately read tables /
        # lengths through the public accessors while the lock is held.
        self._lock = threading.RLock()
        self.cow_copies = 0
        self.adoptions = 0
        # Under block pressure, `allocate` asks the reclaimer to free
        # up to N blocks (the prefix index evicts cold nodes); the
        # countable half feeds `can_allocate` so admission control sees
        # evictable capacity as available instead of rejecting.
        self._reclaimer: Optional[Callable[[int], int]] = None
        self._evictable: Optional[Callable[[], int]] = None
        # The second pool: `[state_slots, *shape]` a declared state, a
        # slot a sequence (None: the model declared none). The two
        # counters sum, over paged steps, the slots in use and the slots
        # there are: their quotient is the pool's occupancy while it
        # decodes.
        self._state = None
        self._set_state = None
        self._slots: Dict[str, int] = {}
        self._free_slots: List[int] = []
        self.state_slot_steps_in_use = 0
        self.state_slot_steps = 0
        if state_shapes:
            self._state = {
                name: self._ns.zeros((state_slots,) + tuple(shape), dt)
                for name, (shape, dt) in state_shapes.items()}
            self._free_slots = list(range(state_slots - 1, -1, -1))
            self._set_state = _state_writer() if self._device else None

    def _pool_shape(self) -> Tuple[int, ...]:
        """The own pool's shape: a position's row in one piece, or held
        by planes (module docstring: storage)."""
        if not self.planes:
            return (self.num_blocks, self.block_size) + self.kv_shape
        return (self.num_blocks, self.kv_shape[0],
                math.prod(self.kv_shape[1:-1]), self.block_size,
                self.kv_shape[-1])

    def set_reclaimer(self, reclaim: Optional[Callable[[int], int]],
                      evictable: Optional[Callable[[], int]] = None
                      ) -> None:
        """Install the block-pressure callbacks. `reclaim(n)` must free
        up to n blocks (via `release`) and return how many it freed; it
        is called WITHOUT the cache lock held. `evictable()` returns how
        many blocks a full reclaim could free right now."""
        self._reclaimer = reclaim
        self._evictable = evictable

    # -- accounting ----------------------------------------------------
    @property
    def capacity_tokens(self) -> int:
        return self.num_blocks * self.block_size

    def free_blocks(self) -> int:
        with self._lock:
            return len(self._free)

    def utilization(self) -> float:
        """Fraction of PHYSICAL blocks allocated (the
        `cache_utilization` gauge) — a block shared by a thousand
        sequences counts once."""
        with self._lock:
            return 1.0 - len(self._free) / self.num_blocks

    def blocks_for(self, n_tokens: int) -> int:
        return max(0, math.ceil(n_tokens / self.block_size))

    def seq_len(self, seq_id: str) -> int:
        with self._lock:
            return self._lens.get(seq_id, 0)

    def block_table(self, seq_id: str) -> List[int]:
        with self._lock:
            return list(self._tables.get(seq_id, ()))

    def block_ref(self, block: int) -> int:
        with self._lock:
            return self._refs.get(block, 0)

    def _first_live(self, target_tokens: int) -> int:
        """The first logical block that holds a position the query at
        ``target_tokens - 1`` sees (0 without a window)."""
        if self.window is None:
            return 0
        return max(0, target_tokens - self.window) // self.block_size

    def _trim_locked(self, seq_id: str, target_tokens: int) -> int:
        """Give back the blocks of `seq_id` every position of which has
        left the window of the query at ``target_tokens - 1``."""
        table = self._tables.get(seq_id)
        if self.window is None or not table:
            return 0
        base = self._base.get(seq_id, 0)
        drop = min(len(table), self._first_live(target_tokens) - base)
        if drop <= 0:
            return 0
        for b in table[:drop]:
            self._release_locked(b)
        del table[:drop]
        self._base[seq_id] = base + drop      # an emptied table's base
        #                                       is set by `_commit`
        self.window_blocks_released += drop
        return drop

    def release_expired(self, seq_id: str, target_tokens: int) -> int:
        """Before the step that makes `seq_id` `target_tokens` long:
        every window group gives back the blocks that step no longer
        sees. Returns how many went back to a free list."""
        with self._lock:
            return self._trim_locked(seq_id, target_tokens) + sum(
                g.release_expired(seq_id, target_tokens)
                for g in self._groups.values())

    def _shortfall(self, seq_id: str, target_tokens: int,
                   writable_from: Optional[int]) -> Tuple[int, int]:
        """(blocks missing, blocks to grow by) for this group alone, the
        blocks a trim would give back counted as free."""
        if self.window is None:
            grow, cow = self._plan(seq_id, target_tokens, writable_from)
            return grow + cow - len(self._free), grow
        table = self._tables.get(seq_id, ())
        first = self._first_live(target_tokens)
        base = self._base.get(seq_id, first) if table else first
        drop = max(0, min(len(table), first - base))
        grow = max(0, self.blocks_for(target_tokens) - max(base, first)
                   - (len(table) - drop))
        return grow - drop - len(self._free), grow

    def _plan(self, seq_id: str, target_tokens: int,
              writable_from: Optional[int]) -> Tuple[int, int]:
        """(growth deficit, COW copies) to cover `target_tokens` with
        every block overlapping [writable_from, target) private."""
        table = self._tables.get(seq_id, ())
        need = self.blocks_for(target_tokens)
        grow = max(0, need - len(table))
        cow = 0
        if writable_from is not None and writable_from < target_tokens:
            first = writable_from // self.block_size
            for b in table[first:min(len(table), need)]:
                if self._refs.get(b, 0) > 1:
                    cow += 1
        return grow, cow

    def can_allocate(self, seq_id: str, target_tokens: int,
                     writable_from: Optional[int] = None) -> bool:
        """Would `allocate(...)` succeed right now — counting blocks a
        reclaim could evict as available?"""
        with self._lock:
            if self._lacks_slot(seq_id) or self._groups_short(
                    seq_id, target_tokens):
                return False
            shortfall, _ = self._shortfall(seq_id, target_tokens,
                                           writable_from)
        if shortfall <= 0:
            return True
        return (self._evictable is not None
                and self._evictable() >= shortfall)

    def _members(self) -> Dict[str, "KVCacheManager"]:
        """Every layer group by name, this one (`global`) first."""
        return {self.GLOBAL: self, **self._groups}

    def _groups_short(self, seq_id: str, target_tokens: int) -> bool:
        """A further group lacks blocks for `target_tokens` (no reclaim
        helps: nothing of such a group is indexed)."""
        return any(g._shortfall(seq_id, target_tokens, None)[0] > 0
                   for g in self._groups.values())

    def _lacks_slot(self, seq_id: str) -> bool:
        """A sequence that has no state slot yet, and none is free (no
        reclaim helps: slots are never shared or indexed)."""
        return (self._state is not None and seq_id not in self._slots
                and not self._free_slots)

    def allocate(self, seq_id: str, target_tokens: int,
                 writable_from: Optional[int] = None) -> bool:
        """Grow `seq_id`'s table to cover `target_tokens` total tokens;
        when `writable_from` is given, additionally privatize (COW)
        every shared block overlapping positions
        [writable_from, target_tokens) so subsequent writes never fault.
        Atomic: returns False (and changes nothing) on a shortfall,
        after asking the reclaimer to evict cold prefixes. Raises
        CacheOverflowError when the request exceeds the whole cache —
        no amount of preemption can satisfy it."""
        if target_tokens > self.capacity_tokens:
            raise CacheOverflowError(
                f"sequence needs {target_tokens} tokens; the cache holds "
                f"{self.capacity_tokens} "
                f"({self.num_blocks}x{self.block_size})")
        while True:
            with self._lock:
                if self._lacks_slot(seq_id):
                    return False
                plans = {}
                if self._groups:    # not on the one-group step's path
                    plans = {g: g._shortfall(seq_id, target_tokens, None)
                             for g in self._groups.values()}
                    if any(short > 0 for short, _ in plans.values()):
                        return False
                shortfall, grow = self._shortfall(seq_id, target_tokens,
                                                  writable_from)
                if shortfall <= 0:
                    self._commit(seq_id, target_tokens, grow,
                                 writable_from)
                    for g, (_, g_grow) in plans.items():
                        g._commit(seq_id, target_tokens, g_grow, None)
                    return True
            # Block pressure: evict cold indexed prefixes (the
            # reclaimer calls `release`, which takes the lock — so the
            # lock must NOT be held here) and retry; no progress means
            # genuinely full.
            if self._reclaimer is None:
                return False
            if self._reclaimer(shortfall) <= 0:
                return False

    def _commit(self, seq_id: str, target_tokens: int, grow: int,
                writable_from: Optional[int]) -> None:
        if self.window is not None:
            self._trim_locked(seq_id, target_tokens)
        table = self._tables.setdefault(seq_id, [])
        if self.window is not None and not table:
            self._base[seq_id] = self._first_live(target_tokens)
        if self._state is not None and seq_id not in self._slots:
            self._slots[seq_id] = self._free_slots.pop()
        for _ in range(grow):
            b = self._free.pop()
            self._refs[b] = 1
            table.append(b)
        if writable_from is not None and writable_from < target_tokens:
            first = writable_from // self.block_size
            last = min(len(table), self.blocks_for(target_tokens))
            for i in range(first, last):
                if self._refs.get(table[i], 0) > 1:
                    self._privatize_locked(seq_id, i)

    def adopt(self, seq_id: str, blocks: Sequence[int],
              n_tokens: int) -> None:
        """Extend `seq_id`'s (empty) table by REFERENCE to existing
        blocks whose contents already cover positions [0, n_tokens) —
        the prefix-hit admission: refcount bumps, no copy, no prefill.
        The adopted coverage is recorded as the sequence's written
        length, so `gather` serves it immediately."""
        with self._lock:
            if self._state is not None:
                raise ValueError(
                    "blocks of KV do not restore a sequence's state: "
                    "nothing is adopted beside a state pool")
            if self._groups or self.window is not None:
                raise ValueError(
                    "an adopted prefix would need the window layers' "
                    "rows at its end: nothing is adopted beside a "
                    "window group")
            if self._tables.get(seq_id):
                raise ValueError(
                    f"adopt requires an empty table for {seq_id!r}")
            if n_tokens > len(blocks) * self.block_size:
                raise ValueError("adopted blocks do not cover n_tokens")
            for b in blocks:
                if self._refs.get(b, 0) < 1:
                    raise ValueError(f"block {b} is not allocated")
            for b in blocks:
                self._refs[b] += 1
            self._tables[seq_id] = list(blocks)
            self._lens[seq_id] = n_tokens
            self.adoptions += 1

    def retain(self, block: int) -> None:
        """Add one reference to an allocated block (the prefix index's
        hold on a block it has indexed)."""
        with self._lock:
            if self._refs.get(block, 0) < 1:
                raise ValueError(f"block {block} is not allocated")
            self._refs[block] += 1

    def release(self, block: int) -> bool:
        """Drop one reference; returns True when the block went back to
        the free list (last holder gone)."""
        with self._lock:
            return self._release_locked(block)

    def _release_locked(self, block: int) -> bool:
        n = self._refs.get(block, 0)
        if n < 1:
            raise ValueError(f"block {block} is not allocated")
        if n == 1:
            del self._refs[block]
            self._free.append(block)
            return True
        self._refs[block] = n - 1
        return False

    def free(self, seq_id: str) -> int:
        """Release every block of a retired/preempted sequence; returns
        how many blocks actually came back to the free list. Shared
        blocks (held by the prefix index or other sequences) survive —
        preemption only reclaims a sequence's private tail."""
        with self._lock:
            table = self._tables.pop(seq_id, [])
            self._lens.pop(seq_id, None)
            self._base.pop(seq_id, None)
            slot = self._slots.pop(seq_id, None)
            if slot is not None:
                self._free_slots.append(slot)
            freed = 0
            for b in reversed(table):
                if self._release_locked(b):
                    freed += 1
            return freed + sum(g.free(seq_id)
                               for g in self._groups.values())

    # -- cross-replica shipping (PR 19) --------------------------------
    def read_block(self, block: int) -> np.ndarray:
        """Copy of one allocated block's contents
        (`[block_size, *kv_shape]`) — what prefix shipping exports. A
        copy, not a view: the frame outlives the lock, and the source
        block may COW/evict underneath a view. A pool of rows only
        (`_no_planes_shipped`)."""
        with self._lock:
            self._no_planes_shipped()
            if self._refs.get(block, 0) < 1:
                raise ValueError(f"block {block} is not allocated")
            return np.array(np.asarray(self._buffer[block]))

    def _no_planes_shipped(self) -> None:
        """Prefix shipping meets pools of rows alone: the models whose
        pools are held by planes adopt no prefix (they keep a window
        group or a pool that rides), so no engine builds an index over
        one."""
        if self.planes:
            raise ValueError(
                "a block of a pool held by planes is not shipped: "
                "read_block and install_block take rows")

    def install_block(self, values) -> Optional[int]:
        """Allocate one free block, fill it with `values`
        (`[block_size, *kv_shape]`) and return its index with ONE
        reference held by the caller — the receiving half of prefix
        shipping (the caller hands the reference to the prefix index
        via `insert` + `release`). Asks the reclaimer under pressure
        like `allocate`; returns None when genuinely full."""
        self._no_planes_shipped()
        values = np.asarray(values)
        expect = (self.block_size,) + self.kv_shape
        if tuple(values.shape) != expect:
            raise ValueError(
                f"install_block expects shape {expect}, got "
                f"{tuple(values.shape)}")
        while True:
            with self._lock:
                if self._free:
                    b = self._free.pop()
                    self._refs[b] = 1
                    if self._ns is np:
                        self._buffer[b] = values
                    else:
                        self._buffer = self._ops.set_block(
                            self._buffer, b,
                            self._ns.asarray(values, self._dtype))
                        self.pool_updates += 1
                    return b
            if self._reclaimer is None or self._reclaimer(1) <= 0:
                return None

    # -- storage -------------------------------------------------------
    def _slot(self, seq_id: str, pos: int) -> Tuple[int, int]:
        table = self._tables.get(seq_id)
        idx = pos // self.block_size - self._base.get(seq_id, 0)
        if table is None or not 0 <= idx < len(table):
            raise IndexError(
                f"position {pos} of sequence {seq_id!r} has no allocated "
                f"block (table covers "
                f"{len(table or ()) * self.block_size} tokens from block "
                f"{self._base.get(seq_id, 0)})")
        return idx, pos % self.block_size

    def _privatize_locked(self, seq_id: str, block_idx: int) -> int:
        """The COW fault: copy a shared block into a fresh private one
        and repoint this sequence's table at the copy. Caller holds the
        lock and has ensured a free block exists."""
        table = self._tables[seq_id]
        old = table[block_idx]
        if not self._free:
            raise RuntimeError(
                "COW fault with no free block — the scheduler must "
                "allocate(writable_from=...) before writing into a "
                "shared block")
        new = self._free.pop()
        if self._ns is np:
            self._buffer[new] = self._buffer[old]
        else:
            self._buffer = self._ops.copy_block(self._buffer, new, old)
            for name, rider in self._riders.items():
                self._riders[name] = self._ops.copy_block(rider, new, old)
            self.pool_updates += 1
        self._refs[new] = 1
        self._refs[old] -= 1          # shared => was > 1, stays >= 1
        table[block_idx] = new
        self.cow_copies += 1
        return new

    def _writable_block(self, seq_id: str, pos: int) -> Tuple[int, int]:
        """Slot lookup that COWs on the way in (backstop — the engine
        pre-privatizes via allocate(writable_from=...))."""
        idx, off = self._slot(seq_id, pos)
        table = self._tables[seq_id]
        if self._refs.get(table[idx], 0) > 1:
            self._privatize_locked(seq_id, idx)
        return table[idx], off

    def _payload(self, values, n: int):
        """`n` token rows as a device array of the pool's dtype, ``[>=
        n, *kv_shape]``. A payload whose rows are on the device
        (`_device_rows`) as it stands, its own padding being the bucket;
        a host payload padded to a pow2 bucket in numpy (one
        transfer)."""
        vals = _device_rows(values)
        if vals is not None:
            return self._ns.asarray(vals, self._dtype)
        padded = np.zeros((_next_pow2(max(n, 1)),) + self.kv_shape,
                          np.dtype(self._dtype))
        padded[:n] = np.asarray(values)[:n]
        return self._ns.asarray(padded)

    def _pool_scatter(self, blocks: np.ndarray, offs: np.ndarray,
                      vals, n: int) -> None:
        """ONE donated scatter for the first `n` rows of `vals`
        (`_payload`): a whole prefill range (any number of blocks, any
        offsets) lands in a single dispatch. Compiles are per row
        bucket: rows past `n` point past the pool and drop."""
        scatter = (self._ops.scatter_planes if self.planes
                   else self._ops.scatter)
        self._buffer = scatter(
            self._buffer, *self._padded_slots(blocks, offs, n,
                                              int(vals.shape[0])), vals)
        self.pool_updates += 1

    def _write_planes(self, start: int, skip: int, blocks: np.ndarray,
                      offs: np.ndarray, vals, n: int) -> None:
        """`_pool_scatter` into a pool held by planes, for rows ``[skip,
        n)`` of a payload whose row 0 is position `start`. Where the
        payload's blocks are the pool's (`start` on a block's edge, as a
        prompt's and a chunk's is), every whole block is set in one
        piece, ``[L, P, bs, dv]`` at its block, and only the ragged tail
        goes by slots: a chunk of 1,024 positions is 64 such pieces,
        where its slots are 1,024 x L x P pieces of `dv` values. One
        dispatch, one compile a row bucket."""
        bs = self.block_size
        rows = int(vals.shape[0])
        if start % bs or skip % bs or rows % bs:
            return self._pool_scatter(blocks, offs, vals, n)
        whole = n // bs
        ids = np.full((rows // bs,), self.num_blocks, np.int32)
        ids[skip // bs:whole] = blocks[skip:whole * bs:bs]
        tail = slice(whole * bs, n)
        self._buffer = self._ops.set_blocks_planes(
            self._buffer, self._ns.asarray(ids), vals,
            *self._padded_slots(blocks[tail], offs[tail], n - whole * bs,
                                bs), np.int32(whole * bs))
        self.pool_updates += 1

    def _padded_slots(self, blocks, offs, n: int, rows: int):
        """The first `n` (block, off) slots as device arrays of `rows`
        entries, the rest past the pool (dropped)."""
        b = np.full((rows,), self.num_blocks, np.int32)
        o = np.zeros((rows,), np.int32)
        b[:n] = blocks[:n]
        o[:n] = offs[:n]
        return self._ns.asarray(b), self._ns.asarray(o)

    def _write_riders(self, blocks, offs, values, n: int) -> None:
        """The payload's rows of every pool that rides, ``[>= n, L,
        ...]`` each (`values.groups[name]`), at the KV's own slots."""
        for name, rider in self._riders.items():
            rows = values.groups[name]
            vals = self._ns.asarray(getattr(rows, "padded", rows))
            self._riders[name] = self._ops.scatter_rider(
                rider, *self._padded_slots(blocks, offs, n,
                                           int(vals.shape[0])), vals)

    def write(self, seq_id: str, pos: int, value) -> None:
        """Store one token's KV entry at logical position `pos`. A
        write into a shared block privatizes it first (COW)."""
        with self._lock:
            block, off = self._writable_block(seq_id, pos)
            if self._ns is np:
                self._buffer[block, off] = value
            else:
                self._pool_scatter(
                    np.asarray([block], np.int32),
                    np.asarray([off], np.int32),
                    self._payload(np.asarray(value)[None], 1), 1)
            self._lens[seq_id] = max(self._lens.get(seq_id, 0), pos + 1)

    def write_range(self, seq_id: str, start: int, values) -> None:
        """Store KV entries for positions [start, start+len(values)) —
        the prefill bulk write. Shared blocks in the range privatize
        first (COW). The numpy pool writes block-sized slices in
        place, from one host copy of a device payload; the device pool
        resolves every token's (block, off) slot and lands the whole
        range in one donated scatter, a device payload without leaving
        the device. `range_writes_device` / `range_writes_host` count
        the calls by whether the payload reached the pool that way."""
        n = len(values)
        state = getattr(values, "state", None)
        with self._lock:
            for name, g in self._groups.items():
                g.write_range(seq_id, start, values.groups[name])
            if self._device and _device_rows(values) is not None:
                self.range_writes_device += 1
            else:
                self.range_writes_host += 1
            # A window group stores only the rows it still holds blocks
            # for: those before its table's first block are skipped.
            skip = min(n, max(0, self._base.get(seq_id, 0)
                              * self.block_size - start))
            if self._ns is np:
                values = np.asarray(values)
                pos = start + skip
                written = skip
                while written < n:
                    block, off = self._writable_block(seq_id, pos)
                    take = min(self.block_size - off, n - written)
                    self._buffer[block, off:off + take] = \
                        values[written:written + take]
                    written += take
                    pos += take
            elif n:
                blocks = np.full((n,), self.num_blocks, np.int32)
                offs = np.zeros((n,), np.int32)
                pos = start + skip
                i = skip
                while i < n:
                    block, off = self._writable_block(seq_id, pos)
                    take = min(self.block_size - off, n - i)
                    blocks[i:i + take] = block
                    offs[i:i + take] = np.arange(off, off + take)
                    i += take
                    pos += take
                vals = self._payload(values, n)
                if self.planes:
                    self._write_planes(start, skip, blocks, offs, vals, n)
                else:
                    self._pool_scatter(blocks, offs, vals, n)
                self._write_riders(blocks, offs, values, n)
            self._lens[seq_id] = max(self._lens.get(seq_id, 0), start + n)
            if state is not None and self._state is not None:
                self._write_state(self._slots[seq_id], state)

    def _write_state(self, slot: int, state: dict) -> None:
        """The state a prefill ended on, into its sequence's slot: one
        donated update on the device, slice writes on the host."""
        if self._set_state is None:
            for name, value in state.items():
                self._state[name][slot] = np.asarray(value)
        else:
            self._state = self._set_state(self._state, np.int32(slot),
                                          state)
            self.pool_updates += 1

    def slot_of(self, seq_id: str) -> Optional[int]:
        with self._lock:
            return self._slots.get(seq_id)

    def read_state(self, seq_id: str) -> dict:
        """Host copy of a sequence's state (tests and tools)."""
        with self._lock:
            slot = self._slots[seq_id]
            return {name: np.array(np.asarray(pool[slot]))
                    for name, pool in self._state.items()}

    def with_pool(self, fn):
        """Run `fn(pool)` on the live device buffer under the cache
        lock — the in-jit reader's entry point (paged prefill passes
        the pool straight into the model's compiled step). Donation
        from a concurrent writer invalidates the previous Python
        handle, so the dispatch must happen before any other thread
        re-binds the buffer; holding the lock across `fn` guarantees
        exactly that. The pool argument must be treated as read-only —
        mutations go through the manager's donated ops."""
        with self._lock:
            return fn(self._buffer)

    def with_pools(self, fn):
        """`with_pool` for a model that reads every layer group (a chunk
        of a prompt reads the positions before it): ``fn(pool)``, or
        with layer groups ``fn({group: pool})``, under the cache lock,
        which every write into a group's pool is made under too. Beside
        a state pool (a chunk of a prompt begins from its sequence's
        slot: `slot_of`) ``fn({"global": pool, "state": {name:
        pool}})``; the state pool is read, not donated."""
        with self._lock:
            if self._state is not None:
                return fn({self.GLOBAL: self._buffer,
                           self.STATE: self._state})
            if not self.grouped:
                return fn(self._buffer)
            return fn({**{name: g._buffer
                          for name, g in self._members().items()},
                       **self._riders})

    def mutate_pool(self, fn):
        """Run ``fn(pool) -> (result, new_pool)`` under the cache lock
        and re-bind the buffer. For callers that hand the pool to a
        DONATING jit (which invalidates the old handle) without going
        through `paged_step`'s slot resolution — e.g. a read-only
        full-prefix-hit decode, where the fused step runs with an empty
        write list and the returned pool is byte-identical."""
        with self._lock:
            result, new_pool = fn(self._buffer)
            self._buffer = new_pool
            if self._device:
                self.pool_updates += 1
            return result

    def paged_step(self, entries: Sequence[Tuple[str, int]], fn):
        """One fused paged decode step. Resolves each entry's
        (seq_id, pos) to a private (block, off) slot (COW backstop,
        same as `write`), calls ``fn(pool, blocks, offs)`` — the
        model's in-place compiled step, which gathers KV, computes,
        scatters the new tokens' KV at the given slots and returns
        ``(result, new_pool)`` with the pool DONATED — then re-binds
        the buffer and records the written lengths. One jit dispatch
        per decode step; the KV payload never exists outside the pool.
        All under the cache lock: readers can neither see the
        pre-write pool after lens advance nor race the donation.

        Beside a state pool the call is ``fn(pool, blocks, offs, state,
        slots)`` with each entry's slot, and returns ``(result,
        new_pool, new_state)``: both pools donated to the one step and
        re-bound here."""
        with self._lock:
            self._count_block_steps()
            if self.grouped:
                return self._grouped_step(entries, fn)
            blocks, offs = self._write_slots(entries)
            if self._state is None:
                result, new_pool = fn(self._buffer, blocks, offs)
            else:
                slots = [self._slots[seq_id] for seq_id, _ in entries]
                result, new_pool, self._state = fn(
                    self._buffer, blocks, offs, self._state, slots)
                self.state_slot_steps_in_use += len(self._slots)
                self.state_slot_steps += self.state_slots
            self._rebind_after_step(new_pool, entries)
            return result

    def _count_block_steps(self) -> None:
        self.block_steps_in_use += self.num_blocks - len(self._free)
        self.block_steps += self.num_blocks

    def _write_slots(self, entries) -> Tuple[List[int], List[int]]:
        blocks: List[int] = []
        offs: List[int] = []
        for seq_id, pos in entries:
            blk, off = self._writable_block(seq_id, pos)
            blocks.append(blk)
            offs.append(off)
        return blocks, offs

    def _rebind_after_step(self, new_pool, entries) -> None:
        self._buffer = new_pool
        if self._device:
            self.pool_updates += 1
        for seq_id, pos in entries:
            self._lens[seq_id] = max(self._lens.get(seq_id, 0), pos + 1)

    def _grouped_step(self, entries, fn):
        """`paged_step` over every layer group: ``fn(pools, blocks,
        offs)`` with a dict a group each, returning ``(result,
        new_pools)``; every pool was donated and is re-bound."""
        members = self._members()
        pools, blocks, offs = {}, {}, {}
        for name, g in members.items():
            if g is not self:
                g._count_block_steps()
            pools[name] = g._buffer
            blocks[name], offs[name] = g._write_slots(entries)
        # A pool that rides is written at the global group's slots.
        pools.update(self._riders)
        result, new_pools = fn(pools, blocks, offs)
        for name, g in members.items():
            g._rebind_after_step(new_pools[name], entries)
        for name in self._riders:
            self._riders[name] = new_pools[name]
        return result

    def step_tables(self, seq_id: str):
        """What a decode step reads `seq_id` through: its block table,
        or with layer groups ``{group: (base, table)}``, `base` the
        logical block the table's first entry is (0 in the global
        group, the first block the window still reaches in a window
        group)."""
        with self._lock:
            if not self.grouped:
                return list(self._tables.get(seq_id, ()))
            return {name: (g._base.get(seq_id, 0),
                           list(g._tables.get(seq_id, ())))
                    for name, g in self._members().items()}

    @property
    def grouped(self) -> bool:
        """Whether a step takes and hands back pools by name."""
        return bool(self._groups or self._riders)

    def group(self, name: str) -> "KVCacheManager":
        """The manager of one layer group (`global`: this one)."""
        return self if name == self.GLOBAL else self._groups[name]

    def gather(self, seq_id: str, length: Optional[int] = None):
        """Contiguous `[length, *kv_shape]` view of a sequence's cache,
        by position (the engine's model reads the pool through block
        tables instead; this is for tests and tools). One fancy-indexing
        gather over whole blocks (no per-position work)."""
        with self._lock:
            self.host_gathers += 1
            n = self._lens.get(seq_id, 0) if length is None else length
            if n == 0:
                return self._ns.zeros((0,) + self.kv_shape, self._dtype) \
                    if self.planes else self._buffer[0, 0:0]
            nblocks = math.ceil(n / self.block_size)
            idx = np.asarray(self._tables.get(seq_id, ())[:nblocks],
                             np.int64)
            if self._ns is np:
                out = self._buffer[idx].reshape(
                    (nblocks * self.block_size,) + self.kv_shape)
            elif self.planes:
                # [nb, L, P, bs, dv] -> a position a row.
                out = self._ns.reshape(
                    self._buffer[self._ns.asarray(idx)].transpose(
                        0, 3, 1, 2, 4),
                    (nblocks * self.block_size,) + self.kv_shape)
            else:
                out = self._ns.reshape(
                    self._buffer[self._ns.asarray(idx)],
                    (nblocks * self.block_size,) + self.kv_shape)
            return out[:n]

    @property
    def pool_residency(self) -> str:
        """Where the block pool lives: `device` (jax array mutated via
        donated jits) or `host` (numpy)."""
        return "device" if self._device else "host"

    @property
    def pool_bytes(self) -> int:
        """Size of the preallocated block pool in bytes."""
        n = self.num_blocks * self.block_size
        for d in self.kv_shape:
            n *= d
        return n * np.dtype(self._dtype).itemsize

    @property
    def state_slots(self) -> int:
        return len(self._slots) + len(self._free_slots)

    @property
    def state_bytes(self) -> int:
        """Size of the state pool in bytes (0 without one)."""
        if self._state is None:
            return 0
        return sum(int(np.prod(pool.shape)) * np.dtype(pool.dtype).itemsize
                   for pool in self._state.values())

    @property
    def state_stores(self) -> Dict[str, int]:
        """Bytes of each store of the state pool, by the name the model
        declared it under (`state_shapes`: a recurrent layer's ``s``, a
        selecting layer's compressed keys ``ck``, ...)."""
        return {name: int(np.prod(pool.shape)) * np.dtype(pool.dtype).itemsize
                for name, pool in (self._state or {}).items()}

    @property
    def rider_bytes(self) -> Dict[str, int]:
        """Bytes of each pool that rides this group's blocks."""
        return {name: int(np.prod(rider.shape))
                * np.dtype(self._dtype).itemsize
                for name, rider in self._riders.items()}

    def _group_stats(self) -> Dict[str, float]:
        with self._lock:
            return {
                "blocks": self.num_blocks,
                "blocks_in_use": self.num_blocks - len(self._free),
                "block_steps_in_use": self.block_steps_in_use,
                "block_steps": self.block_steps,
                "window": self.window or 0,
                "window_blocks_released": self.window_blocks_released,
                "pool_bytes": self.pool_bytes,
            }

    def stats(self) -> Dict[str, float]:
        with self._lock:
            used = self.num_blocks - len(self._free)
            shared = sum(1 for n in self._refs.values() if n > 1)
            groups = {name: g._group_stats()
                      for name, g in self._members().items()}
            for name, nbytes in self.rider_bytes.items():
                groups[name] = dict(groups[self.GLOBAL], pool_bytes=nbytes,
                                    rides=self.GLOBAL)
            return {
                "groups": groups,
                "num_blocks": self.num_blocks,
                "block_size": self.block_size,
                "used_blocks": used,
                "free_blocks": len(self._free),
                "utilization": used / self.num_blocks,
                "sequences": len(self._tables),
                "shared_blocks": shared,
                "cow_copies": self.cow_copies,
                "adoptions": self.adoptions,
                "pool_residency": self.pool_residency,
                "pool_bytes": self.pool_bytes,
                "host_gathers": self.host_gathers,
                "pool_updates": self.pool_updates,
                "state_slots": self.state_slots,
                "state_slots_in_use": len(self._slots),
                "state_bytes": self.state_bytes,
                "state_stores": self.state_stores,
            }
