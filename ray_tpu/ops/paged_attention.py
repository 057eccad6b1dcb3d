"""Decode attention over a paged KV pool, read where the pages lie.

One query token a row attends over that row's cached positions
``[0, position)``, which live in the serving engine's block pool
(`serve/engine/kv_cache.py`) at the blocks its block table names, plus
the step's own key and value (position ``position``, not yet in the
pool). Nothing gathers the row's cache into a dense array first.

**Two layouts of a pool, one rule between them** (`held_by_planes`, from
the key/value heads alone; every reader tells them by the pool's rank,
`by_planes`):

- *rows*, ``[num_blocks, block_size, n_layers, S, n_kv_heads, dv]``
  (`kv_row`: ``S`` slots of the values' width, ``[K, V]`` where keys and
  values are of one width): a position's row in one piece. Where
  ``n_kv_heads`` is a multiple of 8 a position's ``[n_kv_heads, dv]``
  fills whole float32 tiles of 8 sublanes, and the body below meets the
  keys as they lie.
- *planes*, ``[num_blocks, n_layers, S * n_kv_heads, block_size, dv]``:
  where the heads do not fill a tile (4 today: Keye's one group, MiMo's
  global group) plane ``slot * n_kv_heads + head`` of a layer holds that
  head's slot of the block's positions, positions on the sublanes and
  ``dv`` on the lanes, as the index keys' pool holds its one row a
  position (`kv_cache.py`, a pool that rides). A layer's page,
  ``pool[block, layer]``, is one contiguous piece of whole ``[16, 128]``
  bf16 tiles, a key head's keys of a pass are ``[keys, dk]`` by a
  reshape that moves nothing, and the body forms one product a key head
  (`_attend_planes`). The same arithmetic: float32 query, the slab
  converted to float32, products with float32 accumulation, float32
  softmax.

Heads may be grouped: ``n_heads`` query heads over ``n_kv_heads`` key
and value heads, query head ``i`` reading key head ``i // group``. The
pool's rows are then ``n_kv_heads`` wide, in float32 or bfloat16; the
softmax runs in float32 either way.

Two bodies, one result:

- `paged_decode_attention_kernel`: a Pallas TPU kernel (the pattern of
  `jax.experimental.pallas.ops.tpu.paged_attention`, for this pool's
  layout). The grid is the rows; block tables, positions and the layer
  index are scalar-prefetched and the pool stays in HBM. A grid step is
  one row: it walks the table columns that hold a key the row sees in
  *groups* of `pages_per_step` pages (by a page's bytes and the table's
  width: 32 pages of 64 KB, 8 of 256 KB, never more than the table
  names; a row's groups are of one size, so 33 live pages go as 17 and
  16). A group's pages, ``[block_size, 2, n_kv_heads, head_dim]`` slabs
  of the pool at layer ``layer``, are copied into one of two VMEM slabs
  by one async copy a page while the group before it is attended from
  the other; a row's last group starts the next row's first, so only a
  call's first copy is waited for with nothing to do. Columns past a
  row's last cached position (or left of a window) are neither fetched
  nor stepped through: the walk runs to the row's own live count, not
  to the table's width. Online softmax in float32, started from the
  step's own key and value, `_BYTES_A_PASS` of pages an update (256 keys
  of a bf16 pool). With one key head a query head the scores are
  products and sums on the vector unit; with grouped heads over a pool
  of rows the keys stay as they lie (``keys x n_kv_heads`` rows): one
  matrix product gives every query head against every row, the rows of
  other key heads are masked beside the positions the row does not see,
  and a second product takes the probabilities to the values. Over a
  pool held by planes the walk, the two slabs, the groups and the passes
  are the same; a page is copied in one piece and the body is
  `_attend_planes`: a key head's ``group`` query rows against that
  head's keys alone, the heads' scores stacked to ``[H, keys]``, every
  mask one row of lanes.
- `paged_decode_attention_xla`: plain XLA, ``pool[tables, :, layer]``
  (``pool[tables, layer]`` of a pool held by planes) for one layer and
  a masked softmax. The kernel's reference in the
  tests, and what runs off the chip and for head sizes the kernel does
  not take.

A **window bound** (`window`, a layer of sliding-window attention): the
query at ``position`` sees the cached positions ``j`` with ``position -
j < window`` only (itself and the ``window - 1`` before it). Such a row's
table is *compact*: column 0 names logical block ``starts[row]`` of the
sequence, not block 0, because the cache manager has released the blocks
before it (`kv_cache.py`, a layer group with a window), so a table is
``ceil(window / block_size) + 1`` columns wide at any length. The walk
then starts at the first column that holds a live key, and the first and
last live pages are masked. The windowed kernel runs under a name of its
own, ``paged_window_decode_attention``, so that a device trace tells a
window layer's calls from a global layer's; every device operation the
attention adds is the kernel's own call, under one of the two names.
The model counts the live pages its steps' tables named a group
(`decode_kv_pages_read_global`, `decode_kv_pages_read_window`) and the
groups they were fetched in (`decode_kv_page_groups_read*`, by
`page_groups`, the same arithmetic on the host).

A **latent pool** (`ops/latent_attention.py`: a position's row is one
piece of values for every query head, held in planes of 128 lanes, its
value the row's own first lanes) is walked by the same kernel, tables,
walks, slabs and eight-a-turn copies, with a body of its own
(`_attend_latent`, under the name ``paged_latent_decode_attention``):
the step's query heads meet a pass's rows in one product a plane, in the
pool's dtype, and a second product a value plane reads the same slab
again: one fetch a page, never two.

`paged_decode_attention` picks by what it can see (`kernel_eligible`:
the backend, the head size and the head count), as
`attention._flash_eligible` picks flash.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import _NEG_INF


def kernel_eligible(n_heads: int, head_dim: int,
                    n_kv_heads: int = None, v_head_dim: int = None) -> bool:
    """The kernel needs the TPU backend and a pool it reads in whole
    tiles: values that fill the lanes (heads of 64 and the unit tests'
    tiny models take the XLA body; keys may be wider than values, in
    slots of their own) and 2 or 4 key/value heads or a multiple of 8
    (`n_kv_heads` where heads are grouped, else `n_heads`). A multiple
    of 8 fills a float32 tile's sublanes in a pool of rows; 2 and 4 are
    held by planes (`held_by_planes`), a block's positions on the
    sublanes, and read by the per-head body (`_attend_planes`; a model
    whose key/value heads each select their own pages holds the planes
    head-major and walks a head at a time, one key/value head a call:
    `ops/block_sparse_attention.py`). (A pool of rows at 4 heads, which
    no model declares any more, still goes through the body over rows:
    the tests hold the two against each other.) 1 and 12 heads would lie
    in whole tiles by planes too and are not taken yet (ROADMAP R0b): at
    12 heads the chip keeps a pool of rows in another layout (it tiles
    the K/V axis instead, so as not to pad 12 to 16), and the compiler
    would hand the kernel a converted copy of the whole pool, a
    layer."""
    pool_heads = n_heads if n_kv_heads is None else n_kv_heads
    return (jax.default_backend() == "tpu"
            and (v_head_dim or head_dim) % 128 == 0
            and (pool_heads % 8 == 0 or pool_heads in (2, 4))
            and n_heads % pool_heads == 0)


def attention_widths(n_heads: int, head_dim: int, n_kv_heads: int,
                     v_head_dim: int) -> tuple:
    """`kernel_eligible`'s arguments for a layer's widths: the values'
    width named only where it is not the keys'."""
    widths = (n_heads, head_dim, n_kv_heads)
    return widths if v_head_dim == head_dim else widths + (v_head_dim,)


def held_by_planes(n_kv_heads: int) -> bool:
    """Which of the two layouts a pool of `n_kv_heads` key/value heads
    has, from the head count alone: **rows** (``[N, bs, L, S, Hkv,
    dv]``, a position's `kv_row` in one piece) where the heads fill a
    float32 tile's 8 sublanes, **planes** (``[N, L, S * Hkv, bs, dv]``)
    where they do not. The model declares its group with this
    (`kv_cache.py`: ``"planes"``), and every reader tells the two by the
    pool's rank (`by_planes`)."""
    return n_kv_heads % 8 != 0


def by_planes(pool) -> bool:
    """Whether `pool` is held a block, a layer and a plane at a time,
    ``[N, L, S * Hkv, bs, dv]`` (which is ``[N, L * S * Hkv, bs, dv]``
    with a layer's planes named apart): plane ``slot * Hkv + head`` of a
    layer holds that head's slot (`kv_row`) of the block's ``bs``
    positions, positions on the sublanes and ``dv`` on the lanes, the
    layout of the index keys' pool (`kv_cache.py`, a pool that rides).
    A layer's page is then ``pool[block, layer]``, one contiguous piece
    of whole ``[16, 128]`` bf16 tiles. The other kind has rank 6."""
    return pool.ndim == 5


def pool_block_size(pool) -> int:
    return pool.shape[3] if by_planes(pool) else pool.shape[1]


def _page_shape(pool) -> tuple:
    """A layer's page of `pool` as a slab of the kernel holds it."""
    return (pool.shape[2:] if by_planes(pool)
            else pool.shape[1:2] + pool.shape[3:])


def pool_page_bytes(pool) -> int:
    """A layer's page of `pool`, in bytes: the same in both layouts."""
    return math.prod(_page_shape(pool)) * pool.dtype.itemsize


def plane_slots(pool, blocks, offs):
    """The index of rows' slots in a pool held by planes, ``[N, L, P,
    bs, dv]``: ``pool.at[plane_slots(...)]`` is ``[rows, L, P, dv]``,
    the four indexed axes side by side, which the compiler writes in
    place (`kv_cache.rider_slots`: an axis between two indexed ones
    costs copies of the whole pool)."""
    layers, planes = jnp.arange(pool.shape[1]), jnp.arange(pool.shape[2])
    return (blocks[:, None, None], layers[None, :, None],
            planes[None, None, :], offs[:, None, None])


def write_rows(pool, blocks, offs, rows):
    """`pool` with `rows` ``[B, L, S, Hkv, dv]`` (a position's `kv_row`
    of every layer, a row) at the slots ``(blocks[i], offs[i])``, in
    either layout; a block past the pool is dropped."""
    if not by_planes(pool):
        return pool.at[blocks, offs].set(rows, mode="drop")
    return pool.at[plane_slots(pool, blocks, offs)].set(
        rows.reshape((-1,) + pool.shape[1:3] + pool.shape[4:]),
        mode="drop")


def pages_as_rows(pages, n_kv_heads: int):
    """Pages ``[..., nb, S * Hkv, bs, dv]`` of one layer of a planes pool
    -> the positions' rows ``[..., nb * bs, S, Hkv, dv]`` (`kv_row`), as
    a rows pool holds them."""
    *lead, nb, planes, bs, dv = pages.shape
    at = len(lead)
    rows = pages.reshape(*lead, nb, planes // n_kv_heads, n_kv_heads, bs, dv)
    rows = rows.transpose(*range(at), at, at + 3, at + 1, at + 2, at + 4)
    return rows.reshape(*lead, nb * bs, planes // n_kv_heads, n_kv_heads, dv)


def pages_as_heads(pages, n_kv_heads: int, head_dim: int):
    """`pages_as_rows` then `kv_of_rows`, a head at a time: the keys
    ``[Hkv, nb * bs, head_dim]`` and the values ``[Hkv, nb * bs, dv]`` of
    pages ``[nb, S * Hkv, bs, dv]``, as a prefill's forward takes them.
    A plane's ``[bs, dv]`` moves in one piece."""
    nb, planes, bs, dv = pages.shape
    slots = pages.reshape(nb, planes // n_kv_heads, n_kv_heads, bs, dv)
    slots = slots.transpose(1, 2, 0, 3, 4).reshape(
        planes // n_kv_heads, n_kv_heads, nb * bs, dv)
    keys = jnp.concatenate([slots[0]] + list(slots[2:]), axis=-1)
    return keys[..., :head_dim], slots[1]


def heads_of_pages(pool, table, layer, n_kv_heads: int, head_dim: int):
    """One layer's keys ``[Hkv, nb * bs, head_dim]`` and values ``[Hkv,
    nb * bs, dv]`` of the pages `table` ``[nb]`` names in `pool`, in
    either layout. From a pool held by planes a layer's pages are
    gathered alone (``nb`` contiguous pieces); from a pool of rows every
    layer's call gathers the same ``pool[table]``, which the compiler
    makes once."""
    if by_planes(pool):
        return pages_as_heads(pool[table, layer], n_kv_heads, head_dim)
    rows = pool[table][:, :, layer]
    keys, vals = kv_of_rows(rows.reshape((-1,) + rows.shape[2:]), head_dim)
    return keys.transpose(1, 0, 2), vals.transpose(1, 0, 2)


def kv_slots(head_dim: int, v_head_dim: int) -> int:
    """Slots of ``v_head_dim`` values a key/value head takes in a pool
    row: the value's one and the key's ``ceil(head_dim / v_head_dim)``."""
    return 1 + -(-head_dim // v_head_dim)


def kv_row(k, v):
    """The pool rows of keys ``[T, Hkv, dk]`` and values ``[T, Hkv,
    dv]``: ``[T, S, Hkv, dv]``, slot 0 the keys' first ``dv`` values,
    slot 1 the values, and where keys are wider than values (``dk`` 192
    over ``dv`` 128) slots 2 on the keys' further values, the last one
    filled up with zeros. Every slot is whole lanes; ``[K, V]`` where
    the two widths are one."""
    t, hkv, dk = k.shape
    dv = v.shape[-1]
    n = kv_slots(dk, dv) - 1
    if n == 1:
        return jnp.stack([k, v], axis=1)
    k = jnp.pad(k, ((0, 0), (0, 0), (0, n * dv - dk)))
    k = k.reshape(t, hkv, n, dv).transpose(0, 2, 1, 3)
    return jnp.concatenate([k[:, :1], v[:, None], k[:, 1:]], axis=1)


def _keys_of(kv):
    """The keys ``[..., Hkv, (S - 1) * dv]`` of rows ``[..., S, Hkv,
    dv]`` (`kv_row`): the slots but the values', side by side."""
    slots = kv.shape[-3]
    if slots == 2:
        return kv[..., 0, :, :]
    return jnp.concatenate(
        [kv[..., s, :, :] for s in range(slots) if s != 1], axis=-1)


def kv_of_rows(kv, head_dim: int):
    """`kv_row` back: the keys ``[..., Hkv, head_dim]`` and the values
    ``[..., Hkv, dv]`` of pool rows ``[..., S, Hkv, dv]``."""
    return _keys_of(kv)[..., :head_dim], kv[..., 1, :, :]


def _pool_slots(pool, n_kv_heads: int) -> int:
    """The slots a key/value head takes in `pool` (`kv_slots`)."""
    return (pool.shape[2] // n_kv_heads if by_planes(pool)
            else pool.shape[3])


def _as_wide_as_the_pools_keys(x, pool, n_kv_heads: int):
    """q or the step's own keys ``[B, H, dk]``, filled up with zeros to
    the width the pool holds a key in (`kv_row`)."""
    held = (_pool_slots(pool, n_kv_heads) - 1) * pool.shape[-1]
    return x if x.shape[-1] == held else jnp.pad(
        x, ((0, 0), (0, 0), (0, held - x.shape[-1])))


def paged_decode_attention_xla(q, k_new, v_new, pool, tables, positions,
                               layer, window: int = None, starts=None,
                               sink=None, keep=None, own_keep=None):
    """q ``[B, H, dk]``; k_new ``[B, Hkv, dk]``, v_new ``[B, Hkv, dv]``;
    pool ``[N, bs, L, S, Hkv, dv]`` (`kv_row`: ``[N, bs, L, 2, Hkv, hd]``
    where ``dk == dv``) or, held by planes (`by_planes`), ``[N, L, S *
    Hkv, bs, dv]``; tables ``[B, nb]`` int32; positions ``[B]``
    int32; layer a scalar; `window`, where the layer has one, with starts
    ``[B]`` int32, the logical block a row's table begins at (None: 0);
    `sink` ``[H]``, where the layer has one: a logit a query head that
    joins the softmax as a column with no value. ``H`` is a multiple of
    ``Hkv``. Returns ``[B, H, dv]`` float32. Pool positions at or past a
    row's `position` may hold anything (a reused block's stale rows,
    block 0 behind a padded table entry): they are masked, never read
    into the result. `keep` ``[B, nb * bs]`` bool, where the layer
    selects its keys: of the cached positions a row sees, those it
    attends to; `own_keep` ``[B]`` bool, whether it attends to the step's
    own position (None: it does)."""
    b, h, dk = q.shape
    hkv, dv = k_new.shape[1], pool.shape[-1]
    bs = pool_block_size(pool)
    s_pad = tables.shape[1] * bs
    if by_planes(pool):
        kv = pages_as_rows(pool[tables, layer], hkv)
    else:
        kv = pool[tables, :, layer].reshape((b, s_pad) + pool.shape[3:])
    kv = kv.astype(jnp.float32)
    keys, vals = _keys_of(kv), kv[:, :, 1]
    scale = dk ** -0.5
    # Query head i reads key head i // group: [B, Hkv, group, dk].
    q = _as_wide_as_the_pools_keys(q.astype(jnp.float32), pool, hkv)
    q = q.reshape(b, hkv, h // hkv, -1)
    k_new = _as_wide_as_the_pools_keys(k_new.astype(jnp.float32),
                                       pool, hkv)[:, :, None]
    v_new = v_new.astype(jnp.float32)[:, :, None]
    scores = jnp.einsum("bkgd,bskd->bkgs", q, keys,
                        preferred_element_type=jnp.float32) * scale
    at = jnp.arange(s_pad)[None, :]                              # [B, S]
    if starts is not None:
        at = at + starts[:, None] * bs
    cached = at < positions[:, None]
    if window is not None:
        cached &= positions[:, None] - at < window
    if keep is not None:
        cached &= keep
    scores = jnp.where(cached[:, None, None, :], scores, _NEG_INF)
    own = jnp.sum(q * k_new, axis=-1, keepdims=True) * scale
    if own_keep is not None:
        own = jnp.where(own_keep[:, None, None, None], own, _NEG_INF)
    columns = [scores, own]
    if sink is not None:
        columns.append(jnp.broadcast_to(
            sink.astype(jnp.float32).reshape(1, hkv, h // hkv, 1),
            own.shape))
    probs = jax.nn.softmax(jnp.concatenate(columns, axis=-1), axis=-1)
    out = (jnp.einsum("bkgs,bskd->bkgd", probs[..., :s_pad], vals)
           + probs[..., s_pad:s_pad + 1] * v_new)
    return out.reshape(b, h, dv)


# Two slabs of a group's pages may take this much VMEM, and one pass of
# the body this many bytes of pages (`pages_per_step`).
_VMEM_FOR_PAGES = 4 << 20
_BYTES_A_PASS = 1 << 20
# Over a pool held by planes: how many copies are started, or waited
# for, a turn of the loop that does so, in straight-line code. One a turn
# (the walk over rows) leaves the body nothing to run beside: at Keye's
# shape the copies alone take 0.57 ms a layer, the body alone 0.18-0.25,
# and the walk 0.72 at one a turn, 0.574 at 4, 8 or 16 (my chip run,
# PR 58).
_COPIES_A_TURN = 8


def _pow2_at_most(n: int) -> int:
    return 1 << (max(int(n), 1).bit_length() - 1)


def pages_per_step(page_bytes: int, table_width: int) -> int:
    """How many pages the kernel brings into VMEM together (a *group*),
    from what it can see: a page's bytes in the pool (``block_size x 2 x
    n_kv_heads x head_dim x itemsize``: 64 KB in the two sparse models'
    bf16 pools, 256 KB in `olmo-1b`'s float32 one) and the table's width.
    The largest power of two whose two slabs fit `_VMEM_FOR_PAGES` (32
    pages of 64 KB, 8 of 256 KB), and no more than the table can name
    (1, 2, 4 at short contexts). A row's ``n`` live pages then go in
    ``ceil(n / pages)`` groups of one size: nothing is padded."""
    most = _pow2_at_most(_VMEM_FOR_PAGES // (2 * page_bytes))
    width = max(int(table_width), 1)
    return min(most, 1 << (width - 1).bit_length())


def pool_pages_per_step(pool, table_width: int) -> int:
    """`pages_per_step` for a pool as it is held, in either layout (a
    page's bytes are the same in both), and a table of `table_width`
    columns."""
    return pages_per_step(pool_page_bytes(pool), table_width)


def live_pages(position: int, block_size: int, window: int = None) -> int:
    """The pages that hold a cached position the row at `position` sees:
    ``[0, position)``, under a window ``[position - window + 1,
    position)``."""
    oldest = 0 if window is None else max(0, position - window + 1)
    return -(-position // block_size) - oldest // block_size


def page_groups(pool, table_width: int, positions, window: int = None
                ) -> int:
    """On the host: the groups the kernel fetches for a step's rows at
    `positions` from `pool` through tables of `table_width` columns: a
    row's live pages ÷ `pool_pages_per_step`, rounded up; a row with
    nothing cached has none."""
    pages = pool_pages_per_step(pool, table_width)
    return sum(-(-live_pages(int(p), pool_block_size(pool), window) // pages)
               for p in positions)


def _pages_a_pass(pages: int, page_bytes: int) -> int:
    """Of a group's pages, how many one pass of the body attends (one
    online-softmax update): `_BYTES_A_PASS` of them where the group
    holds as many (256 keys of the bf16 pools, 64 of the float32 one;
    passes of half as many cost the global layers' call 40% more, of
    twice as many nothing less: my chip run, PR 36), and a divisor of
    the group. The body's code grows with a pass's keys, not with the
    group."""
    return math.gcd(pages, _pow2_at_most(_BYTES_A_PASS // page_bytes))


def _seen(at, position, until, window):
    """Keys at positions `at` that the query at `position` sees, short
    of `until`: the end of the group's pages in the slab or `position`,
    whichever comes first."""
    keep = at < until
    if window is not None:
        keep &= position - at < window
    return keep


def _start_from_own_token(q, k_new_ref, v_new_ref, sink_ref, m_ref, l_ref,
                          acc_ref, scale: float, own_keep=None):
    """The running softmax starts from the step's own key and value,
    and from the layer's sink (a column with no value) where it has
    one. With `own_keep` (a scalar, where the layer selects its keys) 0
    the own key's score is the mask's: the first key the row does attend
    to wipes the start out."""
    f32 = jnp.float32
    h, n_kv = q.shape[0], k_new_ref.shape[0]
    k_own = k_new_ref[...].astype(f32)                       # [Hkv, dk]
    v_own = v_new_ref[...].astype(f32)
    if h != n_kv:
        # Query head i reads key head i // group.
        group = h // n_kv
        k_own, v_own = (jnp.concatenate(
            [jnp.broadcast_to(x[j][None], (group, x.shape[-1]))
             for j in range(n_kv)], axis=0) for x in (k_own, v_own))
    own = jnp.sum(q * k_own, axis=-1, keepdims=True) * scale
    if own_keep is not None:
        own = jnp.where(own_keep > 0, own, _NEG_INF)
    if sink_ref is None:
        m_ref[...] = own
        l_ref[...] = jnp.ones_like(l_ref)
        acc_ref[...] = v_own
        return
    sink = sink_ref[...]                                     # [H, 1]
    m = jnp.maximum(own, sink)
    p_own = jnp.exp(own - m)
    m_ref[...] = m
    l_ref[...] = p_own + jnp.exp(sink - m)
    acc_ref[...] = p_own * v_own


def _attend(q, kv, first, position, until, m_ref, l_ref, acc_ref, *,
            scale: float, window):
    """One online-softmax update over the keys of `kv` ``[T, S, H, dv]``
    (float32; key t at position ``first + t``, those from `until` on
    not this group's), one key head a query head: products and sums on
    the vector unit."""
    keys, vals = _keys_of(kv), kv[:, 1]                      # [T, H, ..]
    scores = jnp.sum(q[None] * keys, axis=-1,
                     keepdims=True) * scale                  # [T, H, 1]
    at = first + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
    scores = jnp.where(_seen(at, position, until, window), scores,
                       _NEG_INF)
    m_prev = m_ref[...]
    m_next = jnp.maximum(m_prev, jnp.max(scores, axis=0))
    alpha = jnp.exp(m_prev - m_next)
    p = jnp.exp(scores - m_next[None])                       # [T, H, 1]
    m_ref[...] = m_next
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=0)
    acc_ref[...] = alpha * acc_ref[...] + jnp.sum(p * vals, axis=0)


def _kept_lanes(kept, n_kv: int):
    """kept ``[P, bs]`` float32 (a page a row, 1.0 at a position the row
    attends to) -> ``[1, P * bs * n_kv]``, lane ``c`` the flag of key
    ``c // n_kv``: as `_attend_grouped` lays its score columns. By one
    small product (position ``s`` of every page onto the lanes of slot
    ``s``) and a masked sum over the pages: a reshape from sublanes onto
    lanes the compiler does not take."""
    f32, i32 = jnp.float32, jnp.int32
    pages, bs = kept.shape
    lanes = pages * bs * n_kv
    slot = jax.lax.broadcasted_iota(i32, (bs, lanes), 0)
    lane = jax.lax.broadcasted_iota(i32, (bs, lanes), 1)
    onto = (jax.lax.rem(jax.lax.div(lane, i32(n_kv)), i32(bs))
            == slot).astype(f32)
    spread = jnp.dot(kept, onto, preferred_element_type=f32)  # [P, lanes]
    page = jax.lax.broadcasted_iota(i32, (pages, lanes), 0)
    lane = jax.lax.broadcasted_iota(i32, (pages, lanes), 1)
    own = jax.lax.div(lane, i32(bs * n_kv)) == page
    return jnp.sum(jnp.where(own, spread, 0.0), axis=0, keepdims=True)


def _attend_grouped(q, kv, first, position, until, m_ref, l_ref, acc_ref,
                    *, scale: float, window, kept=None):
    """`_attend` for ``H`` query heads over kv's ``Hkv`` key heads, query
    head i reading key head ``i // group``. The keys stay as they lie,
    ``T x Hkv`` rows of ``hd``: one matrix product gives every query
    head's score with every row, the rows of another key head are masked
    with the positions the query does not see, and one more product takes
    the probabilities to the values. (Taking a key head's ``[T, hd]`` out
    of the slab first costs a pass over its sublanes a head: that pass,
    not the products, bounded the kernel a page a grid step.)"""
    f32 = jnp.float32
    t, _, n_kv, dv = kv.shape
    h = q.shape[0]
    keys = _keys_of(kv).reshape(t * n_kv, q.shape[1])
    vals = kv[:, 1].reshape(t * n_kv, dv)
    scores = jax.lax.dot_general(
        q, keys, (((1,), (1,)), ((), ())),
        preferred_element_type=f32) * scale              # [H, T * Hkv]
    column = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    head = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
    key_head = jax.lax.rem(column, jnp.int32(n_kv))
    keep = (_seen(first + jax.lax.div(column, jnp.int32(n_kv)), position,
                  until, window)
            & (key_head == jax.lax.div(head, jnp.int32(h // n_kv))))
    if kept is not None:
        keep &= _kept_lanes(kept, n_kv) > 0.5
    scores = jnp.where(keep, scores, _NEG_INF)
    m_prev = m_ref[...]
    m_next = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_next)
    p = jnp.exp(scores - m_next)                         # [H, T * Hkv]
    m_ref[...] = m_next
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
        p, vals, preferred_element_type=f32)


def _attend_planes(q, plane, first, position, until, m_ref, l_ref, acc_ref,
                   *, n_kv: int, scale: float, window, kept=None):
    """`_attend_grouped` over a pass's pages of a pool held by planes.
    ``plane(p)`` is plane ``p`` of the pass's pages, ``[T, dv]`` float32
    (key t at position ``first + t``): whole tiles, a key head's keys
    apart from every other's. One product a key head: its ``group``
    query rows against its keys, a key wider than ``dv`` as its slots'
    products added (`kv_row`: slot 0, then slots 2 on); the heads'
    scores stacked to ``[H, T]``. Every mask is one row of ``T`` lanes,
    and no score is formed to be masked for its key head. Then a second
    product a head, the probabilities against that head's values."""
    f32 = jnp.float32
    h, held = q.shape
    group = h // n_kv
    dv = acc_ref.shape[1]
    key_slots = [0] + list(range(2, 1 + held // dv))

    def scores_of(j):
        rows = q[j * group:(j + 1) * group]
        return sum(jax.lax.dot_general(
            rows[:, i * dv:(i + 1) * dv], plane(slot * n_kv + j),
            (((1,), (1,)), ((), ())), preferred_element_type=f32)
            for i, slot in enumerate(key_slots))

    scores = jnp.concatenate([scores_of(j) for j in range(n_kv)],
                             axis=0) * scale                 # [H, T]
    at = first + jax.lax.broadcasted_iota(jnp.int32, (1, scores.shape[1]), 1)
    keep = _seen(at, position, until, window)                # [1, T]
    if kept is not None:
        keep &= _kept_lanes(kept, 1) > 0.5
    scores = jnp.where(keep, scores, _NEG_INF)
    m_prev = m_ref[...]
    m_next = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_next)
    p = jnp.exp(scores - m_next)                             # [H, T]
    m_ref[...] = m_next
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = alpha * acc_ref[...] + jnp.concatenate(
        [jnp.dot(p[j * group:(j + 1) * group], plane(n_kv + j),
                 preferred_element_type=f32) for j in range(n_kv)], axis=0)


def _attend_latent(q, plane, first, position, until, m_ref, l_ref, acc_ref,
                   *, scale: float, window=None):
    """One online-softmax update over a pass's pages of a *latent* pool
    (`ops/latent_attention.py`): a position's row is one piece of
    ``held`` values for every query head, the planes side by side
    (``plane(p)`` ``[T, 128]``, the row's lanes ``128 p`` on, in the
    pool's dtype), and its value is the row's own first ``acc_ref.shape[1]``
    lanes: the pass is fetched once and read twice. The step's ``H``
    query heads meet the pass in one product a plane, added; a second
    product a value plane takes the probabilities to the values. Both
    take their operands in the pool's dtype and accumulate in float32
    (the model's rule; a float32 pool is met in float32)."""
    f32 = jnp.float32
    lanes = plane(0).shape[1]
    planes = [plane(p) for p in range(q.shape[1] // lanes)]
    q = q.astype(planes[0].dtype)
    scores = sum(jax.lax.dot_general(
        q[:, p * lanes:(p + 1) * lanes], rows, (((1,), (1,)), ((), ())),
        preferred_element_type=f32)
        for p, rows in enumerate(planes)) * scale            # [H, T]
    at = first + jax.lax.broadcasted_iota(jnp.int32, (1, scores.shape[1]), 1)
    scores = jnp.where(_seen(at, position, until, window), scores, _NEG_INF)
    m_prev = m_ref[...]
    m_next = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_next)
    p = jnp.exp(scores - m_next)                             # [H, T]
    m_ref[...] = m_next
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
    p = p.astype(planes[0].dtype)
    acc_ref[...] = alpha * acc_ref[...] + jnp.concatenate(
        [jnp.dot(p, rows, preferred_element_type=f32)
         for rows in planes[:acc_ref.shape[1] // lanes]], axis=1)


# A row's walk, as `_row_walks` hands it to the kernel.
_FIRST, _COUNT, _SIZE, _GROUPS, _BLOCK0 = range(5)


def _row_walks(positions, starts, block_size: int, table_width: int,
               pages: int, window):
    """``[B, 5]`` int32, a row: the first table column that holds a key
    the row sees, how many do (`live_pages`), the size and number of the
    groups they go in (``ceil(n / pages)`` groups of one size: 33 pages
    at 32 a group are 17 and 16, not 32 and 1, a group of one page
    leaving the copy after it nothing to hide behind), and the logical
    block of that first column. Worked out here, for all rows at once and
    outside the kernel: as scalar arithmetic inside it, it was half of
    what lowering the kernel cost a program at every start."""
    start = 0 if starts is None else starts
    end = (positions + block_size - 1) // block_size - start
    first = jnp.zeros_like(positions)
    if window is not None:
        first = jnp.maximum(
            jnp.maximum(positions - window + 1, 0) // block_size - start, 0)
    count = jnp.clip(end - first, 0, table_width - first)
    groups = (count + pages - 1) // pages
    size = -(-count // jnp.maximum(groups, 1))
    return jnp.stack([first, count, size, groups, start + first],
                     axis=1).astype(jnp.int32)


def _kernel_body(tables_ref, positions_ref, layer_ref, walks_ref, *refs,
                 block_size: int, scale: float, window, with_sink: bool,
                 with_keep: bool = False, with_own_keep: bool = False,
                 latent: bool = False):
    """One grid step is one row. Its live table columns are walked in
    groups (`_row_walks`): a group's pages are copied from the pool in
    HBM into one of two VMEM slabs while the group before it is attended
    from the other, and the row's last group starts the next row's
    first. Scalars are worked with `lax`'s own operations: every `jnp`
    operator in here is traced and lowered once a program and kernel, at
    every start of a replica, and a step's programs are many."""
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    own_keep_ref = refs[0] if with_own_keep else None
    q_ref, k_new_ref, v_new_ref, pool_ref, *rest = refs[int(with_own_keep):]
    sink_ref = rest[0] if with_sink else None
    keep_ref = rest[int(with_sink)] if with_keep else None
    (o_ref, slabs, arrived, ahead_ref, m_ref, l_ref,
     acc_ref) = rest[int(with_sink) + int(with_keep):]
    i32 = jnp.int32
    row, last_row = pl.program_id(0), pl.num_programs(0) - 1
    layer = layer_ref[0]
    pages = slabs.shape[1]
    # A pool held by planes: a slab's page is ``[S * Hkv, bs, dv]``, and
    # a layer's page in the pool one contiguous piece.
    planes = len(slabs.shape) == 5
    a_pass = _pages_a_pass(pages, math.prod(slabs.shape[2:])
                           * jnp.dtype(slabs.dtype).itemsize)
    # Copies are started and waited for `turn` a turn of their loop, in
    # straight-line code (over planes; one a turn over rows, as ever).
    turn = math.gcd(pages, _COPIES_A_TURN) if planes else 1

    def page_of(block):
        return (pool_ref.at[block, layer] if planes
                else pool_ref.at[block, :, layer])

    def fetch(r, column, count, slab):
        def one(i, _):
            pltpu.make_async_copy(
                page_of(tables_ref[r, lax.add(column, i)]),
                slabs.at[slab, i], arrived.at[slab]).start()

        def some(t, _):
            # A turn past the group's last page copies that page again,
            # into a slot of the slab that no pass reads unmasked.
            last = lax.sub(lax.add(column, count), i32(1))
            for j in range(turn):
                i = lax.add(lax.mul(t, i32(turn)), i32(j))
                pltpu.make_async_copy(
                    page_of(tables_ref[r, lax.min(lax.add(column, i), last)]),
                    slabs.at[slab, i], arrived.at[slab]).start()
        lax.fori_loop(i32(0), turns_of(count), one if turn == 1 else some,
                      None)

    def turns_of(count):
        return count if turn == 1 else lax.div(
            lax.add(count, i32(turn - 1)), i32(turn))

    @pl.when(lax.eq(row, i32(0)))
    def _first_row():
        # A pass may read a slab's pages that no copy of this call has
        # filled (masked, but 0 x NaN is NaN): what they hold is finite.
        zeros = jnp.zeros(slabs.shape[2:], slabs.dtype)

        def clear(i, _):
            slabs[lax.div(i, i32(pages)), lax.rem(i, i32(pages))] = zeros
        lax.fori_loop(i32(0), i32(2 * pages), clear, None)
        ahead_ref[0] = i32(0)   # the slab of this row's first group
        ahead_ref[1] = i32(0)   # whether the row before started its copy

    q = q_ref[...].astype(jnp.float32)                       # [H, hd]
    _start_from_own_token(q, k_new_ref, v_new_ref, sink_ref, m_ref, l_ref,
                          acc_ref, scale,
                          own_keep_ref[row] if with_own_keep else None)
    position = positions_ref[row]
    first, n, size, n_groups, block0 = (walks_ref[row, k] for k in range(5))
    if latent:
        attend = functools.partial(_attend_latent, scale=scale)
    elif planes:
        attend = functools.partial(_attend_planes, n_kv=k_new_ref.shape[0],
                                   scale=scale, window=window)
    else:
        attend = functools.partial(
            _attend if q.shape[0] == k_new_ref.shape[0] else _attend_grouped,
            scale=scale, window=window)

    @pl.when(lax.gt(n, i32(0)))
    def _attend_row():
        @pl.when(lax.eq(ahead_ref[1], i32(0)))
        def _fetch_own_first_group():
            fetch(row, first, size, ahead_ref[0])

        following = lax.min(lax.add(row, i32(1)), last_row)
        has_next = lax.bitwise_and(lax.lt(row, last_row),
                                   lax.gt(walks_ref[following, _COUNT],
                                          i32(0)))

        def group(g, slab):
            done = lax.mul(g, size)
            count = lax.min(lax.sub(n, done), size)
            other = lax.sub(i32(1), slab)
            more = lax.lt(lax.add(g, i32(1)), n_groups)

            # While this group is attended the copy of the next one
            # runs: the row's next, or the next row's first.
            @pl.when(lax.bitwise_or(more, has_next))
            def _fetch_the_next_group():
                ahead = lax.add(done, size)
                fetch(lax.select(more, row, following),
                      lax.select(more, lax.add(first, ahead),
                                 walks_ref[following, _FIRST]),
                      lax.select(more, lax.min(lax.sub(n, ahead), size),
                                 walks_ref[following, _SIZE]), other)

            def arrive(i, _):
                for _ in range(turn):
                    pltpu.make_async_copy(page_of(0), slabs.at[slab, 0],
                                          arrived.at[slab]).wait()
            lax.fori_loop(i32(0), turns_of(count), arrive, None)
            # What the slab holds past the group's pages is another
            # group's, or nothing.
            at = lax.mul(lax.add(block0, done), i32(block_size))
            until = lax.min(position, lax.add(
                at, lax.mul(count, i32(block_size))))

            def a_pass_over(c, _):
                page = lax.mul(c, i32(a_pass))
                if planes:
                    # A plane of the pass's pages: whole tiles, and the
                    # reshape moves nothing.
                    # (A latent pool's planes go to their products in
                    # the pool's dtype.)
                    def kv(p):
                        rows = slabs[slab, pl.ds(page, a_pass), p]
                        if not latent:
                            rows = rows.astype(jnp.float32)
                        return rows.reshape(a_pass * block_size,
                                            slabs.shape[-1])
                else:
                    kv = slabs[slab, pl.ds(page, a_pass)].astype(jnp.float32)
                    kv = kv.reshape((a_pass * block_size,) + kv.shape[2:])
                kept = {}
                if with_keep:
                    # The pass's pages by their table columns.
                    column = lax.add(lax.add(first, done), page)
                    kept["kept"] = keep_ref[pl.ds(column, a_pass), :]
                attend(q, kv, lax.add(at, lax.mul(page, i32(block_size))),
                       position, until, m_ref, l_ref, acc_ref, **kept)
            lax.fori_loop(i32(0), lax.div(lax.add(count, i32(a_pass - 1)),
                                          i32(a_pass)), a_pass_over, None)
            return other

        ahead_ref[0] = lax.fori_loop(i32(0), n_groups, group, ahead_ref[0])
        ahead_ref[1] = has_next.astype(i32)

    o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "pages", "interpret",
                                             "name", "latent_scale"))
def paged_decode_attention_kernel(q, k_new, v_new, pool, tables,
                                  positions, layer, window: int = None,
                                  starts=None, sink=None, keep=None,
                                  own_keep=None, *, pages: int = None,
                                  interpret: bool = False,
                                  name: str = None,
                                  latent_scale: float = None):
    """Same arguments and result as `paged_decode_attention_xla`. The
    pool stays in HBM as it stands; the kernel copies a row's live pages
    of one layer into VMEM, `pages` at a time (`pages_per_step`, where
    None), two slabs deep. Jitted for the step that calls it a layer: its
    layers of one shape (the layer index is an argument) are then traced
    and lowered to the kernel's MLIR once, not once a layer, which a
    step's program pays at every start, compiled or fetched (12 calls
    cost a `repo-context` bucket 2.4 s of 4.0: my chip run, PR 36).
    With `keep` (grouped heads only) the walk is the same, every live
    page fetched, and a position the row does not attend to is masked
    like one it does not see: a further operand ``[nb, bs]`` a row in
    VMEM. A call without one traces what it always has. `name`: what a
    device trace calls the kernel where the default (the module
    docstring's two names) would mislead: `sparse_paged_decode_attention`
    and the latent walk pass one. `latent_scale` (the softmax scale, a
    float): `pool` is a *latent* pool held by planes, ``[N, L, P, bs,
    128]`` (`ops/latent_attention.py`), q ``[B, H, 128 P]`` and k_new
    ``[B, 1, 128 P]`` are as wide as its row, v_new ``[B, 1, dv]`` is
    the row's first ``dv`` lanes, and the body is `_attend_latent`: the
    same tables, walks, slabs and copies."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, dk = q.shape
    nb = tables.shape[1]
    bs = pool_block_size(pool)
    hkv, dv = v_new.shape[1:]
    latent = latent_scale is not None
    if latent:
        page = (dk // pool.shape[-1], bs, pool.shape[-1])
    else:
        page = ((kv_slots(dk, dv) * hkv, bs, dv) if by_planes(pool)
                else (bs, kv_slots(dk, dv), hkv, dv))
    if (_page_shape(pool) != page or h % hkv
            or k_new.shape != (b, hkv, dk)):
        raise ValueError(f"pool {pool.shape} does not hold K rows of "
                         f"{(hkv, dk)} and V rows of {(hkv, dv)} for {h} "
                         "query heads")
    if pages is None:
        pages = pool_pages_per_step(pool, nb)
    positions = positions.astype(jnp.int32)
    prefetched = [tables.astype(jnp.int32), positions,
                  jnp.reshape(layer, (1,)).astype(jnp.int32),
                  _row_walks(positions, starts, bs, nb, pages, window)]
    if own_keep is not None:
        prefetched.append(own_keep.astype(jnp.int32))
    if keep is not None and h == hkv:
        raise ValueError("a keep mask goes with grouped heads")
    if not latent:
        q, k_new = (_as_wide_as_the_pools_keys(x, pool, hkv)
                    for x in (q, k_new))
    held = q.shape[2]

    def row_map(row, *prefetched_refs):
        return (row, 0, 0)

    in_specs = [pl.BlockSpec((None, h, held), row_map),
                pl.BlockSpec((None, hkv, held), row_map),
                pl.BlockSpec((None, hkv, dv), row_map),
                pl.BlockSpec(memory_space=pl.ANY)]
    operands = [q, k_new, v_new, pool]
    if sink is not None:
        in_specs.append(pl.BlockSpec((h, 1), lambda row, *refs: (0, 0)))
        operands.append(sink.astype(jnp.float32).reshape(h, 1))
    if keep is not None:
        # A pass reads its pages' rows by their table columns, and its
        # last may reach past the row's last live page: `pages` rows of
        # nothing kept behind the table's.
        in_specs.append(pl.BlockSpec((None, nb + pages, bs), row_map))
        operands.append(jnp.pad(
            keep.reshape(b, nb, bs).astype(jnp.float32),
            ((0, 0), (0, pages), (0, 0))))
    return pl.pallas_call(
        functools.partial(_kernel_body, block_size=bs,
                          scale=latent_scale if latent else dk ** -0.5,
                          window=window, with_sink=sink is not None,
                          with_keep=keep is not None,
                          with_own_keep=own_keep is not None, latent=latent),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetched),
            grid=(b,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((None, h, dv), row_map),
            scratch_shapes=[pltpu.VMEM((2, pages) + page, pool.dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SMEM((2,), jnp.int32),
                            pltpu.VMEM((h, 1), jnp.float32),
                            pltpu.VMEM((h, 1), jnp.float32),
                            pltpu.VMEM((h, dv), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, h, dv), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name=name or ("paged_decode_attention" if window is None
                      else "paged_window_decode_attention"),
        interpret=interpret,
    )(*prefetched, *operands)


def paged_decode_attention(q, k_new, v_new, pool, tables, positions,
                           layer, window: int = None, starts=None,
                           sink=None, keep=None, own_keep=None):
    """One layer's decode attention through the block tables: the
    kernel where `kernel_eligible`, the XLA body elsewhere. With `keep`
    ``[B, nb * bs]`` and `own_keep` ``[B]`` (a layer that selects its
    keys) every live page is still walked, by the same kernel under the
    same name, and the positions not kept are masked."""
    body = (paged_decode_attention_kernel
            if kernel_eligible(*attention_widths(
                q.shape[1], q.shape[2], k_new.shape[1], v_new.shape[2]))
            else paged_decode_attention_xla)
    return body(q, k_new, v_new, pool, tables, positions, layer, window,
                starts, sink, keep, own_keep)


# -- attention over chosen rows alone ---------------------------------------
def chosen_slots(keep, tables, block_size: int, most: int):
    """keep ``[B, nb * bs]`` bool -> the pool's slots (``block * bs +
    offset``) of a row's kept positions, ``[B, most]`` int32 in the order
    of their positions, and how many there are, ``[B]`` (a row keeps at
    most `most`: one more is dropped)."""
    b, s = keep.shape
    rank = jnp.cumsum(keep, axis=-1, dtype=jnp.int32) - 1
    at = jnp.arange(s, dtype=jnp.int32)
    slot = (jnp.take_along_axis(tables, at[None] // block_size, axis=1)
            * block_size + at[None] % block_size)
    chosen = jnp.zeros((b, most), jnp.int32).at[
        jnp.arange(b)[:, None], jnp.where(keep, rank, most)].set(
        slot, mode="drop")
    return chosen, jnp.minimum(rank[:, -1] + 1, most)


def sparse_paged_decode_attention(q, k_new, v_new, pool, tables, positions,
                                  layer, keep, own_keep, most: int, *,
                                  interpret: bool = None):
    """`paged_decode_attention` with `keep`, by fetching the kept rows
    alone: the walk's kernel over the pool seen as pages of ONE position
    (``pool[block, slot, layer]`` is one piece of a position's bytes),
    through a table of the kept positions' slots (`chosen_slots`), under
    the name ``sparse_paged_decode_attention``. A row's copies are as
    many as it keeps (`most` at the most) and of a position's bytes
    each, where the walk's are a page's. Off the chip (`interpret` None)
    the XLA body over the same table. No model runs it: on the chip the
    copies' starts bound it (1.48 ms a layer at 16 rows whatever their
    length), and the walk under a mask is faster at every length a cell
    reaches (PERF.md, Findings, PR 57; ROADMAP R13 a names the traffic
    that would earn it a place). The tests hold it against the walk."""
    del positions
    bs = pool_block_size(pool)
    slots, count = chosen_slots(keep, tables, bs, most)
    if by_planes(pool):
        # A position's bytes lie a plane apart: the chosen rows are
        # gathered into a pool of their own, a page a row, and the table
        # names them in order.
        picked = pool[slots // bs, layer, :, slots % bs]   # [B, most, P, dv]
        rows = picked.reshape((-1, 1, 1, picked.shape[2] // k_new.shape[1],
                               k_new.shape[1], picked.shape[3]))
        slots = jnp.arange(slots.size, dtype=jnp.int32).reshape(slots.shape)
        layer = jnp.int32(0)
    else:
        rows = pool.reshape((pool.shape[0] * bs, 1) + pool.shape[2:])
    if interpret is None and not kernel_eligible(*attention_widths(
            q.shape[1], q.shape[2], k_new.shape[1], v_new.shape[2])):
        return paged_decode_attention_xla(
            q, k_new, v_new, rows, slots, count, layer, own_keep=own_keep)
    return paged_decode_attention_kernel(
        q, k_new, v_new, rows, slots, count, layer, own_keep=own_keep,
        pages=min(512, most), interpret=bool(interpret),
        name="sparse_paged_decode_attention")
