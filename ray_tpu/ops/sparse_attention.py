"""Attention that selects its keys: the indexer's scores and the exact
selection of the `topk` positions a query attends to.

A layer whose attention selects its keys has, beside its query, key and
value heads, a small *indexer*: ``J`` index query heads of ``di`` values
over ONE index key of ``di`` values a position, and a weight a head. The
score of key ``s`` for the query at ``t`` is

    I[t, s] = sum_j w[t, j] * relu(qi[t, j] . ki[s]),   s <= t,

and the query attends to the `topk` positions with the largest scores
(all of them while ``t < topk``) and to no other. The scores' ReLU,
weights and sum are float32; the products take both operands in the
index keys' dtype and accumulate in float32.

Three things are here:

- **The selection**, exact (`select_topk`): a position is kept iff its
  score is at least the row's `topk`-th largest. The threshold is found
  by a 32-step search over the scores' bit patterns (`sortable_bits`:
  a float32 as the unsigned integer that sorts as it does; step ``i``
  decides bit ``31 - i`` of the threshold by counting the keys at or
  above a candidate), which costs 32 passes of compare-and-count over
  the scores where a sort costs a pass for each of ``log2(n)^2 / 2``
  stages. Scores that tie with the threshold are all kept (a row may
  keep more than `topk` then); a row with fewer than `topk` valid
  positions keeps them all.
- **A decode step's scores** over the index pool's pages
  (`paged_index_scores`): the pool is ``[num_blocks, layers, block_size,
  di]`` (`serve/engine/kv_cache.py`, a pool that rides the global
  group's table: a layer's page of 16 positions in one piece, a key of
  64 values in a row of 128, `index_row_width`),
  read through a row's block table, ``[B, positions]`` float32 out. On
  the chip a Pallas kernel (one grid step a row: the row's live pages of
  one layer are copied into VMEM a group at a time, two slabs deep, one
  product ``[J, di] x [di, keys]`` a group, ReLU, weights and the sum
  over heads on the vector unit); `paged_index_scores_xla` is its
  reference and the CPU's path. What either returns at a position at or
  past the row's own is not a score: the caller masks it.
- **A prompt's or a chunk's scores** (`prefill_index_scores`): ``[Sq,
  Sk]`` float32 from ``[J, Sq, di]`` queries, ``[Sk, di]`` keys and ``[Sq,
  J]`` weights; on the chip a Pallas kernel a ``512 x 512`` tile (the
  heads' loop inside it: the ``[J, Sq, Sk]`` products never exist), in
  XLA a loop over the heads.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


# -- the selection ----------------------------------------------------------
def sortable_bits(x):
    """float32 -> uint32 that sorts as the floats do (-0.0 as +0.0);
    every number's key is above 0, which stands for "no score"."""
    x = jnp.where(x == 0, jnp.float32(0), x.astype(jnp.float32))
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(0x80000000))


def topk_threshold(keys, k: int):
    """keys ``[..., n]`` uint32 (`sortable_bits`; 0: no score) -> the
    `k`-th largest key of each row, ``[...]`` uint32; 0 for a row with
    fewer than `k` keys above 0."""
    u32 = jnp.uint32

    def decide(i, thr):
        bit = jax.lax.shift_left(u32(1), (31 - i).astype(u32))
        candidate = thr | bit
        at_least = jnp.sum(keys >= candidate[..., None], axis=-1,
                           dtype=jnp.int32)
        return jnp.where(at_least >= k, candidate, thr)

    return jax.lax.fori_loop(0, 32, decide,
                             jnp.zeros(keys.shape[:-1], u32))


def select_topk(scores, valid, k: int):
    """scores ``[..., n]`` float32, valid ``[..., n]`` bool -> keep
    ``[..., n]`` bool: the valid positions whose score is at least the
    `k`-th largest valid score of their row (every valid one where there
    are fewer than `k`)."""
    with jax.named_scope("index_select"):
        keys = jnp.where(valid, sortable_bits(scores), jnp.uint32(0))
        return valid & (keys >= topk_threshold(keys, k)[..., None])


# -- a decode step's scores -------------------------------------------------
def index_kernel_eligible(index_dim: int, block_size: int) -> bool:
    """The two kernels need the TPU backend, index keys of 64 values or a
    multiple of 128 (the pool's rows are whole lanes: `index_row_width`),
    and pages of whole sublane tiles."""
    return (jax.default_backend() == "tpu"
            and (index_dim == 64 or index_dim % 128 == 0)
            and block_size % 8 == 0)


def index_row_width(index_dim: int) -> int:
    """Values an index key takes in the pool: whole lanes of 128 (a key
    of 64 lies in the first half of its row, zeros behind it). The chip
    lays a minor dimension of 64 out in 128 lanes whatever the shape
    says, and a copy of half a lane row is none the kernel may make, so
    the pool says what it holds."""
    return -(-index_dim // 128) * 128


def _as_wide_as_the_pools_rows(x, pool):
    """x ``[..., di]`` filled up with zeros to the pool's row width."""
    fill = pool.shape[-1] - x.shape[-1]
    return x if not fill else jnp.pad(
        x, [(0, 0)] * (x.ndim - 1) + [(0, fill)])


def own_index_scores(qi, w, ki_new):
    """The step's own position's score: qi ``[B, J, di]``, w ``[B, J]``,
    ki_new ``[B, di]`` (as the pool will hold it) -> ``[B]`` float32."""
    s = jnp.einsum("bjd,bd->bj", qi.astype(ki_new.dtype), ki_new,
                   preferred_element_type=jnp.float32)
    return jnp.sum(w * jax.nn.relu(s), axis=-1)


def paged_index_scores_xla(qi, w, pool, tables, positions, layer):
    """qi ``[B, J, di]``, w ``[B, J]`` float32, pool ``[N, L, bs, row]``
    (`index_row_width`: a key's ``di`` values first in its row), tables
    ``[B, nb]`` int32, positions ``[B]`` (unused here: every table entry
    is read), layer a scalar -> ``[B, nb * bs]`` float32."""
    del positions
    b = qi.shape[0]
    keys = pool[tables, layer].reshape(b, -1, pool.shape[-1])
    qi = _as_wide_as_the_pools_rows(qi, pool)
    s = jnp.einsum("bjd,bsd->bjs", qi.astype(pool.dtype), keys,
                   preferred_element_type=jnp.float32)
    return jnp.einsum("bj,bjs->bs", w.astype(jnp.float32), jax.nn.relu(s))


# Pages of a row's index keys the kernel brings into VMEM together, and
# how many copies' starts the loop over them lays side by side.
_INDEX_PAGES = 64
_ISSUE_UNROLL = 8


def _index_scores_body(tables_ref, pages_ref, layer_ref, q_ref, w_ref,
                       pool_ref, o_ref, slabs, arrived, *, group: int):
    """One grid step is one row: its live pages of layer `layer` go into
    one of two VMEM slabs `group` at a time, the copy of a group running
    while the one before it is scored."""
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    i32 = jnp.int32
    row = pl.program_id(0)
    layer = layer_ref[0]
    n_pages = pages_ref[row]
    n_groups = lax.div(lax.add(n_pages, i32(group - 1)), i32(group))
    bs = slabs.shape[2]
    keys_a_group = group * bs
    unroll = math.gcd(group, _ISSUE_UNROLL)

    def pages_of(g):
        return lax.min(lax.sub(n_pages, lax.mul(g, i32(group))), i32(group))

    def fetch(g, slab):
        first = lax.mul(g, i32(group))
        count = pages_of(g)

        # A copy costs the scalar core about as much to start as the
        # chip takes to deliver it (45 ns a page of 4 KB: my chip run,
        # PR 57), so the loop that starts them is unrolled.
        def some(j, _):
            for u in range(unroll):
                i = lax.add(lax.mul(j, i32(unroll)), i32(u))

                @pl.when(lax.lt(i, count))
                def _start():
                    pltpu.make_async_copy(
                        pool_ref.at[tables_ref[row, lax.add(first, i)],
                                    layer],
                        slabs.at[slab, i], arrived.at[slab]).start()
        lax.fori_loop(i32(0), i32(group // unroll), some, None)

    @pl.when(lax.eq(row, i32(0)))
    def _first_row():
        # A group's last pages may be no copy's: what they hold is finite.
        slabs[...] = jnp.zeros(slabs.shape, slabs.dtype)

    @pl.when(lax.gt(n_groups, i32(0)))
    def _first_group():
        fetch(i32(0), i32(0))

    q = q_ref[...]                                          # [J, di]
    weight = w_ref[...]                                     # [J, 1]

    def score(g, slab):
        other = lax.sub(i32(1), slab)

        @pl.when(lax.lt(lax.add(g, i32(1)), n_groups))
        def _fetch_the_next():
            fetch(lax.add(g, i32(1)), other)

        count = pages_of(g)

        # A whole group is waited for at once (a wait counts bytes: one
        # for the slab's is `group` for a page's), a row's last, shorter
        # group a page at a time.
        @pl.when(lax.eq(count, i32(group)))
        def _a_whole_group():
            pltpu.make_async_copy(slabs.at[slab], slabs.at[slab],
                                  arrived.at[slab]).wait()

        @pl.when(lax.lt(count, i32(group)))
        def _the_rows_last_group():
            def arrive(i, _):
                pltpu.make_async_copy(pool_ref.at[0, layer],
                                      slabs.at[slab, 0],
                                      arrived.at[slab]).wait()
            lax.fori_loop(i32(0), count, arrive, None)
        keys = slabs[slab].reshape(keys_a_group, slabs.shape[3])
        s = lax.dot_general(q, keys, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        total = jnp.sum(weight * jnp.maximum(s, 0.0), axis=0, keepdims=True)
        at = pl.multiple_of(lax.mul(g, i32(keys_a_group)), keys_a_group)
        o_ref[:, pl.ds(at, keys_a_group)] = total
        return other

    lax.fori_loop(i32(0), n_groups, score, i32(0))


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_index_scores_kernel(qi, w, pool, tables, positions, layer, *,
                              interpret: bool = False):
    """Same arguments and result as `paged_index_scores_xla`; a row's
    pages past its last cached position (``[0, position)``) are neither
    fetched nor scored, and what the result holds there is whatever the
    buffer held."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    qi = _as_wide_as_the_pools_rows(qi, pool)
    b, j, di = qi.shape
    nb = tables.shape[1]
    bs = pool.shape[2]
    group = min(_INDEX_PAGES, nb)
    if nb % group:
        raise ValueError(f"a table of {nb} columns is not whole groups "
                         f"of {group} pages")
    pages = (positions.astype(jnp.int32) + bs - 1) // bs
    prefetched = [tables.astype(jnp.int32), jnp.minimum(pages, nb),
                  jnp.reshape(layer, (1,)).astype(jnp.int32)]

    def row_map(row, *refs):
        return (row, 0, 0)

    out = pl.pallas_call(
        functools.partial(_index_scores_body, group=group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetched),
            grid=(b,),
            in_specs=[pl.BlockSpec((None, j, di), row_map),
                      pl.BlockSpec((None, j, 1), row_map),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((None, 1, nb * bs), row_map),
            scratch_shapes=[pltpu.VMEM((2, group, bs, di), pool.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((b, 1, nb * bs), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="paged_index_scores",
        interpret=interpret,
    )(*prefetched, qi.astype(pool.dtype),
      w.astype(jnp.float32)[..., None], pool)
    return out[:, 0]


def paged_index_scores(qi, w, pool, tables, positions, layer):
    """One layer's index scores of a decode step through the block
    tables: the kernel where `index_kernel_eligible`, the XLA body
    elsewhere."""
    body = (paged_index_scores_kernel
            if index_kernel_eligible(qi.shape[-1], pool.shape[2])
            else paged_index_scores_xla)
    with jax.named_scope("index_scores"):
        return body(qi, w, pool, tables, positions, layer)


# -- a prompt's or a chunk's scores -----------------------------------------
def prefill_index_scores_xla(qi, w, ki, offset=0):
    """qi ``[J, Sq, di]``, w ``[Sq, J]`` float32, ki ``[Sk, di]`` ->
    ``[Sq, Sk]`` float32, a head at a time (`offset` is the kernel's)."""
    del offset
    qi = qi.astype(ki.dtype)

    def one_head(total, xs):
        q, weight = xs
        s = jnp.dot(q, ki.T, preferred_element_type=jnp.float32)
        return total + weight[:, None] * jax.nn.relu(s), None

    total, _ = jax.lax.scan(
        one_head, jnp.zeros((qi.shape[1], ki.shape[0]), jnp.float32),
        (qi, w.astype(jnp.float32).T))
    return total


_INDEX_TILE = 512


def _prefill_index_body(at_ref, q_ref, w_ref, k_ref, o_ref):
    from jax.experimental import pallas as pl

    heads, tile = q_ref.shape[0], o_ref.shape[0]
    qi, kj = pl.program_id(0), pl.program_id(1)

    # A tile wholly past the diagonal holds no score any query keeps.
    @pl.when(kj * tile <= at_ref[0] + qi * tile + tile - 1)
    def _score():
        keys = k_ref[...]
        total = jnp.zeros(o_ref.shape, jnp.float32)
        for j in range(heads):
            s = jax.lax.dot_general(q_ref[j], keys, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            total += w_ref[:, j:j + 1] * jnp.maximum(s, 0.0)
        o_ref[...] = total


@functools.partial(jax.jit, static_argnames=("interpret",))
def prefill_index_scores_kernel(qi, w, ki, offset=0, *,
                                interpret: bool = False):
    """`prefill_index_scores_xla` in tiles of 512 queries by 512 keys;
    query ``i`` lies on key ``offset + i``, and a tile every key of which
    lies past every query of it is not computed (what the result holds
    there is whatever the buffer held: no query keeps such a key)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    j, sq, di = qi.shape
    sk = ki.shape[0]
    tile = min(_INDEX_TILE, sq, sk)
    if sq % tile or sk % tile:
        raise ValueError(f"{sq} queries over {sk} keys are not whole tiles "
                         f"of {tile}")
    return pl.pallas_call(
        _prefill_index_body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(sq // tile, sk // tile),
            in_specs=[pl.BlockSpec((j, tile, di),
                                   lambda qi_, kj, at: (0, qi_, 0)),
                      pl.BlockSpec((tile, j), lambda qi_, kj, at: (qi_, 0)),
                      pl.BlockSpec((tile, di), lambda qi_, kj, at: (kj, 0))],
            out_specs=pl.BlockSpec((tile, tile),
                                   lambda qi_, kj, at: (qi_, kj))),
        out_shape=jax.ShapeDtypeStruct((sq, sk), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="prefill_index_scores",
        interpret=interpret,
    )(jnp.reshape(offset, (1,)).astype(jnp.int32), qi.astype(ki.dtype),
      w.astype(jnp.float32), ki)


def prefill_index_scores(qi, w, ki, offset=0):
    """A prompt's or a chunk's index scores ``[Sq, Sk]``: the kernel on
    the chip for lengths that tile, the loop over heads elsewhere."""
    sq, sk = qi.shape[1], ki.shape[0]
    tile = min(_INDEX_TILE, sq, sk)
    on_chip = (index_kernel_eligible(qi.shape[-1], 8) and tile % 128 == 0
               and sq % tile == 0 and sk % tile == 0)
    body = prefill_index_scores_kernel if on_chip else prefill_index_scores_xla
    with jax.named_scope("index_scores"):
        return body(qi, w, ki, offset)


def prefill_keep(scores, offset, live, k: int):
    """The selection of a prompt's or a chunk's queries: scores ``[Sq,
    Sk]`` (query ``i`` on key ``offset + i``), of the keys only the first
    `live` exist -> keep ``[Sq, Sk]`` bool, causal and selected."""
    sq, sk = scores.shape
    at_q = offset + jnp.arange(sq)[:, None]
    at_k = jnp.arange(sk)[None, :]
    return select_topk(scores, (at_k <= at_q) & (at_k < live), k)
