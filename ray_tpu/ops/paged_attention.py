"""Decode attention over a paged KV pool, read where the pages lie.

One query token a row attends over that row's cached positions
``[0, position)``, which live in the serving engine's block pool
``[num_blocks, block_size, n_layers, 2, n_kv_heads, head_dim]``
(`serve/engine/kv_cache.py`) at the blocks its block table names, plus
the step's own key and value (position ``position``, not yet in the
pool). Nothing gathers the row's cache into a dense array first.

Heads may be grouped: ``n_heads`` query heads over ``n_kv_heads`` key
and value heads, query head ``i`` reading key head ``i // group``. The
pool's rows are then ``n_kv_heads`` wide, in float32 or bfloat16; the
softmax runs in float32 either way.

Two bodies, one result:

- `paged_decode_attention_kernel`: a Pallas TPU kernel (the pattern of
  `jax.experimental.pallas.ops.tpu.paged_attention`, for this pool's
  layout). Grid ``(row, page)``; block
  tables, positions and the layer index are scalar-prefetched, and the
  pool's `BlockSpec` index map picks block ``tables[row, page]`` at
  layer ``layer``: a ``[block_size, 2, n_heads, head_dim]`` slab,
  ``block_size`` contiguous runs of the pool. Online softmax in float32,
  started from the step's own key and value. With one key head a query
  head the scores are products and sums on the vector unit; with grouped
  heads a key head's page meets its group of query heads in two small
  matrix products. Pages past a row's last
  cached position are not fetched (the index map stays on the last live
  page, and the pipeline skips a block index it already holds) and not
  computed (`pl.when`).
- `paged_decode_attention_xla`: plain XLA, ``pool[tables, :, layer]``
  for one layer and a masked softmax. The kernel's reference in the
  tests, and what runs off the chip and for head sizes the kernel does
  not take.

A **window bound** (`window`, a layer of sliding-window attention): the
query at ``position`` sees the cached positions ``j`` with ``position -
j < window`` only (itself and the ``window - 1`` before it). Such a row's
table is *compact*: column 0 names logical block ``starts[row]`` of the
sequence, not block 0, because the cache manager has released the blocks
before it (`kv_cache.py`, a layer group with a window), so a table is
``ceil(window / block_size) + 1`` columns wide at any length. The page
loop then starts at the first page that holds a live key: a column whose
positions all lie left of the window or at or past ``position`` is
neither fetched nor computed, and the first and last live pages are
masked. The windowed kernel runs under a name of its own,
``paged_window_decode_attention``, so that a device trace tells a window
layer's calls from a global layer's. The model counts the live pages its
steps' tables named a group (`decode_kv_pages_read_global`,
`decode_kv_pages_read_window`).

`paged_decode_attention` picks by what it can see (`kernel_eligible`:
the backend, the head size and the head count), as
`attention._flash_eligible` picks flash.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import _NEG_INF


def kernel_eligible(n_heads: int, head_dim: int,
                    n_kv_heads: int = None) -> bool:
    """The kernel needs the TPU backend and a pool whose ``[n_kv_heads,
    head_dim]`` rows are whole ``(8, 128)`` tiles: heads that fill the
    lanes (heads of 64 and the unit tests' tiny models take the XLA
    body) and a multiple of 8 of them (`n_kv_heads` where heads are
    grouped, else `n_heads`). At 12 heads the chip keeps the pool in
    another layout (it tiles the K/V axis instead, so as not to pad 12
    to 16), and the compiler would hand the kernel a converted copy of
    the whole pool, a layer."""
    pool_heads = n_heads if n_kv_heads is None else n_kv_heads
    return (jax.default_backend() == "tpu" and head_dim % 128 == 0
            and pool_heads % 8 == 0 and n_heads % pool_heads == 0)


def paged_decode_attention_xla(q, k_new, v_new, pool, tables, positions,
                               layer, window: int = None, starts=None):
    """q ``[B, H, hd]``; k_new, v_new ``[B, Hkv, hd]``; pool ``[N, bs,
    L, 2, Hkv, hd]``; tables ``[B, nb]`` int32; positions ``[B]`` int32;
    layer a scalar; `window`, where the layer has one, with starts
    ``[B]`` int32, the logical block a row's table begins at (None: 0).
    ``H`` is a multiple of ``Hkv``. Returns ``[B, H,
    hd]`` float32. Pool positions at or past a row's
    `position` may hold anything (a reused block's stale rows, block 0
    behind a padded table entry): they are masked, never read into the
    result."""
    b, h, hd = q.shape
    hkv = pool.shape[4]
    s_pad = tables.shape[1] * pool.shape[1]
    kv = pool[tables, :, layer].reshape(b, s_pad, 2, hkv, hd)
    kv = kv.astype(jnp.float32)
    scale = hd ** -0.5
    # Query head i reads key head i // group: [B, Hkv, group, hd].
    q = q.astype(jnp.float32).reshape(b, hkv, h // hkv, hd)
    k_new = k_new.astype(jnp.float32)[:, :, None]
    v_new = v_new.astype(jnp.float32)[:, :, None]
    scores = jnp.einsum("bkgd,bskd->bkgs", q, kv[:, :, 0],
                        preferred_element_type=jnp.float32) * scale
    at = jnp.arange(s_pad)[None, :]                              # [B, S]
    if starts is not None:
        at = at + starts[:, None] * pool.shape[1]
    cached = at < positions[:, None]
    if window is not None:
        cached &= positions[:, None] - at < window
    scores = jnp.where(cached[:, None, None, :], scores, _NEG_INF)
    own = jnp.sum(q * k_new, axis=-1, keepdims=True) * scale
    probs = jax.nn.softmax(jnp.concatenate([scores, own], axis=-1),
                           axis=-1)
    out = (jnp.einsum("bkgs,bskd->bkgd", probs[..., :-1], kv[:, :, 1])
           + probs[..., -1:] * v_new)
    return out.reshape(b, h, hd)


def _page_positions(starts_ref, positions_ref, block_size: int, window):
    """Of this grid step: the row's position, the position of the page's
    first slot, and whether the page holds a key the row sees.
    `starts_ref` is None where every table begins at block 0."""
    from jax.experimental import pallas as pl

    row, page = pl.program_id(0), pl.program_id(1)
    position = positions_ref[row]
    first = page * block_size
    if starts_ref is not None:
        first += starts_ref[row] * block_size
    live = first < position
    if window is not None:
        live &= first + block_size > position - window + 1
    return position, first, live


def _seen(at, position, window):
    keep = at < position
    if window is not None:
        keep &= position - at < window
    return keep


def _kernel_body(tables_ref, positions_ref, layer_ref, starts_ref, q_ref,
                 k_new_ref, v_new_ref, page_ref, o_ref, m_ref, l_ref,
                 acc_ref, *, block_size: int, scale: float, window=None):
    from jax.experimental import pallas as pl

    del tables_ref, layer_ref      # the index maps' business
    page = pl.program_id(1)
    position, first, live = _page_positions(starts_ref, positions_ref,
                                            block_size, window)
    q = q_ref[...].astype(jnp.float32)                       # [H, hd]

    @pl.when(page == 0)
    def _start_from_own_token():
        own = jnp.sum(q * k_new_ref[...].astype(jnp.float32), axis=-1,
                      keepdims=True) * scale                 # [H, 1]
        m_ref[...] = own
        l_ref[...] = jnp.ones_like(l_ref)
        acc_ref[...] = v_new_ref[...].astype(jnp.float32)

    @pl.when(live)
    def _attend_page():
        keys = page_ref[:, 0].astype(jnp.float32)            # [bs, H, hd]
        vals = page_ref[:, 1].astype(jnp.float32)
        scores = jnp.sum(q[None] * keys, axis=-1,
                         keepdims=True) * scale              # [bs, H, 1]
        at = first + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
        scores = jnp.where(_seen(at, position, window), scores, _NEG_INF)
        m_prev = m_ref[...]
        m_next = jnp.maximum(m_prev, jnp.max(scores, axis=0))
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(scores - m_next[None])                   # [bs, H, 1]
        m_ref[...] = m_next
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=0)
        acc_ref[...] = alpha * acc_ref[...] + jnp.sum(p * vals, axis=0)

    @pl.when(page == pl.num_programs(1) - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def _grouped_kernel_body(tables_ref, positions_ref, layer_ref, starts_ref,
                         q_ref, k_new_ref, v_new_ref, page_ref, o_ref,
                         m_ref, l_ref, acc_ref, *, block_size: int,
                         scale: float, group: int, window=None):
    """`_kernel_body` for `group` query heads a key head: q, o and the
    scratch are ``[H, ...]``, the step's own K/V and the page's
    ``[Hkv, hd]``. A key head's ``[bs, hd]`` page meets its ``[group,
    hd]`` queries in a matrix product, and the probabilities its values
    in another."""
    from jax.experimental import pallas as pl

    del tables_ref, layer_ref
    page = pl.program_id(1)
    position, first, live = _page_positions(starts_ref, positions_ref,
                                            block_size, window)
    f32 = jnp.float32
    q = q_ref[...].astype(f32)                               # [H, hd]
    n_kv = k_new_ref.shape[0]

    def per_key_head(fn):
        """``fn(j, rows j*group .. (j+1)*group of q)`` for every key
        head, stacked back to ``[H, ...]``."""
        return jnp.concatenate(
            [fn(j, slice(j * group, (j + 1) * group))
             for j in range(n_kv)], axis=0)

    @pl.when(page == 0)
    def _start_from_own_token():
        k_own = k_new_ref[...].astype(f32)                   # [Hkv, hd]
        v_own = v_new_ref[...].astype(f32)
        m_ref[...] = per_key_head(lambda j, rows: jnp.sum(
            q[rows] * k_own[j][None], axis=-1, keepdims=True)) * scale
        l_ref[...] = jnp.ones_like(l_ref)
        acc_ref[...] = per_key_head(lambda j, rows: jnp.broadcast_to(
            v_own[j][None], (group, v_own.shape[-1])))

    @pl.when(live)
    def _attend_page():
        kv = page_ref[...].astype(f32)               # [bs, 2, Hkv, hd]
        scores = per_key_head(lambda j, rows: jax.lax.dot_general(
            q[rows], kv[:, 0, j], (((1,), (1,)), ((), ())),
            preferred_element_type=f32)) * scale             # [H, bs]
        at = first + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        scores = jnp.where(_seen(at, position, window), scores, _NEG_INF)
        m_prev = m_ref[...]
        m_next = jnp.maximum(m_prev, jnp.max(scores, axis=1,
                                             keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(scores - m_next)                         # [H, bs]
        m_ref[...] = m_next
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + per_key_head(
            lambda j, rows: jnp.dot(p[rows], kv[:, 1, j],
                                    preferred_element_type=f32))

    @pl.when(page == pl.num_programs(1) - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def _without_starts(body, tables_ref, positions_ref, layer_ref, *refs):
    """A kernel body for three prefetched scalars: no `starts_ref`."""
    return body(tables_ref, positions_ref, layer_ref, None, *refs)


def paged_decode_attention_kernel(q, k_new, v_new, pool, tables,
                                  positions, layer, window: int = None,
                                  starts=None, *, interpret: bool = False):
    """Same arguments and result as `paged_decode_attention_xla`. The
    pool is an operand of the kernel as it stands in HBM; a grid step
    brings one page of one layer into VMEM (double-buffered by the
    pipeline)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, hd = q.shape
    nb = tables.shape[1]
    bs = pool.shape[1]
    hkv = k_new.shape[1]
    if pool.shape[3:] != (2, hkv, hd) or h % hkv:
        raise ValueError(f"pool {pool.shape} does not hold K and V rows "
                         f"of {(hkv, hd)} for {h} query heads")
    body = functools.partial(_kernel_body, block_size=bs, scale=hd ** -0.5,
                             window=window)
    if h != hkv:
        body = functools.partial(_grouped_kernel_body, block_size=bs,
                                 scale=hd ** -0.5, group=h // hkv,
                                 window=window)
    prefetched = [tables.reshape(-1).astype(jnp.int32),
                  positions.astype(jnp.int32),
                  jnp.reshape(layer, (1,)).astype(jnp.int32)]
    # A table that begins at block 0 under no window (every layer of a
    # model without a window group) is the kernel it was before there
    # were windows, to the scalar: the grid's steps bound this kernel,
    # and the compact table's index arithmetic, done for every table,
    # cost `decode-heavy` 0.15 ms a step (my chip run, PR 35).
    compact = starts is not None or window is not None
    if compact:
        prefetched.append((jnp.zeros((b,), jnp.int32) if starts is None
                           else starts).astype(jnp.int32))
    else:
        body = functools.partial(_without_starts, body)

    def row_map(row, page, *prefetched_refs):
        return (row, 0, 0)

    def page_map(row, page, tables_ref, positions_ref, layer_ref,
                 *starts_ref):
        # Stay on the page of the row's last cached position (column 0
        # for a row with nothing cached), and with a window on or after
        # the first page that holds a key the row sees: a block index
        # the pipeline already holds is not fetched again.
        position = positions_ref[row]
        last = (jnp.maximum(position, 1) - 1) // bs
        column = jnp.minimum(page, last)
        if compact:
            start = starts_ref[0][row]
            column = jnp.minimum(page, last - start)
            if window is not None:
                oldest = jnp.maximum(position - window + 1, 0) // bs - start
                column = jnp.maximum(column, oldest)
            column = jnp.clip(column, 0, nb - 1)
        return (tables_ref[row * nb + column], 0, layer_ref[0], 0, 0, 0)

    row_spec = pl.BlockSpec((None, h, hd), row_map)
    kv_row_spec = pl.BlockSpec((None, hkv, hd), row_map)
    return pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetched),
            grid=(b, nb),
            in_specs=[row_spec, kv_row_spec, kv_row_spec,
                      pl.BlockSpec((None, bs, None, 2, hkv, hd), page_map)],
            out_specs=row_spec,
            scratch_shapes=[pltpu.VMEM((h, 1), jnp.float32),
                            pltpu.VMEM((h, 1), jnp.float32),
                            pltpu.VMEM((h, hd), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, h, hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name=("paged_decode_attention" if window is None
              else "paged_window_decode_attention"),
        interpret=interpret,
    )(*prefetched, q, k_new, v_new, pool)


def paged_decode_attention(q, k_new, v_new, pool, tables, positions,
                           layer, window: int = None, starts=None):
    """One layer's decode attention through the block tables: the
    kernel where `kernel_eligible`, the XLA body elsewhere."""
    body = (paged_decode_attention_kernel
            if kernel_eligible(q.shape[1], q.shape[2], k_new.shape[1])
            else paged_decode_attention_xla)
    return body(q, k_new, v_new, pool, tables, positions, layer, window,
                starts)

