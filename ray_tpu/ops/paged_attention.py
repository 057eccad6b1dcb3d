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
                               layer):
    """q ``[B, H, hd]``; k_new, v_new ``[B, Hkv, hd]``; pool ``[N, bs,
    L, 2, Hkv, hd]``; tables ``[B, nb]`` int32; positions ``[B]`` int32;
    layer a scalar. ``H`` is a multiple of ``Hkv``. Returns ``[B, H,
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
    cached = jnp.arange(s_pad)[None, :] < positions[:, None]     # [B, S]
    scores = jnp.where(cached[:, None, None, :], scores, _NEG_INF)
    own = jnp.sum(q * k_new, axis=-1, keepdims=True) * scale
    probs = jax.nn.softmax(jnp.concatenate([scores, own], axis=-1),
                           axis=-1)
    out = (jnp.einsum("bkgs,bskd->bkgd", probs[..., :-1], kv[:, :, 1])
           + probs[..., -1:] * v_new)
    return out.reshape(b, h, hd)


def _kernel_body(tables_ref, positions_ref, layer_ref, q_ref, k_new_ref,
                 v_new_ref, page_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 block_size: int, scale: float):
    from jax.experimental import pallas as pl

    del tables_ref, layer_ref      # the index maps' business
    row, page = pl.program_id(0), pl.program_id(1)
    position = positions_ref[row]
    q = q_ref[...].astype(jnp.float32)                       # [H, hd]

    @pl.when(page == 0)
    def _start_from_own_token():
        own = jnp.sum(q * k_new_ref[...].astype(jnp.float32), axis=-1,
                      keepdims=True) * scale                 # [H, 1]
        m_ref[...] = own
        l_ref[...] = jnp.ones_like(l_ref)
        acc_ref[...] = v_new_ref[...].astype(jnp.float32)

    @pl.when(page * block_size < position)
    def _attend_page():
        keys = page_ref[:, 0].astype(jnp.float32)            # [bs, H, hd]
        vals = page_ref[:, 1].astype(jnp.float32)
        scores = jnp.sum(q[None] * keys, axis=-1,
                         keepdims=True) * scale              # [bs, H, 1]
        at = page * block_size + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 0)
        scores = jnp.where(at < position, scores, _NEG_INF)
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


def _grouped_kernel_body(tables_ref, positions_ref, layer_ref, q_ref,
                         k_new_ref, v_new_ref, page_ref, o_ref, m_ref,
                         l_ref, acc_ref, *, block_size: int, scale: float,
                         group: int):
    """`_kernel_body` for `group` query heads a key head: q, o and the
    scratch are ``[H, ...]``, the step's own K/V and the page's
    ``[Hkv, hd]``. A key head's ``[bs, hd]`` page meets its ``[group,
    hd]`` queries in a matrix product, and the probabilities its values
    in another."""
    from jax.experimental import pallas as pl

    del tables_ref, layer_ref
    row, page = pl.program_id(0), pl.program_id(1)
    position = positions_ref[row]
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

    @pl.when(page * block_size < position)
    def _attend_page():
        kv = page_ref[...].astype(f32)               # [bs, 2, Hkv, hd]
        scores = per_key_head(lambda j, rows: jax.lax.dot_general(
            q[rows], kv[:, 0, j], (((1,), (1,)), ((), ())),
            preferred_element_type=f32)) * scale             # [H, bs]
        at = page * block_size + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 1)
        scores = jnp.where(at < position, scores, _NEG_INF)
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


def paged_decode_attention_kernel(q, k_new, v_new, pool, tables,
                                  positions, layer, *,
                                  interpret: bool = False):
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
    body = functools.partial(_kernel_body, block_size=bs, scale=hd ** -0.5)
    if h != hkv:
        body = functools.partial(_grouped_kernel_body, block_size=bs,
                                 scale=hd ** -0.5, group=h // hkv)

    def row_map(row, page, tables_ref, positions_ref, layer_ref):
        return (row, 0, 0)

    def page_map(row, page, tables_ref, positions_ref, layer_ref):
        # Stay on the page of the row's last cached position (page 0
        # for a row with nothing cached): a block index the pipeline
        # already holds is not fetched again.
        last = (jnp.maximum(positions_ref[row], 1) - 1) // bs
        return (tables_ref[row * nb + jnp.minimum(page, last)], 0,
                layer_ref[0], 0, 0, 0)

    row_spec = pl.BlockSpec((None, h, hd), row_map)
    kv_row_spec = pl.BlockSpec((None, hkv, hd), row_map)
    return pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
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
        name="paged_decode_attention",
        interpret=interpret,
    )(tables.reshape(-1).astype(jnp.int32), positions.astype(jnp.int32),
      jnp.reshape(layer, (1,)).astype(jnp.int32), q, k_new, v_new, pool)


def paged_decode_attention(q, k_new, v_new, pool, tables, positions,
                           layer):
    """One layer's decode attention through the block tables: the
    kernel where `kernel_eligible`, the XLA body elsewhere."""
    if kernel_eligible(q.shape[1], q.shape[2], k_new.shape[1]):
        return paged_decode_attention_kernel(q, k_new, v_new, pool,
                                             tables, positions, layer)
    return paged_decode_attention_xla(q, k_new, v_new, pool, tables,
                                      positions, layer)

