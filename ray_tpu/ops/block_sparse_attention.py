"""Attention that selects its key BLOCKS from compressed keys of the
cache itself: no indexer, no second projection.

A key/value head ``g`` keeps, beside its keys, their *compressed keys*:
the mean of `kernel` consecutive keys every `stride` positions,

    c[g, j] = mean(k[g, stride j .. stride j + kernel - 1]),

whole kernels only (``stride j + kernel - 1 <= t`` for a query at ``t``).
With ``kernel = 2 stride`` a compressed key is two *stride sums* (the
sum of the keys of one stride of positions) added and divided: a sum is
made once, where its stride of positions is complete, and a compressed
key straddles two of them (`stride_sums`, `compress`).

A query head scores them, ``p[h, j] = softmax_j(q_h . c[g, j] /
sqrt(hd))``; a group's heads are summed, ``r[g, j] = sum_h p[h, j]``
(`compressed_scores`); a *block* of `block` positions takes the largest
score of the kernels that overlap it (`block_scores`: a max-pool of
``block / stride + 1`` kernels every ``block / stride``, one kernel of
padding in front); and the query at ``t`` attends, a group, to

- the first `init_blocks` blocks,
- the blocks that hold a position of ``t - window + 1 .. t``,
- the `topk` blocks of the rest with the largest scores, exact
  (`sparse_attention.topk_threshold`'s search; of blocks that tie on the
  threshold the first in order, so never more than `topk`),

and while ``t < dense_len`` to every block (`select_blocks`). The
softmax, the sum over heads and the pooling are float32; the scores'
products take both operands in the compressed keys' dtype and accumulate
in float32.

**A decode step reads the chosen pages alone.** The selection is a
group's own, so the pool is held by planes *head-major*: ``[N, L, Hkv *
2, bs, hd]`` with plane ``2 g`` the keys and ``2 g + 1`` the values of
head ``g``, which is ``[N, L * Hkv, 2, bs, hd]`` without moving
anything: one key/value head a "layer", its page ``[K, V]`` in one piece.
A row's chosen blocks become a compact table of their pages
(`chosen_pages`: a block of 64 is four pages of 16) in the order of
their positions, the page that holds the row's last cached position last,
and the paged walk of `ops/paged_attention.py` runs over that table a
head at a time, 16 query heads against one key/value head, under the
name ``block_sparse_paged_decode_attention`` (`head_walk_attention`).
The walk masks by count alone (the keys of a full page all, of the last
page the first few), which is all a layer without a position signal
needs.

**A prompt's or a chunk's forward** runs the flash forward of
`ops/flash_attention.py` a group at a time under the group's mask
(`block_sparse_prefill_attention`). The forward masks and skips
nothing: 512 queries that each keep 64 of some 600 blocks leave no tile
of 512 x 512 unchosen (ROADMAP R13 b).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu.ops.sparse_attention import sortable_bits, topk_threshold

DECODE_KERNEL_NAME = "block_sparse_paged_decode_attention"


# -- compressed keys --------------------------------------------------------
def stride_sums(k, stride: int):
    """k ``[S, Hkv, hd]`` -> the sums of every `stride` positions' keys,
    ``[ceil(S / stride), Hkv, hd]`` float32 (a last, shorter stride is
    the sum of what it has)."""
    s = k.shape[0]
    k = jnp.pad(k.astype(jnp.float32), ((0, -s % stride), (0, 0), (0, 0)))
    return jnp.sum(k.reshape((-1, stride) + k.shape[1:]), axis=1)


def compress(sums, kernel: int):
    """Stride sums ``[n + 1, ...]`` -> the ``n`` compressed keys that
    straddle two neighbours, ``(sums[i] + sums[i + 1]) / kernel``."""
    return (sums[:-1] + sums[1:]) * (1.0 / kernel)


def valid_kernels(positions, kernel: int, stride: int):
    """How many compressed keys are whole for a query at `positions`:
    those ``j`` with ``stride j + kernel - 1 <= t``."""
    return jnp.maximum((positions - (kernel - 1)) // stride + 1, 0)


def compressed_scores(q, ck, n_valid):
    """q ``[T, H, hd]``, ck ``[Hkv, J, hd]``, n_valid ``[T]`` (of the
    ``J`` compressed keys a query sees the first ``n_valid``) -> ``r
    [Hkv, T, J]`` float32: the softmax of a head's scores over the keys it
    sees, summed over the group's heads; 0 at a key it does not see. A
    head at a time: the ``[H, T, J]`` probabilities never exist."""
    f32 = jnp.float32
    t, h, hd = q.shape
    hkv, j, _ = ck.shape
    heads = q.reshape(t, hkv, h // hkv, hd).transpose(2, 1, 0, 3)
    seen = jnp.arange(j)[None, :] < n_valid[:, None]           # [T, J]

    def one_head(total, qh):                                # [Hkv, T, hd]
        s = jnp.einsum("gtd,gjd->gtj", qh.astype(ck.dtype), ck,
                       preferred_element_type=f32) * hd ** -0.5
        top = jnp.max(jnp.where(seen, s, -jnp.inf), axis=-1, keepdims=True)
        e = jnp.where(seen, jnp.exp(s - jnp.where(seen, top, 0.0)), 0.0)
        return total + e / jnp.maximum(
            jnp.sum(e, axis=-1, keepdims=True), 1e-30), None

    total, _ = jax.lax.scan(one_head, jnp.zeros((hkv, t, j), f32), heads)
    return total


def block_scores(r, kernels_a_block: int):
    """r ``[..., J]`` (0: no score) -> ``[..., J / kernels_a_block]``:
    block ``b`` takes the largest of kernels ``n b - 1 .. n b + n - 1``
    (``n`` kernels begin inside it, and the one before overlaps its
    head)."""
    n = kernels_a_block
    blocks = r.shape[-1] // n
    padded = jnp.pad(r, [(0, 0)] * (r.ndim - 1) + [(1, 0)])
    inside = jnp.max(padded[..., :blocks * n].reshape(
        r.shape[:-1] + (blocks, n)), axis=-1)
    return jnp.maximum(inside, padded[..., n::n])


def topk_first_of_ties(scores, valid, k: int):
    """`sparse_attention.select_topk`, but never more than `k`: the
    scores above the `k`-th largest all, and of those that tie with it
    the first in order, as many as are left (neighbouring blocks share
    the kernel that straddles them, so two blocks with one score are
    common). What a stable sort by falling score keeps."""
    with jax.named_scope("index_select"):
        keys = jnp.where(valid, sortable_bits(scores), jnp.uint32(0))
        threshold = topk_threshold(keys, k)[..., None]
        above = keys > threshold
        ties = valid & (keys == threshold)
        room = k - jnp.sum(above, axis=-1, keepdims=True, dtype=jnp.int32)
        return above | (ties & (jnp.cumsum(ties, axis=-1,
                                           dtype=jnp.int32) <= room))


def select_blocks(r, positions, *, block: int, stride: int,
                  init_blocks: int, window: int, topk: int,
                  dense_len: int):
    """r ``[Hkv, T, J]`` (`compressed_scores`), positions ``[T]`` (the
    queries') -> keep ``[Hkv, T, J stride / block]`` bool: the blocks a
    query attends to (module docstring)."""
    scores = block_scores(r, block // stride)
    at = jnp.arange(scores.shape[-1])[None, :]
    t = positions[:, None]
    exists = at <= t // block
    forced = (at < init_blocks) | (
        at >= jnp.maximum(t - window + 1, 0) // block)
    chosen = topk_first_of_ties(scores, (exists & ~forced)[None], topk)
    return exists[None] & (forced[None] | chosen | (t < dense_len)[None])


# -- a decode step over the chosen pages ------------------------------------
def chosen_pages(keep, tables, positions, block_size: int, most: int):
    """keep ``[B, Hkv, nblk]`` bool (the blocks a row's group attends
    to), tables ``[B, nb]`` int32, positions ``[B]`` (a row's cached
    positions are ``[0, position)``) -> the pool's pages of the chosen
    blocks that hold a cached position, ``[B, Hkv, most]`` int32 in the
    order of their positions, and the *count* the walk masks by, ``[B,
    Hkv]``: ``bs`` a page but the last, which holds the row's last cached
    position and counts what it has of them. A row keeps at most `most`
    pages (one more is dropped: `most` is what the selection can reach)."""
    b, hkv, nblk = keep.shape
    nb = tables.shape[1]
    pages = jnp.repeat(keep, nb // nblk, axis=-1)
    pages &= (jnp.arange(nb) * block_size)[None, None, :] \
        < positions[:, None, None]
    rank = jnp.cumsum(pages, axis=-1, dtype=jnp.int32) - 1
    chosen = jnp.zeros((b, hkv, most), jnp.int32).at[
        jnp.arange(b)[:, None, None], jnp.arange(hkv)[None, :, None],
        jnp.where(pages, rank, most)].set(
        jnp.broadcast_to(tables[:, None, :], pages.shape), mode="drop")
    count = jnp.minimum(rank[..., -1] + 1, most)
    in_last = positions - (positions - 1) // block_size * block_size
    return chosen, jnp.where(count > 0, (count - 1) * block_size
                             + in_last[:, None], 0)


def heads_apart(pool):
    """A pool held by planes head-major, ``[N, L, Hkv * 2, bs, hd]``, as
    ``[N, L * Hkv, 2, bs, hd]``: a key/value head's ``[K, V]`` page a
    "layer" of its own. Moves nothing."""
    n, layers, planes, bs, hd = pool.shape
    return pool.reshape(n, layers * planes // 2, 2, bs, hd)


def head_rows(k, v):
    """Keys and values ``[T, Hkv, hd]`` -> a position's row in a pool
    held head-major, ``[T, Hkv, 2, hd]``."""
    return jnp.stack([k, v], axis=2)


def heads_of_head_major_pages(pages):
    """Pages ``[nb, Hkv * 2, bs, hd]`` of one layer -> keys and values
    ``[Hkv, nb * bs, hd]``."""
    nb, planes, bs, hd = pages.shape
    kv = pages.reshape(nb, planes // 2, 2, bs, hd).transpose(2, 1, 0, 3, 4)
    kv = kv.reshape(2, planes // 2, nb * bs, hd)
    return kv[0], kv[1]


def head_walk_attention(q, k_new, v_new, pool, tables, counts, layer, *,
                        interpret: bool = None):
    """One layer's decode attention a key/value head at a time: q ``[B,
    H, hd]``, k_new and v_new ``[B, Hkv, hd]`` (the step's own), pool
    ``[N, L, Hkv * 2, bs, hd]`` head-major, tables ``[B, Hkv, nb]`` (a
    head's pages in order, `chosen_pages`; ``[B, nb]``: one table for
    every head) and counts ``[B, Hkv]`` or ``[B]`` (the positions the
    tables' pages hold: every page full but the last), layer a scalar.
    ``[B, H, hd]`` float32. The paged walk's kernel where
    `kernel_eligible` (`interpret` None: by the backend), its XLA body
    elsewhere."""
    from ray_tpu.ops.paged_attention import (kernel_eligible,
                                             paged_decode_attention_kernel,
                                             paged_decode_attention_xla)

    b, h, hd = q.shape
    hkv = k_new.shape[1]
    group = h // hkv
    apart = heads_apart(pool)
    on_chip = interpret is not None or kernel_eligible(h, hd, hkv)
    out = []
    for g in range(hkv):
        args = (q[:, g * group:(g + 1) * group], k_new[:, g:g + 1],
                v_new[:, g:g + 1], apart,
                tables if tables.ndim == 2 else tables[:, g],
                counts if counts.ndim == 1 else counts[:, g],
                layer * hkv + g)
        out.append(paged_decode_attention_kernel(
            *args, interpret=bool(interpret), name=DECODE_KERNEL_NAME)
            if on_chip else paged_decode_attention_xla(*args))
    return jnp.concatenate(out, axis=1)


# -- a prompt's or a chunk's forward ----------------------------------------
def block_sparse_prefill_attention(q, k, v, keep, *, block: int, offset=0):
    """q ``[H, Sq, hd]`` over k, v ``[Hkv, Sk, hd]`` (query ``i`` on key
    ``offset + i``, every key up to the last query's existing) under
    keep ``[Hkv, Sq, Sk / block]`` bool (a group's queries' blocks): the
    causal forward a group at a time under the group's mask (in a trace
    ``flash_prefill_fwd_selected``). Returns ``[H, Sq, hd]`` float32."""
    from ray_tpu.ops.attention import prefill_attention

    h, sq, _ = q.shape
    hkv, sk, _ = k.shape
    group = h // hkv
    return jnp.concatenate([
        prefill_attention(
            q[g * group:(g + 1) * group], k[g:g + 1], v[g:g + 1],
            offset=offset, live=offset + sq,
            keep=jnp.repeat(keep[g], block, axis=1)[:, :sk])
        for g in range(hkv)], axis=0)
