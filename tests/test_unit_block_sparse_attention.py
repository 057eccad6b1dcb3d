"""Attention that selects its key blocks from compressed keys of the
cache (`ops/block_sparse_attention.py`): the compressed keys, the
selection against a sort, the chosen pages' table, the decode walk a
key/value head at a time (the Pallas body, interpreted, against the XLA
body and against full attention), and the prefill's forward under a
block mask."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops import block_sparse_attention as bsa
from ray_tpu.ops.flash_attention import prefill_attention_fwd
from ray_tpu.ops.paged_attention import (held_by_planes, kernel_eligible,
                                         paged_decode_attention_xla,
                                         write_rows)

pytestmark = pytest.mark.unit

RULE = dict(block=16, stride=4, init_blocks=1, window=32, topk=2,
            dense_len=64)
KERNEL = 8


def _by_a_sort(r, t):
    """The selection as the equations say it, one query, by a sort."""
    per = RULE["block"] // RULE["stride"]
    blocks = r.shape[0] // per
    scores = [max(r[j] for j in range(per * b - 1, per * b + per)
                  if 0 <= j < r.shape[0]) for b in range(blocks)]
    last = t // RULE["block"]
    first_window = max(t - RULE["window"] + 1, 0) // RULE["block"]
    keep = np.zeros(blocks, bool)
    rest = []
    for b in range(last + 1):
        if b < RULE["init_blocks"] or b >= first_window \
                or t < RULE["dense_len"]:
            keep[b] = True
        else:
            rest.append(b)
    for b in sorted(rest, key=lambda b: -scores[b])[:RULE["topk"]]:
        keep[b] = True
    return keep


def test_compressed_keys_are_means_of_whole_kernels():
    rng = np.random.default_rng(0)
    k = rng.normal(size=(37, 2, 8)).astype(np.float32)
    sums = bsa.stride_sums(k, 4)
    assert sums.shape == (10, 2, 8)
    ck = bsa.compress(sums, KERNEL)
    for j in range(8):                      # whole kernels: 4 j + 7 <= 36
        np.testing.assert_allclose(ck[j], k[4 * j:4 * j + 8].mean(0),
                                   rtol=1e-5, atol=1e-6)
    assert int(bsa.valid_kernels(jnp.asarray(36), KERNEL, 4)) == 8
    # A context shorter than one kernel has none.
    assert [int(bsa.valid_kernels(jnp.asarray(t), KERNEL, 4))
            for t in (0, 6, 7, 10, 11)] == [0, 0, 1, 1, 2]


@pytest.mark.parametrize("t", [3, 20, 63, 64, 70, 100, 127])
def test_the_selection_is_the_sort(t):
    rng = np.random.default_rng(t)
    q = jnp.asarray(rng.normal(size=(1, 4, 8)), jnp.float32)
    ck = jnp.asarray(rng.normal(size=(2, 32, 8)), jnp.float32)
    at = jnp.asarray([t])
    r = bsa.compressed_scores(q, ck, bsa.valid_kernels(at, KERNEL, 4))
    keep = np.asarray(bsa.select_blocks(r, at, **RULE))
    for g in range(2):
        want = _by_a_sort(np.asarray(r[g, 0]), t)
        assert keep[g, 0].tolist() == want.tolist(), (t, g)
        # The first block and the window's are always in; below
        # `dense_len` every block that holds a position.
        assert keep[g, 0, 0] and keep[g, 0, t // 16]
        assert not keep[g, 0, t // 16 + 1:].any()
        if t < 64:
            assert keep[g, 0, :t // 16 + 1].all()
        else:
            first_window = (t - 31) // 16
            assert keep[g, 0].sum() == (1 + (t // 16 - first_window + 1)
                                        + min(2, first_window - 1))
    # The probabilities of a head sum to one over the kernels it sees.
    seen = int(bsa.valid_kernels(at, KERNEL, 4)[0])
    np.testing.assert_allclose(np.asarray(r).sum(-1), 2.0 if seen else 0.0,
                               rtol=1e-5)


def test_blocks_that_tie_give_way_to_those_above_them():
    # Neighbouring blocks share the kernel that straddles them: blocks 1
    # and 2 tie, block 3 is above both; two are kept: 3 and the first.
    scores = jnp.asarray([[9.0, 0.5, 0.5, 0.7, 0.1, 0.0]])
    valid = jnp.asarray([[False, True, True, True, True, False]])
    chosen = np.asarray(bsa.topk_first_of_ties(scores, valid, 2))
    assert chosen.tolist() == [[False, True, False, True, False, False]]
    # Fewer than k valid: all of them.
    assert np.asarray(bsa.topk_first_of_ties(scores, valid, 8)).tolist() \
        == valid.tolist()


def test_chosen_pages_come_in_order_with_the_last_page_last():
    keep = jnp.asarray([[[True, False, True, True],
                         [True, True, False, True]]])          # blocks of 32
    tables = jnp.asarray([[10, 11, 12, 13, 14, 15, 16, 17]])   # pages of 16
    pages, counts = bsa.chosen_pages(keep, tables, jnp.asarray([100]), 16, 6)
    # Position 100: cached 0..99, the last cached page is 6 (96..99).
    assert np.asarray(pages)[0, 0].tolist() == [10, 11, 14, 15, 16, 0]
    assert np.asarray(pages)[0, 1].tolist() == [10, 11, 12, 13, 16, 0]
    assert np.asarray(counts).tolist() == [[4 * 16 + 4, 4 * 16 + 4]]
    # A row on a page's edge has no cached position in its own page.
    _, counts = bsa.chosen_pages(keep, tables, jnp.asarray([96]), 16, 6)
    assert np.asarray(counts).tolist() == [[4 * 16, 4 * 16]]


def _pool_with(k, v, tables, n_blocks=12, layers=2, layer=1):
    """A head-major planes pool holding k, v ``[B, S, Hkv, hd]`` at
    `layer` through `tables`."""
    b, s, hkv, hd = k.shape
    pool = jnp.zeros((n_blocks, layers, hkv * 2, 16, hd), jnp.float32)
    for i in range(b):
        rows = jnp.zeros((s, layers, hkv, 2, hd)).at[:, layer].set(
            bsa.head_rows(k[i], v[i]))
        at = jnp.arange(s)
        pool = write_rows(pool, jnp.asarray(tables[i])[at // 16], at % 16,
                          rows)
    return pool


@pytest.mark.parametrize("positions", [[37, 64], [5, 48]])
def test_the_head_walk_is_the_xla_body_and_full_attention(positions):
    assert held_by_planes(2) and not kernel_eligible(32, 128, 2)  # no TPU
    rng = np.random.default_rng(1)
    b, h, hkv, hd, s = 2, 8, 2, 128, 64
    k, v = (jnp.asarray(rng.normal(size=(b, s, hkv, hd)), jnp.float32)
            for _ in range(2))
    q = jnp.asarray(rng.normal(size=(b, h, hd)), jnp.float32)
    k_new, v_new = (jnp.asarray(rng.normal(size=(b, hkv, hd)), jnp.float32)
                    for _ in range(2))
    tables = np.asarray([[3, 7, 1, 9], [2, 8, 4, 6]], np.int32)
    pool = _pool_with(k, v, tables)
    at = jnp.asarray(positions, jnp.int32)
    # Every block chosen: the walk over the whole table by `positions`.
    walked = bsa.head_walk_attention(q, k_new, v_new, pool,
                                     jnp.asarray(tables), at, jnp.int32(1))
    kernel = bsa.head_walk_attention(q, k_new, v_new, pool,
                                     jnp.asarray(tables), at, jnp.int32(1),
                                     interpret=True)
    for i in range(b):
        n = positions[i]
        keys = jnp.concatenate([k[i, :n], k_new[i][None]])
        vals = jnp.concatenate([v[i, :n], v_new[i][None]])
        for head in range(h):
            g = head // (h // hkv)
            p = jax.nn.softmax(keys[:, g] @ q[i, head] * hd ** -0.5)
            np.testing.assert_allclose(walked[i, head], p @ vals[:, g],
                                       rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(kernel, walked, rtol=2e-5, atol=2e-5)
    # A head's own chosen pages: blocks of 32, head 0 keeps the first and
    # last, head 1 every one.
    keep = jnp.asarray([[[True, False], [True, True]]] * b)
    keep = keep.at[:, 0, -1].set(True)
    pages, counts = bsa.chosen_pages(keep, jnp.asarray(tables), at, 16, 4)
    chosen = bsa.head_walk_attention(q, k_new, v_new, pool, pages, counts,
                                     jnp.int32(1))
    chosen_kernel = bsa.head_walk_attention(q, k_new, v_new, pool, pages,
                                            counts, jnp.int32(1),
                                            interpret=True)
    np.testing.assert_allclose(chosen_kernel, chosen, rtol=2e-5, atol=2e-5)
    # Head 1's heads saw every block: as the whole walk.
    np.testing.assert_allclose(chosen[:, h // 2:], walked[:, h // 2:],
                               rtol=2e-5, atol=2e-5)
    for i in range(b):
        n = positions[i]
        seen = np.asarray([j for j in range(n)
                           if bool(keep[i, 0, j // 32])])
        keys = jnp.concatenate([k[i, seen, 0], k_new[i, :1]])
        vals = jnp.concatenate([v[i, seen, 0], v_new[i, :1]])
        p = jax.nn.softmax(keys @ q[i, 0] * hd ** -0.5)
        np.testing.assert_allclose(chosen[i, 0], p @ vals, rtol=2e-5,
                                   atol=2e-5)


def test_the_paged_kernel_takes_two_heads_by_planes():
    """`kernel_eligible` takes 2 key/value heads: the generic walk over a
    slot-major planes pool, interpreted, is the XLA body."""
    rng = np.random.default_rng(2)
    b, h, hkv, hd, s = 2, 8, 2, 128, 48
    from ray_tpu.ops.paged_attention import (kv_row,
                                             paged_decode_attention_kernel)
    k, v = (jnp.asarray(rng.normal(size=(s, hkv, hd)), jnp.float32)
            for _ in range(2))
    pool = jnp.zeros((8, 1, 2 * hkv, 16, hd), jnp.float32)
    table = jnp.asarray([5, 2, 7])
    at = jnp.arange(s)
    pool = write_rows(pool, table[at // 16], at % 16,
                      kv_row(k, v)[:, None])
    q = jnp.asarray(rng.normal(size=(b, h, hd)), jnp.float32)
    k_new, v_new = (jnp.asarray(rng.normal(size=(b, hkv, hd)), jnp.float32)
                    for _ in range(2))
    args = (q, k_new, v_new, pool, jnp.stack([table, table]),
            jnp.asarray([48, 21]), jnp.int32(0))
    np.testing.assert_allclose(
        paged_decode_attention_kernel(*args, interpret=True),
        paged_decode_attention_xla(*args), rtol=2e-5, atol=2e-5)


def test_the_prefill_under_a_block_mask_is_the_masked_softmax():
    rng = np.random.default_rng(3)
    h, hkv, hd, sq, sk, offset, block = 4, 2, 16, 32, 64, 32, 16
    q = jnp.asarray(rng.normal(size=(h, sq, hd)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(hkv, sk, hd)), jnp.float32)
            for _ in range(2))
    keep = rng.random((hkv, sq, sk // block)) < 0.5
    at_q = offset + np.arange(sq)
    keep[:, np.arange(sq), at_q // block] = True       # a query's own block
    out = bsa.block_sparse_prefill_attention(
        q, k, v, jnp.asarray(keep), block=block, offset=offset)
    for head in range(h):
        g = head // (h // hkv)
        seen = (np.repeat(keep[g], block, axis=1)
                & (np.arange(sk)[None, :] <= at_q[:, None]))
        scores = np.where(seen, np.asarray(q[head] @ k[g].T) * hd ** -0.5,
                          -np.inf)
        p = np.asarray(jax.nn.softmax(jnp.asarray(scores), axis=-1))
        np.testing.assert_allclose(out[head], p @ np.asarray(v[g]),
                                   rtol=2e-5, atol=2e-5)


def test_a_tile_nobody_chose_adds_nothing_to_the_forward():
    """The Pallas forward, interpreted, under a mask by blocks that
    leaves whole tiles unchosen: a tile masked whole contributes nothing
    and leaves no NaN, a group with its own mask each, as the plain form
    computes it."""
    from ray_tpu.ops.attention import banded_attention

    rng = np.random.default_rng(4)
    h, hkv, hd, sq, sk, offset, tile = 4, 2, 128, 256, 512, 256, 128
    q = jnp.asarray(rng.normal(size=(h, sq, hd)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(hkv, sk, hd)), jnp.float32)
            for _ in range(2))
    blocks = np.ones((hkv, sq, sk // 64), bool)
    blocks[0, :, 2:4] = False           # group 0: key tile 1 is nobody's
    blocks[0, :128, 0:2] = False        # nor key tile 0 query tile 0's
    blocks[1, :, 0:4] = False           # group 1: the chunk's own keys alone
    for g in range(hkv):
        keep = jnp.repeat(jnp.asarray(blocks[g]), 64, axis=1)
        heads = slice(g * (h // hkv), (g + 1) * (h // hkv))
        got = prefill_attention_fwd(q[heads], k[g:g + 1], v[g:g + 1],
                                    offset=offset, live=sk, keep=keep,
                                    block=tile, interpret=True)
        assert np.isfinite(np.asarray(got)).all()
        np.testing.assert_allclose(
            got, banded_attention(q[heads], k[g:g + 1], v[g:g + 1],
                                  offset=offset, live=sk, keep=keep),
            rtol=2e-5, atol=2e-5)
