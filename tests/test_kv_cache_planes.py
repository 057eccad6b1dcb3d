"""A group's own pool held by planes (PR 58): ``[blocks, layers, planes,
block_size, values]`` where the pool of rows is ``[blocks, block_size,
layers, ..., values]``. Every way the manager writes, copies and reads
such a pool gives, position for position, what the same calls give on a
pool of rows: the layout is storage, not accounting."""

import numpy as np
import pytest

import jax.numpy as jnp

from ray_tpu.serve.engine import KVCacheManager
from ray_tpu.serve.engine.model import PromptKV

pytestmark = pytest.mark.unit

BS, BLOCKS, ROW = 4, 12, (3, 2, 4, 8)       # (layers, slots, heads, values)


def _pair(**kw):
    """The same cache twice: its pool in rows, and held by planes."""
    return (KVCacheManager(BLOCKS, BS, ROW, array_ns=jnp, **kw),
            KVCacheManager(BLOCKS, BS, ROW, array_ns=jnp, planes=True, **kw))


def _rows(seed, n):
    return np.random.default_rng(seed).standard_normal(
        (n,) + ROW).astype(np.float32)


def _same(by_rows, by_planes, seq, n):
    a, b = by_rows.gather(seq, n), by_planes.gather(seq, n)
    assert a.shape == b.shape == (n,) + ROW
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    return np.asarray(b)


def test_the_pool_is_held_a_block_a_layer_and_a_plane_at_a_time():
    by_rows, by_planes = _pair()
    shapes = [m.with_pool(lambda pool: pool.shape)
              for m in (by_rows, by_planes)]
    assert shapes == [(BLOCKS, BS) + ROW, (BLOCKS, 3, 2 * 4, BS, 8)]
    assert by_rows.pool_bytes == by_planes.pool_bytes == \
        BLOCKS * BS * 3 * 2 * 4 * 8 * 4
    assert by_planes.planes and not by_rows.planes
    assert by_planes.kv_shape == by_rows.kv_shape == ROW


# A prefill's payload on the device with its bucket's padding, and on the
# host: whole blocks, a ragged tail, fewer rows than a block, a bucket
# narrower than a block.
@pytest.mark.parametrize("n, padded, device", [
    (8, 8, True), (16, 16, True), (7, 8, True), (13, 16, True),
    (3, 4, True), (2, 2, True), (13, 16, False), (8, 8, False)],
    ids=["two_blocks", "four_blocks", "ragged_tail", "blocks_and_a_tail",
         "less_than_a_block", "a_bucket_under_a_block", "host_ragged",
         "host_blocks"])
def test_write_range_stores_what_a_pool_of_rows_stores(n, padded, device):
    rows = _rows(n, padded)
    for cache in (pair := _pair()):
        assert cache.allocate("a", n, writable_from=0)
        cache.write_range("a", 0, PromptKV(jnp.asarray(rows), n) if device
                          else rows[:n])
        assert cache.seq_len("a") == n
        assert (cache.range_writes_device, cache.range_writes_host) == \
            ((1, 0) if device else (0, 1))
    got = _same(*pair, "a", n)
    np.testing.assert_array_equal(got, rows[:n])
    # The rows past the prompt's, the bucket's padding, were dropped:
    # the block after the prompt's last is as it was.
    by_planes = pair[1]
    table = by_planes.block_table("a")
    pool = np.asarray(by_planes.with_pool(lambda pool: pool))
    untouched = [b for b in range(BLOCKS) if b not in table]
    assert not pool[untouched].any()
    if n % BS:
        assert not pool[table[-1]][:, :, n % BS:].any()


def test_a_chunk_after_a_chunk_and_a_range_off_the_blocks_edge():
    """A second block-aligned range lands behind the first (a chunked
    prefill), and a range that begins inside a block goes by slots."""
    pair = _pair()
    first, second, third = _rows(1, 8), _rows(2, 8), _rows(3, 4)
    for cache in pair:
        assert cache.allocate("a", 8, writable_from=0)
        cache.write_range("a", 0, PromptKV(jnp.asarray(first), 8))
        assert cache.allocate("a", 14, writable_from=8)
        cache.write_range("a", 8, PromptKV(jnp.asarray(second), 6))
        assert cache.allocate("a", 17, writable_from=14)
        cache.write_range("a", 14, PromptKV(jnp.asarray(third), 3))
    got = _same(*pair, "a", 17)
    np.testing.assert_array_equal(
        got, np.concatenate([first, second[:6], third[:3]]))


def test_write_and_copy_on_write_leave_the_other_holder_its_block():
    pair = _pair()
    rows, late = _rows(4, 8), _rows(5, 1)[0]
    for cache in pair:
        assert cache.allocate("a", 8, writable_from=0)
        cache.write_range("a", 0, PromptKV(jnp.asarray(rows), 8))
        cache.adopt("b", cache.block_table("a"), 8)
        # `b` writes position 5: block 1 is shared, so it gets its own.
        assert cache.allocate("b", 8, writable_from=5)
        cache.write("b", 5, late)
        assert cache.cow_copies == 1
        assert cache.block_table("a")[0] == cache.block_table("b")[0]
        assert cache.block_table("a")[1] != cache.block_table("b")[1]
    np.testing.assert_array_equal(_same(*pair, "a", 8), rows)
    changed = rows.copy()
    changed[5] = late
    np.testing.assert_array_equal(_same(*pair, "b", 8), changed)


def test_a_paged_step_hands_the_model_the_pool_as_it_is_held():
    """`paged_step` resolves the write slots; the step writes a row a
    sequence through `ops.paged_attention.write_rows`, and a padding
    row's block lies past the pool."""
    from ray_tpu.ops.paged_attention import by_planes, write_rows

    pair = _pair()
    rows = _rows(6, 3)
    for cache in pair:
        for seq in ("a", "b"):
            assert cache.allocate(seq, 1, writable_from=0)

        def step(pool, blocks, offs):
            assert by_planes(pool) == cache.planes
            blocks = jnp.asarray(list(blocks) + [BLOCKS], jnp.int32)
            offs = jnp.asarray(list(offs) + [0], jnp.int32)
            return None, write_rows(pool, blocks, offs, jnp.asarray(rows))

        cache.paged_step([("a", 0), ("b", 0)], step)
    np.testing.assert_array_equal(_same(*pair, "a", 1), rows[:1])
    np.testing.assert_array_equal(_same(*pair, "b", 1), rows[1:2])
    assert not pair[1].gather("nobody").shape[0]


def test_a_further_group_may_be_held_by_planes_too():
    cache = KVCacheManager(BLOCKS, BS, ROW, array_ns=jnp, groups={
        "window": {"num_blocks": 8, "kv_shape": (2, 2, 4, 8), "window": 6,
                   "planes": True}})
    window = cache.group("window")
    assert window.planes and not cache.planes
    shapes = cache.with_pools(lambda pools: {k: v.shape
                                             for k, v in pools.items()})
    assert shapes == {"global": (BLOCKS, BS) + ROW,
                      "window": (8, 2, 2 * 4, BS, 8)}
    assert cache.stats()["groups"]["window"]["pool_bytes"] == \
        8 * BS * 2 * 2 * 4 * 8 * 4


def test_prefix_shipping_and_host_pools_meet_rows_alone():
    by_planes = _pair()[1]
    assert by_planes.allocate("a", BS, writable_from=0)
    with pytest.raises(ValueError, match="held by planes"):
        by_planes.read_block(by_planes.block_table("a")[0])
    with pytest.raises(ValueError, match="held by planes"):
        by_planes.install_block(np.zeros((BS,) + ROW, np.float32))
    with pytest.raises(ValueError, match="held by planes"):
        KVCacheManager(BLOCKS, BS, ROW, planes=True)          # numpy
    with pytest.raises(ValueError, match="held by planes"):
        KVCacheManager(BLOCKS, BS, (8,), array_ns=jnp, planes=True)
