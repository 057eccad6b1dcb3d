"""The cache manager's accounting by layer group: a window group gives a
block back exactly when its last position leaves the window, holds at
most ``ceil(window / block) + 1`` blocks a sequence, stores only the rows
of a prefill that the window still reaches; `allocate` and `can_allocate`
count every group and stay atomic; `free` returns every free list to
full; a model without groups is one global group with the answers it has
always had."""

import numpy as np
import pytest

from ray_tpu.serve.engine import (EngineConfig, InferenceEngine,
                                  KVCacheManager, TinyLM)

pytestmark = pytest.mark.unit

W, BS = 40, 8
BOUND = -(-W // BS) + 1


class _Rows:
    """A prefill's payload a group, on the host."""

    def __init__(self, rows, groups=None):
        self.rows, self.groups = rows, groups or {}

    def __len__(self):
        return len(self.rows)

    def __array__(self, dtype=None, copy=None):
        return self.rows


def _cache(window_blocks=14, blocks=64, ns=None):
    return KVCacheManager(blocks, BS, (2,), array_ns=ns, groups={
        "window": {"num_blocks": window_blocks, "kv_shape": (3,),
                   "window": W}})


def _prefill(cache, sid, n):
    g = np.arange(n * 2, dtype=np.float32).reshape(n, 2)
    w = np.arange(n * 3, dtype=np.float32).reshape(n, 3) + 0.5
    assert cache.allocate(sid, n, writable_from=0)
    cache.write_range(sid, 0, _Rows(g, {"window": _Rows(w)}))
    return g, w


@pytest.mark.parametrize("device", [False, True], ids=["numpy", "jax"])
def test_a_window_block_is_released_exactly_when_its_last_position_leaves(
        device):
    import jax.numpy as jnp

    cache = _cache(ns=jnp if device else None)
    window = cache.group("window")
    n = 100
    _, rows = _prefill(cache, "a", n)
    # Only the rows the window of the next query (position n - 1) still
    # reaches were stored: blocks 7.. of 13.
    base, table = cache.step_tables("a")["window"]
    assert base == (n - W) // BS == 7 and len(table) == 13 - 7
    assert len(cache.step_tables("a")["global"][1]) == 13
    held_max = 0
    for target in range(n + 1, 180):
        p = target - 1                       # the query's position
        before = window.window_blocks_released
        released = cache.release_expired("a", target)
        # Exactly when the oldest block's last position, (base + 1) * BS
        # - 1, is W or more positions behind p.
        leaves = (base + 1) * BS - 1 <= p - W
        assert released == (1 if leaves else 0), (p, base)
        assert window.window_blocks_released == before + released
        assert cache.allocate("a", target, writable_from=p)
        base, table = cache.step_tables("a")["window"]
        assert base == max(0, p - W + 1) // BS
        held_max = max(held_max, len(table))
        cache.paged_step([("a", p)], lambda pools, blocks, offs: (
            None, {k: (v.at[blocks[k][0], offs[k][0]].set(float(p))
                       if device else _set(v, blocks[k][0], offs[k][0], p))
                   for k, v in pools.items()}))
        if p % 17 == 0:
            # Every position the window reaches reads back; nothing
            # before it has a block.
            for j in range(max(0, p - W + 1), p + 1):
                idx, off = window._slot("a", j)
                got = np.asarray(window._buffer[window._tables["a"][idx],
                                                off])
                assert np.all(got == (rows[j] if j < n else j)), (p, j)
            with pytest.raises(IndexError):
                window._slot("a", (base - 1) * BS)
    assert held_max == BOUND
    groups = cache.stats()["groups"]
    assert groups["window"]["blocks_in_use"] <= BOUND
    assert groups["window"]["block_steps"] == 79 * 14
    assert groups["global"]["block_steps"] == 79 * 64
    assert 0 < groups["window"]["block_steps_in_use"] <= 79 * BOUND
    assert groups["global"]["window_blocks_released"] == 0
    assert cache.free("a") > 0
    assert cache.free_blocks() == 64 and window.free_blocks() == 14
    assert cache.stats()["host_gathers"] == 0


def _set(pool, block, off, value):
    pool[block, off] = value
    return pool


def test_allocate_counts_every_group_and_changes_nothing_when_one_is_short():
    cache = _cache(window_blocks=BOUND + 2)
    window = cache.group("window")
    _prefill(cache, "a", 90)                    # holds BOUND - 1 or so
    held = BOUND + 2 - window.free_blocks()
    assert 0 < held <= BOUND
    # A second long sequence does not fit the window group, though the
    # global group has room: refused whole, nothing taken from either.
    free_global = cache.free_blocks()
    assert not cache.can_allocate("b", 90)
    assert not cache.allocate("b", 90, writable_from=0)
    assert cache.free_blocks() == free_global
    assert window.free_blocks() == BOUND + 2 - held
    assert cache.step_tables("b") == {"global": (0, []), "window": (0, [])}
    # A short one fits (one block of each group).
    assert cache.can_allocate("c", 5) and cache.allocate("c", 5, 0)
    # And the other way round: the global group short, the window not.
    tight = KVCacheManager(6, BS, (2,), groups={
        "window": {"num_blocks": 20, "kv_shape": (3,), "window": W}})
    assert tight.allocate("z", 3 * BS)
    assert not tight.can_allocate("a", 5 * BS)
    assert not tight.allocate("a", 5 * BS)
    assert tight.group("window").free_blocks() == 20 - 3
    # Preemption (`free`) gives both groups back.
    cache.free("a")
    cache.free("c")
    assert cache.free_blocks() == 64
    assert window.free_blocks() == BOUND + 2


def test_a_window_group_needs_room_for_one_sequence_and_adopts_nothing():
    with pytest.raises(ValueError, match="needs 6 blocks"):
        _cache(window_blocks=BOUND - 1)
    cache = _cache()
    _prefill(cache, "a", 20)
    with pytest.raises(ValueError, match="window"):
        cache.adopt("b", cache.block_table("a")[:1], BS)
    with pytest.raises(ValueError, match="state slots"):
        KVCacheManager(8, BS, (2,), state_shapes={"s": ((2,), np.float32)},
                       state_slots=2, groups={"window": {
                           "num_blocks": 8, "kv_shape": (3,), "window": 8}})


def test_a_model_without_groups_is_one_global_group_as_before():
    cache = KVCacheManager(16, 4, (1,))
    assert not cache.grouped
    assert cache.allocate("a", 10, writable_from=0)
    cache.write_range("a", 0, np.arange(10, dtype=np.float32)[:, None])
    assert cache.step_tables("a") == cache.block_table("a")
    assert cache.release_expired("a", 11) == 0
    seen = {}

    def step(pool, blocks, offs):
        seen.update(pool=pool, blocks=blocks, offs=offs)
        return "result", pool

    assert cache.allocate("a", 11, writable_from=10)
    assert cache.paged_step([("a", 10)], step) == "result"
    assert isinstance(seen["blocks"], list) and seen["offs"] == [2]
    assert np.asarray(cache.gather("a"))[:10, 0].tolist() == list(range(10))
    stats = cache.stats()
    assert set(stats["groups"]) == {"global"}
    assert stats["groups"]["global"]["block_steps"] == 16
    assert stats["groups"]["global"]["block_steps_in_use"] == 3
    assert stats["used_blocks"] == 3
    # The engine over such a model still builds its prefix index and
    # reports the global group's counters alone.
    engine = InferenceEngine(TinyLM(), EngineConfig(block_size=4,
                                                    num_blocks=32))
    assert engine.prefix_index is not None and not engine.cache.grouped
    stream = engine.submit([3, 4, 5], 4)
    while engine.step():
        pass
    assert 1 <= len(list(stream)) <= 4
    stats = engine.stats()
    assert stats["kv_global_block_steps"] == stats["paged_steps"] * 32
    assert "kv_window_block_steps" not in stats
    assert stats["window_release_s"] == 0


def test_the_engine_refuses_groups_the_config_does_not_size():
    class Grouped(TinyLM):
        kv_groups = {"window": {"kv_shape": (1,), "window": 8}}

    with pytest.raises(ValueError, match="group_blocks"):
        InferenceEngine(Grouped(), EngineConfig(block_size=4, num_blocks=32))
    with pytest.raises(ValueError, match="group_blocks"):
        InferenceEngine(TinyLM(), EngineConfig(
            block_size=4, num_blocks=32, group_blocks={"window": 8}))
