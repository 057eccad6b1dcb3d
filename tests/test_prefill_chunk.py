"""A long prompt prefilled in chunks (`LayerGroupsEngineModel.
prefill_chunk`, the scheduler's prompt in flight): the serving prefill's
flash forward with its two scalars, the first query's key and the count
of live keys (interpreted), against `banded_attention` with the same and
against itself over the whole prompt, bit for bit; and both layer-groups
models at the toy widths of their configurations, a prompt run as chunks
against the whole path: the last token's logits and the rows in both
pools."""

import json
import os

import numpy as np
import pytest

from benchmarks.harness import manifest

pytestmark = pytest.mark.unit


# ---------------------------------------------------------------------------
# the kernel
# ---------------------------------------------------------------------------
def _qkv(heads, hkv, seq, dk, dv, seed=0, with_sink=False):
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    q, k, v = (jnp.asarray(rng.standard_normal(shape), jnp.float32)
               for shape in ((heads, seq, dk), (hkv, seq, dk),
                             (hkv, seq, dv)))
    sink = (jnp.asarray(rng.standard_normal((heads,)), jnp.float32)
            if with_sink else None)
    return q, k, v, sink


# Key/value heads 4 and 8 (and 2 under 8 query heads each), keys of 192
# over values of 128, a sink, windows shorter than the chunk (one a tile,
# one not on the tile grid) and none; a chunk in the middle and at the
# end of the keys.
SHAPES = [
    # heads, hkv, dk, dv, window, sink, start
    (8, 4, 128, 128, None, False, 256),
    (8, 8, 192, 128, None, False, 128),
    (8, 8, 192, 128, 128, True, 256),
    (16, 4, 192, 128, 100, True, 128),
    (16, 2, 64, 128, 128, False, 256),
    (8, 4, 128, 128, 100, False, 0),
]


@pytest.mark.parametrize("heads, hkv, dk, dv, window, with_sink, start",
                         SHAPES, ids=[
                             "4kv_causal", "8kv_wide_keys_causal",
                             "8kv_wide_keys_window_sink",
                             "4kv_window100_sink", "2kv_window128",
                             "first_chunk"])
def test_a_chunks_forward_is_the_whole_prompts_rows_bit_for_bit(
        heads, hkv, dk, dv, window, with_sink, start):
    """Queries ``[start, start + 128)`` of 512 positions. Over every key
    at its position (a global layer's keys: `offset` = `start`), and
    over the window's tail then the chunk (a window layer's: `offset` =
    the tail), with a tail no position filled where the chunk is the
    prompt's first: each time the whole prompt's rows, bit for bit, and
    `banded_attention` with the same two numbers to rounding."""
    import jax.numpy as jnp

    from ray_tpu.ops.attention import banded_attention
    from ray_tpu.ops.flash_attention import prefill_attention_fwd

    q, k, v, sink = _qkv(heads, hkv, 512, dk, dv, start + heads, with_sink)
    rows = slice(start, start + 128)
    whole = prefill_attention_fwd(q, k, v, window, sink, block=128,
                                  interpret=True)
    plain = banded_attention(q, k, v, window, sink)
    assert float(jnp.max(jnp.abs(whole - plain))) < 2e-5
    # With offset 0 and every key live: what it gives without them.
    assert bool(jnp.all(whole == prefill_attention_fwd(
        q, k, v, window, sink, offset=jnp.int32(0), live=jnp.int32(512),
        block=128, interpret=True)))
    at = dict(offset=jnp.int32(start), live=jnp.int32(start + 128))
    got = prefill_attention_fwd(q[:, rows], k, v, window, sink, block=128,
                                interpret=True, **at)
    assert bool(jnp.all(got == whole[:, rows]))
    assert float(jnp.max(jnp.abs(got - banded_attention(
        q[:, rows], k, v, window, sink, **at)))) < 2e-5
    if window is None:
        return
    # The tail: the 128 positions before the chunk, or where there are
    # none 128 rows of other keys scaled up, which must not be seen.
    if start:
        tail_k, tail_v = k[:, start - 128:start], v[:, start - 128:start]
    else:
        tail_k, tail_v = 50.0 * k[:, 300:428], v[:, 300:428]
    keys = jnp.concatenate([tail_k, k[:, rows]], axis=1)
    vals = jnp.concatenate([tail_v, v[:, rows]], axis=1)
    at = dict(offset=128, live=jnp.int32(min(start, 128) + 128))
    got = prefill_attention_fwd(q[:, rows], keys, vals, window, sink,
                                block=128, interpret=True, **at)
    assert bool(jnp.all(got == whole[:, rows]))
    assert float(jnp.max(jnp.abs(whole[:, rows] - banded_attention(
        q[:, rows], keys, vals, window, sink, **at)))) < 2e-5


def test_a_dead_tail_changes_the_result_where_it_is_taken_for_live():
    """The control of the test above: the same keys with every one
    counted live give other rows."""
    import jax.numpy as jnp

    from ray_tpu.ops.flash_attention import prefill_attention_fwd

    q, k, v, _ = _qkv(8, 4, 256, 128, 128)
    keys = jnp.concatenate([50.0 * k[:, 128:], k[:, :128]], axis=1)
    vals = jnp.concatenate([v[:, 128:], v[:, :128]], axis=1)
    args = (q[:, :128], keys, vals, 100, None)
    dead = prefill_attention_fwd(*args, offset=128, live=128, block=128,
                                 interpret=True)
    seen = prefill_attention_fwd(*args, offset=128, live=256, block=128,
                                 interpret=True)
    assert float(jnp.max(jnp.abs(dead - seen))) > 0.1


def test_the_forward_refuses_keys_it_cannot_tile():
    import jax.numpy as jnp

    from ray_tpu.ops.flash_attention import prefill_attention_fwd

    x = jnp.zeros((4, 256, 128))
    with pytest.raises(ValueError, match="does not go over"):
        prefill_attention_fwd(x[:, :128], x[:, :64], x[:, :64],
                              interpret=True)         # fewer keys
    with pytest.raises(ValueError, match="no multiple"):
        prefill_attention_fwd(x[:, :128], x[:, :192], x[:, :192],
                              block=128, interpret=True)


# ---------------------------------------------------------------------------
# the models, at the toy widths of both configurations
# ---------------------------------------------------------------------------
BLOCK = 16
ENGINE = {"paged_decode": True, "max_batch_size": 3, "block_size": BLOCK,
          "num_blocks": 64, "group_blocks": {"window": 12}, "max_queue": 64}
CHUNK = 32
# float32 against float32 in another order of sums (`test_mimo_engine`'s
# and `test_laguna_engine`'s limit against their references).
TOLERANCE = 2e-4


def _toy(family_name, config_name):
    family = manifest.load_family(family_name)
    with open(os.path.join(manifest.ROOT, "benchmarks", "configs",
                           config_name)) as f:
        return family, family.toy_widths(family.widths(json.load(f)))


@pytest.fixture(scope="module", params=[
    ("mimo_v2", "mimo-v2.5.json"), ("laguna", "laguna-s-2.1.json")],
    ids=["mimo", "laguna"])
def served(request):
    """(family, widths, model, an engine that prefills whole, one that
    prefills in chunks of 32) over one model and its seeded weights."""
    from ray_tpu.serve.engine import InferenceEngine

    family, widths = _toy(*request.param)
    built = family.build_serving(
        widths, {"max_seq_len": 256, "engine": ENGINE}, 7)
    model = built["model"]
    model.prefill_chunk_tokens = CHUNK
    chunks = InferenceEngine(model, built["engine_config"])
    whole = InferenceEngine(model, built["engine_config"])
    whole._chunk = None
    return family, widths, model, whole, chunks


def _gap(row, expect):
    return float(np.sqrt(np.mean((row - expect) ** 2))
                 / np.sqrt(np.mean(expect ** 2)))


def _rows(engine, sid, n):
    """{group: (its first position, the rows its table holds up to n)}."""
    out = {}
    for name, (base, table) in engine.cache.step_tables(sid).items():
        pool = np.asarray(engine.cache.group(name).with_pool(
            lambda p: p[np.asarray(table)]))
        out[name] = (base * BLOCK,
                     pool.reshape((-1,) + pool.shape[2:])[:n - base * BLOCK])
    return out


def test_the_toys_keep_what_a_chunk_has_to_get_right(served):
    family, widths, model, _, _ = served
    # A window shorter than a chunk, on a tile's edge or not (16 and 24
    # under 32), so the tail is a chunk's last 32 positions of which the
    # window group still holds one block or two.
    assert widths["window"] < CHUNK == model._chunk_tail_tokens()
    assert model.window_table_blocks(BLOCK) * BLOCK <= CHUNK + BLOCK
    if family.__name__.endswith("mimo_v2"):
        # Keys wider than values, a sink, 2 global to 4 window key heads.
        assert (widths["head_dim"], widths["v_head_dim"]) == (24, 16)
        assert (widths["kv_heads_global"], widths["kv_heads_window"]) == \
            (2, 4)


# One chunk and a token, two chunks to the token, off the grid of chunks
# and of blocks, many chunks; the last one's whole length is 7 chunks.
@pytest.mark.parametrize("n", [33, 64, 70, 96, 130, 224])
def test_a_prompt_in_chunks_gives_the_whole_paths_logits_and_rows(served, n):
    family, widths, model, whole, chunks = served
    rng = np.random.default_rng(n)
    prompt = rng.integers(2, widths["vocab_size"], n).tolist()
    # The whole path: one call, its rows into the pools.
    want, kv = model.prefill(prompt)
    assert whole.cache.allocate("w", n, writable_from=0)
    whole.cache.write_range("w", 0, kv)
    # The chunks, as the scheduler runs them.
    calls, start, got = model.prefill_calls, 0, None
    while start < n:
        end = min(n, start + CHUNK)
        tables = chunks.cache.step_tables("c")
        got, kv = chunks.cache.with_pools(
            lambda pools: model.prefill_chunk(prompt, pools, tables, start,
                                              BLOCK))
        assert (got is None) == (end < n) and len(kv) == end - start
        assert chunks.cache.allocate("c", end, writable_from=start)
        chunks.cache.write_range("c", start, kv)
        held = chunks.cache.step_tables("c")["window"][1]
        assert len(held) <= model.window_table_blocks(BLOCK)
        start = end
    assert model.prefill_calls - calls == -(-n // CHUNK)
    assert _gap(got, want) < TOLERANCE
    assert int(np.argmax(got)) == int(np.argmax(want))
    ours, theirs = _rows(chunks, "c", n), _rows(whole, "w", n)
    assert set(ours) == {"global", "window"}
    for name in ours:
        assert ours[name][0] == theirs[name][0]
        assert ours[name][1].shape == theirs[name][1].shape
        np.testing.assert_allclose(ours[name][1], theirs[name][1],
                                   rtol=0, atol=2e-5)
    # The window group holds the rows the window reaches, no more.
    assert ours["window"][0] == max(0, n - widths["window"]) // BLOCK * BLOCK
    for engine, sid in ((whole, "w"), (chunks, "c")):
        engine.cache.free(sid)
        assert engine.cache.free_blocks() == ENGINE["num_blocks"]


def test_the_engine_in_chunks_emits_the_whole_paths_tokens(served):
    family, widths, model, whole, chunks = served
    rng = np.random.default_rng(5)
    prompts = [rng.integers(2, widths["vocab_size"], n).tolist()
               for n in (100, 40, 20)]
    tokens = {}
    for label, engine in (("whole", whole), ("chunks", chunks)):
        before = engine.stats()
        streams = [engine.submit(p, 8) for p in prompts]
        while engine.step():
            pass
        tokens[label] = [list(s) for s in streams]
        after = engine.stats()
        assert after["prefills"] - before["prefills"] == 3
        assert after["prefill_chunks"] - before["prefill_chunks"] == \
            (4 + 2 if engine is chunks else 0)
        assert after["prefill_chunk_tokens"] - before["prefill_chunk_tokens"] \
            == (140 if engine is chunks else 0)
        assert engine.cache.free_blocks() == ENGINE["num_blocks"]
        assert engine.cache.group("window").free_blocks() == \
            ENGINE["group_blocks"]["window"]
    assert tokens["chunks"] == tokens["whole"]
    assert all(len(t) == 8 for t in tokens["whole"])
