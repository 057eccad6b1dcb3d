"""A pool of 4 key/value heads held by planes (PR 58): ``[N, L, S * Hkv,
bs, dv]``, a layer's page one contiguous piece of whole tiles, against
the same positions in a pool of rows. The per-head body interpreted
against `paged_decode_attention_xla` and a float64 softmax; the XLA body,
the fetch of chosen rows and the host's page arithmetic on either layout.
(The bodies compiled for the described v5e are in
`test_ops_paged_attention.py`, with every other such compile.)"""

import numpy as np
import pytest

import jax.numpy as jnp

from ray_tpu.ops import paged_attention as pa

BS, LAYERS, BLOCKS = 16, 3, 40


def _pools(rng, hkv, dk, dv, dtype):
    """The same positions in both layouts: a pool of rows and the pool
    held by planes."""
    k = jnp.asarray(rng.standard_normal((BLOCKS * BS, LAYERS, hkv, dk)),
                    dtype)
    v = jnp.asarray(rng.standard_normal((BLOCKS * BS, LAYERS, hkv, dv)),
                    dtype)
    rows = jnp.stack([pa.kv_row(k[:, layer], v[:, layer])
                      for layer in range(LAYERS)], axis=1)
    slots = pa.kv_slots(dk, dv)
    by_rows = rows.reshape(BLOCKS, BS, LAYERS, slots, hkv, dv)
    by_planes = by_rows.reshape(BLOCKS, BS, LAYERS, slots * hkv,
                                dv).transpose(0, 2, 3, 1, 4)
    return (np.asarray(k, np.float64), np.asarray(v, np.float64), by_rows,
            by_planes)


def _case(seed, h, hkv, dk, dv, positions, nb=BLOCKS, *, keep=False,
          sink=False, window=None, dtype=jnp.bfloat16):
    rng = np.random.default_rng(seed)
    b = len(positions)
    k, v, by_rows, by_planes = _pools(rng, hkv, dk, dv, dtype)
    tables = np.stack([rng.permutation(BLOCKS)[:nb] for _ in range(b)])
    args = dict(
        q=jnp.asarray(rng.standard_normal((b, h, dk)), jnp.float32),
        k_new=jnp.asarray(rng.standard_normal((b, hkv, dk)), dtype),
        v_new=jnp.asarray(rng.standard_normal((b, hkv, dv)), dtype),
        tables=jnp.asarray(tables, jnp.int32),
        positions=jnp.asarray(positions, jnp.int32), layer=jnp.int32(1))
    more = {}
    if keep:
        kept = rng.random((b, nb * BS)) < 0.3
        # A row attends to something: to its own position at the least.
        alone = ~(kept & (np.arange(nb * BS) < np.asarray(positions)[:, None])
                  ).any(axis=1)
        more["keep"] = jnp.asarray(kept)
        more["own_keep"] = jnp.asarray((rng.random(b) < 0.5) | alone)
    if sink:
        more["sink"] = jnp.asarray(rng.standard_normal(h), jnp.float32)
    if window is not None:
        # A compact table: column 0 is the block the window begins in.
        more["window"] = window
        more["starts"] = jnp.asarray(
            [max(0, p - window + 1) // BS for p in positions], jnp.int32)
    return k, v, by_rows, by_planes, args, more


def _float64_softmax(k, v, case_args, more):
    """Plain attention in float64 over the positions a row attends to,
    read through its table."""
    q = np.asarray(case_args["q"], np.float64)
    k_new = np.asarray(case_args["k_new"].astype(jnp.float32), np.float64)
    v_new = np.asarray(case_args["v_new"].astype(jnp.float32), np.float64)
    tables = np.asarray(case_args["tables"])
    layer = int(case_args["layer"])
    b, h, dk = q.shape
    group = h // k_new.shape[1]
    out = np.zeros((b, h, v_new.shape[-1]))
    for row, position in enumerate(np.asarray(case_args["positions"])):
        start = int(more["starts"][row]) * BS if "starts" in more else 0
        at = start + np.arange(tables.shape[1] * BS)
        seen = at < position
        if "window" in more:
            seen &= position - at < more["window"]
        if "keep" in more:
            seen &= np.asarray(more["keep"][row])
        where = (tables[row][:, None] * BS + np.arange(BS)).reshape(-1)[seen]
        for head in range(h):
            g = head // group
            keys = [k[where, layer, g], k_new[row, g][None]]
            vals = [v[where, layer, g], v_new[row, g][None]]
            if "own_keep" in more and not bool(more["own_keep"][row]):
                keys, vals = keys[:1], vals[:1]
            keys, vals = np.concatenate(keys), np.concatenate(vals)
            s = keys @ q[row, head] / np.sqrt(dk)
            top = s.max() if len(s) else -np.inf
            if "sink" in more:
                top = max(top, float(more["sink"][head]))
            p = np.exp(s - top)
            total = p.sum() + (np.exp(float(more["sink"][head]) - top)
                               if "sink" in more else 0.0)
            out[row, head] = (p / total) @ vals
    return out


# 1, 17 and 33 live pages (a group of 32: one group, one, then 17 and
# 16) beside a row with nothing cached; a last page half full.
POSITIONS = [0, BS, 17 * BS - 5, 33 * BS]


@pytest.mark.parametrize("name, h, dk, more", [
    ("keye_8_a_key_head", 32, 128, {}),
    ("keye_keep", 32, 128, {"keep": True}),
    ("mimo_16_a_key_head_keys_of_192", 64, 192, {}),
    ("keys_of_192_sink", 64, 192, {"sink": True}),
    ("keys_of_192_keep_sink", 64, 192, {"keep": True, "sink": True}),
    ("8_a_key_head_sink_keep", 32, 128, {"keep": True, "sink": True}),
])
def test_per_head_body_matches_the_xla_body_and_a_float64_softmax(
        name, h, dk, more):
    k, v, by_rows, by_planes, args, more = _case(
        len(name), h, 4, dk, 128, POSITIONS, **more)
    want = _float64_softmax(k, v, args, more)
    xla = np.asarray(pa.paged_decode_attention_xla(
        pool=by_planes, **args, **more))
    got = np.asarray(pa.paged_decode_attention_kernel(
        pool=by_planes, **args, **more, interpret=True))
    assert got.shape == (len(POSITIONS), h, 128)
    np.testing.assert_allclose(xla, want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    # The XLA body reads the same numbers out of either layout.
    np.testing.assert_array_equal(xla, np.asarray(
        pa.paged_decode_attention_xla(pool=by_rows, **args, **more)))


@pytest.mark.parametrize("pages", [None, 2, 8])
@pytest.mark.parametrize("h, dk, sink", [(32, 128, False), (64, 192, True)],
                         ids=["8_a_key_head", "16_a_key_head_192_sink"])
def test_per_head_body_under_a_window_walks_a_compact_table(h, dk, sink,
                                                            pages):
    """`starts`: a row's table begins at the block its window begins in,
    and the first and the last live page are masked in part. At any group
    size: a group a page, or the whole table at once."""
    window, positions = 40, [3, 40, 41, 200, 16 * 37 + 9]
    k, v, _, by_planes, args, more = _case(
        7, h, 4, dk, 128, positions, nb=4, window=window, sink=sink)
    # What the compact table names are the blocks from `starts` on.
    want = _float64_softmax(k, v, args, more)
    got = np.asarray(pa.paged_decode_attention_kernel(
        pool=by_planes, **args, **more, pages=pages, interpret=True))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(pa.paged_decode_attention_xla(
        pool=by_planes, **args, **more)), want, rtol=2e-5, atol=2e-5)


def test_per_head_body_takes_a_float32_pool_too():
    k, v, _, by_planes, args, more = _case(
        3, 32, 4, 128, 128, POSITIONS, keep=True, dtype=jnp.float32)
    got = np.asarray(pa.paged_decode_attention_kernel(
        pool=by_planes, **args, **more, interpret=True))
    np.testing.assert_allclose(got, _float64_softmax(k, v, args, more),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("position", [5, 17 * BS - 5])
def test_what_lies_past_a_rows_position_never_reaches_the_result(position):
    """A reused block's stale rows, block 0 behind a padded table entry:
    masked in the planes body as in the other."""
    _, _, _, by_planes, args, more = _case(5, 32, 4, 128, 128, [position],
                                           keep=True)
    want = np.asarray(pa.paged_decode_attention_kernel(
        pool=by_planes, **args, **more, interpret=True))
    # Every position at or past the row's, in every block its table
    # names, holds something else, and something huge.
    spoiled = np.array(by_planes.astype(jnp.float32))
    for column, block in enumerate(np.asarray(args["tables"])[0]):
        stale = column * BS + np.arange(BS) >= position
        spoiled[block][:, :, stale] = 3e4
    got = np.asarray(pa.paged_decode_attention_kernel(
        pool=jnp.asarray(spoiled, jnp.bfloat16), **args, **more,
        interpret=True))
    np.testing.assert_array_equal(got, want)


def test_fetch_of_chosen_rows_reads_either_layout():
    _, _, by_rows, by_planes, args, more = _case(
        11, 32, 4, 128, 128, [40, 17 * BS - 5], nb=20, keep=True)
    # The fetch takes a selection as the model makes it: of the cached
    # positions alone.
    more["keep"] &= jnp.arange(20 * BS)[None, :] < args["positions"][:, None]
    walk = np.asarray(pa.paged_decode_attention_xla(
        pool=by_rows, **args, **more))
    for pool in (by_rows, by_planes):
        for interpret in (None, True):   # the XLA body, then the kernel
            got = np.asarray(pa.sparse_paged_decode_attention(
                args["q"], args["k_new"], args["v_new"], pool,
                args["tables"], args["positions"], args["layer"],
                more["keep"], more["own_keep"], most=128,
                interpret=interpret))
            np.testing.assert_allclose(got, walk, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("hkv, slots", [(4, 2), (4, 3), (8, 2), (2, 2)])
@pytest.mark.parametrize("width", [1, 9, 512, 1024])
def test_a_pages_bytes_and_its_groups_are_the_same_in_both_layouts(
        hkv, slots, width):
    """`decode_kv_pages_per_fetch` reads `page_groups`: the same bytes
    give the same groups, whichever way the pool lies."""
    import jax

    by_rows = jax.ShapeDtypeStruct((64, BS, 5, slots, hkv, 128),
                                   jnp.bfloat16)
    by_planes = jax.ShapeDtypeStruct((64, 5, slots * hkv, BS, 128),
                                     jnp.bfloat16)
    assert not pa.by_planes(by_rows) and pa.by_planes(by_planes)
    assert pa.pool_block_size(by_rows) == pa.pool_block_size(by_planes) == BS
    assert pa.pool_page_bytes(by_rows) == pa.pool_page_bytes(by_planes) \
        == BS * slots * hkv * 128 * 2
    assert pa.pool_pages_per_step(by_rows, width) == \
        pa.pool_pages_per_step(by_planes, width) == \
        pa.pages_per_step(pa.pool_page_bytes(by_rows), width)
    positions = [0, 1, 16, 17, 600, 12288]
    for window in (None, 128):
        assert pa.page_groups(by_rows, width, positions, window) == \
            pa.page_groups(by_planes, width, positions, window)


@pytest.mark.parametrize("heads, planes", [
    (1, True), (2, True), (4, True), (12, True), (8, False), (16, False),
    (32, False)])
def test_the_layout_follows_the_key_value_heads_alone(heads, planes):
    assert pa.held_by_planes(heads) is planes


def test_rows_written_at_slots_read_back_from_either_layout():
    """`write_rows`, the decode step's write: a position's row of every
    layer at a (block, offset), a block past the pool dropped; and
    `heads_of_pages`, a chunk's read of one layer through a table."""
    rng = np.random.default_rng(2)
    _, _, by_rows, by_planes = _pools(rng, 4, 192, 128, jnp.float32)
    rows = jnp.asarray(rng.standard_normal((3, LAYERS, 3, 4, 128)),
                       jnp.float32)
    blocks = jnp.asarray([7, BLOCKS, 7], jnp.int32)      # one dropped
    offs = jnp.asarray([2, 3, 9], jnp.int32)
    new_rows = pa.write_rows(by_rows, blocks, offs, rows)
    new_planes = pa.write_rows(by_planes, blocks, offs, rows)
    np.testing.assert_array_equal(np.asarray(new_rows[7, 2]),
                                  np.asarray(rows[0]))
    np.testing.assert_array_equal(np.asarray(new_rows[7, 9]),
                                  np.asarray(rows[2]))
    np.testing.assert_array_equal(
        np.asarray(new_planes),
        np.asarray(new_rows.reshape(BLOCKS, BS, LAYERS, 12, 128)
                   .transpose(0, 2, 3, 1, 4)))
    table = jnp.asarray([7, 0, 39], jnp.int32)
    for layer in range(LAYERS):
        got = pa.heads_of_pages(new_planes, table, layer, 4, 192)
        want = pa.heads_of_pages(new_rows, table, layer, 4, 192)
        assert got[0].shape == (4, 3 * BS, 192)
        assert got[1].shape == (4, 3 * BS, 128)
        for a, b in zip(got, want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
