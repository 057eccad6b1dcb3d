"""Attention that selects its keys (`ops/sparse_attention.py`, the `keep`
operands of `ops/paged_attention.py` and of the prefill's forward, the
sectioned rotary): each body against a plain form of the same thing, the
kernels interpreted."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import paged_attention as pa
from ray_tpu.ops import sparse_attention as sa
from ray_tpu.ops.attention import banded_attention
from ray_tpu.ops.flash_attention import prefill_attention_fwd
from ray_tpu.ops.rotary import (apply_rotary_partial, rotary_cos_sin,
                                rotary_cos_sin_sections, rotary_inv_freq)


def _rng(*key):
    return np.random.default_rng(list(key))


# -- the selection ----------------------------------------------------------
def _keep_by_sort(scores, valid, k):
    """A position is kept iff it is valid and its score is at least the
    k-th largest valid score of its row."""
    masked = np.where(valid, scores, -np.inf)
    ordered = -np.sort(-masked, axis=-1)
    n_valid = valid.sum(axis=-1)
    kth = np.where(n_valid >= k, ordered[..., min(k, scores.shape[-1]) - 1],
                   -np.inf)
    return valid & (masked >= kth[..., None])


@pytest.mark.parametrize("case", ["random", "ties", "short_rows",
                                  "negative_and_zero", "k_is_n"])
def test_selection_is_the_sorts(case):
    rng = _rng(1, len(case))
    scores = rng.standard_normal((6, 96)).astype(np.float32)
    valid = np.ones(scores.shape, bool)
    k = 16
    if case == "ties":
        scores = np.round(scores * 2) / 2      # many equal scores
    elif case == "short_rows":
        for row, n in enumerate((0, 1, 15, 16, 17, 96)):
            valid[row, n:] = False
    elif case == "negative_and_zero":
        scores = -np.abs(scores)
        scores[:, ::3] = 0.0
        scores[:, 1::6] = -0.0
    elif case == "k_is_n":
        k = 96
    got = np.asarray(sa.select_topk(jnp.asarray(scores), jnp.asarray(valid),
                                    k))
    want = _keep_by_sort(scores, valid, k)
    assert (got == want).all()
    if case == "random":
        assert (got.sum(axis=-1) == k).all()


def test_sortable_bits_sort_as_floats():
    x = np.asarray([-np.inf, -3.5, -1e-30, -0.0, 0.0, 1e-30, 2.0, np.inf],
                   np.float32)
    bits = np.asarray(sa.sortable_bits(jnp.asarray(x)))
    assert (np.diff(bits.astype(np.int64)) >= 0).all()
    assert bits[3] == bits[4] and bits.min() > 0


# -- a decode step's index scores -------------------------------------------
def _index_case(seed, b=3, j=4, di=64, n=24, layers=2, bs=8, nb=8):
    rng = _rng(2, seed)
    # A key of 64 values in a row of 128, zeros behind it.
    pool = jnp.asarray(rng.standard_normal((n, layers, bs, di)), jnp.float32)
    pool = jnp.pad(pool, ((0, 0),) * 3 + ((0, sa.index_row_width(di) - di),))
    tables = jnp.asarray(rng.integers(0, n, (b, nb)), jnp.int32)
    qi = jnp.asarray(rng.standard_normal((b, j, di)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((b, j)), jnp.float32)
    positions = jnp.asarray([5, 37, nb * bs - 1][:b], jnp.int32)
    return qi, w, pool, tables, positions


def test_paged_index_scores_xla_is_the_equation():
    qi, w, pool, tables, positions = _index_case(0)
    got = np.asarray(sa.paged_index_scores_xla(qi, w, pool, tables,
                                               positions, jnp.int32(1)))
    for row in range(qi.shape[0]):
        keys = np.asarray(pool)[np.asarray(tables[row]), 1, :, :64].reshape(
            -1, 64)
        s = np.maximum(np.asarray(qi[row]) @ keys.T, 0.0)
        want = np.asarray(w[row]) @ s
        np.testing.assert_allclose(got[row], want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("nb", [8, 128])
def test_paged_index_scores_kernel_is_the_xla_body(nb):
    qi, w, pool, tables, positions = _index_case(nb, nb=nb, n=40)
    positions = jnp.asarray([5, nb * 8 // 2 + 3, nb * 8 - 1], jnp.int32)
    want = np.asarray(sa.paged_index_scores_xla(qi, w, pool, tables,
                                                positions, jnp.int32(1)))
    got = np.asarray(sa.paged_index_scores_kernel(
        qi, w, pool, tables, positions, jnp.int32(1), interpret=True))
    for row, p in enumerate(np.asarray(positions)):
        np.testing.assert_allclose(got[row, :p], want[row, :p], rtol=2e-5,
                                   atol=2e-5)


def test_own_index_score_is_the_pools_after_the_write():
    qi, w, pool, tables, positions = _index_case(3)
    ki = pool[tables[:, 0], 1, 0, :64]                 # any key a row
    got = np.asarray(sa.own_index_scores(qi, w, ki))
    want = np.asarray(sa.paged_index_scores_xla(
        qi, w, pool, tables, positions, jnp.int32(1)))[:, 0]
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


# -- a prompt's index scores ------------------------------------------------
@pytest.mark.parametrize("offset", [0, 128])
def test_prefill_index_scores_kernel_is_the_loop_over_heads(offset):
    rng = _rng(4, offset)
    qi = jnp.asarray(rng.standard_normal((4, 128, 64)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((128, 4)), jnp.float32)
    ki = jnp.asarray(rng.standard_normal((256, 64)), jnp.float32)
    want = np.asarray(sa.prefill_index_scores_xla(qi, w, ki))
    plain = np.einsum("qj,jqs->qs", np.asarray(w), np.maximum(np.einsum(
        "jqd,sd->jqs", np.asarray(qi), np.asarray(ki)), 0.0))
    np.testing.assert_allclose(want, plain, rtol=2e-4, atol=2e-4)
    got = np.asarray(sa.prefill_index_scores_kernel(qi, w, ki, offset,
                                                    interpret=True))
    seen = np.arange(256)[None, :] <= offset + np.arange(128)[:, None]
    np.testing.assert_allclose(np.where(seen, got, 0.0),
                               np.where(seen, want, 0.0), rtol=2e-4,
                               atol=2e-4)


def test_prefill_keep_is_causal_and_keeps_k():
    rng = _rng(5)
    scores = jnp.asarray(rng.standard_normal((16, 48)), jnp.float32)
    keep = np.asarray(sa.prefill_keep(scores, 32, 48, 8))
    at_q, at_k = 32 + np.arange(16)[:, None], np.arange(48)[None, :]
    assert not keep[at_k > at_q * np.ones_like(at_k)].any()
    assert (keep.sum(axis=-1) == 8).all()
    assert (keep == _keep_by_sort(np.asarray(scores), at_k <= at_q, 8)).all()


# -- decode attention over the chosen positions -----------------------------
def _decode_case(seed, b=3, h=8, hkv=4, d=128, n=40, layers=2, bs=8, nb=8,
                 dtype=jnp.float32):
    rng = _rng(6, seed)
    pool = jnp.asarray(rng.standard_normal((n, bs, layers, 2, hkv, d)),
                       dtype)
    tables = jnp.asarray(
        np.stack([rng.permutation(n)[:nb] for _ in range(b)]), jnp.int32)
    q = jnp.asarray(rng.standard_normal((b, h, d)), jnp.float32)
    k_new = jnp.asarray(rng.standard_normal((b, hkv, d)), dtype)
    v_new = jnp.asarray(rng.standard_normal((b, hkv, d)), dtype)
    positions = jnp.asarray([5, 37, nb * bs - 1][:b], jnp.int32)
    cached = np.arange(nb * bs)[None, :] < np.asarray(positions)[:, None]
    keep = cached & (rng.random((b, nb * bs)) < 0.4)
    keep[:, 0] = cached[:, 0]                # every row keeps a key
    own = np.asarray([True, False, True][:b])
    return (q, k_new, v_new, pool, tables, positions, jnp.int32(1),
            jnp.asarray(keep), jnp.asarray(own))


def _plain_over_chosen(q, k_new, v_new, pool, tables, positions, layer,
                       keep, own):
    """Softmax attention over the kept cached positions of a row (and
    its own, where it keeps it), a row and a head at a time."""
    q, k_new, v_new, pool = (np.asarray(x, np.float32)
                             for x in (q, k_new, v_new, pool))
    b, h, d = q.shape
    hkv = k_new.shape[1]
    out = np.zeros((b, h, d), np.float32)
    for row in range(b):
        kv = pool[np.asarray(tables[row]), :, int(layer)].reshape(
            -1, 2, hkv, d)
        chosen = np.flatnonzero(np.asarray(keep[row]))
        for head in range(h):
            g = head // (h // hkv)
            keys, vals = kv[chosen, 0, g], kv[chosen, 1, g]
            if own[row]:
                keys = np.concatenate([keys, k_new[row, g][None]])
                vals = np.concatenate([vals, v_new[row, g][None]])
            s = keys @ q[row, head] / np.sqrt(d)
            p = np.exp(s - s.max())
            out[row, head] = (p / p.sum()) @ vals
    return out


def test_masked_xla_body_is_plain_attention_over_the_chosen():
    case = _decode_case(0)
    want = _plain_over_chosen(*case)
    q, k_new, v_new, pool, tables, positions, layer, keep, own = case
    got = np.asarray(pa.paged_decode_attention_xla(
        q, k_new, v_new, pool, tables, positions, layer, keep=keep,
        own_keep=own))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("pages", [None, 2])
def test_masked_walk_kernel_is_plain_attention_over_the_chosen(pages):
    case = _decode_case(1)
    want = _plain_over_chosen(*case)
    q, k_new, v_new, pool, tables, positions, layer, keep, own = case
    got = np.asarray(pa.paged_decode_attention_kernel(
        q, k_new, v_new, pool, tables, positions, layer, keep=keep,
        own_keep=own, pages=pages, interpret=True))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_fetch_of_chosen_rows_is_plain_attention_over_the_chosen():
    case = _decode_case(2)
    want = _plain_over_chosen(*case)
    q, k_new, v_new, pool, tables, positions, layer, keep, own = case
    for interpret in (None, True):      # the XLA body, then the kernel
        got = np.asarray(pa.sparse_paged_decode_attention(
            q, k_new, v_new, pool, tables, positions, layer, keep, own,
            most=32, interpret=interpret))
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_chosen_slots_name_the_kept_positions_in_order():
    rng = _rng(7)
    keep = rng.random((2, 32)) < 0.3
    tables = jnp.asarray(rng.permutation(20)[:8].reshape(2, 4), jnp.int32)
    slots, count = pa.chosen_slots(jnp.asarray(keep), tables, 8, 16)
    for row in range(2):
        at = np.flatnonzero(keep[row])
        want = np.asarray(tables)[row, at // 8] * 8 + at % 8
        assert int(count[row]) == len(at)
        assert (np.asarray(slots)[row, :len(at)] == want).all()


def test_a_call_without_a_selection_is_the_old_call():
    q, k_new, v_new, pool, tables, positions, layer, _, _ = _decode_case(3)
    old = pa.paged_decode_attention_xla(q, k_new, v_new, pool, tables,
                                        positions, layer)
    everything = jnp.ones((q.shape[0], tables.shape[1] * pool.shape[1]),
                          bool)
    new = pa.paged_decode_attention_kernel(
        q, k_new, v_new, pool, tables, positions, layer, keep=everything,
        own_keep=jnp.ones((q.shape[0],), bool), interpret=True)
    np.testing.assert_allclose(np.asarray(new), np.asarray(old), rtol=2e-4,
                               atol=2e-4)


# -- the prefill's forward under a selection --------------------------------
@pytest.mark.parametrize("offset", [0, 128])
def test_selected_prefill_forward_is_plain_attention_under_the_mask(offset):
    rng = _rng(8, offset)
    h, hkv, sq, sk, d = 4, 2, 128, 256, 128
    q = jnp.asarray(rng.standard_normal((h, sq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((hkv, sk, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((hkv, sk, d)), jnp.float32)
    scores = jnp.asarray(rng.standard_normal((sq, sk)), jnp.float32)
    keep = sa.prefill_keep(scores, offset, offset + sq, 24)
    want = np.asarray(banded_attention(q, k, v, offset=offset, keep=keep))
    # Plain attention under the same mask, a head at a time.
    mask = np.asarray(keep)
    for head in range(h):
        s = np.asarray(q[head]) @ np.asarray(k[head // 2]).T / np.sqrt(d)
        s = np.where(mask, s, -np.inf)
        p = np.exp(s - s.max(axis=-1, keepdims=True))
        plain = (p / p.sum(axis=-1, keepdims=True)) @ np.asarray(
            v[head // 2])
        np.testing.assert_allclose(want[head], plain, rtol=2e-4, atol=2e-4)
    got = np.asarray(prefill_attention_fwd(
        q, k, v, offset=offset, live=offset + sq, keep=keep, block=128,
        interpret=True))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


# -- the sectioned rotary ---------------------------------------------------
def test_sectioned_rotary_turns_each_pair_by_its_stream():
    rng = _rng(9)
    t, d, sections = 12, 32, (4, 6, 6)
    x = jnp.asarray(rng.standard_normal((t, 3, d)), jnp.float32)
    streams = jnp.asarray(rng.integers(0, 500, (3, t)), jnp.int32)
    inv = rotary_inv_freq(d, 1e7)
    cos, sin = rotary_cos_sin_sections(streams, inv, sections)
    got = np.asarray(apply_rotary_partial(x, cos, sin))
    stream_of = np.repeat(np.arange(3), sections)
    x = np.asarray(x)
    for i in range(d // 2):
        angle = (np.asarray(streams)[stream_of[i]].astype(np.float64)
                 * 1e7 ** (-2.0 * i / d))
        c, s = np.cos(angle)[:, None], np.sin(angle)[:, None]
        np.testing.assert_allclose(
            got[..., i], x[..., i] * c - x[..., i + d // 2] * s, rtol=1e-4,
            atol=1e-4)
        np.testing.assert_allclose(
            got[..., i + d // 2], x[..., i + d // 2] * c + x[..., i] * s,
            rtol=1e-4, atol=1e-4)
    same = jnp.broadcast_to(streams[:1], streams.shape)
    one = rotary_cos_sin(streams[0], inv)
    for a, b in zip(rotary_cos_sin_sections(same, inv, sections), one):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError):
        rotary_cos_sin_sections(streams, inv, (4, 6, 5))


# -- compiled, not run, for a described v5e at the cell's shapes ------------
@pytest.fixture(scope="module")
def one_chip():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no compiler, no test
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("kernel", ["paged_index_scores", "masked_walk",
                                    "fetch_of_chosen_rows",
                                    "prefill_index_scores",
                                    "selected_prefill_forward"])
def test_the_kernels_compile_for_v5e_at_the_cells_shapes(one_chip, kernel):
    """`long-doc`'s shapes: 16 rows through tables of 1,024 blocks over
    pools of 17,408, 12 layers; a chunk of 1,024 queries over 16,384
    keys. The pools are operands as they stand: no copy of one."""
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    bf = jnp.bfloat16

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    b, nb, n, layers = 16, 1024, 17408, 12
    pool = spec((n, 16, layers, 2, 4, 128), bf)
    decode = (spec((b, 32, 128)), spec((b, 4, 128), bf),
              spec((b, 4, 128), bf), pool, spec((b, nb), jnp.int32),
              spec((b,), jnp.int32), spec((), jnp.int32),
              spec((b, nb * 16), jnp.bool_), spec((b,), jnp.bool_))
    calls = {
        "paged_index_scores": (sa.paged_index_scores_kernel, (
            spec((b, 16, 64)), spec((b, 16)),
            spec((n, layers, 16, sa.index_row_width(64)), bf),
            spec((b, nb), jnp.int32), spec((b,), jnp.int32),
            spec((), jnp.int32))),
        "masked_walk": (
            lambda q, k, v, p, t, ps, l, keep, own:
            pa.paged_decode_attention_kernel(
                q, k, v, p, t, ps, l, keep=keep, own_keep=own), decode),
        "fetch_of_chosen_rows": (
            lambda q, k, v, p, t, ps, l, keep, own:
            pa.sparse_paged_decode_attention(
                q, k, v, p, t, ps, l, keep, own, 2080, interpret=False),
            decode),
        "prefill_index_scores": (sa.prefill_index_scores_kernel, (
            spec((16, 1024, 64), bf), spec((1024, 16)),
            spec((16384, 64), bf), spec((), jnp.int32))),
        "selected_prefill_forward": (
            lambda q, k, v, keep, off: prefill_attention_fwd(
                q, k, v, offset=off, live=off + 1024, keep=keep), (
            spec((32, 1024, 128), bf), spec((4, 16384, 128), bf),
            spec((4, 16384, 128), bf), spec((1024, 16384), jnp.bool_),
            spec((), jnp.int32))),
    }
    fn, args = calls[kernel]
    try:
        compiled = jax.jit(fn).lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
