"""Attention with a window bound and grouped heads, off the chip: the
windowed paged decode attention (its XLA twin, and the Pallas kernel
interpreted) and the windowed grouped flash forward of the serving
prefill (interpreted), each against `plain_attention` under the band
mask, at groups of 6 and 9 query heads a key head; the two rotary schemes
against a direct float64 formula; the router's softmax scoring. The
kernels are compiled for a described v5e in
`tests/test_ops_paged_attention.py`, which owns the topology."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import paged_attention as pa
from ray_tpu.ops.attention import banded_attention, plain_attention
from ray_tpu.ops.flash_attention import prefill_attention_fwd

pytestmark = pytest.mark.unit

HD, BS = 128, 16


def _band_reference(q, k, v, window):
    """`plain_attention` over one sequence with the band mask folded in:
    q [S, H, hd], k, v [S, Hkv, hd]; keys i - j >= window are taken out
    by a large negative bias on their scores (added through a key
    dimension of ones), so the plain causal form does the rest."""
    s, h, hd = q.shape
    group = h // k.shape[1]
    kr, vr = (jnp.repeat(t, group, axis=1) for t in (k, v))
    idx = jnp.arange(s)
    scores = jnp.einsum("qhd,khd->hqk", q, kr) * hd ** -0.5
    keep = idx[:, None] >= idx[None, :]
    if window is not None:
        keep &= idx[:, None] - idx[None, :] < window
    probs = jax.nn.softmax(jnp.where(keep[None], scores, -1e30), axis=-1)
    return jnp.einsum("hqk,khd->qhd", probs, vr)


def test_the_band_reference_is_plain_attention_without_a_window():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(key, (1, 40, 4, 16)) for key in ks)
    want = plain_attention(q, k, v)[0]
    got = _band_reference(q[0], k[0], v[0], None)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5


def _paged_case(heads, window, lengths, seed=0, dtype=jnp.float32):
    """Sequences of `lengths` cached tokens (plus the step's own) in a
    pool of 2 layers, the window group's way: each row's table compact,
    from the first block its window reaches, shuffled physical blocks,
    garbage in the blocks no table names."""
    hkv, layer = 8, 1
    rng = np.random.default_rng(seed)
    b = len(lengths)
    width = -(-window // BS) + 1 if window else -(-max(lengths) // BS) + 1
    n_blocks = b * width + 3
    pool = rng.normal(size=(n_blocks, BS, 2, 2, hkv, HD)).astype(np.float32)
    free = list(rng.permutation(n_blocks))
    tables = np.zeros((b, width), np.int32)
    starts = np.zeros((b,), np.int32)
    dense = []
    for i, n in enumerate(lengths):
        kv = rng.normal(size=(n + 1, 2, hkv, HD)).astype(np.float32)
        dense.append(kv)
        first = max(0, n - window + 1) // BS if window else 0
        starts[i] = first
        for blk in range(first, -(-n // BS)):
            phys = free.pop()
            tables[i, blk - first] = phys
            rows = kv[blk * BS:min((blk + 1) * BS, n)]
            pool[phys, :len(rows), layer] = rows
    q = rng.normal(size=(b, heads, HD)).astype(np.float32)
    want = []
    for i, n in enumerate(lengths):
        kv = jnp.asarray(dense[i])
        qs = jnp.zeros((n + 1, heads, HD)).at[n].set(q[i])
        want.append(_band_reference(qs, kv[:, 0], kv[:, 1], window)[n])
    own = np.stack([d[-1] for d in dense])
    return (jnp.asarray(q), jnp.asarray(own[:, 0], dtype),
            jnp.asarray(own[:, 1], dtype), jnp.asarray(pool, dtype),
            jnp.asarray(tables), jnp.asarray(lengths, jnp.int32),
            jnp.int32(layer), window, jnp.asarray(starts)), jnp.stack(want)


# Below the window, at it, a block boundary after it, far past it; and a
# row with nothing cached.
LENGTHS = [0, 5, 40, 48, 49, 64, 137]


# Pages a fetch: one, what `pages_per_step` takes for the table (the
# compact table's 11 columns under a window of 40: 16; the global table's
# 36: 64), 3 (a ragged last group in both tables), 4 (a row's 9 live
# pages go as three groups of 3, a page short of the slab, which a pass
# of the body reads whole) and 16 (more than the compact table names).
PAGES = [1, None, 3, 4, 16]
PAGES_IDS = ["pages1", "pages_by_rule", "pages3_ragged", "pages4_short",
             "pages16"]


@pytest.mark.parametrize("pages", PAGES, ids=PAGES_IDS)
@pytest.mark.parametrize("heads", [48, 72], ids=["group6", "group9"])
@pytest.mark.parametrize("window", [40, None], ids=["window40", "global"])
def test_windowed_paged_attention_matches_the_band_mask(heads, window,
                                                        pages):
    args, want = _paged_case(heads, window, LENGTHS)
    twin = pa.paged_decode_attention_xla(*args)
    assert float(jnp.max(jnp.abs(twin - want))) < 2e-5
    kernel = pa.paged_decode_attention_kernel(*args, pages=pages,
                                              interpret=True)
    assert float(jnp.max(jnp.abs(kernel - want))) < 2e-5


def test_a_window_table_one_block_short_changes_the_result():
    """The same pool read with a window one block shorter: the rows past
    the window differ, the rows inside it do not."""
    args, want = _paged_case(72, 40, LENGTHS)
    short = pa.paged_decode_attention_xla(*args[:7], 40 - BS, args[8])
    gaps = np.asarray(jnp.max(jnp.abs(short - want), axis=(1, 2)))
    assert (gaps[:2] < 2e-5).all() and (gaps[2:] > 1e-3).all()


@pytest.mark.parametrize("pages", PAGES, ids=PAGES_IDS)
def test_windowed_kernel_reads_a_bf16_pool_like_its_twin(pages):
    args, _ = _paged_case(72, 40, LENGTHS, dtype=jnp.bfloat16)
    twin = pa.paged_decode_attention_xla(*args)
    kernel = pa.paged_decode_attention_kernel(*args, pages=pages,
                                              interpret=True)
    assert float(jnp.max(jnp.abs(kernel - twin))) < 1e-4


def test_the_group_walk_starts_at_the_first_page_the_window_reaches():
    """A compact table whose first live key lies in column 1, not 0 (the
    cache manager releases a block a step after it left the window):
    with a fetch of 2 pages the walk starts there, so columns 1-2 are
    one group; garbage in column 0's block never reaches the result."""
    args, want = _paged_case(72, 40, [137])
    q, k, v, pool, tables, lengths, layer, window, starts = args
    # Hand the row the block before its window too, full of NaN.
    nan_block = min(set(range(pool.shape[0]))
                    - set(np.asarray(tables).ravel().tolist()))
    assert int(tables[0, -1]) == 0      # the table has a column to spare
    pool = pool.at[nan_block].set(jnp.nan)
    tables = jnp.concatenate(
        [jnp.full((1, 1), nan_block, jnp.int32), tables[:, :-1]], axis=1)
    shifted = (q, k, v, pool, tables, lengths, layer, window, starts - 1)
    for pages in (1, 2, None):
        kernel = pa.paged_decode_attention_kernel(*shifted, pages=pages,
                                                  interpret=True)
        assert float(jnp.max(jnp.abs(kernel - want))) < 2e-5


@pytest.mark.parametrize("heads, hkv", [(6, 1), (9, 1), (18, 2)],
                         ids=["group6", "group9", "two_groups_of_9"])
@pytest.mark.parametrize("window", [None, 128, 200, 512],
                         ids=["causal", "w128", "w200", "w512"])
def test_prefill_flash_forward_matches_the_band_mask(heads, hkv, window):
    s = 384
    ks = jax.random.split(jax.random.PRNGKey(heads), 3)
    q = jax.random.normal(ks[0], (s, heads, HD), jnp.float32)
    k = jax.random.normal(ks[1], (s, hkv, HD), jnp.float32)
    v = jax.random.normal(ks[2], (s, hkv, HD), jnp.float32)
    want = _band_reference(q, k, v, window)
    args = tuple(t.transpose(1, 0, 2) for t in (q, k, v))
    got = prefill_attention_fwd(*args, window, block=128, interpret=True)
    assert got.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(got.transpose(1, 0, 2) - want))) < 2e-5
    plain = banded_attention(*args, window)
    assert float(jnp.max(jnp.abs(plain.transpose(1, 0, 2) - want))) < 2e-5


def test_prefill_flash_forward_refuses_shapes_it_cannot_tile():
    x = jnp.zeros((6, 200, HD))
    with pytest.raises(ValueError, match="divides"):
        prefill_attention_fwd(x, x[:1], x[:1], interpret=True)
    with pytest.raises(ValueError, match="does not go over"):
        prefill_attention_fwd(x, x[:4], x[:4], block=128, interpret=True)


# -- rotary ----------------------------------------------------------------
YARN = {"factor": 128, "original_max_position_embeddings": 8192,
        "beta_fast": 32, "beta_slow": 1}


def _direct_rotation(x, positions, rot, theta, yarn=None, factor=1.0):
    """float64, written out pair by pair."""
    x = np.asarray(x, np.float64)
    out = x.copy()
    half = rot // 2
    for i in range(half):
        freq = theta ** (-2.0 * i / rot)
        if yarn:
            def pair(turns):
                return rot * math.log(
                    yarn["original_max_position_embeddings"]
                    / (turns * 2 * math.pi)) / (2 * math.log(theta))
            low = max(math.floor(pair(yarn["beta_fast"])), 0)
            high = min(math.ceil(pair(yarn["beta_slow"])), rot - 1)
            ramp = min(max((i - low) / (high - low), 0.0), 1.0)
            freq = freq / yarn["factor"] * ramp + freq * (1 - ramp)
        for t, p in enumerate(positions):
            c, s = math.cos(p * freq) * factor, math.sin(p * freq) * factor
            a, b = x[t, :, i], x[t, :, i + half]
            out[t, :, i] = a * c - b * s
            out[t, :, i + half] = b * c + a * s
    return out


@pytest.mark.parametrize("rot, theta, yarn, factor", [
    (64, 500000.0, YARN, 1.4852030263919618), (128, 10000.0, None, 1.0)],
    ids=["partial_yarn", "whole_plain"])
def test_rotary_schemes_match_a_direct_float64_formula(rot, theta, yarn,
                                                       factor):
    from ray_tpu.ops.rotary import (apply_rotary_partial, rotary_cos_sin,
                                    rotary_inv_freq)

    positions = np.array([0, 1, 7, 511, 4095, 4479])
    x = np.random.default_rng(0).normal(size=(6, 3, 128)).astype(np.float32)
    cos, sin = rotary_cos_sin(jnp.asarray(positions),
                              rotary_inv_freq(rot, theta, yarn), factor)
    got = np.asarray(apply_rotary_partial(jnp.asarray(x), cos, sin))
    want = _direct_rotation(x, positions, rot, theta, yarn, factor)
    # float32 angles at position 4479: a few 1e-4 of a radian.
    assert np.max(np.abs(got - want)) < 3e-3
    assert np.array_equal(got[..., rot:], x[..., rot:])
    if yarn:
        # The pairs past the ramp turn 128 times slower than plain ones.
        plain = rotary_inv_freq(rot, theta)
        scaled = rotary_inv_freq(rot, theta, yarn)
        assert np.allclose(scaled[-1] * 128, plain[-1], rtol=1e-6)
        assert np.allclose(scaled[0], plain[0])


def test_whole_head_rotation_is_apply_rotary():
    from ray_tpu.ops.rotary import (apply_rotary, apply_rotary_partial,
                                    rotary_cos_sin, rotary_freqs,
                                    rotary_inv_freq)

    x = jax.random.normal(jax.random.PRNGKey(1), (9, 2, 16))
    cos, sin = rotary_freqs(16, 32)
    want = apply_rotary(x, cos, sin)
    got = apply_rotary_partial(x, *rotary_cos_sin(
        jnp.arange(9), rotary_inv_freq(16, 10000.0)))
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5


# -- the router ------------------------------------------------------------
def test_route_scores_by_softmax_or_sigmoid_as_it_is_told():
    from ray_tpu.ops.experts import route

    y = jax.random.normal(jax.random.PRNGKey(2), (7, 24))
    w = jax.random.normal(jax.random.PRNGKey(3), (24, 16))
    experts, weights = route(y, w, None, 4, 2.5, "softmax")
    probs = np.asarray(jax.nn.softmax(
        np.asarray(y, np.float64) @ np.asarray(w, np.float64), axis=-1))
    order = np.argsort(-probs, axis=-1)[:, :4]
    assert np.array_equal(np.sort(np.asarray(experts), -1),
                          np.sort(order, -1))
    chosen = np.take_along_axis(probs, np.asarray(experts), axis=-1)
    assert np.allclose(np.asarray(weights),
                       chosen / chosen.sum(-1, keepdims=True) * 2.5,
                       atol=1e-5)
    assert np.allclose(np.asarray(weights).sum(-1), 2.5, atol=1e-5)
    # Softmax is monotone in the logits, as sigmoid is: the same experts
    # are chosen; the weights differ.
    sig_experts, sig_weights = route(y, w, jnp.zeros((16,)), 4, 2.5)
    assert np.array_equal(np.asarray(sig_experts), np.asarray(experts))
    assert not np.allclose(np.asarray(sig_weights), np.asarray(weights))
    with pytest.raises(ValueError, match="sigmoid or softmax"):
        route(y, w, None, 4, 1.0, "tanh")
