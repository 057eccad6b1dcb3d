"""Attention with keys wider than values (192 over 128), fewer than 8
key/value heads (4 under 16 query heads each), a learned sink logit a
head and a window narrower than the prefill's tile, off the chip: the
paged decode attention (its XLA body, and the Pallas kernel interpreted)
and the serving prefill's flash forward (interpreted) with
`banded_attention`, each against a softmax written out here; the pool
row of the two widths written and read back. The kernels are compiled
for a described v5e in `tests/test_ops_paged_attention.py`, which owns
the topology."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import paged_attention as pa
from ray_tpu.ops.attention import banded_attention
from ray_tpu.ops.flash_attention import prefill_attention_fwd, prefill_block

pytestmark = pytest.mark.unit

DK, DV, BS = 192, 128, 16


def _reference(q, k, v, window, sink):
    """q [S, H, dk], k [S, Hkv, dk], v [S, Hkv, dv], sink [H] or None:
    every query's softmax over the keys it sees and the sink's column,
    float64, a head at a time."""
    q, k, v = (np.asarray(t, np.float64) for t in (q, k, v))
    s, h, dk = q.shape
    group = h // k.shape[1]
    idx = np.arange(s)
    keep = idx[:, None] >= idx[None, :]
    if window is not None:
        keep &= idx[:, None] - idx[None, :] < window
    out = np.zeros((s, h, v.shape[2]))
    for i in range(h):
        scores = q[:, i] @ k[:, i // group].T / np.sqrt(dk)
        scores = np.where(keep, scores, -np.inf)
        top = scores.max(axis=1, keepdims=True)
        if sink is not None:
            top = np.maximum(top, float(sink[i]))
        p = np.exp(scores - top)
        total = p.sum(axis=1, keepdims=True)
        if sink is not None:
            total = total + np.exp(float(sink[i]) - top)
        out[:, i] = (p / total) @ v[:, i // group]
    return out


def _paged_case(heads, hkv, window, lengths, with_sink, seed=0,
                dtype=jnp.float32):
    """Sequences of `lengths` cached tokens (plus the step's own) in a
    pool of 2 layers whose rows hold keys of 192 and values of 128
    (`kv_row`): tables compact under a window, shuffled physical blocks,
    garbage in the blocks no table names."""
    layer = 1
    rng = np.random.default_rng(seed)
    b = len(lengths)
    width = -(-window // BS) + 1 if window else -(-max(lengths) // BS) + 1
    n_blocks = b * width + 3
    slots = pa.kv_slots(DK, DV)
    pool = rng.normal(size=(n_blocks, BS, 2, slots, hkv, DV)
                      ).astype(np.float32)
    free = list(rng.permutation(n_blocks))
    tables = np.zeros((b, width), np.int32)
    starts = np.zeros((b,), np.int32)
    dense = []
    for i, n in enumerate(lengths):
        k = rng.normal(size=(n + 1, hkv, DK)).astype(np.float32)
        v = rng.normal(size=(n + 1, hkv, DV)).astype(np.float32)
        dense.append((k, v))
        rows = np.asarray(pa.kv_row(jnp.asarray(k), jnp.asarray(v)))
        first = max(0, n - window + 1) // BS if window else 0
        starts[i] = first
        for blk in range(first, -(-n // BS)):
            phys = free.pop()
            tables[i, blk - first] = phys
            part = rows[blk * BS:min((blk + 1) * BS, n)]
            pool[phys, :len(part), layer] = part
    q = rng.normal(size=(b, heads, DK)).astype(np.float32)
    sink = (rng.normal(size=(heads,)).astype(np.float32) * 2
            if with_sink else None)
    want = []
    for i, n in enumerate(lengths):
        k, v = dense[i]
        qs = np.zeros((n + 1, heads, DK), np.float32)
        qs[n] = q[i]
        want.append(_reference(qs, k, v, window, sink)[n])
    return (jnp.asarray(q),
            jnp.asarray(np.stack([d[0][-1] for d in dense]), dtype),
            jnp.asarray(np.stack([d[1][-1] for d in dense]), dtype),
            jnp.asarray(pool, dtype), jnp.asarray(tables),
            jnp.asarray(lengths, jnp.int32), jnp.int32(layer), window,
            jnp.asarray(starts),
            None if sink is None else jnp.asarray(sink)), np.stack(want)


LENGTHS = [0, 5, 40, 48, 49, 64, 137]


@pytest.mark.parametrize("pages", [1, None, 3], ids=["pages1", "by_rule",
                                                     "pages3_ragged"])
@pytest.mark.parametrize("with_sink", [False, True], ids=["plain", "sink"])
@pytest.mark.parametrize("window", [None, 40], ids=["global", "window40"])
@pytest.mark.parametrize("heads, hkv", [(64, 4), (64, 8)],
                         ids=["4_key_heads_under_16", "8_under_8"])
def test_paged_attention_takes_wide_keys_few_key_heads_and_a_sink(
        heads, hkv, window, with_sink, pages):
    args, want = _paged_case(heads, hkv, window, LENGTHS, with_sink)
    twin = pa.paged_decode_attention_xla(*args)
    assert twin.shape == (len(LENGTHS), heads, DV)
    assert float(np.max(np.abs(np.asarray(twin) - want))) < 3e-5
    kernel = pa.paged_decode_attention_kernel(*args, pages=pages,
                                              interpret=True)
    assert float(np.max(np.abs(np.asarray(kernel) - want))) < 3e-5


def test_the_sink_changes_every_row_and_a_large_one_takes_the_mass():
    args, want = _paged_case(64, 4, 40, LENGTHS, True)
    without = pa.paged_decode_attention_xla(*args[:9], None)
    gaps = np.max(np.abs(np.asarray(without) - want), axis=(1, 2))
    assert (gaps > 1e-3).all()
    heavy = pa.paged_decode_attention_kernel(
        *args[:9], jnp.full((64,), 80.0), interpret=True)
    assert float(jnp.max(jnp.abs(heavy))) < 1e-6


def test_the_kernel_reads_a_bf16_pool_of_two_widths_like_its_twin():
    args, _ = _paged_case(64, 4, 40, LENGTHS, True, dtype=jnp.bfloat16)
    twin = pa.paged_decode_attention_xla(*args)
    kernel = pa.paged_decode_attention_kernel(*args, interpret=True)
    assert float(jnp.max(jnp.abs(kernel - twin))) < 1e-4


@pytest.mark.parametrize("hkv, dk, dv, slots", [
    (4, 192, 128, 3), (8, 192, 128, 3), (8, 128, 128, 2), (2, 24, 16, 3),
    (2, 40, 16, 4)])
def test_a_pool_row_of_two_widths_is_written_and_read_back(hkv, dk, dv,
                                                           slots):
    ks = jax.random.split(jax.random.PRNGKey(hkv), 2)
    k = jax.random.normal(ks[0], (5, hkv, dk))
    v = jax.random.normal(ks[1], (5, hkv, dv))
    rows = pa.kv_row(k, v)
    assert rows.shape == (5, slots, hkv, dv) == (5, pa.kv_slots(dk, dv),
                                                 hkv, dv)
    keys = pa._keys_of(rows)
    assert jnp.array_equal(keys[..., :dk], k)
    assert not jnp.any(keys[..., dk:])          # filled up with zeros
    assert jnp.array_equal(rows[:, 1], v)
    if dk == dv:
        assert jnp.array_equal(rows, jnp.stack([k, v], axis=1))


def test_the_kernel_refuses_a_pool_of_other_rows():
    args, _ = _paged_case(64, 4, None, [5], False)
    pool = args[3][:, :, :, :2]                 # no slot for the wide keys
    with pytest.raises(ValueError, match="does not hold"):
        pa.paged_decode_attention_kernel(*args[:3], pool, *args[4:],
                                         interpret=True)


def test_eligibility_takes_4_key_heads_and_values_of_128(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pa.kernel_eligible(64, 192, 4, 128)
    assert pa.kernel_eligible(64, 192, 8, 128)
    assert pa.kernel_eligible(48, 128, 8)       # as before
    assert pa.kernel_eligible(64, 192, 2, 128)      # 2 heads by planes too
    assert not pa.kernel_eligible(64, 192, 1, 128)
    assert not pa.kernel_eligible(64, 192, 4, 64)
    assert not pa.kernel_eligible(12, 128)
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert not pa.kernel_eligible(64, 192, 4, 128)


@pytest.mark.parametrize("with_sink", [False, True], ids=["plain", "sink"])
@pytest.mark.parametrize("window", [None, 128, 100], ids=["causal", "w128",
                                                          "w100"])
@pytest.mark.parametrize("heads, hkv", [(16, 1), (16, 2)],
                         ids=["group16", "two_groups_of_8"])
def test_prefill_forward_takes_wide_keys_a_sink_and_a_narrow_window(
        heads, hkv, window, with_sink):
    s = 512
    ks = jax.random.split(jax.random.PRNGKey(heads + hkv), 4)
    q = jax.random.normal(ks[0], (s, heads, DK), jnp.float32)
    k = jax.random.normal(ks[1], (s, hkv, DK), jnp.float32)
    v = jax.random.normal(ks[2], (s, hkv, DV), jnp.float32)
    sink = 2 * jax.random.normal(ks[3], (heads,)) if with_sink else None
    want = _reference(q, k, v, window, sink)
    args = tuple(t.transpose(1, 0, 2) for t in (q, k, v))
    # The tile by the rule: 128 under these windows, 512 without one.
    got = prefill_attention_fwd(*args, window, sink, interpret=True)
    assert got.shape == (heads, s, DV) and got.dtype == jnp.float32
    assert float(np.max(np.abs(
        np.asarray(got.transpose(1, 0, 2)) - want))) < 3e-5
    plain = banded_attention(*args, window, sink)
    assert float(np.max(np.abs(
        np.asarray(plain.transpose(1, 0, 2)) - want))) < 3e-5


@pytest.mark.parametrize("seq, window, block", [
    (8192, None, 512), (8192, 512, 512), (8192, 128, 128), (8192, 100, 128),
    (8192, 256, 256), (256, None, 256), (256, 128, 128), (128, 16, 128)])
def test_the_prefill_tile_narrows_to_a_narrow_window(seq, window, block):
    assert prefill_block(seq, window) == block
