"""Multi-head latent attention over a cache of one row a position
(`ops/latent_attention.py`): the absorbed form gives the expanded form's
numbers on the same latents, the paged walk's latent body (interpreted)
gives the XLA body's, a row is 576 values in 5 planes written once, and
the delta rule with one scalar decay a head (`ops/delta_rule.py`) gives
the per-channel form's numbers on a broadcast decay."""

import numpy as np
import pytest

pytestmark = pytest.mark.unit

H, RANK, ROPE, NOPE, DV = 8, 128, 64, 32, 32


def _layer(seed=0, dtype="float32"):
    import jax
    import jax.numpy as jnp

    k = jax.random.split(jax.random.PRNGKey(seed), 2)
    dt = jnp.dtype(dtype)
    return {"wuk": (jax.random.normal(k[0], (RANK, H * NOPE))
                    * RANK ** -0.5).astype(dt),
            "wuv": (jax.random.normal(k[1], (RANK, H * DV))
                    * RANK ** -0.5).astype(dt)}


def _pool_of(rows, n_blocks, bs, table, dtype):
    """A planes pool of two layers whose layer 1 holds `rows` ``[S, P,
    128]`` at the blocks `table` names, noise elsewhere."""
    import jax
    import jax.numpy as jnp

    planes = rows.shape[1]
    pool = jax.random.normal(jax.random.PRNGKey(9),
                             (n_blocks, 2, planes, bs, 128)).astype(dtype)
    s = rows.shape[0]
    pad = -s % bs
    blocks = jnp.pad(rows, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, bs, planes, 128).transpose(0, 2, 1, 3)
    return pool.at[jnp.asarray(table[:blocks.shape[0]]), 1].set(
        blocks.astype(dtype))


def test_a_row_is_the_latent_and_the_rotary_key_in_whole_planes():
    import jax.numpy as jnp

    from ray_tpu.ops import latent_attention as la

    assert la.latent_planes(512 + 64) == 5
    c_kv = jnp.arange(3 * 512, dtype=jnp.float32).reshape(3, 512)
    k_r = -jnp.arange(3 * 64, dtype=jnp.float32).reshape(3, 64)
    row = la.latent_row(c_kv, k_r)
    assert row.shape == (3, 5, 128)
    flat = np.asarray(row.reshape(3, 640))
    np.testing.assert_array_equal(flat[:, :512], c_kv)
    np.testing.assert_array_equal(flat[:, 512:576], k_r)
    assert not flat[:, 576:].any()
    # Pages -> rows is the inverse of how the cache lays a block.
    pages = row.reshape(1, 3, 5, 128).transpose(0, 2, 1, 3)   # [nb, P, bs, 128]
    np.testing.assert_array_equal(la.rows_of_pages(pages), flat)


@pytest.mark.parametrize("positions", [[0, 5, 37], [16, 48, 63]])
def test_absorbed_is_expanded_on_the_same_latents(positions):
    """The decode step's form (the query takes W_uk, the rows are met as
    they lie, the output takes W_uv) against the prompt's form (keys and
    values a head multiplied out, a plain causal softmax) for the query
    at each row's position."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import latent_attention as la
    from ray_tpu.ops.attention import banded_attention

    lp = _layer()
    k = jax.random.split(jax.random.PRNGKey(1), 4)
    s, bs = 64, 16
    c_kv = jax.random.normal(k[0], (s, RANK))
    k_r = jax.random.normal(k[1], (s, ROPE))
    q_nope = jax.random.normal(k[2], (s, H, NOPE))
    q_r = jax.random.normal(k[3], (s, H, ROPE))
    scale = 0.37 * (NOPE + ROPE) ** -0.5
    with jax.default_matmul_precision("highest"):
        keys, vals = la.expand_latent(c_kv, k_r, lp["wuk"], lp["wuv"], H)
        q = jnp.concatenate([q_nope, q_r], axis=-1).transpose(1, 0, 2)
        want = banded_attention(q * 0.37, keys, vals).transpose(1, 0, 2)
        rows = la.latent_row(c_kv, k_r)
        table = [3, 7, 1, 5]
        pool = _pool_of(rows, 9, bs, table, jnp.float32)
        at = jnp.asarray(positions)
        width = rows.shape[1] * 128
        q_abs = la.absorb_query(q_nope[at], q_r[at], lp["wuk"], width)
        tables = jnp.asarray([table] * len(positions), jnp.int32)
        for interpret in (None, True):
            o_lat = la.paged_latent_decode_attention(
                q_abs, rows[at].reshape(len(positions), -1), pool, tables,
                at.astype(jnp.int32), jnp.int32(1), RANK, scale,
                interpret=interpret)
            got = la.unabsorb_output(o_lat, lp["wuv"])
            np.testing.assert_allclose(got, want[at], atol=2e-5)


def test_the_walks_latent_body_matches_the_xla_body_in_bf16():
    """Both bodies round the query and the probabilities to the pool's
    dtype and accumulate in float32."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import latent_attention as la

    k = jax.random.split(jax.random.PRNGKey(2), 4)
    b, bs, n, nb, planes = 3, 16, 40, 8, 2
    pool = jax.random.normal(k[0], (n, 2, planes, bs, 128)).astype(
        jnp.bfloat16)
    tables = jax.random.permutation(k[1], n)[:b * nb].reshape(
        b, nb).astype(jnp.int32)
    positions = jnp.asarray([0, 37, 127], jnp.int32)
    q = jax.random.normal(k[2], (b, H, planes * 128))
    row = jax.random.normal(k[3], (b, planes * 128)).astype(jnp.bfloat16)
    want = la.paged_latent_decode_attention_xla(
        q, row, pool, tables, positions, jnp.int32(1), 128, 0.1)
    got = la.paged_latent_decode_attention(
        q, row, pool, tables, positions, jnp.int32(1), 128, 0.1,
        interpret=True)
    assert got.shape == (b, H, 128) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=2e-2)
    # Position 0 sees its own row alone: the row's latent comes back.
    np.testing.assert_allclose(
        got[0], jnp.broadcast_to(row[0, :128].astype(jnp.float32),
                                 (H, 128)), atol=1e-6)


def test_a_stale_row_past_the_position_is_never_read():
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import latent_attention as la

    k = jax.random.split(jax.random.PRNGKey(3), 3)
    pool = jax.random.normal(k[0], (6, 1, 2, 16, 128))
    tables = jnp.asarray([[2, 4, 0, 0]], jnp.int32)
    q = jax.random.normal(k[1], (1, H, 256))
    row = jax.random.normal(k[2], (1, 256))
    args = (tables, jnp.asarray([21], jnp.int32), jnp.int32(0), 128, 0.2)
    base = la.paged_latent_decode_attention(q, row, pool, *args,
                                            interpret=True)
    # Block 4's offsets 5.. (positions 21..) and block 0 hold NaN-free
    # garbage of another magnitude: nothing moves.
    dirty = pool.at[4, 0, :, 5:].set(1e6).at[0].set(-1e6)
    for interpret in (None, True):
        again = la.paged_latent_decode_attention(q, row, dirty, *args,
                                                 interpret=interpret)
        np.testing.assert_allclose(again, base, atol=1e-5)


def test_the_kernel_takes_whole_planes_and_whole_sublanes(monkeypatch):
    import jax

    from ray_tpu.ops import latent_attention as la

    assert not la.kernel_eligible(64, 512)          # off the chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert la.kernel_eligible(64, 512)
    assert not la.kernel_eligible(64, 96) and not la.kernel_eligible(4, 512)


# -- the delta rule with one scalar decay a head ---------------------------
def _delta_inputs(s=64, h=4, dk=16, seed=0, strong=False):
    import jax
    import jax.numpy as jnp

    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(k[0], (s, h, dk)) * dk ** -0.5
    kk = jax.random.normal(k[1], (s, h, dk))
    kk = kk / jnp.linalg.norm(kk, axis=-1, keepdims=True)
    v = jax.random.normal(k[2], (s, h, dk))
    g = -jax.random.uniform(k[3], (s, h)) * (12.0 if strong else 1.0)
    beta = jax.random.uniform(k[4], (s, h))
    s0 = jax.random.normal(k[5], (h, dk, dk))
    return q, kk, v, g, beta, s0


@pytest.mark.parametrize("strong", [False, True],
                         ids=["mild", "a_chunk_sums_past_88"])
def test_scalar_decay_is_the_per_channel_form_on_a_broadcast_decay(strong):
    import jax.numpy as jnp

    from ray_tpu.ops.delta_rule import delta_rule_chunked, delta_rule_step

    q, k, v, g, beta, s0 = _delta_inputs(strong=strong)
    wide = jnp.broadcast_to(g[..., None], q.shape)
    o_scalar, s_scalar = delta_rule_chunked(q, k, v, g, beta, s0, 16)
    o_channel, s_channel = delta_rule_chunked(q, k, v, wide, beta, s0, 16)
    assert np.isfinite(np.asarray(o_scalar)).all()
    np.testing.assert_allclose(o_scalar, o_channel, atol=2e-5)
    np.testing.assert_allclose(s_scalar, s_channel, atol=2e-5)
    o1, s1 = delta_rule_step(s0, q[0], k[0], v[0], g[0], beta[0])
    o2, s2 = delta_rule_step(s0, q[0], k[0], v[0], wide[0], beta[0])
    np.testing.assert_allclose(o1, o2, atol=1e-6)
    np.testing.assert_allclose(s1, s2, atol=1e-6)


def test_chunked_scalar_decay_is_the_token_by_token_rule_from_any_state():
    """A chunked prefill that starts from a carried state ends on what
    token-by-token steps from the same state end on."""
    import jax.numpy as jnp

    from ray_tpu.ops.delta_rule import delta_rule_chunked, delta_rule_step

    q, k, v, g, beta, s0 = _delta_inputs(seed=1)
    o_chunked, s_chunked = delta_rule_chunked(q, k, v, g, beta, s0, 16)
    state, outs = s0, []
    for t in range(q.shape[0]):
        o, state = delta_rule_step(state, q[t], k[t], v[t], g[t], beta[t])
        outs.append(o)
    np.testing.assert_allclose(o_chunked, jnp.stack(outs), atol=2e-5)
    np.testing.assert_allclose(s_chunked, state, atol=2e-5)
    # In two halves, the second from the first's end state.
    half = q.shape[0] // 2
    _, s_half = delta_rule_chunked(q[:half], k[:half], v[:half], g[:half],
                                   beta[:half], s0, 16)
    o_rest, s_rest = delta_rule_chunked(q[half:], k[half:], v[half:],
                                        g[half:], beta[half:], s_half, 16)
    np.testing.assert_allclose(o_rest, o_chunked[half:], atol=2e-5)
    np.testing.assert_allclose(s_rest, s_chunked, atol=2e-5)


def test_scalar_decay_padding_leaves_the_state_bit_for_bit():
    import jax.numpy as jnp

    from ray_tpu.ops.delta_rule import delta_rule_step

    q, k, v, g, beta, s0 = _delta_inputs(seed=2)
    _, s1 = delta_rule_step(s0, q[0], k[0], v[0], jnp.zeros_like(g[0]),
                            jnp.zeros_like(beta[0]))
    np.testing.assert_array_equal(s1, s0)


def test_the_per_channel_programs_keep_their_jaxpr():
    """`decode-wide`'s delta rule: a decay of the keys' rank traces what
    it did before the scalar form was there (no `[H, C, C]` product on
    its path, the pairs' sums on the vector unit)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.delta_rule import delta_rule_chunked

    q, k, v, g, beta, s0 = _delta_inputs()
    wide = jnp.broadcast_to(g[..., None], q.shape)
    channel = str(jax.make_jaxpr(
        lambda *a: delta_rule_chunked(*a, 16))(q, k, v, wide, beta, s0))
    scalar = str(jax.make_jaxpr(
        lambda *a: delta_rule_chunked(*a, 16))(q, k, v, g, beta, s0))
    assert "f32[4,16,16,16]" in channel         # [H, C, C, dk] decays
    assert "f32[4,16,16,16]" not in scalar
