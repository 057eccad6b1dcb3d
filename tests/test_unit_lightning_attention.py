"""Lightning attention (`ops/lightning_attention.py`): the chunked form,
the one step and the plain recurrence give one result; a prompt in chunks
ends on the whole prompt's state; a padded position leaves the state as
it is."""

import numpy as np
import pytest

import jax.numpy as jnp

from ray_tpu.ops.lightning_attention import (lightning_chunked,
                                             lightning_step,
                                             lightning_step_in_pool,
                                             lightning_step_kernel)

pytestmark = pytest.mark.unit

H, DK = 3, 8
LOG_DECAY = np.log(np.asarray([0.55, 0.9, 0.997], np.float32))


def _inputs(s, seed=0):
    rng = np.random.default_rng(seed)
    q, k, v = (rng.normal(size=(s, H, DK)).astype(np.float32)
               for _ in range(3))
    return q, k, v


def _recurrence(q, k, v, state=None):
    """``S_t = lambda S_{t-1} + k_t v_t^T``, ``o_t = S_t^T q_t``, numpy."""
    state = np.zeros((H, DK, DK), np.float64) if state is None else state
    decay = np.exp(LOG_DECAY.astype(np.float64))[:, None, None]
    out = []
    for t in range(q.shape[0]):
        state = decay * state + k[t][:, :, None] * v[t][:, None, :]
        out.append(np.einsum("hkv,hk->hv", state, q[t]))
    return np.stack(out), state


@pytest.mark.parametrize("s,chunk", [(8, 8), (32, 8), (32, 16), (48, 4)])
def test_chunked_is_the_recurrence(s, chunk):
    q, k, v = _inputs(s, s + chunk)
    g = np.broadcast_to(LOG_DECAY, (s, H))
    o, state = lightning_chunked(q, k, v, g, jnp.zeros((H, DK, DK)), chunk)
    want_o, want_state = _recurrence(q, k, v)
    np.testing.assert_allclose(o, want_o, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(state, want_state, rtol=2e-5, atol=2e-5)


def test_one_step_at_a_time_is_the_recurrence():
    q, k, v = _inputs(20, 3)
    state = jnp.zeros((H, DK, DK))
    outs = []
    for t in range(20):
        o, state = lightning_step(state, q[t], k[t], v[t],
                                  jnp.asarray(LOG_DECAY))
        outs.append(np.asarray(o))
    want_o, want_state = _recurrence(q, k, v)
    np.testing.assert_allclose(np.stack(outs), want_o, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(state, want_state, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("cut", [8, 16, 24])
def test_a_prompt_in_two_parts_ends_on_the_whole_prompts_state(cut):
    q, k, v = _inputs(32, cut)
    g = np.broadcast_to(LOG_DECAY, (32, H))
    zero = jnp.zeros((H, DK, DK))
    o, state = lightning_chunked(q, k, v, g, zero, 8)
    o1, s1 = lightning_chunked(q[:cut], k[:cut], v[:cut], g[:cut], zero, 8)
    o2, s2 = lightning_chunked(q[cut:], k[cut:], v[cut:], g[cut:], s1, 8)
    np.testing.assert_allclose(np.concatenate([o1, o2]), o, rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(s2, state, rtol=1e-5, atol=1e-5)


def test_padded_positions_leave_the_state_as_it_is():
    q, k, v = _inputs(16, 5)
    live = np.arange(16) < 11
    g = np.where(live[:, None], LOG_DECAY[None], 0.0).astype(np.float32)
    _, state = lightning_chunked(q, np.where(live[:, None, None], k, 0.0),
                                 v, g, jnp.zeros((H, DK, DK)), 8)
    _, want = _recurrence(q[:11], k[:11], v[:11])
    np.testing.assert_allclose(state, want, rtol=2e-5, atol=2e-5)
    # A step of a slot no row uses: bit for bit.
    o, same = lightning_step(state, jnp.zeros((H, DK)), jnp.zeros((H, DK)),
                             jnp.zeros((H, DK)), jnp.zeros((H,)))
    assert np.array_equal(np.asarray(same), np.asarray(state))


def test_lengths_that_are_no_whole_chunks_are_refused():
    q, k, v = _inputs(12)
    with pytest.raises(ValueError, match="no multiple"):
        lightning_chunked(q, k, v, np.zeros((12, H), np.float32),
                          jnp.zeros((H, DK, DK)), 8)


def _pool_step(n=3, layers=2, h=16, dk=16, dv=128, seed=5):
    rng = np.random.default_rng(seed)
    pool = jnp.asarray(rng.normal(size=(n, layers, h, dk, dv)), jnp.float32)
    q, k = (jnp.asarray(rng.normal(size=(n, h, dk)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.normal(size=(n, h, dv)), jnp.float32)
    g = jnp.asarray(-rng.random((n, h)), jnp.float32)
    # Slot 1 has no row: no decay, no key.
    return pool, q, k.at[1].set(0.0), v, g.at[1].set(0.0)


@pytest.mark.parametrize("layer", [0, 1])
def test_the_step_kernel_is_the_step_over_its_layer_in_place(layer):
    """The Pallas body, interpreted: the layer's states moved on as
    `lightning_step` moves them, every other layer and a slot without a
    row left bit for bit."""
    pool, q, k, v, g = _pool_step()
    o, new = lightning_step_kernel(pool, layer, q, k, v, g, interpret=True)
    want_o, want_state = lightning_step(pool[:, layer], q, k, v, g)
    np.testing.assert_allclose(o, want_o, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(new[:, layer], want_state, rtol=1e-6,
                               atol=1e-6)
    assert (new[:, 1 - layer] == pool[:, 1 - layer]).all()
    assert (new[1, layer] == pool[1, layer]).all()


def test_the_step_in_a_pool_off_the_chip_is_the_sliced_step():
    pool, q, k, v, g = _pool_step(h=3, dk=8, dv=8)
    o, new = lightning_step_in_pool(pool, 1, q, k, v, g)
    want_o, want_state = lightning_step(pool[:, 1], q, k, v, g)
    np.testing.assert_array_equal(o, want_o)
    np.testing.assert_array_equal(new[:, 1], want_state)
    assert (new[:, 0] == pool[:, 0]).all()
