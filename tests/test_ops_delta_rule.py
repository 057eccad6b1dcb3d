"""The gated delta rule (`ops/delta_rule.py`): the chunked prefill form,
the one-step decode form and the recurrence written out in float64 give
the same outputs and the same state, with `beta` over 1 present."""

import numpy as np
import pytest

pytestmark = pytest.mark.unit

S, H, DK, DV = 48, 3, 16, 8


def _inputs(seed=0):
    rng = np.random.default_rng(seed)
    q = rng.normal(size=(S, H, DK))
    k = rng.normal(size=(S, H, DK))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.normal(size=(S, H, DV))
    g = -rng.uniform(0.001, 0.3, size=(S, H, DK))
    beta = rng.uniform(0.0, 2.0, size=(S, H))
    state = rng.normal(size=(H, DK, DV))
    assert (beta > 1).any()
    return q, k, v, g, beta, state


def _recurrence(q, k, v, g, beta, state):
    """S_t = (I - beta k k^T) diag(a) S_{t-1} + beta k v^T; o = S^T q."""
    s = state.astype(np.float64).copy()
    outs = []
    for t in range(q.shape[0]):
        for h in range(H):
            decayed = np.exp(g[t, h])[:, None] * s[h]
            s[h] = ((np.eye(DK) - beta[t, h] * np.outer(k[t, h], k[t, h]))
                    @ decayed + beta[t, h] * np.outer(k[t, h], v[t, h]))
        outs.append(np.einsum("hkv,hk->hv", s, q[t]))
    return np.stack(outs), s


@pytest.mark.parametrize("chunk", [8, 16, 48])
def test_chunked_form_equals_the_recurrence(chunk):
    import jax.numpy as jnp

    from ray_tpu.ops.delta_rule import delta_rule_chunked

    inputs = _inputs()
    want_o, want_s = _recurrence(*inputs)
    o, s = delta_rule_chunked(
        *(jnp.asarray(x, jnp.float32) for x in inputs), chunk=chunk)
    np.testing.assert_allclose(o, want_o, atol=2e-5)
    np.testing.assert_allclose(s, want_s, atol=2e-5)


@pytest.mark.parametrize("strongest", [4.0, 40.0])
def test_chunked_form_holds_under_a_decay_that_sums_past_100_a_chunk(
        strongest):
    """A decay of up to `strongest` a step and channel beside channels
    that hardly decay: a chunk of 48 sums to 100 and to 1,000, where a
    factor ``exp(-G)`` would overflow float32 (88). The chunked form
    takes the decay between two positions as it stands, so it equals
    the recurrence and the one-step form, with nothing that is not
    finite."""
    import jax.numpy as jnp

    from ray_tpu.ops.delta_rule import delta_rule_chunked, delta_rule_step

    q, k, v, g, beta, state = _inputs(3)
    rng = np.random.default_rng(4)
    g = -rng.uniform(0.001, strongest, size=g.shape)
    g[:, :, ::4] *= 1e-3                     # slow channels among fast
    summed = -g.sum(axis=0)
    assert summed.max() > 100 and summed.min() < 1
    want_o, want_s = _recurrence(q, k, v, g, beta, state)
    args = [jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta, state)]
    o, s = delta_rule_chunked(*args, chunk=48)
    assert np.isfinite(o).all() and np.isfinite(s).all()
    np.testing.assert_allclose(o, want_o, atol=2e-5, rtol=1e-4)
    np.testing.assert_allclose(s, want_s, atol=2e-5, rtol=1e-4)
    step_s = args[5]
    for t in range(S):
        step_o, step_s = delta_rule_step(step_s, *(x[t] for x in args[:5]))
        np.testing.assert_allclose(step_o, want_o[t], atol=2e-5, rtol=1e-4)
    np.testing.assert_allclose(step_s, want_s, atol=2e-5, rtol=1e-4)


def test_one_step_form_equals_the_recurrence_and_the_chunked_form():
    import jax.numpy as jnp

    from ray_tpu.ops.delta_rule import delta_rule_chunked, delta_rule_step

    q, k, v, g, beta, state = (jnp.asarray(x, jnp.float32)
                               for x in _inputs(1))
    want_o, want_s = _recurrence(*_inputs(1))
    s, outs = state, []
    for t in range(S):
        o, s = delta_rule_step(s, q[t], k[t], v[t], g[t], beta[t])
        outs.append(o)
    np.testing.assert_allclose(np.stack(outs), want_o, atol=2e-5)
    np.testing.assert_allclose(s, want_s, atol=2e-5)
    # A prefill of 32 positions hands its state to decode steps.
    o_head, s_head = delta_rule_chunked(q[:32], k[:32], v[:32], g[:32],
                                        beta[:32], state, chunk=16)
    for t in range(32, S):
        o, s_head = delta_rule_step(s_head, q[t], k[t], v[t], g[t],
                                    beta[t])
        np.testing.assert_allclose(o, want_o[t], atol=2e-5)
    np.testing.assert_allclose(s_head, want_s, atol=2e-5)


def test_padding_positions_leave_the_state_bit_for_bit():
    """g = 0 and beta = 0 (what a padded position or an unused slot is
    handed): the one-step form returns the state unchanged, and the
    chunked form's final state is that of the live positions alone."""
    import jax.numpy as jnp

    from ray_tpu.ops.delta_rule import delta_rule_chunked, delta_rule_step

    q, k, v, g, beta, state = (jnp.asarray(x, jnp.float32)
                               for x in _inputs(2))
    _, same = delta_rule_step(state, q[0], k[0], v[0], jnp.zeros_like(g[0]),
                              jnp.zeros_like(beta[0]))
    np.testing.assert_array_equal(same, state)
    live = 20
    mask = (jnp.arange(32) < live)
    o, s = delta_rule_chunked(
        q[:32], k[:32], v[:32], jnp.where(mask[:, None, None], g[:32], 0.0),
        jnp.where(mask[:, None], beta[:32], 0.0), state, chunk=16)
    want_o, want_s = _recurrence(*(np.asarray(x)[:live] for x in
                                   (q, k, v, g, beta)), np.asarray(state))
    np.testing.assert_allclose(o[:live], want_o, atol=2e-5)
    np.testing.assert_allclose(s, want_s, atol=2e-5)


def test_a_length_off_the_chunk_grid_is_refused():
    import jax.numpy as jnp

    from ray_tpu.ops.delta_rule import delta_rule_chunked

    q, k, v, g, beta, state = (jnp.asarray(x, jnp.float32)
                               for x in _inputs())
    with pytest.raises(ValueError, match="no multiple"):
        delta_rule_chunked(q[:20], k[:20], v[:20], g[:20], beta[:20],
                           state, chunk=16)
