"""The dropless expert layer that is told which experts it holds
(`ops/experts.py`), against a dense loop over (token, choice) pairs."""

import numpy as np
import pytest

pytestmark = pytest.mark.unit

T, D, F, E, K = 37, 32, 24, 16, 4


def _weights(seed=0):
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)

    def mat(*shape):
        return jnp.asarray(rng.normal(size=shape) / np.sqrt(shape[-2]),
                           jnp.float32)

    return {"y": jnp.asarray(rng.normal(size=(T, D)), jnp.float32),
            "router": mat(D, E),
            "bias": jnp.asarray(0.1 * rng.normal(size=(E,)), jnp.float32),
            "w_gate": mat(E, D, F), "w_up": mat(E, D, F),
            "w_down": mat(E, F, D)}


def _dense(w, experts, weights, lo, hi, valid=None):
    y = np.asarray(w["y"], np.float64)
    out = np.zeros((T, D))
    for t in range(T):
        if valid is not None and not valid[t]:
            continue
        for j in range(K):
            e = int(experts[t, j])
            if lo <= e < hi:
                gate = y[t] @ np.asarray(w["w_gate"][e], np.float64)
                up = y[t] @ np.asarray(w["w_up"][e], np.float64)
                out[t] += float(weights[t, j]) * (
                    (gate / (1 + np.exp(-gate)) * up)
                    @ np.asarray(w["w_down"][e], np.float64))
    return out


def _held(w, experts, weights, lo, hi, valid=None):
    from ray_tpu.ops.experts import held_experts_ffn

    return held_experts_ffn(w["y"], experts, weights, w["w_gate"][lo:hi],
                            w["w_up"][lo:hi], w["w_down"][lo:hi], (lo, hi),
                            valid)


def test_router_scores_all_experts_and_normalises_over_the_chosen():
    from ray_tpu.ops.experts import route

    w = _weights()
    experts, weights = route(w["y"], w["router"], w["bias"], K, 2.0)
    scores = 1 / (1 + np.exp(-np.asarray(w["y"]) @ np.asarray(w["router"])))
    want = np.argsort(-(scores + np.asarray(w["bias"])), axis=-1)[:, :K]
    np.testing.assert_array_equal(np.sort(experts, -1), np.sort(want, -1))
    np.testing.assert_allclose(np.sum(weights, -1), 2.0, rtol=1e-6)
    chosen = np.take_along_axis(scores, np.asarray(experts), -1)
    np.testing.assert_allclose(
        weights, 2.0 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-5)


@pytest.mark.parametrize("lo,hi", [(0, 2), (6, 8), (3, 11), (0, 16)])
def test_held_part_equals_the_dense_loop_over_its_pairs(lo, hi):
    from ray_tpu.ops.experts import route

    w = _weights(1)
    experts, weights = route(w["y"], w["router"], w["bias"], K)
    out, load = _held(w, experts, weights, lo, hi)
    np.testing.assert_allclose(out, _dense(w, experts, weights, lo, hi),
                               atol=1e-5)
    want_load = [(np.asarray(experts) == e).sum() for e in range(lo, hi)]
    np.testing.assert_array_equal(load, want_load)


def test_the_shares_of_all_chips_add_up_to_the_whole_layer():
    from ray_tpu.ops.experts import route

    w = _weights(2)
    experts, weights = route(w["y"], w["router"], w["bias"], K)
    parts = [_held(w, experts, weights, lo, lo + 2) for lo in range(0, E, 2)]
    whole, load = _held(w, experts, weights, 0, E)
    np.testing.assert_allclose(sum(p[0] for p in parts), whole, atol=1e-5)
    np.testing.assert_array_equal(
        np.concatenate([p[1] for p in parts]), load)
    assert int(load.sum()) == T * K          # no pair dropped anywhere


def test_one_expert_that_takes_every_token_drops_none():
    """A selection bias that sends every token to expert 3: its load is
    every token, whatever the imbalance, and the result is the dense
    loop's."""
    from ray_tpu.ops.experts import route

    w = _weights(3)
    experts, weights = route(w["y"], w["router"],
                             w["bias"].at[3].set(100.0), K)
    assert (np.asarray(experts) == 3).any(axis=-1).all()
    out, load = _held(w, experts, weights, 2, 4)
    assert int(load[1]) == T
    np.testing.assert_allclose(out, _dense(w, experts, weights, 2, 4),
                               atol=1e-5)


def test_rows_that_are_no_sequence_route_nowhere():
    import jax.numpy as jnp

    from ray_tpu.ops.experts import route

    w = _weights(4)
    experts, weights = route(w["y"], w["router"], w["bias"], K)
    valid = np.arange(T) % 3 != 0
    out, load = _held(w, experts, weights, 0, 8, jnp.asarray(valid))
    np.testing.assert_allclose(
        out, _dense(w, experts, weights, 0, 8, valid), atol=1e-5)
    assert not np.asarray(out)[~valid].any()
    assert int(load.sum()) == sum(
        int(((np.asarray(experts)[t] >= 0) & (np.asarray(experts)[t] < 8))
            .sum()) for t in range(T) if valid[t])


def test_it_runs_under_jit_with_a_fixed_number_of_tiles():
    import jax

    from ray_tpu.ops.experts import _tile_rows, route

    assert [_tile_rows(t) for t in (1, 8, 32, 100, 128, 1024)] == \
        [8, 8, 32, 128, 128, 128]
    w = _weights(5)

    @jax.jit
    def run(y):
        experts, weights = route(y, w["router"], w["bias"], K)
        return _held(dict(w, y=y), experts, weights, 4, 8)

    experts, weights = route(w["y"], w["router"], w["bias"], K)
    out, _ = run(w["y"])
    np.testing.assert_allclose(out, _dense(w, experts, weights, 4, 8),
                               atol=1e-5)
