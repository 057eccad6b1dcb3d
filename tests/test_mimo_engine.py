"""The sparse model of window and global layers on different key/value
heads (`MimoEngineModel`: keys wider than values, a sink a head in the
window layers, a value scale, a sigmoid router without a shared expert)
through the engine and its cache by layer group, against the plain
reference of its family (`benchmarks/families/mimo_v2.py`) on the same
seeded weights at toy widths: float32 throughout, so the two agree to
rounding. Controls that must fail the comparison: the reference without
the sink, without the value scale, with a window one block short, and a
model whose two groups' key/value head counts are swapped. And the share
test: the routed sums of all the shares add up to the uncut layer."""

import json
import os

import numpy as np
import pytest

from benchmarks.harness import manifest

pytestmark = pytest.mark.unit

FAMILY = manifest.load_family("mimo_v2")
with open(os.path.join(manifest.ROOT, "benchmarks", "configs",
                       "mimo-v2.5.json")) as f:
    CONFIG = json.load(f)
TOY = FAMILY.toy_widths(FAMILY.widths(CONFIG))
BLOCK = 16
ENGINE = {"paged_decode": True, "max_batch_size": 3, "block_size": BLOCK,
          "num_blocks": 64, "group_blocks": {"window": 12}, "max_queue": 64}
# ceil(16 / 16) + 1: what a sequence may hold of the window group.
WINDOW_BLOCKS = -(-TOY["window"] // BLOCK) + 1
TOLERANCE = 2e-4       # float32 against float32; another row's KV gives ~1


def _serve(widths=TOY, seed=7, **engine):
    from ray_tpu.serve.engine import InferenceEngine

    served = FAMILY.build_serving(
        widths, {"max_seq_len": 256, "engine": dict(ENGINE, **engine)}, seed)
    return served, InferenceEngine(served["model"], served["engine_config"])


@pytest.fixture(scope="module")
def toy():
    served, engine = _serve()
    return served, engine, FAMILY.reference_logits(TOY)


def _gap(row, expect):
    return float(np.sqrt(np.mean((row - expect) ** 2))
                 / np.sqrt(np.mean(expect ** 2)))


def test_the_published_widths_are_the_configurations():
    w = FAMILY.widths(CONFIG)
    assert (w["d_model"], w["n_heads"], w["head_dim"], w["v_head_dim"],
            w["kv_heads_global"], w["kv_heads_window"], w["window"]) == \
        (4096, 64, 192, 128, 4, 8, 128)
    assert (w["n_experts"], w["experts_held"], w["top_k"],
            w["expert_width"], w["dense_width"]) == \
        (256, [0, 16], 8, 2048, 16384)
    assert (w["rot_dim"], w["theta_global"], w["theta_window"],
            w["value_scale"], w["norm_eps"]) == \
        (64, 1e7, 1e4, 0.707, 1e-5)
    # Layers 0-6: the dense global layer and one whole period.
    assert w["layer_is_window"] == [False, True, True, True, True, False,
                                    True]
    assert w["layer_is_dense"] == [True] + [False] * 6
    assert sum(w["published"]["layer_is_window"]) == 39
    assert len(w["published"]["layer_is_window"]) == 48
    # The toy keeps every mechanism: 3 : 2, 2 key heads to 4, the layers.
    assert (TOY["head_dim"], TOY["v_head_dim"], TOY["kv_heads_global"],
            TOY["kv_heads_window"]) == (24, 16, 2, 4)
    assert TOY["layer_is_window"] == w["layer_is_window"]


def test_the_models_rows_are_of_two_widths_and_two_head_counts(toy):
    served, engine, _ = toy
    model = served["model"]
    # [layers of the group, a key's slot, the value's, the key's second
    # (half zeros), key/value heads, the values' width].
    assert model.kv_token_shape == (2, 3, 2, 16)
    assert model.kv_groups["window"] == {"kv_shape": (5, 3, 4, 16),
                                         "window": 16}
    assert model.kv_token_bytes_model == {"global": 2 * 2 * 40 * 4,
                                          "window": 5 * 4 * 40 * 4}
    assert model.kv_token_bytes_held == {"global": 2 * 2 * 48 * 4,
                                         "window": 5 * 4 * 48 * 4}
    pools = {"global": engine.cache, "window": engine.cache.group("window")}
    assert pools["global"].kv_shape == (2, 3, 2, 16)
    assert pools["window"].kv_shape == (5, 3, 4, 16)


# Prompts shorter than the window, at it, on and off the block grid,
# longer than it (the prefill then stores only the rows the window
# reaches), then 20 decode steps past the window and across a block
# boundary of each group (a window block is released on the way).
@pytest.mark.parametrize("n, steps", [(5, 20), (16, 20), (23, 20),
                                      (40, 20), (49, 36)])
def test_prefill_then_decode_through_both_groups_matches_the_reference(
        toy, n, steps):
    served, engine, ref = toy
    rng = np.random.default_rng(n)
    prompt = rng.integers(2, TOY["vocab_size"], n).tolist()
    window = engine.cache.group("window")
    released = window.window_blocks_released
    got, tokens = FAMILY.drive(engine, served, prompt, steps, f"check-{n}")
    want = np.asarray(ref(served["params"], np.asarray(tokens, np.int32)))
    for j, row in enumerate(got):
        assert _gap(row, want[n - 1 + j]) < TOLERANCE, (n, j)
    readings = served["own_limits"][-1]
    assert readings["ok"]
    assert readings["window_blocks_held_max"] <= WINDOW_BLOCKS
    if n + steps > TOY["window"] + BLOCK:
        assert window.window_blocks_released > released
    assert engine.cache.free_blocks() == ENGINE["num_blocks"]
    assert window.free_blocks() == ENGINE["group_blocks"]["window"]


@pytest.mark.parametrize("control", [
    {"without": ["sink"]}, {"without": ["value_scale"]},
    {"without": ["select_bias"]}, {"window": TOY["window"] // 2}],
    ids=["no_sink", "no_value_scale", "no_selection_bias",
         "window_half_a_block_short"])
def test_a_reference_without_a_mechanism_fails_the_comparison(toy, control):
    """The engine against a reference that lacks one mechanism: the rows
    differ beyond the family's limits at a prompt past the window, and
    `drive` hands them back as no numbers (or outside the harness's
    limit)."""
    served, engine, _ = toy
    n, steps = 40, 20
    prompt = np.random.default_rng(n).integers(
        2, TOY["vocab_size"], n).tolist()
    lacking = dict(TOY, **control)
    served["reference_widths"] = lacking
    try:
        got, tokens = FAMILY.drive(engine, served, prompt, steps, "control")
    finally:
        del served["reference_widths"]
    readings = served["own_limits"].pop()
    assert not readings["ok"]
    assert readings["median"] > 10 * TOLERANCE
    worst = readings["positions"][-1]
    assert worst > FAMILY.LOGIT_TOLERANCE or np.isnan(got[0]).all()


def test_a_window_one_block_short_in_the_engine_fails_the_comparison():
    """The engine at a window of 16 under a reference at 32."""
    served, engine = _serve()
    served["reference_widths"] = dict(TOY, window=TOY["window"] + BLOCK)
    prompt = np.random.default_rng(3).integers(
        2, TOY["vocab_size"], 40).tolist()
    FAMILY.drive(engine, served, prompt, 20, "short")
    assert not served["own_limits"][-1]["ok"]


def test_the_two_groups_key_head_counts_swapped_cannot_run():
    """The seeded weights' shapes carry the head counts: a model that
    takes the global layers for 4 key heads and the window layers for 2
    cannot shape its keys."""
    served, _ = _serve()
    from ray_tpu.serve.engine import MimoEngineModel

    swapped = FAMILY.model_config(dict(
        TOY, kv_heads_global=TOY["kv_heads_window"],
        kv_heads_window=TOY["kv_heads_global"]))
    model = MimoEngineModel(served["params"], swapped, max_batch_size=3)
    assert model.kv_token_shape == (2, 3, 4, 16)
    with pytest.raises(Exception, match="reshape|shape"):
        model.prefill(list(range(2, 22)))


def test_a_layer_without_a_shared_expert_adds_none(toy):
    """By the tree's keys: this model's expert layers have no
    `shared_gate`, and the engine's rows are the reference's, which has
    no shared expert; Laguna's tree has one, and keeps it."""
    served, _, _ = toy
    sparse = [layer["mlp"] for layer in served["params"]["layers"]
              if "router" in layer["mlp"]]
    assert len(sparse) == 6
    assert all("shared_gate" not in mp and "select_bias" in mp
               for mp in sparse)
    assert all("sink" in layer["mixer"]
               for layer, window in zip(served["params"]["layers"],
                                        TOY["layer_is_window"]) if window)
    assert all("sink" not in layer["mixer"]
               for layer, window in zip(served["params"]["layers"],
                                        TOY["layer_is_window"])
               if not window)


def test_the_sixteen_shares_add_up_to_the_uncut_sparse_layer():
    """The guide's share test at toy widths with 16 experts, top 4: the
    routed sums of all sixteen shares (`held` = each sixteenth in turn:
    one expert a share), with what every chip computes alike (the router
    and its weights over all 16, normalised over all the chosen) counted
    once, add up to the uncut reference's sparse layer; and the engine's
    expert layer gives a share's sum."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.mimo_v2 import init_params

    whole = dict(TOY, n_experts=16, experts_held=[0, 16], top_k=4)
    params = init_params(jax.random.PRNGKey(5), FAMILY.model_config(whole))
    mp = next(layer["mlp"] for layer in params["layers"]
              if "router" in layer["mlp"])
    y = jax.random.normal(jax.random.PRNGKey(6), (24, whole["d_model"]))
    with jax.default_matmul_precision("highest"):
        uncut = FAMILY.sparse_layer_share(y, mp, whole)
        weights = FAMILY._ref_routing(y, mp, whole)
        total = jnp.zeros_like(uncut)
        for lo in range(16):
            share = {k: mp[k] for k in ("router", "select_bias")}
            share.update({k: mp[k][lo:lo + 1]
                          for k in ("w_gate", "w_up", "w_down")})
            part = FAMILY.sparse_layer_share(
                y, share, dict(whole, experts_held=[lo, lo + 1]))
            # A share alone is not the layer: the others are left out.
            assert float(jnp.max(jnp.abs(part - uncut))) > 1e-3
            total = total + part
    # Every token's weights over the experts sum to one, once.
    assert np.allclose(np.asarray(weights.sum(axis=-1)), 1.0, atol=1e-6)
    assert int((np.asarray(weights) > 0).sum(axis=-1).max()) == 4
    assert float(jnp.max(jnp.abs(total - uncut))) < 1e-5

    # The program's expert layer on one share: the same sum.
    from ray_tpu.ops.experts import held_experts_ffn, route

    experts, w = route(y, mp["router"], mp["select_bias"], 4, 1.0, "sigmoid")
    for lo in (0, 7, 15):
        got, _ = held_experts_ffn(
            y, experts, w, mp["w_gate"][lo:lo + 1], mp["w_up"][lo:lo + 1],
            mp["w_down"][lo:lo + 1], (lo, lo + 1))
        with jax.default_matmul_precision("highest"):
            want = FAMILY.routed_share(
                y, {k: mp[k][lo:lo + 1]
                    for k in ("w_gate", "w_up", "w_down")},
                weights[:, lo:lo + 1], (0, 1))
        assert float(jnp.max(jnp.abs(got - want))) < 1e-4


def test_the_engine_serves_requests_through_both_groups(toy):
    """The scheduler's path: three requests at once through admission,
    prefill, the packed step over both groups, window release and
    retirement; the greedy tokens are the reference's argmax."""
    from ray_tpu.serve.engine import InferenceEngine

    served, _, ref = toy
    engine = InferenceEngine(served["model"], served["engine_config"])
    engine.start()
    try:
        rng = np.random.default_rng(11)
        prompts = [rng.integers(2, TOY["vocab_size"], n).tolist()
                   for n in (12, 33, 47)]
        streams = [engine.submit(p, 24) for p in prompts]
        outs = [list(s) for s in streams]
    finally:
        engine.stop()
    stats = engine.stats()
    assert stats["paged_steps"] > 0 and stats["cache"]["host_gathers"] == 0
    assert stats["kv_window_window_blocks_released"] > 0
    for prompt, out in zip(prompts, outs):
        assert len(out) == 24
        tokens = np.asarray(prompt + out, np.int32)
        want = np.asarray(ref(served["params"], tokens))
        greedy = want[len(prompt) - 1:-1].argmax(axis=-1)
        # float32 against float32: a near tie may flip one token, after
        # which the two continue apart; the first tokens agree.
        assert list(greedy[:4]) == out[:4]
