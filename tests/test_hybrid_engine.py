"""The hybrid sparse model with per-sequence state (`HybridEngineModel`)
through the engine and its cache, against the plain reference of its
family (`benchmarks/families/solar_open2.py`) on the same seeded weights
at toy widths: float32 throughout, so the two agree to rounding and a
greedy token is the reference's argmax."""

import json
import os

import numpy as np
import pytest

from benchmarks.harness import manifest

pytestmark = pytest.mark.unit

FAMILY = manifest.load_family("solar_open2")
with open(os.path.join(manifest.ROOT, "benchmarks", "configs",
                       "solar-open2-250b.json")) as f:
    CONFIG = json.load(f)
TOY = FAMILY.toy_widths(FAMILY.widths(CONFIG))
ENGINE = {"paged_decode": True, "max_batch_size": 3, "block_size": 16,
          "num_blocks": 64, "max_queue": 64}
TOLERANCE = 2e-4       # float32 against float32; a stale state gives ~1


def _serve(widths=TOY, seed=7, max_seq_len=128, **engine):
    from ray_tpu.serve.engine import InferenceEngine

    served = FAMILY.build_serving(
        widths, {"max_seq_len": max_seq_len,
                 "engine": dict(ENGINE, **engine)}, seed)
    return served, InferenceEngine(served["model"], served["engine_config"])


@pytest.fixture(scope="module")
def toy():
    served, engine = _serve()
    return served, engine, FAMILY.reference_logits(TOY)


def _gap(row, expect):
    return float(np.sqrt(np.mean((row - expect) ** 2))
                 / np.sqrt(np.mean(expect ** 2)))


def _follows_reference(ref, params, prompt, generated):
    """Every generated token is the argmax of the reference's logits at
    the position before it (one reference call over the whole text)."""
    text = list(prompt) + list(generated)
    want = np.asarray(ref(params, np.asarray(text, np.int32)))
    return [int(np.argmax(want[len(prompt) - 1 + i]))
            for i in range(len(generated))] == list(generated)


# Lengths off the chunk grid (16) and the block grid (16), on them, and
# over several chunks; then three decode steps through cache and state.
@pytest.mark.parametrize("n", [5, 16, 23, 40, 49])
def test_prefill_then_decode_through_the_cache_matches_the_reference(toy, n):
    served, engine, ref = toy
    rng = np.random.default_rng(n)
    prompt = rng.integers(2, TOY["vocab_size"], n).tolist()
    got, tokens = FAMILY.drive(engine, served, prompt, 3, f"check-{n}")
    want = np.asarray(ref(served["params"], np.asarray(tokens, np.int32)))
    for j, row in enumerate(got):
        assert _gap(row, want[n - 1 + j]) < TOLERANCE, (n, j)
    assert engine.cache.stats()["state_slots_in_use"] == 0


def test_a_long_prompt_through_the_prompt_kernel_matches_the_reference(
        monkeypatch):
    """A prompt of more rows than a tile through `held_experts_ffn_prefill`
    inside the engine's prefill, steered on here and interpreted, at a
    `d_model` of one sublane of 128 lanes (the kernel moves a row as its
    sublanes), against the reference; the decode steps keep the scan,
    and the model counts one program by the kernel."""
    from functools import partial

    from ray_tpu.ops import experts as ex

    widths = dict(TOY, d_model=128)
    n, steps = 130, 2
    prompt = np.random.default_rng(n).integers(
        2, TOY["vocab_size"], n).tolist()
    calls, interpreted = [], partial(ex.held_experts_ffn_prefill,
                                     interpret=True)

    def kernel(*args, **kwargs):
        calls.append(args[0].shape)
        return interpreted(*args, **kwargs)

    monkeypatch.setattr(ex, "kernel_eligible",
                        lambda t, *widths: t > ex._ROWS_MOST)
    monkeypatch.setattr(ex, "held_experts_ffn_prefill", kernel)
    served, engine = _serve(widths, max_seq_len=512)
    got, tokens = FAMILY.drive(engine, served, prompt, steps, "prompt-kernel")
    assert calls and set(calls) == {(256, 128)}
    stats = engine.stats()
    assert (stats["moe_steps_kernel"], stats["moe_steps_scan"]) == (1, steps)
    want = np.asarray(FAMILY.reference_logits(widths)(
        served["params"], np.asarray(tokens, np.int32)))
    for j, row in enumerate(got):
        assert _gap(row, want[n - 1 + j]) < TOLERANCE, (n, j)


def test_a_model_of_one_period_runs_inline_and_matches(toy):
    widths = dict(TOY, n_periods=1,
                  published={"n_periods": 1, "vocab_size": 512})
    served, engine = _serve(widths, seed=11)
    ref = FAMILY.reference_logits(widths)
    prompt = np.random.default_rng(0).integers(2, 512, 21).tolist()
    got, tokens = FAMILY.drive(engine, served, prompt, 2, "one-period")
    want = np.asarray(ref(served["params"], np.asarray(tokens, np.int32)))
    assert max(_gap(row, want[20 + j]) for j, row in enumerate(got)) \
        < TOLERANCE


def test_a_mixed_batch_with_rows_joining_and_leaving(toy):
    """Five requests of different lengths over a batch of three: rows
    leave as they finish and the waiting ones join. Every stream is the
    reference's greedy text, and no state slot is left behind."""
    served, engine, ref = toy
    rng = np.random.default_rng(5)
    prompts = [rng.integers(2, TOY["vocab_size"], n).tolist()
               for n in (7, 30, 18, 3, 41)]
    new = (9, 4, 12, 6, 5)
    before = engine.stats()
    streams = [engine.submit(p, m) for p, m in zip(prompts, new)]
    while engine.step():
        pass
    for prompt, stream, m in zip(prompts, streams, new):
        generated = list(stream)
        assert len(generated) == m
        assert _follows_reference(ref, served["params"], prompt, generated)
    after = engine.stats()
    assert after["cache"]["state_slots_in_use"] == 0
    assert after["cache"]["state_slots"] == ENGINE["max_batch_size"]
    assert after["cache"]["state_bytes"] == ENGINE["max_batch_size"] * \
        FAMILY.counts(TOY, {"weights": {"bytes_per_value": 4},
                            "kv_pool": {"bytes_per_value": 4}}
                      )["state_bytes_per_sequence"]
    assert after["cache"]["used_blocks"] == 0
    steps = after["paged_steps"] - before["paged_steps"]
    assert steps > 0
    # One upload and one fetch a decode step: the ids and the three
    # expert counters come back in one array.
    model = served["model"]
    assert after["decode_h2d_arrays"] - before["decode_h2d_arrays"] == steps
    assert (after["decode_d2h_bytes"] - before["decode_d2h_bytes"]) \
        % 4 == 0
    assert after["moe_local_assignments"] > before["moe_local_assignments"]
    assert after["moe_expert_touches"] <= \
        after["moe_local_assignments"]
    assert 0 < after["state_slot_steps_in_use"] <= after["state_slot_steps"]
    assert model.moe_max_expert_load >= after["moe_expert_touches"] // 2 > 0


def test_a_preempted_row_is_recomputed_by_prefill():
    """Blocks for two of three long rows: the youngest is preempted, its
    slot and blocks freed, and re-admitted by a prefill of what it had
    generated; its text is still the reference's."""
    served, engine = _serve(seed=9, num_blocks=7)
    ref = FAMILY.reference_logits(TOY)
    rng = np.random.default_rng(6)
    prompts = [rng.integers(2, TOY["vocab_size"], 30).tolist()
               for _ in range(3)]
    streams = [engine.submit(p, 20) for p in prompts]
    while engine.step():
        pass
    stats = engine.stats()
    assert stats["preemptions"] > 0
    for prompt, stream in zip(prompts, streams):
        generated = list(stream)
        assert len(generated) == 20
        assert _follows_reference(ref, served["params"], prompt, generated)
    assert stats["cache"]["state_slots_in_use"] == 0
    assert stats["cache"]["used_blocks"] == 0


def test_no_prefix_is_adopted_over_a_model_with_state(toy):
    """Two requests with a common prefix of two blocks: no index is
    built, nothing is adopted or exported, and both follow the
    reference. Decided from the model's declared state alone."""
    served, engine, ref = toy
    assert engine.config.prefix_sharing and engine.prefix_index is None
    rng = np.random.default_rng(8)
    common = rng.integers(2, TOY["vocab_size"], 32).tolist()
    prompts = [common + rng.integers(2, TOY["vocab_size"], n).tolist()
               for n in (3, 9)]
    streams = [engine.submit(p, 5) for p in prompts]
    while engine.step():
        pass
    for prompt, stream in zip(prompts, streams):
        assert _follows_reference(ref, served["params"], prompt,
                                  list(stream))
    stats = engine.stats()
    assert stats["prefix_hit_tokens"] == 0 and stats["prefix_index"] is None
    assert stats["cache"]["adoptions"] == 0
    assert engine.export_prefix(common) == ([], [])
    assert engine.import_prefix([], []) == 0
    with pytest.raises(ValueError, match="state"):
        engine.cache.adopt("x", [0], 16)
    with pytest.raises(ValueError, match="whole"):
        served["model"].prefill_paged(common, None, [0, 1], 16, 16)


def test_a_router_that_sends_every_token_to_one_expert_still_matches():
    """Total imbalance: a selection bias that puts held expert 1 among
    every token's choices. No capacity, no dropped token: the engine
    still equals the reference."""
    served, engine = _serve(seed=13)
    params = served["params"]
    for layer in params["moe"]:
        layer["select_bias"] = layer["select_bias"].at[:, 1].set(100.0)
    ref = FAMILY.reference_logits(TOY)
    prompt = np.random.default_rng(2).integers(2, 512, 37).tolist()
    got, tokens = FAMILY.drive(engine, served, prompt, 2, "skewed")
    want = np.asarray(ref(params, np.asarray(tokens, np.int32)))
    assert max(_gap(row, want[36 + j]) for j, row in enumerate(got)) \
        < TOLERANCE
    # Every decode row's pair on expert 1: the largest load a layer is
    # the step's rows (1), in each of the 8 layers of both steps.
    model = served["model"]
    assert model.moe_max_expert_load == 2 * 8
    assert model.moe_local_assignments >= 2 * 8


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The routed parts that the eight shares of a layer compute, plus
    the shared expert counted once, equal the reference's layer with
    every expert held: the cut leaves out what other chips add, nothing
    else."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.hybrid_moe import init_params
    from ray_tpu.ops.experts import held_experts_ffn, route

    whole = dict(TOY, experts_held=[0, TOY["n_experts"]])
    cfg = FAMILY.model_config(whole)
    layer = jax.tree.map(lambda a: a[0], init_params(
        jax.random.PRNGKey(3), cfg)["moe"][2])
    y = jnp.asarray(np.random.default_rng(4).normal(
        size=(29, TOY["d_model"])), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = FAMILY._ref_experts(y, layer, whole)
        shared = FAMILY._gated_ffn(y, layer["shared_gate"],
                                   layer["shared_up"], layer["shared_down"])
    experts, weights = route(y, layer["router"], layer["select_bias"],
                             cfg.top_k, cfg.routed_scaling)
    per_share = TOY["n_experts"] // CONFIG["share_chips"]
    total, pairs = shared, 0
    for lo in range(0, TOY["n_experts"], per_share):
        hi = lo + per_share
        part, load = held_experts_ffn(
            y, experts, weights, layer["w_gate"][lo:hi],
            layer["w_up"][lo:hi], layer["w_down"][lo:hi], (lo, hi))
        # One share alone is the reference handed that share.
        with jax.default_matmul_precision("highest"):
            alone = FAMILY._ref_experts(
                y, dict(layer, **{k: layer[k][lo:hi] for k in
                                  ("w_gate", "w_up", "w_down")}),
                dict(whole, experts_held=[lo, hi]))
        np.testing.assert_allclose(part + shared, alone, atol=1e-5)
        total, pairs = total + part, pairs + int(load.sum())
    np.testing.assert_allclose(total, want, atol=1e-5)
    assert pairs == 29 * cfg.top_k


def test_the_declared_state_is_what_the_configuration_states(toy):
    """The delta rule's state is float32 whatever the weights are kept
    in, the tails and the KV pool follow the weights: the
    configuration's `arithmetic`, which no later PR may lower."""
    import jax.numpy as jnp

    served, engine, _ = toy
    arithmetic = CONFIG["arithmetic"]
    assert arithmetic["delta_rule_state"] == "float32"
    published = FAMILY.widths(CONFIG)
    cfg = FAMILY.model_config(published)
    assert (cfg.dtype == arithmetic["weights"] == arithmetic["kv_pool"]
            == arithmetic["conv_tails"] == "bfloat16")
    shapes = served["model"].state_shapes
    assert shapes["s"][1] == jnp.float32
    assert engine.cache.read_state  # the pool exists: slots were declared
    # At the published widths: 12.58 MB of state and 0.44 MB of tails a
    # sequence, 4 KB of KV a token (ISSUE 32's arithmetic).
    counts = FAMILY.counts(published)
    assert counts["state_bytes_per_sequence"] == \
        3 * (64 * 128 * 128 * 4 + 3 * 3 * 8192 * 2)
    assert counts["kv_bytes_per_token"] == 4096
    params = counts["params"]
    assert round(params["held"] / 1e9, 2) == 3.31
    assert 240e9 < params["total"] < 260e9 and 13e9 < params["active"] < 16e9
    assert 21 < counts["experts_touched"](32) < 23
    assert 5.0e9 < counts["decode_step_bytes"](32, 32 * 700) < 5.3e9


def test_the_steps_keep_every_value_of_the_states_shape_in_float32():
    """With the weights in bf16, as the configuration runs them: inside
    the jitted decode step and the jitted prefill every value shaped as
    the delta rule's state (``[..., H, dk, dv]``: the pool, a layer's
    slice, the decayed state, the rank-one update) is float32. A step
    that computed in bf16 and stored float32 would pass a test of the
    declared dtype; it does not pass this one."""
    import jax
    import jax.numpy as jnp

    widths = dict(TOY, dtype="bfloat16", kda_heads=3)
    served, engine = _serve(widths, seed=3)
    model = served["model"]
    heads, dk = widths["kda_heads"], widths["kda_head_dim"]

    def dtypes_of_state_shaped_values(fn, *args):
        found = set()

        def walk(jaxpr):
            values = list(jaxpr.invars) + list(jaxpr.outvars)
            for eqn in jaxpr.eqns:
                values += list(eqn.outvars)
                for inner in jax.core.jaxprs_in_params(eqn.params):
                    walk(inner)
            for value in values:
                aval = getattr(value, "aval", None)
                if aval is not None and \
                        tuple(aval.shape[-3:]) == (heads, dk, dk) and \
                        jnp.issubdtype(aval.dtype, jnp.floating):
                    found.add(str(aval.dtype))

        walk(jax.make_jaxpr(fn)(*args).jaxpr)
        return found

    slots = ENGINE["max_batch_size"]
    state = {name: jnp.zeros((slots,) + tuple(shape), dtype)
             for name, (shape, dtype) in model.state_shapes.items()}
    pool = jnp.zeros((ENGINE["num_blocks"], ENGINE["block_size"])
                     + tuple(model.kv_token_shape), model.kv_dtype)
    packed = np.zeros((2, 6 + 2), np.int32)
    step = model._build_decode_paged(2, 2, ENGINE["block_size"])
    assert dtypes_of_state_shaped_values(
        step, pool, state, served["params"], packed,
        np.zeros(model._ids_width(2) + 3, np.int32)) == {"float32"}
    prefill = model._build_prefill(32)
    assert dtypes_of_state_shaped_values(
        prefill, served["params"], np.zeros(32, np.int32),
        np.int32(20)) == {"float32"}


def test_the_state_a_drive_ends_on_is_the_references(toy):
    """The state slot after a prefill and three decode steps against the
    reference's recurrence on the same tokens, and the family's own
    limits over the drive: float32 against float32."""
    served, engine, _ = toy
    prompt = np.random.default_rng(8).integers(
        2, TOY["vocab_size"], 37).tolist()
    served.pop("own_limits", None)
    FAMILY.drive(engine, served, prompt, 3, "state-check")
    readings = served["own_limits"][-1]
    assert readings["ok"]
    assert readings["state"] < TOLERANCE
    assert readings["positions"][-1] < TOLERANCE
    assert readings["state_bf16_share"] < 0.001


# -- the cache manager's state slots, by themselves --------------------
def _manager(slots=2, blocks=8):
    from ray_tpu.serve.engine import KVCacheManager

    return KVCacheManager(
        blocks, 4, kv_shape=(1,), state_slots=slots,
        state_shapes={"s": ((2, 3), np.float32), "tail": ((5,), np.float32)})


def test_a_slot_comes_with_the_first_block_and_goes_with_free():
    cache = _manager()
    assert cache.stats()["state_slots"] == 2
    assert cache.stats()["state_bytes"] == 2 * (6 + 5) * 4
    assert cache.allocate("a", 5) and cache.allocate("b", 3)
    assert cache.slot_of("a") != cache.slot_of("b")
    assert cache.allocate("a", 9)                  # growth: same slot
    assert cache.stats()["state_slots_in_use"] == 2
    # No slot left: admission says no, allocate changes nothing.
    assert not cache.can_allocate("c", 1)
    free = cache.free_blocks()
    assert not cache.allocate("c", 1)
    assert cache.free_blocks() == free and cache.slot_of("c") is None
    cache.free("a")                                # preempt / retire / shed
    assert cache.can_allocate("c", 1) and cache.allocate("c", 1)
    cache.free("b"), cache.free("c"), cache.free("never-admitted")
    stats = cache.stats()
    assert stats["state_slots_in_use"] == 0 and stats["state_slots"] == 2
    assert stats["used_blocks"] == 0


def test_paged_step_hands_both_pools_and_the_rows_slots():
    cache = _manager()
    cache.allocate("a", 2), cache.allocate("b", 2)

    class Rows(list):
        state = {"s": np.full((2, 3), 7.0, np.float32),
                 "tail": np.arange(5, dtype=np.float32)}

    cache.write_range("b", 0, Rows([[1.0], [2.0]]))
    np.testing.assert_array_equal(cache.read_state("b")["tail"], np.arange(5))
    assert not cache.read_state("a")["s"].any()
    seen = {}

    def step(pool, blocks, offs, state, slots):
        seen.update(slots=slots, blocks=blocks)
        state["s"][slots[0]] += 1.0
        return "result", pool, state

    assert cache.paged_step([("b", 2)], step) == "result"
    assert seen["slots"] == [cache.slot_of("b")]
    assert (cache.read_state("b")["s"] == 8.0).all()
    assert cache.state_slot_steps == 2 and cache.state_slot_steps_in_use == 2


def test_a_model_without_state_gets_no_state_pool():
    from ray_tpu.serve.engine import (EngineConfig, InferenceEngine,
                                      KVCacheManager, TinyLM)

    cache = KVCacheManager(4, 4, kv_shape=(1,))
    stats = cache.stats()
    assert (stats["state_slots"], stats["state_slots_in_use"],
            stats["state_bytes"]) == (0, 0, 0)
    assert cache.allocate("a", 3) and cache.slot_of("a") is None
    assert cache.paged_step([("a", 3)], lambda pool, blocks, offs:
                            ("r", pool)) == "r"
    engine = InferenceEngine(TinyLM(), EngineConfig())
    assert engine.prefix_index is not None
    with pytest.raises(ValueError, match="state_slots"):
        KVCacheManager(4, 4, state_shapes={"s": ((1,), np.float32)})
