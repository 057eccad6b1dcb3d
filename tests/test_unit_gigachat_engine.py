"""The latent-attention / Gated DeltaNet model (`GigaChatEngineModel`)
through the engine and its cache, against the plain reference of its
family (`benchmarks/families/gigachat3_5.py`: expanded attention, the
delta rule a token at a time) on the same seeded weights at toy widths:
float32 throughout, so the two agree to rounding and a greedy token is
the reference's argmax. Prefill whole, prefill in chunks that carry
state, decode through the latent pool and the state slots, and the
scheduler's own loop over all three."""

import json
import os

import numpy as np
import pytest

from benchmarks.harness import manifest

pytestmark = pytest.mark.unit

FAMILY = manifest.load_family("gigachat3_5")
with open(os.path.join(manifest.ROOT, "benchmarks", "configs",
                       "gigachat3.5-432b-a28b.json")) as f:
    CONFIG = json.load(f)
TOY = FAMILY.toy_widths(FAMILY.widths(CONFIG))
ENGINE = {"paged_decode": True, "max_batch_size": 3, "block_size": 16,
          "num_blocks": 64, "max_queue": 64}
TOLERANCE = 2e-4       # float32 against float32; a stale state gives ~1


def _serve(widths=TOY, seed=7, **engine):
    from ray_tpu.serve.engine import InferenceEngine

    served = FAMILY.build_serving(
        widths, {"max_seq_len": 128, "engine": dict(ENGINE, **engine)}, seed)
    return served, InferenceEngine(served["model"], served["engine_config"])


@pytest.fixture(scope="module")
def toy():
    served, engine = _serve()
    return served, engine, FAMILY.reference(TOY)


def _gap(row, expect):
    return float(np.sqrt(np.mean((row - expect) ** 2))
                 / np.sqrt(np.mean(expect ** 2)))


# Whole (at most a chunk of 16) and in chunks, off and on the chunk and
# block grids; then three decode steps through the pool and the state.
@pytest.mark.parametrize("n", [5, 16, 23, 32, 49])
def test_prefill_then_decode_through_the_cache_matches_the_reference(toy, n):
    served, engine, ref = toy
    model = served["model"]
    rng = np.random.default_rng(n)
    prompt = rng.integers(2, TOY["vocab_size"], n).tolist()
    later = model.prefill_later_chunks
    got, tokens = FAMILY.drive(engine, served, prompt, 3, f"check-{n}")
    want = np.asarray(ref(served["params"],
                          np.asarray(tokens, np.int32))[0])
    for j, row in enumerate(got):
        assert _gap(row, want[n - 1 + j]) < TOLERANCE, (n, j)
    assert served["own_limits"][-1]["ok"]
    assert engine.cache.stats()["state_slots_in_use"] == 0
    # A prompt past a chunk went in chunks, every later one from its slot.
    assert model.prefill_later_chunks - later == max(0, -(-n // 16) - 1)
    assert model.prefill_later_chunks == model.prefill_state_chunks


def test_state_after_chunks_is_after_whole_is_after_token_steps(toy):
    """One prompt three ways: in chunks of 16 through the cache, whole in
    one program (the chunk turned up), and a token at a time through
    decode steps; the delta rule's state and the convolution's tail in
    the slot are the same, and the reference's."""
    served, engine, ref = toy
    model, cache = served["model"], engine.cache
    prompt = np.random.default_rng(3).integers(2, 512, 40).tolist()

    def state_of(run):
        run("s")
        state = cache.read_state("s")
        cache.free("s")
        return state

    in_chunks = state_of(lambda sid: FAMILY.prefill_as_the_scheduler(
        engine, model, prompt, sid))
    model.prefill_chunk_tokens = 64
    try:
        whole = state_of(lambda sid: FAMILY.prefill_as_the_scheduler(
            engine, model, prompt, sid))
    finally:
        model.prefill_chunk_tokens = 16

    def by_steps(sid):
        FAMILY.prefill_as_the_scheduler(engine, model, prompt[:1], sid)
        for pos in range(1, len(prompt)):
            cache.allocate(sid, pos + 1, writable_from=pos)
            table = cache.block_table(sid)
            cache.paged_step(
                [(sid, pos)],
                lambda pool, blocks, offs, state, slots: model.decode_paged(
                    pool, [table], [prompt[pos]], [pos], blocks, offs, 16,
                    state, slots))

    stepped = state_of(by_steps)
    want = ref(served["params"], np.asarray(prompt, np.int32))[1]
    for name in ("s", "conv"):
        np.testing.assert_allclose(in_chunks[name], whole[name], atol=2e-5)
        np.testing.assert_allclose(stepped[name], whole[name], atol=2e-5)
    np.testing.assert_allclose(whole["s"], want, atol=2e-5)


def test_one_row_a_position_of_576_values_written_once():
    """At the published widths the model declares one row a position:
    ``[1, 5, 128]`` (576 values in 640 lanes) in a pool held by planes;
    at toy widths a chunk writes its rows once and a later chunk and the
    decode steps leave them bit for bit."""
    import jax

    from ray_tpu.models.gigachat35 import init_params
    from ray_tpu.serve.engine import GigaChatEngineModel

    cfg = FAMILY.model_config(FAMILY.widths(CONFIG))
    assert cfg.latent_width == 576 and cfg.n_mla_layers == 1
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    model = GigaChatEngineModel(shapes, cfg, max_batch_size=32)
    assert model.kv_token_shape == (1, 5, 128)
    assert model.kv_planes == {"global": True}
    assert (model.kv_token_bytes_model, model.kv_token_bytes_held) == \
        (1152, 1280)
    assert model.state_shapes["s"][0] == (4, 64, 128, 128)
    assert model.state_shapes["conv"][0] == (4, 3, 16384)
    assert round(cfg.softmax_scale * 192 ** 0.5, 4) == \
        round((0.1 * np.log(8) + 1) ** 2, 4)

    served, engine = _serve()
    cache = engine.cache
    prompt = np.random.default_rng(5).integers(2, 512, 40).tolist()
    FAMILY.prefill_as_the_scheduler(engine, served["model"], prompt[:32],
                                    "a")
    first = np.asarray(cache.gather("a", 32))
    assert first.shape == (32, 1, 1, 128)       # toy: 40 values, one plane
    assert not first[..., 40:].any() and first[..., :40].all()
    cache.free("a")
    got, _ = FAMILY.drive(engine, served, prompt, 2, "b")
    del got
    FAMILY.prefill_as_the_scheduler(engine, served["model"], prompt, "c")
    np.testing.assert_array_equal(np.asarray(cache.gather("c", 32)), first)
    cache.free("c")


def test_the_scheduler_serves_long_prompts_in_chunks_that_carry_state(toy):
    """Through `InferenceEngine.submit`: prompts past a chunk beside
    running rows, every token the reference's argmax; every later chunk
    began from its sequence's slot, and all slots come back."""
    served, _, ref = toy
    _, engine = _serve(seed=7)
    engine.start()
    try:
        rng = np.random.default_rng(11)
        prompts = [rng.integers(2, 512, n).tolist()
                   for n in (40, 9, 33, 57, 16)]
        streams = [engine.submit(p, 6) for p in prompts]
        outs = [list(s) for s in streams]
    finally:
        engine.stop()
    model = engine.model
    for prompt, out in zip(prompts, outs):
        text = prompt + out
        want = np.asarray(ref(served["params"],
                              np.asarray(text, np.int32))[0])
        assert [int(np.argmax(want[len(prompt) - 1 + i]))
                for i in range(len(out))] == out
    stats = engine.stats()
    assert stats["prefill_chunks"] == 3 + 3 + 4
    assert stats["prefill_later_chunks"] == stats["prefill_state_chunks"] \
        == 2 + 2 + 3
    assert stats["decode_latent_pages_read"] == 0      # the XLA body
    assert stats["cache"]["state_slots_in_use"] == 0
    assert stats["cache"]["state_slots"] == 3
    assert model.decode_calls > 0


@pytest.mark.parametrize("how", ["cancelled", "failed", "stopped"])
def test_a_prompt_in_flight_gives_its_slot_back_with_its_blocks(how):
    """A prompt that ends between two chunks, however it ends, frees the
    state slot its first chunk took."""
    served, engine = _serve()
    model, cache = served["model"], engine.cache
    prompt = np.random.default_rng(2).integers(2, 512, 60).tolist()
    # Beside a running row a prompt's chunks come one an iteration.
    engine.submit(prompt[:5], 50)
    engine.step()
    held = cache.free_blocks()
    stream = engine.submit(prompt, 4)
    engine.step()                               # the first chunk
    assert engine._in_flight is not None
    assert cache.stats()["state_slots_in_use"] == 2
    if how == "cancelled":
        stream.cancel()
        engine.step()
    elif how == "failed":
        def broken(*args, **kwargs):
            raise RuntimeError("a chunk failed")
        model.prefill_chunk = broken
        engine.step()
    else:
        engine.stop()
    assert engine._in_flight is None
    if how == "stopped":
        held = cache.num_blocks
    assert cache.stats()["state_slots_in_use"] == (how != "stopped")
    assert cache.free_blocks() >= held - 1    # the running row may have grown


def test_a_chunk_past_position_0_needs_its_slot(toy):
    served, engine, _ = toy
    model = served["model"]
    prompt = list(range(2, 42))
    with pytest.raises(ValueError, match="without its sequence's state"):
        engine.cache.with_pools(lambda pools: model.prefill_chunk(
            prompt, pools, [], 16, 16))


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """The routed parts that the sixteen shares of a layer compute (top 8
    of the router's width, scaling 2.5, both factors clamped), plus the
    shared expert counted once, equal the reference's layer with every
    expert held: the cut leaves out what other chips add, nothing
    else."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.gigachat35 import init_params
    from ray_tpu.ops.experts import held_experts_ffn, route

    widths = dict(TOY, n_experts=32, top_k=8)
    whole = dict(widths, experts_held=[0, 32])
    cfg = FAMILY.model_config(whole)
    assert cfg.routed_scaling == 2.5 and cfg.swiglu_limit == 10.0
    layer = init_params(jax.random.PRNGKey(3), cfg)["layers"][2]["mlp"]
    # Inputs large enough for the clamp to bite somewhere.
    y = 6.0 * jnp.asarray(np.random.default_rng(4).normal(
        size=(29, TOY["d_model"])), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = FAMILY._ref_experts(y, layer, whole)
        shared = FAMILY._clamped_ffn(
            y, layer["shared_gate"], layer["shared_up"],
            layer["shared_down"], cfg.swiglu_limit)
        unclamped = FAMILY._clamped_ffn(
            y, layer["shared_gate"], layer["shared_up"],
            layer["shared_down"], 1e9)
    assert float(jnp.max(jnp.abs(shared - unclamped))) > 1e-3
    experts, weights = route(y, layer["router"], layer["select_bias"],
                             cfg.top_k, cfg.routed_scaling)
    per_share = 32 // CONFIG["share_chips"]
    total, pairs = shared, 0
    for lo in range(0, 32, per_share):
        hi = lo + per_share
        part, load = held_experts_ffn(
            y, experts, weights, layer["w_gate"][lo:hi],
            layer["w_up"][lo:hi], layer["w_down"][lo:hi], (lo, hi),
            limit=cfg.swiglu_limit)
        with jax.default_matmul_precision("highest"):
            alone = FAMILY._ref_experts(
                y, dict(layer, **{k: layer[k][lo:hi] for k in
                                  ("w_gate", "w_up", "w_down")}),
                dict(whole, experts_held=[lo, hi]))
        np.testing.assert_allclose(part + shared, alone, atol=2e-4)
        total, pairs = total + part, pairs + int(load.sum())
    np.testing.assert_allclose(total, want, atol=5e-4)
    assert pairs == 29 * cfg.top_k


def test_the_engine_exports_the_model_and_the_hybrid_shares_its_base():
    from ray_tpu.serve import engine as package
    from ray_tpu.serve.engine import hybrid_model, state_model

    assert "GigaChatEngineModel" in package.__all__
    for model in (package.GigaChatEngineModel, package.HybridEngineModel):
        assert issubclass(model, state_model.StateEngineModel)
        assert model.decode_paged is state_model.StateEngineModel.decode_paged
    assert hybrid_model.PromptState is state_model.PromptState
    assert not hasattr(package.HybridEngineModel, "prefill_chunk")
