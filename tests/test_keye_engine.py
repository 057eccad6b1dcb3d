"""The sparse model whose attention selects its keys (`KeyeEngineModel`:
a learned indexer picks the positions a query attends to, its keys a
second kind of row in the paged cache, norms on q and k, a rotary in
sections, a softmax router without a shared expert) through the engine
and its cache, against the plain reference of its family
(`benchmarks/families/keye_vl2.py`) on the same seeded weights at toy
widths: float32 throughout, so the two agree to rounding, and `topk` 8
under prompts of 16-44. Controls that must fail the comparison: the
reference that attends to every causal key, one that keeps half as many,
one without the norms on q and k. And the share test: the routed sums of
all eight shares add up to the uncut layer."""

import json
import os

import numpy as np
import pytest

from benchmarks.harness import manifest

pytestmark = pytest.mark.unit

FAMILY = manifest.load_family("keye_vl2")
with open(os.path.join(manifest.ROOT, "benchmarks", "configs",
                       "keye-vl-2.0-30b-a3b.json")) as f:
    CONFIG = json.load(f)
TOY = FAMILY.toy_widths(FAMILY.widths(CONFIG))
BLOCK = 16
ENGINE = {"paged_decode": True, "max_batch_size": 4, "block_size": BLOCK,
          "num_blocks": 48, "max_queue": 64}
TOLERANCE = 2e-4       # float32 against float32; another row's KV gives ~1


def _serve(widths=TOY, seed=7, chunk=1024, **engine):
    """`chunk`: the toy widths name 16; 1,024 prefills these prompts
    whole."""
    from ray_tpu.serve.engine import InferenceEngine

    served = FAMILY.build_serving(
        widths, {"max_seq_len": 256, "engine": dict(ENGINE, **engine)}, seed)
    served["model"].prefill_chunk_tokens = chunk
    return served, InferenceEngine(served["model"], served["engine_config"])


@pytest.fixture(scope="module")
def toy():
    served, engine = _serve()
    return served, engine, FAMILY.reference_logits(TOY)


@pytest.fixture(scope="module")
def toy_chunked():
    """The same weights, prompts past 16 positions in chunks of 16."""
    served, engine = _serve(chunk=16)
    return served, engine, FAMILY.reference_logits(TOY)


def _gap(row, expect):
    return float(np.sqrt(np.mean((row - expect) ** 2))
                 / np.sqrt(np.mean(expect ** 2)))


def _prompt(n, seed=None):
    return np.random.default_rng(n if seed is None else seed).integers(
        2, TOY["vocab_size"], n).tolist()


def test_the_published_widths_are_the_configurations():
    w = FAMILY.widths(CONFIG)
    assert (w["d_model"], w["n_layers"], w["n_heads"], w["n_kv_heads"],
            w["head_dim"]) == (2048, 12, 32, 4, 128)
    assert (w["rope_theta"], w["mrope_section"], w["norm_eps"]) == \
        (1e7, [16, 24, 24], 1e-6)
    assert (w["index_heads"], w["index_dim"], w["index_topk"]) == \
        (16, 64, 2048)
    assert (w["n_experts"], w["experts_held"], w["top_k"],
            w["expert_width"]) == (128, [0, 16], 8, 768)
    assert w["published"] == {"n_layers": 48, "vocab_size": 151936}
    assert w["vocab_size"] == 18992 == 151936 // 8
    # The toy keeps every mechanism: grouped heads, three sections, an
    # indexer that keeps fewer positions than a prompt has.
    assert TOY["n_heads"] // TOY["n_kv_heads"] == 4
    assert sum(TOY["mrope_section"]) * 2 == TOY["head_dim"]
    assert TOY["index_topk"] == 8


def test_a_position_keeps_two_kinds_of_row(toy):
    served, engine, _ = toy
    model, cache = served["model"], engine.cache
    assert model.kv_token_shape == (3, 2, 2, 16)
    # An index key of 8 values lies in a row of whole lanes.
    assert model.kv_groups == {"index": {"kv_shape": (3, 128),
                                         "rides": True}}
    assert (model.index_token_bytes_model, model.index_token_bytes_held) \
        == (3 * 8 * 4, 3 * 128 * 4)
    assert cache.grouped and engine.prefix_index is None
    stats = cache.stats()
    # The index pool rides the global group's blocks: a layer's page in
    # one piece, no table and no free list of its own.
    assert stats["groups"]["index"]["rides"] == "global"
    assert stats["groups"]["index"]["pool_bytes"] == 48 * 3 * BLOCK * 128 * 4
    assert stats["groups"]["global"]["pool_bytes"] == \
        48 * BLOCK * 3 * 2 * 2 * 16 * 4
    shapes = cache.with_pools(lambda pools: {k: v.shape
                                             for k, v in pools.items()})
    # 2 key/value heads do not fill a float32 tile: the KV's own pool is
    # held by planes (layer, slot x head), like the index keys'.
    assert model.kv_planes == {"global": True}
    assert shapes == {"global": (48, 3, 2 * 2, BLOCK, 16),
                      "index": (48, 3, BLOCK, 128)}
    assert set(cache.step_tables("nobody")) == {"global"}


# Prompts under `topk`, at it, on and off the block grid and several
# times past it, then 20 decode steps, whole and in chunks of 16.
@pytest.mark.parametrize("how", ["whole", "chunks"])
@pytest.mark.parametrize("n, steps", [(5, 20), (8, 20), (16, 20), (23, 20),
                                      (44, 20)])
def test_prefill_then_decode_through_both_pools_matches_the_reference(
        toy, toy_chunked, how, n, steps):
    served, engine, ref = toy if how == "whole" else toy_chunked
    model = served["model"]
    prompt = _prompt(n)
    chunks = model.prefill_calls
    got, tokens = FAMILY.drive(engine, served, prompt, steps, f"check-{n}")
    want = np.asarray(ref(served["params"], np.asarray(tokens, np.int32)))
    for j, row in enumerate(got):
        assert _gap(row, want[n - 1 + j]) < TOLERANCE, (n, j)
    readings = served["own_limits"][-1]
    assert readings["ok"] and readings["selection_overlap"] == 1.0
    if how == "chunks":
        assert model.prefill_calls - chunks == -(-n // 16)
    assert engine.cache.free_blocks() == ENGINE["num_blocks"]


@pytest.mark.parametrize("control", [
    {"without": ["selection"]}, {"index_topk": TOY["index_topk"] // 2},
    {"without": ["qk_norm"]}],
    ids=["every_causal_key", "topk_halved", "no_qk_norm"])
def test_a_reference_without_a_mechanism_fails_the_comparison(toy, control):
    """The engine against a reference that lacks one mechanism: the rows
    differ beyond the family's limits at a prompt past `topk`, and
    `drive` hands them back as no numbers (or outside the harness's
    limit)."""
    served, engine, _ = toy
    n, steps = 40, 20
    served["reference_widths"] = dict(TOY, **control)
    try:
        got, tokens = FAMILY.drive(engine, served, _prompt(n), steps,
                                   "control")
    finally:
        del served["reference_widths"]
    readings = served["own_limits"].pop()
    assert not readings["ok"], readings
    assert readings["median"] > 0.02
    assert (not np.isfinite(got[-1]).all()
            or readings["positions"][-1] > FAMILY.LOGIT_TOLERANCE)


def test_a_batch_of_rows_on_both_sides_of_topk(toy):
    """Rows of different lengths in one step, through the scheduler:
    each row's tokens are its own drive's."""
    served, engine, ref = toy
    lengths, steps = [5, 12, 30, 44], 12
    prompts = [_prompt(n, seed=100 + n) for n in lengths]
    alone = []
    for i, prompt in enumerate(prompts):
        _, tokens = FAMILY.drive(engine, served, prompt, steps,
                                 f"alone-{i}")
        alone.append(tokens[len(prompt):])
    engine.start()
    try:
        streams = [engine.submit(prompt, steps) for prompt in prompts]
        together = [list(stream) for stream in streams]
    finally:
        engine.stop()
    assert together == alone
    model = served["model"]
    assert model.decode_kv_tokens_selected > 0
    assert model.decode_kv_tokens_selected <= model.decode_kv_tokens_read
    stats = engine.stats()
    for name in model.own_counters:
        assert stats[name] == getattr(model, name)
    assert engine.cache.free_blocks() == ENGINE["num_blocks"]


def test_the_scheduler_prefills_a_long_prompt_in_chunks(toy_chunked):
    served, engine, ref = toy_chunked
    prompt = _prompt(44, seed=5)
    _, tokens = FAMILY.drive(engine, served, prompt, 8, "alone")
    engine.start()
    try:
        got = list(engine.submit(prompt, 8))
    finally:
        engine.stop()
    assert got == tokens[len(prompt):]
    assert engine.stats()["prefill_chunks"] >= 3
    assert served["model"].prefill_selected_queries > 0


def test_the_step_counts_what_it_scores_selects_and_reads(toy):
    served, engine, _ = toy
    model = served["model"]
    before = {name: getattr(model, name) for name in model.own_counters}
    n, layers, topk = 40, TOY["n_layers"], TOY["index_topk"]
    FAMILY.drive(engine, served, _prompt(n), 2, "count")
    delta = {name: getattr(model, name) - before[name]
             for name in model.own_counters}
    live = (n + 1) + (n + 2)
    assert delta["decode_index_tokens_scored"] == live * layers
    assert delta["decode_kv_tokens_selected"] == 2 * topk * layers
    # The masked walk fetches every live position.
    assert delta["decode_kv_tokens_read"] == live * layers
    assert delta["decode_index_bytes_read"] == live * layers * 128 * 4
    assert delta["prefill_selected_queries"] == n - topk


def test_a_step_through_the_kernels_counts_both_pools_pages(toy, monkeypatch):
    """As `layer_groups_model.py` counts them where the kernels run: the
    live pages, and their bytes of both pools as they hold a position (an
    index key of 8 values in a row of 128) and as the model counts one."""
    served, engine, _ = toy
    model = served["model"]
    monkeypatch.setattr(model, "_attn_inplace", True)   # counting alone
    before = {name: getattr(model, name) for name in (
        "decode_kv_pages_read", "decode_attn_inplace_steps",
        "decode_kv_bytes_read_held", "decode_kv_bytes_read_model")}
    n, layers = 40, TOY["n_layers"]
    FAMILY.drive(engine, served, _prompt(n), 2, "pages")
    delta = {name: getattr(model, name) - was
             for name, was in before.items()}
    block = ENGINE["block_size"]
    pages = -(-n // block) + -(-(n + 1) // block)
    kv = layers * 2 * TOY["n_kv_heads"] * TOY["head_dim"] * 4
    assert delta["decode_attn_inplace_steps"] == 2
    assert delta["decode_kv_pages_read"] == pages
    assert delta["decode_kv_bytes_read_held"] == pages * block * (
        kv + layers * 128 * 4)
    assert delta["decode_kv_bytes_read_model"] == pages * block * (
        kv + layers * TOY["index_dim"] * 4)


def test_preemption_and_free_return_both_pools_blocks():
    served, engine = _serve(num_blocks=8, max_batch_size=2)
    cache = engine.cache
    engine.start()
    try:
        streams = [engine.submit(_prompt(40, seed=i), 40) for i in range(2)]
        outs = [list(s) for s in streams]
    finally:
        engine.stop()
    assert all(len(out) == 40 for out in outs)
    # Two rows of 80 positions need 10 of 8 blocks: one was preempted.
    assert engine.stats()["preemptions"] >= 1
    assert cache.free_blocks() == 8
    assert cache.stats()["groups"]["index"]["blocks_in_use"] == 0


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The guide's test: each of the 8 chips holds 1 / 8 of the experts
    and computes its own terms of the routed sum; the eight sums add up
    to the layer that holds them all."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.keye_vl2 import init_params

    shares = TOY["n_experts"] // 2
    whole_w = dict(TOY, experts_held=[0, TOY["n_experts"]])
    whole = init_params(jax.random.PRNGKey(3), FAMILY.model_config(whole_w))
    mp = whole["layers"][1]["mlp"]
    y = jnp.asarray(np.random.default_rng(1).standard_normal(
        (11, TOY["d_model"])), jnp.float32)
    with jax.default_matmul_precision("highest"):
        uncut = FAMILY.sparse_layer_share(y, mp, whole_w)
        total = jnp.zeros_like(uncut)
        for chip in range(shares):
            lo, hi = 2 * chip, 2 * chip + 2
            mine = dict(mp, **{k: mp[k][lo:hi]
                               for k in ("w_gate", "w_up", "w_down")})
            total += FAMILY.sparse_layer_share(
                y, mine, dict(TOY, experts_held=[lo, hi]))
    assert _gap(np.asarray(total), np.asarray(uncut)) < 1e-5
    # And the engine's expert layer, told which it holds, adds its part.
    from ray_tpu.serve.engine import KeyeEngineModel

    cfg = FAMILY.model_config(dict(TOY, experts_held=[2, 4]))
    mine = dict(mp, **{k: mp[k][2:4] for k in ("w_gate", "w_up", "w_down")})
    model = KeyeEngineModel(dict(whole, layers=[]), cfg)
    x = jnp.zeros_like(y)
    ones = jnp.ones((TOY["d_model"],), jnp.float32)
    # `_experts` norms its input: hand it rows whose norm is themselves.
    rows = y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True) + cfg.norm_eps)
    with jax.default_matmul_precision("highest"):
        got, _ = model._experts(rows, ones, mine, jnp.ones((11,), bool))
        want = FAMILY.sparse_layer_share(
            FAMILY._rms_norm(rows, ones, cfg.norm_eps), mine,
            dict(TOY, experts_held=[2, 4]))
    assert _gap(np.asarray(got - rows), np.asarray(want)) < 1e-4
    del x
