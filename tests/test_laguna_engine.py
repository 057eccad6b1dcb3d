"""The window-and-global sparse model (`LagunaEngineModel`) through the
engine and its cache by layer group, against the plain reference of its
family (`benchmarks/families/laguna.py`) on the same seeded weights at
toy widths: float32 throughout, so the two agree to rounding and a greedy
token is the reference's argmax."""

import json
import os

import numpy as np
import pytest

from benchmarks.harness import manifest

pytestmark = pytest.mark.unit

FAMILY = manifest.load_family("laguna")
with open(os.path.join(manifest.ROOT, "benchmarks", "configs",
                       "laguna-s-2.1.json")) as f:
    CONFIG = json.load(f)
TOY = FAMILY.toy_widths(FAMILY.widths(CONFIG))
BLOCK = 16
ENGINE = {"paged_decode": True, "max_batch_size": 3, "block_size": BLOCK,
          "num_blocks": 64, "group_blocks": {"window": 12}, "max_queue": 64}
# ceil(24 / 16) + 1: what a sequence may hold of the window group.
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
    assert (w["d_model"], w["heads_full"], w["heads_sliding"],
            w["n_kv_heads"], w["head_dim"], w["window"]) == \
        (3072, 48, 72, 8, 128, 512)
    assert (w["n_experts"], w["experts_held"], w["top_k"],
            w["expert_width"], w["dense_width"], w["routed_scaling"]) == \
        (256, [0, 32], 10, 1024, 12288, 2.5)
    assert w["rope_full"]["rot_dim"] == 64 \
        and w["rope_sliding"]["rot_dim"] == 128
    p = FAMILY.param_counts(w)
    # ISSUE 35's arithmetic: 44.19 M, 63.14 M, 4,325 M held, 117.56 B.
    assert round(p["full_layer"] / 1e6, 2) == 44.19
    assert round(p["sliding_layer"] / 1e6, 2) == 63.14
    assert 4325 <= p["held"] / 1e6 < 4326
    assert round(p["total"] / 1e9, 2) == 117.56


# Prompts shorter than the window, on and off the block grid, longer than
# the window (the prefill then stores only the rows the window reaches),
# then decode steps past the window and across a block boundary of each
# group (a window block is released on the way).
@pytest.mark.parametrize("n, steps", [(5, 3), (16, 3), (23, 20), (40, 20),
                                      (49, 36)])
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


@pytest.mark.parametrize("n, steps", [(16, 3), (130, 4)])
def test_the_experts_kernel_through_the_engine_matches_the_reference(
        toy, n, steps, monkeypatch):
    """The same drive with the held experts' layer through its Pallas
    kernel, steered on here and interpreted (the backend is the CPU and
    the toy widths are no whole lanes): a decode step's rows and a short
    prompt's through every sparse layer, against the reference and the
    scan's rows; a prompt of more rows than a tile keeps the scan. The
    model counts its programs by the body their expert layers got."""
    from functools import partial

    from ray_tpu.ops import experts as ex

    plain, plain_engine, ref = toy
    prompt = np.random.default_rng(n).integers(
        2, TOY["vocab_size"], n).tolist()
    before = plain_engine.stats()
    scanned, _ = FAMILY.drive(plain_engine, plain, prompt, steps,
                              f"scan-{n}")
    after = plain_engine.stats()
    # Off the chip every program keeps the scan: a prefill and the steps.
    assert after["moe_steps_kernel"] == before["moe_steps_kernel"] == 0
    assert after["moe_steps_scan"] - before["moe_steps_scan"] == 1 + steps
    calls, interpreted = [], partial(ex.grouped_ffn_kernel, interpret=True)

    def kernel(*args, **kwargs):
        calls.append(args[0].shape)
        return interpreted(*args, **kwargs)

    monkeypatch.setattr(ex, "kernel_eligible",
                        lambda t, *widths: t <= ex._ROWS_MOST)
    monkeypatch.setattr(ex, "grouped_ffn_kernel", kernel)
    served, engine = _serve()
    got, tokens = FAMILY.drive(engine, served, prompt, steps, f"kernel-{n}")
    # Traced once a sparse layer in the decode step's program, and in
    # the prompt's where its bucket is one tile (16 rows; 256 are not).
    sparse = TOY["n_periods"] * 4 - 1
    short = n <= ex._ROWS_MOST
    assert len(calls) == (2 if short else 1) * sparse
    stats = engine.stats()
    assert stats["moe_steps_kernel"] == steps + short
    assert stats["moe_steps_scan"] == 1 - short
    want = np.asarray(ref(served["params"], np.asarray(tokens, np.int32)))
    for j, row in enumerate(got):
        assert _gap(row, want[n - 1 + j]) < TOLERANCE, (n, j)
        assert _gap(row, scanned[j]) < TOLERANCE, (n, j)
    assert served["own_limits"][-1]["ok"]


def test_a_long_prompt_through_the_prompt_kernel_matches_the_reference(
        monkeypatch):
    """A prompt of more rows than a tile through `held_experts_ffn_prefill`
    inside the engine's prefill, steered on here and interpreted, at a
    `d_model` of one sublane of 128 lanes (the kernel moves a row as its
    sublanes): every sparse layer's call, against the reference; the
    decode steps keep the scan, and the model counts one program by the
    kernel."""
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
    served, engine = _serve(widths)
    got, tokens = FAMILY.drive(engine, served, prompt, steps, "prompt-kernel")
    assert calls == [(256, 128)] * (TOY["n_periods"] * 4 - 1)
    stats = engine.stats()
    assert (stats["moe_steps_kernel"], stats["moe_steps_scan"]) == (1, steps)
    want = np.asarray(FAMILY.reference_logits(widths)(
        served["params"], np.asarray(tokens, np.int32)))
    for j, row in enumerate(got):
        assert _gap(row, want[n - 1 + j]) < TOLERANCE, (n, j)
    assert served["own_limits"][-1]["ok"]


def test_a_window_one_block_short_is_off_the_reference(toy):
    """The same weights served with a window of 8 instead of 24: rows
    past position 8 differ from the reference's."""
    served, _, ref = toy
    short, engine = _serve(dict(TOY, window=TOY["window"] - BLOCK))
    prompt = np.random.default_rng(3).integers(2, 512, 40).tolist()
    got, tokens = FAMILY.drive(engine, short, prompt, 4, "short")
    want = np.asarray(ref(short["params"], np.asarray(tokens, np.int32)))
    assert min(_gap(row, want[39 + j]) for j, row in enumerate(got)
               if np.isfinite(row).all()) > 100 * TOLERANCE \
        or not np.isfinite(got[0]).all()


def test_served_requests_follow_the_reference_and_count_by_group(toy):
    """Through `submit` and the scheduler's loop: three rows decode
    together past the window; every token is the reference's argmax, a
    sequence never holds more than the window's blocks, the release is
    counted and timed, one upload a step, and both free lists return to
    full."""
    from ray_tpu.core import flight

    served, engine = _serve(seed=13)
    ref = FAMILY.reference_logits(TOY)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(2, 512, n).tolist() for n in (9, 30, 47)]
    was = flight.enabled
    flight.enable()
    try:
        streams = [engine.submit(p, 40) for p in prompts]
        held = 0
        while engine.step():
            for seq_id in list(engine.cache._tables):
                held = max(held, len(
                    engine.cache.step_tables(seq_id)["window"][1]))
        outs = [list(s) for s in streams]
    finally:
        if not was:
            flight.disable()
    for prompt, out in zip(prompts, outs):
        assert len(out) == 40
        text = prompt + out
        want = np.asarray(ref(served["params"], np.asarray(text, np.int32)))
        assert [int(np.argmax(want[len(prompt) - 1 + i]))
                for i in range(40)] == out
    assert 0 < held <= WINDOW_BLOCKS
    stats = engine.stats()
    groups = stats["cache"]["groups"]
    assert groups["window"]["window_blocks_released"] > 0
    assert groups["global"]["window_blocks_released"] == 0
    assert stats["kv_window_window_blocks_released"] == \
        groups["window"]["window_blocks_released"]
    assert stats["kv_window_block_steps"] == \
        stats["paged_steps"] * ENGINE["group_blocks"]["window"]
    assert 0 < stats["kv_window_block_steps_in_use"] \
        <= stats["paged_steps"] * 3 * WINDOW_BLOCKS
    assert stats["decode_h2d_arrays"] == stats["paged_steps"]
    assert stats["cache"]["host_gathers"] == 0
    assert stats["prefill_kv_device_writes"] == 3
    assert stats["moe_local_assignments"] > 0
    assert 0 < stats["window_release_s"] <= stats["phase.capacity_s"]
    assert engine.prefix_index is None
    assert groups["global"]["blocks_in_use"] == 0
    assert groups["window"]["blocks_in_use"] == 0


def test_the_page_group_counters_are_a_direct_count_by_group():
    """The model counts, beside the live pages of each layer group, the
    groups the paged kernel fetches them in; `stats()` carries them flat.
    Steered on here (the CPU's steps take the XLA body, which the
    counters do not ask about), against a walk written out over the
    positions of every decode call: a row's live columns from the first
    its query sees, `pages_per_step` at a time."""
    from ray_tpu.ops import paged_attention as pa

    served, engine = _serve(seed=17)
    model = served["model"]
    model._attn_inplace = True
    calls = []
    inner = model._decode_paged

    def recording(pools, block_tables, last_tokens, positions, *rest):
        calls.append(([int(p) for p in positions],
                      {g: pool for g, pool in pools.items()}))
        return inner(pools, block_tables, last_tokens, positions, *rest)

    model._decode_paged = recording
    rng = np.random.default_rng(11)
    streams = [engine.submit(rng.integers(2, 512, n).tolist(), 30)
               for n in (5, 33, 60)]
    while engine.step():
        pass
    assert all(len(list(s)) == 30 for s in streams)
    window, want = TOY["window"], {"global": [0, 0], "window": [0, 0]}
    for positions, pools in calls:
        widest = max(p // BLOCK + 1 for p in positions)
        widths = {"global": 1 << (widest - 1).bit_length(),
                  "window": WINDOW_BLOCKS}
        for group, bound in (("global", None), ("window", window)):
            pages = pa.pool_pages_per_step(pools[group], widths[group])
            for p in positions:
                live = [c for c in range(-(-p // BLOCK))
                        if bound is None or c * BLOCK + BLOCK > p - bound + 1]
                want[group][0] += len(live)
                want[group][1] += len(range(0, len(live), pages))
    stats = engine.stats()
    assert stats["decode_attn_inplace_steps"] == stats["paged_steps"] > 0
    for group, (pages, groups) in want.items():
        assert stats[f"decode_kv_pages_read_{group}"] == pages > 0
        assert stats[f"decode_kv_page_groups_read_{group}"] == groups > 0
    assert stats["decode_kv_page_groups_read"] == \
        want["global"][1] + want["window"][1]
    assert stats["decode_kv_pages_read"] == \
        want["global"][0] + want["window"][0]
    # Tables this narrow are one fetch a row: pages ÷ groups is the live
    # pages of a row, as the benchmark's `decode_kv_pages_per_fetch`
    # would read it.
    assert stats["decode_kv_pages_read_global"] \
        > stats["decode_kv_page_groups_read_global"]


def test_a_preempted_row_frees_both_groups_and_is_recomputed():
    """A window pool that holds two sequences' windows but not three:
    the third row is preempted, both of its tables are freed, and every
    request still ends on the reference's tokens."""
    served, engine = _serve(seed=17, group_blocks={
        "window": 2 * WINDOW_BLOCKS + 1})
    ref = FAMILY.reference_logits(TOY)
    rng = np.random.default_rng(9)
    prompts = [rng.integers(2, 512, n).tolist() for n in (33, 35, 37)]
    streams = [engine.submit(p, 24) for p in prompts]
    while engine.step():
        pass
    for prompt, stream in zip(prompts, streams):
        out = list(stream)
        want = np.asarray(ref(served["params"],
                              np.asarray(prompt + out, np.int32)))
        assert [int(np.argmax(want[len(prompt) - 1 + i]))
                for i in range(24)] == out
    assert engine.cache.group("window").free_blocks() == \
        2 * WINDOW_BLOCKS + 1
    assert engine.cache.free_blocks() == ENGINE["num_blocks"]


def test_the_eight_shares_and_the_shared_expert_add_up_to_the_uncut_layer():
    """The routed parts of the shares [0, 2) .. [14, 16) of a 16-expert
    layer plus the shared expert once equal the reference's layer that
    holds all 16: what a share leaves out is exactly what the others
    add."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.laguna import init_params

    whole = dict(TOY, experts_held=[0, TOY["n_experts"]])
    params = init_params(jax.random.PRNGKey(3), FAMILY.model_config(whole))
    mp = params["periods"][0]["mlp"][1]
    y = jax.random.normal(jax.random.PRNGKey(4), (11, TOY["d_model"]),
                          jnp.float32)
    with jax.default_matmul_precision("highest"):
        uncut = FAMILY._ref_experts(y, mp, whole)
        weights = FAMILY._ref_routing(y, mp["router"], whole)
        shared = FAMILY._gated_ffn(y, mp["shared_gate"], mp["shared_up"],
                                   mp["shared_down"])
        per = TOY["n_experts"] // 8
        total = shared
        for share in range(8):
            lo, hi = share * per, (share + 1) * per
            part = {k: mp[k][lo:hi] for k in ("w_gate", "w_up", "w_down")}
            total = total + FAMILY._ref_routed(y, part, weights, (lo, hi))
    assert float(jnp.max(jnp.abs(total - uncut))) < 1e-5 * float(
        jnp.max(jnp.abs(uncut)))
    # And the engine's expert layer holds a share's terms alone.
    from ray_tpu.ops.experts import held_experts_ffn, route

    experts, wts = route(y, mp["router"], None, TOY["top_k"],
                         TOY["routed_scaling"], "softmax")
    routed, _ = held_experts_ffn(
        y, experts, wts, mp["w_gate"][2:4], mp["w_up"][2:4],
        mp["w_down"][2:4], (2, 4))
    with jax.default_matmul_precision("highest"):
        part = {k: mp[k][2:4] for k in ("w_gate", "w_up", "w_down")}
        want = FAMILY._ref_routed(y, part, weights, (2, 4))
    assert float(jnp.max(jnp.abs(routed - want))) < 1e-4
