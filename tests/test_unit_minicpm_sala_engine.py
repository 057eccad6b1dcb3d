"""The block-selecting / lightning model (`MiniCPMSALAEngineModel`)
through the engine and its cache, against the plain reference of its
family (`benchmarks/families/minicpm_sala.py`: the selection by a sort,
the lightning layers by their recurrence) on the same seeded weights at
toy widths: float32 throughout, so the two agree to rounding and a
greedy token is the reference's argmax. Prefill whole, prefill in chunks
that carry the states and the compressed keys, decode over the chosen
pages and the state slots, and the scheduler's own loop over all three."""

import json
import os

import numpy as np
import pytest

from benchmarks.harness import manifest

pytestmark = pytest.mark.unit

FAMILY = manifest.load_family("minicpm_sala")
with open(os.path.join(manifest.ROOT, "benchmarks", "configs",
                       "minicpm-sala.json")) as f:
    CONFIG = json.load(f)
TOY = FAMILY.toy_widths(FAMILY.widths(CONFIG))
ENGINE = {"paged_decode": True, "max_batch_size": 3, "block_size": 16,
          "num_blocks": 64, "max_queue": 64}
TOLERANCE = 2e-4       # float32 against float32; a stale state gives ~1


def _serve(widths=TOY, seed=7, **engine):
    from ray_tpu.serve.engine import InferenceEngine

    served = FAMILY.build_serving(
        widths, {"max_seq_len": 256, "engine": dict(ENGINE, **engine)}, seed)
    return served, InferenceEngine(served["model"], served["engine_config"])


@pytest.fixture(scope="module")
def toy():
    served, engine = _serve()
    return served, engine, FAMILY.reference(TOY)


def _gap(row, expect):
    return float(np.sqrt(np.mean((row - expect) ** 2))
                 / np.sqrt(np.mean(expect ** 2)))


# Whole (at most a chunk of 16) and in chunks, off and on the chunk, block
# and stride grids; below `dense_len` (64: every block) and past it (the
# chunk program and the decode bucket select); then four decode steps
# through the pool and the state.
@pytest.mark.parametrize("n", [5, 16, 23, 49, 66, 100, 131])
def test_prefill_then_decode_through_the_cache_matches_the_reference(toy, n):
    served, engine, ref = toy
    model = served["model"]
    rng = np.random.default_rng(n)
    prompt = rng.integers(2, TOY["vocab_size"], n).tolist()
    later = model.prefill_later_chunks
    got, tokens = FAMILY.drive(engine, served, prompt, 4, f"check-{n}")
    want = np.asarray(ref(served["params"],
                          np.asarray(tokens, np.int32))["logits"])
    for j, row in enumerate(got):
        assert row.shape == (TOY["vocab_size"],)     # the padding left out
        assert _gap(row, want[len(want) - len(got) + j]) < TOLERANCE, (n, j)
    readings = served["own_limits"][-1]
    assert readings["ok"] and readings["selection_overlap"] == 1.0
    assert engine.cache.stats()["state_slots_in_use"] == 0
    # A prompt past a chunk went in chunks, every later one from its slot.
    assert model.prefill_later_chunks - later == max(0, -(-n // 16) - 1)
    assert model.prefill_later_chunks == model.prefill_state_chunks


def test_a_prompt_in_chunks_gives_the_whole_prompts_rows_and_state(toy):
    """One prompt two ways: in chunks of 16 through the cache, and whole
    in one program (the chunk turned up): the KV rows in the pool, the
    lightning states, the compressed keys and the strides' sums in the
    slot are the same; and the states are the reference's."""
    served, engine, ref = toy
    model, cache = served["model"], engine.cache
    prompt = np.random.default_rng(3).integers(2, 500, 58).tolist()

    def kept_by(run):
        run("s")
        state = cache.read_state("s")
        rows = FAMILY._rows_in_cache(cache, "s", len(prompt), TOY)
        cache.free("s")
        return state, rows

    in_chunks, rows_chunks = kept_by(
        lambda sid: FAMILY.prefill_as_the_scheduler(engine, model, prompt,
                                                    sid))
    model.prefill_chunk_tokens = 64
    try:
        whole, rows_whole = kept_by(
            lambda sid: FAMILY.prefill_as_the_scheduler(engine, model,
                                                        prompt, sid))
    finally:
        model.prefill_chunk_tokens = 16
    np.testing.assert_allclose(rows_chunks, rows_whole, rtol=2e-4, atol=2e-5)
    whole_kernels = (58 - 8) // 4 + 1
    for name in ("s", "ksum"):
        np.testing.assert_allclose(in_chunks[name], whole[name], rtol=2e-4,
                                   atol=2e-5)
    np.testing.assert_allclose(in_chunks["ck"][:, :, :whole_kernels],
                               whole["ck"][:, :, :whole_kernels], rtol=2e-4,
                               atol=2e-5)
    want = ref(served["params"], np.asarray(prompt, np.int32))
    np.testing.assert_allclose(in_chunks["s"], want["states"], rtol=2e-4,
                               atol=2e-5)
    np.testing.assert_allclose(rows_chunks, want["kv"], rtol=2e-4, atol=2e-5)
    # 58 positions: 14 whole strides of 4; the last whole one's sum and
    # the two positions of the stride being filled.
    keys = np.asarray(want["kv"])[:, :, :, 0]            # [L, S, Hkv, hd]
    np.testing.assert_allclose(in_chunks["ksum"][:, 0],
                               keys[:, 52:56].sum(1), rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(in_chunks["ksum"][:, 1],
                               keys[:, 56:58].sum(1), rtol=2e-4, atol=2e-5)


def test_the_scheduler_serves_rows_of_unequal_length_and_reuses_a_slot(toy):
    """Through `InferenceEngine.submit`: prompts below and past
    `dense_len` beside running rows, more prompts than slots (a freed
    slot serves the next), every token the reference's argmax."""
    served, _, ref = toy
    _, engine = _serve(seed=7)
    engine.start()
    try:
        rng = np.random.default_rng(11)
        prompts = [rng.integers(2, 500, n).tolist()
                   for n in (40, 9, 97, 70, 16)]
        streams = [engine.submit(p, 6) for p in prompts]
        outs = [list(s) for s in streams]
    finally:
        engine.stop()
    for prompt, out in zip(prompts, outs):
        text = prompt + out
        want = np.asarray(ref(served["params"],
                              np.asarray(text, np.int32))["logits"])
        assert [int(np.argmax(want[len(want) - len(out) - 1 + i]))
                for i in range(len(out))] == out
    stats = engine.stats()
    assert stats["prefill_chunks"] == 3 + 7 + 5
    assert stats["prefill_later_chunks"] == stats["prefill_state_chunks"] \
        == 2 + 6 + 4
    assert stats["decode_blocks_scored"] > 0
    assert 0 < stats["decode_blocks_selected"]
    assert stats["decode_kv_tokens_read"] < stats["decode_index_tokens_scored"] \
        + stats["decode_blocks_selected"] * 16
    assert stats["compressed_keys_written"] > 0
    assert stats["lightning_state_bytes_moved"] > 0
    assert stats["cache"]["state_slots_in_use"] == 0
    assert stats["cache"]["state_slots"] == 3
    # The cache names the compressed keys' store and its bytes.
    stores = stats["cache"]["state_stores"]
    assert stores["ck"] == 3 * 2 * 2 * (256 // 4) * 16 * 4
    assert sum(stores.values()) == stats["cache"]["state_bytes"]


def test_a_step_reads_the_chosen_blocks_alone(toy):
    """The step's counters are its program's arithmetic: a row at 120
    attends to the first block, the window's three and the top two."""
    served, engine, _ = toy
    model = served["model"]
    assert model.selected_blocks(40) == 3          # below dense_len: all
    assert model.selected_blocks(120) == 1 + 3 + 2
    assert model.selected_blocks(64) == 1 + 3 + 1  # one block is the rest
    before = {name: getattr(model, name) for name in model.own_counters}
    model._count_step(None, [8], 8, [120], 16)
    each = 2 * 2                                   # layers x key/value heads
    assert model.decode_blocks_selected - before["decode_blocks_selected"] \
        == each * 6
    assert model.decode_blocks_scored - before["decode_blocks_scored"] \
        == each * 8
    # Five whole blocks and the 8 cached positions of block 7's page.
    assert model.decode_kv_tokens_read - before["decode_kv_tokens_read"] \
        == each * 6 * 16
    assert model.decode_index_tokens_scored \
        - before["decode_index_tokens_scored"] == each * 121


def test_a_chunk_past_position_0_needs_its_slot(toy):
    served, engine, _ = toy
    model = served["model"]
    prompt = list(range(2, 42))
    with pytest.raises(ValueError, match="without its sequence's state"):
        engine.cache.with_pools(lambda pools: model.prefill_chunk(
            prompt, pools, [], 16, 16))


def test_a_whole_prompt_past_dense_len_is_refused(toy):
    served, _, _ = toy
    with pytest.raises(ValueError, match="goes in chunks"):
        served["model"]._build_prefill(128)


def test_the_dense_model_shares_the_bases_and_sends_no_expert_counts():
    from ray_tpu.serve.engine import (GigaChatEngineModel,
                                      MiniCPMSALAEngineModel)
    from ray_tpu.serve.engine.sparse_model import (DecoderEngineModel,
                                                   SparseEngineModel)
    from ray_tpu.serve.engine import HybridEngineModel
    from ray_tpu.serve.engine.state_model import (StateChunks,
                                                  StateEngineModel,
                                                  StateSteps)

    assert issubclass(MiniCPMSALAEngineModel, (StateSteps,
                                               DecoderEngineModel))
    assert not issubclass(MiniCPMSALAEngineModel, SparseEngineModel)
    assert issubclass(GigaChatEngineModel, StateEngineModel)
    assert issubclass(StateEngineModel, (StateSteps, SparseEngineModel))
    assert MiniCPMSALAEngineModel._ids_trail == 0
    assert SparseEngineModel._ids_trail == 3
    # One host side of a chunk, for the two models that offer the call;
    # the hybrid prefills whole and must not seem to.
    assert MiniCPMSALAEngineModel.prefill_chunk is StateChunks.prefill_chunk
    assert GigaChatEngineModel._prefill_chunk is StateChunks._prefill_chunk
    assert not hasattr(HybridEngineModel, "prefill_chunk")
    # One `decode_paged`, one `prefill`: the mixin's.
    assert MiniCPMSALAEngineModel.decode_paged is StateSteps.decode_paged
    assert GigaChatEngineModel.decode_paged is StateSteps.decode_paged
