"""A chunk of a prompt that is not its last is not waited for (PR 62):
the model's call returns once the chunk is dispatched, its rows' write
and the batch's decode step go out behind it, and what keeps the result
the whole path's is the device's order of programs, not the host's wait.
Through the engine at toy widths on the three models that offer
`prefill_chunk` (the layer-groups model as MiMo and as Laguna, Keye's,
GigaChat's): tokens, the counter, the spans, the failure and the cancel
paths, and a block that changes hands behind a chunk's dispatch. Counts
and values only: no assertion on how long anything took."""

import functools
import json
import os

import numpy as np
import pytest

from benchmarks.harness import manifest

BLOCK = 16
# family, its configuration, the toy's chunk (a window group's chunk is
# no shorter than its window's tail: `test_prefill_chunk`'s 32).
MODELS = {
    "mimo": ("mimo_v2", "mimo-v2.5.json", 32),
    "laguna": ("laguna", "laguna-s-2.1.json", 32),
    "keye": ("keye_vl2", "keye-vl-2.0-30b-a3b.json", 16),
    "gigachat": ("gigachat3_5", "gigachat3.5-432b-a28b.json", 16),
}
# One of the two layer-groups models in the tests every model runs; both
# where a window group's block changes hands.
THREE = ["mimo", "keye", "gigachat"]
WINDOWED = ["mimo", "laguna"]
ENGINE = {"paged_decode": True, "max_batch_size": 3, "block_size": BLOCK,
          "num_blocks": 64, "max_queue": 64}


@functools.lru_cache(maxsize=None)
def _built(name):
    """(family, toy widths, what `build_serving` gave, the chunk): one
    model and its seeded weights a family, for every engine of a test."""
    family_name, config_name, chunk = MODELS[name]
    family = manifest.load_family(family_name)
    with open(os.path.join(manifest.ROOT, "benchmarks", "configs",
                           config_name)) as f:
        widths = family.toy_widths(family.widths(json.load(f)))
    engine = dict(ENGINE)
    if name in WINDOWED:
        engine["group_blocks"] = {"window": 12}
    served = family.build_serving(
        widths, {"max_seq_len": 256, "engine": engine}, 7)
    served["model"].prefill_chunk_tokens = chunk
    return family, widths, served, chunk


def _engine(name, whole=False):
    from ray_tpu.serve.engine import InferenceEngine

    _, _, served, _ = _built(name)
    engine = InferenceEngine(served["model"], served["engine_config"])
    if whole:
        engine._chunk = None
    return engine


def _prompt(name, n, seed):
    widths = _built(name)[1]
    return np.random.default_rng(seed).integers(
        2, widths["vocab_size"], n).tolist()


ROW_TOKENS, NEW_TOKENS = 40, 8


def _beside_a_running_row(name, engine):
    """A short prompt that runs, then a prompt of three and a half
    chunks submitted beside it. Returns both streams and the long
    prompt."""
    chunk = _built(name)[3]
    short = engine.submit(_prompt(name, 5, 1), ROW_TOKENS)
    engine.step()                      # its prefill and its first step
    assert engine._in_flight is None and len(engine._running) == 1
    prompt = _prompt(name, 3 * chunk + chunk // 2, 2)
    return short, engine.submit(prompt, NEW_TOKENS), prompt


@pytest.mark.parametrize("name", THREE)
def test_a_long_prompt_beside_a_batch_gives_the_whole_paths_tokens(name):
    """Token for token what the whole-prompt path gives and what the
    family's reference gives; every chunk but the prompt's last went
    unwaited, and the model counted it where it skipped the wait."""
    family, widths, served, _ = _built(name)
    tokens = {}
    for how in ("chunks", "whole"):
        engine = _engine(name, whole=how == "whole")
        before = engine.stats()
        short, stream, prompt = _beside_a_running_row(name, engine)
        while engine.step():
            pass
        tokens[how] = (list(short), list(stream))
        after = engine.stats()
        chunks = after["prefill_chunks"] - before["prefill_chunks"]
        unwaited = (after["prefill_chunks_unwaited"]
                    - before["prefill_chunks_unwaited"])
        # One prompt went in chunks: four of them, the last one read.
        assert (chunks, unwaited) == ((4, 3) if how == "chunks" else (0, 0))
        assert engine.cache.free_blocks() == ENGINE["num_blocks"]
    assert tokens["chunks"] == tokens["whole"]
    out = tokens["chunks"][1]
    assert len(out) == NEW_TOKENS
    ref = family.reference_logits(widths)
    want = np.asarray(ref(served["params"],
                          np.asarray(prompt + out, np.int32)))
    assert [int(np.argmax(want[len(prompt) - 1 + i]))
            for i in range(len(out))] == out


@pytest.mark.parametrize("name", THREE)
def test_only_a_prompts_last_chunk_has_a_wait(name, recorder):
    """No `model.prefill.logits_wait` span and no growth of the model's
    `prefill_wait_s` for a chunk that is not the prompt's last; one span
    for the last. Every chunk keeps its prep and its dispatch."""
    engine = _engine(name)
    model = engine.model
    _beside_a_running_row(name, engine)
    recorder.reset()
    seen = []
    while engine._in_flight is not None or not seen:
        waited, unwaited = (model.phase["prefill_wait_s"],
                            model.prefill_chunks_unwaited)
        engine.step()                  # one chunk, then the row's step
        labels = [e[3] for e in recorder.snapshot(categories={"model"})]
        recorder.reset()
        assert labels.count("prefill") == labels.count("prefill.prep") \
            == labels.count("prefill.dispatch") == 1
        assert labels.count("decode.logits_wait") == 1
        seen.append((labels.count("prefill.logits_wait"),
                     model.phase["prefill_wait_s"] > waited,
                     model.prefill_chunks_unwaited - unwaited))
    assert seen == [(0, False, 1)] * 3 + [(1, True, 0)]
    while engine.step():
        pass


@pytest.mark.parametrize("name", THREE)
def test_a_chunk_that_raises_at_dispatch_fails_its_stream_alone(name):
    """The batch goes on and gives the tokens it gives alone; the
    prompt's blocks (and its state slot) come back."""
    control = _engine(name)
    alone = control.submit(_prompt(name, 5, 1), ROW_TOKENS)
    while control.step():
        pass
    engine = _engine(name)
    model = engine.model
    short, stream, _ = _beside_a_running_row(name, engine)
    engine.step()                               # the first chunk
    assert engine._in_flight is not None
    held = engine.cache.free_blocks()

    def broken(*args, **kwargs):
        raise RuntimeError("a chunk failed at dispatch")
    model.prefill_chunk = broken
    try:
        engine.step()
    finally:
        del model.prefill_chunk                 # the class's again
    assert engine._in_flight is None and len(engine._running) == 1
    assert engine.cache.free_blocks() > held
    with pytest.raises(RuntimeError, match="failed at dispatch"):
        list(stream)
    while engine.step():
        pass
    assert list(short) == list(alone)
    assert engine.cache.free_blocks() == ENGINE["num_blocks"]
    assert engine.cache.stats().get("state_slots_in_use", 0) == 0


@pytest.mark.parametrize("name", THREE)
def test_a_cancel_between_two_chunks_frees_the_prompts_blocks(name):
    """The cancelled prompt's last chunk was never waited for: its
    blocks go back while its rows' write may still be on the device, and
    the prompt that takes them next is written behind it: it gives the
    tokens it gives on a fresh engine."""
    chunk = _built(name)[3]
    nxt = _prompt(name, 2 * chunk + 3, 3)
    fresh = _engine(name)
    want = fresh.submit(nxt, 6)
    while fresh.step():
        pass
    engine = _engine(name)
    short, stream, _ = _beside_a_running_row(name, engine)
    engine.step()
    engine.step()                               # two chunks in the pools
    assert engine._in_flight is not None
    held = engine.cache.free_blocks()
    stream.cancel()
    follower = engine.submit(nxt, 6)
    engine.step()          # reaps the one, admits the other's first chunk
    assert engine._in_flight is not None
    assert engine._in_flight.stream is follower
    assert engine.cache.free_blocks() >= held - 1
    while engine.step():
        pass
    assert list(stream) == [] and stream.finished
    assert list(follower) == list(want)
    assert len(list(short)) == ROW_TOKENS
    assert engine.cache.free_blocks() == ENGINE["num_blocks"]
    assert engine.cache.stats().get("state_slots_in_use", 0) == 0


def _window_rows(engine, table):
    return np.asarray(engine.cache.group("window").with_pool(
        lambda p: p[np.asarray(table)]))


@pytest.mark.parametrize("name", WINDOWED)
def test_a_window_block_given_back_behind_a_chunk_is_written_behind_it(name):
    """The window group gives back the blocks a chunk's end no longer
    sees while the chunk that reads them may still be on the device,
    unwaited. (`allocate(seq, end, writable_from=start)` trims and grows
    in one, and off a free list that hands out the last block returned
    the sequence takes its own blocks back: its own rows' write behind
    the chunk is the same hazard, which the token tests above hold. Here
    the trim is made apart, `release_expired`, so that ANOTHER sequence
    gets them.) The thief writes its own rows there at once: a later
    program. The chunk's rows are what they are without the thief, and
    the thief's blocks hold the thief's rows."""
    _, _, served, chunk = _built(name)
    model = served["model"]
    prompt = _prompt(name, 3 * chunk + chunk // 2, 2)
    other = _prompt(name, chunk, 4)
    rows = {}
    for thief in (False, True):
        engine = _engine(name)
        cache, window = engine.cache, engine.cache.group("window")
        start, logits = 0, None
        while start < len(prompt):
            end = min(len(prompt), start + chunk)
            tables = cache.step_tables("c")
            logits, kv = cache.with_pools(
                lambda pools: model.prefill_chunk(prompt, pools, tables,
                                                  start, BLOCK))
            assert (logits is None) == (end < len(prompt))
            if thief and start == chunk:
                read = set(tables["window"][1])
                free = set(window._free)
                assert cache.release_expired("c", end) > 0
                given = set(window._free) - free
                assert given and given <= read   # the chunk reads them
                _, theirs = model.prefill(other)
                assert cache.allocate("t", len(other), writable_from=0)
                cache.write_range("t", 0, theirs)
                taken = cache.step_tables("t")["window"][1]
                assert given & set(taken)
                rows["thief"] = (_window_rows(engine, taken), engine, taken)
            assert cache.allocate("c", end, writable_from=start)
            cache.write_range("c", start, kv)
            start = end
        held = cache.step_tables("c")["window"][1]
        rows[thief] = (np.asarray(logits), _window_rows(engine, held))
    np.testing.assert_array_equal(rows[True][0], rows[False][0])
    np.testing.assert_array_equal(rows[True][1], rows[False][1])
    # Behind every later chunk of "c" the thief's blocks still hold what
    # the thief wrote.
    first, engine, taken = rows["thief"]
    np.testing.assert_array_equal(_window_rows(engine, taken), first)


def test_the_counter_is_the_models_and_the_engine_forwards_it():
    """Counted where the wait is skipped (the shared base), 0 for a
    model without the call, there from construction."""
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine, TinyLM
    from ray_tpu.serve.engine.sparse_model import SparseEngineModel

    tiny = InferenceEngine(TinyLM(), EngineConfig(block_size=4,
                                                  num_blocks=32))
    assert tiny.stats()["prefill_chunks_unwaited"] == 0
    assert not hasattr(TinyLM(), "prefill_chunks_unwaited")
    for name in THREE:
        model = _built(name)[2]["model"]
        assert isinstance(model, SparseEngineModel)
        # One copy of the rule: no model has an end of its own.
        assert "_prompt_logits" not in vars(type(model))
        assert _engine(name).stats()["prefill_chunks_unwaited"] == \
            model.prefill_chunks_unwaited
