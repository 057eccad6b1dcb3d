"""`prefill_chunked_tokens_pct` (PR 56): of a window's prefilled prompt
tokens, the share the engine ran in chunks with a decode step between
two of them, from the window's counter deltas."""

import pytest

from benchmarks.harness import manifest

NAME = "prefill_chunked_tokens_pct"
SERVE_CELLS = ["olmo-1b.serve.chat-steady", "olmo-1b.serve.decode-heavy",
               "solar-open2-250b.serve.decode-wide",
               "laguna-s-2.1.serve.repo-context",
               "mimo-v2.5.serve.doc-context"]


def test_the_entry_names_the_five_serve_cells_and_its_layer():
    entry = {m["name"]: m for m in
             manifest.load_manifest()["per_layer"]}[NAME]
    assert entry["workloads"] == SERVE_CELLS
    assert (entry["source"], entry["layer"], entry["moves"], entry["unit"],
            entry["better"]) == ("program_counter", "Engine scheduler",
                                 "serve_itl_p99_ms", "%", "higher")
    assert manifest.problems() == []
    for cell_name in SERVE_CELLS:
        cell = manifest.load_cell(cell_name)
        assert NAME in [m["name"] for m in cell["per_layer"]]
    for cell_name in ("smollm2-1.7b.train.seq2k", "olmo-1b.train.fsdp4"):
        cell = manifest.load_cell(cell_name)
        assert NAME not in [m["name"] for m in cell["per_layer"]]


# A window's counter deltas: every prompt in chunks (`doc-context`: 4
# prompts of 6,016), prompts of exactly one chunk among longer ones
# (`repo-context`: 5 of 128 prompts are of 1,024), a model without the
# call, the parent's (no such counter), a window that prefilled nothing,
# and one without the model's count.
@pytest.mark.parametrize("counters, want", [
    ({"prefill_chunk_tokens": 24_064, "model.prefill_tokens": 24_064,
      "prefill_chunks": 24}, 100.0),
    ({"prefill_chunk_tokens": 30_720, "model.prefill_tokens": 31_744},
     100.0 * 30_720 / 31_744),
    ({"prefill_chunk_tokens": 0, "model.prefill_tokens": 2_304}, 0.0),
    ({"model.prefill_tokens": 24_064}, None),
    ({"prefill_chunk_tokens": 0, "model.prefill_tokens": 0}, None),
    ({"prefill_chunk_tokens": 1_024}, None),
], ids=["all_in_chunks", "one_chunk_prompts_whole", "model_without_the_call",
        "parent_has_no_counter", "no_prefill", "no_model_count"])
def test_the_reader_divides_chunked_by_prefilled_tokens_or_finds_nothing(
        counters, want):
    read = manifest.load_reader(NAME)
    got = read({"counters": counters, "trace": None})
    assert got == (pytest.approx(want) if want is not None else None)
    # A context without the key at all (a hand-built one) reads nothing.
    assert read({}) is None


def test_the_reader_reads_an_engines_own_counters():
    """The two snapshots a window subtracts, as the harness's `snapshot`
    takes them (the engine's top-level numbers and the model's
    `prefill_tokens`), from an engine over a model that offers the call:
    a prompt of three chunks and a bit, and one of a chunk's length,
    which goes whole."""
    import numpy as np

    from ray_tpu.serve.engine import EngineConfig, InferenceEngine, TinyLM

    class Chunks(TinyLM):
        prefill_chunk_tokens = 4

        def prefill_chunk(self, tokens, pool, table, start, block_size, *,
                          meanwhile=None):
            end = min(len(tokens), start + 4)
            self.prefill_tokens += end - start
            logits = None
            if end == len(tokens):
                logits, _ = TinyLM().prefill(tokens)
            return logits, np.asarray(tokens[start:end], np.float32)[:, None]

    def numbers(engine):
        out = {k: v for k, v in engine.stats().items()
               if isinstance(v, (int, float)) and not isinstance(v, bool)}
        out["model.prefill_tokens"] = engine.model.prefill_tokens
        return out

    model = Chunks()
    eng = InferenceEngine(model, EngineConfig(
        max_batch_size=4, block_size=4, num_blocks=64,
        prefix_sharing=False))
    before = numbers(eng)
    long = eng.submit(list(range(2, 16)), 3)
    short = eng.submit([5, 9, 3, 7], 3)
    while eng.step():
        pass
    assert list(long) == model.oracle(list(range(2, 16)), 3)
    assert list(short) == model.oracle([5, 9, 3, 7], 3)
    after = numbers(eng)
    counters = {k: after[k] - before[k] for k in before}
    assert counters["prefill_chunks"] == 4
    assert counters["prefill_chunk_tokens"] == 14
    assert counters["model.prefill_tokens"] == 18
    assert manifest.load_reader(NAME)({"counters": counters}) == \
        pytest.approx(100.0 * 14 / 18)
    # A model without the call: the counter is there and stands at 0.
    plain = InferenceEngine(TinyLM(), EngineConfig(block_size=4,
                                                   num_blocks=64))
    stream = plain.submit(list(range(2, 16)), 3)
    while plain.step():
        pass
    assert len(list(stream)) == 3
    assert manifest.load_reader(NAME)({"counters": numbers(plain)}) == 0.0
