"""`prefill_chunks_unwaited_pct` (PR 62): of a window's chunks of
prompts, the share the host did not wait for, from the window's counter
deltas."""

import pytest

from benchmarks.harness import manifest

NAME = "prefill_chunks_unwaited_pct"
# The cells whose prompts go in chunks: where the reader finds something
# to read.
CHUNKED_CELLS = ["laguna-s-2.1.serve.repo-context",
                 "mimo-v2.5.serve.doc-context",
                 "keye-vl-2.0-30b-a3b.serve.long-doc",
                 "gigachat3.5-432b-a28b.serve.long-reason"]


def test_the_chunked_cells_are_among_the_entrys_cells():
    """Membership, not position: the next PR appends a cell to the list
    and a metric behind this one (ROADMAP B0 a0)."""
    entries = {m["name"]: m for m in manifest.load_manifest()["per_layer"]}
    assert NAME in entries
    entry = entries[NAME]
    assert set(CHUNKED_CELLS) <= set(entry["workloads"])
    assert (entry["source"], entry["layer"], entry["moves"], entry["unit"],
            entry["better"]) == ("program_counter", "Engine scheduler",
                                 "serve_out_tokens_per_s", "%", "higher")
    assert manifest.problems() == []
    # Every cell it names reports the end-to-end metric it moves.
    moved = next(m for m in manifest.load_manifest()["end_to_end"]
                 if m["name"] == entry["moves"])
    assert set(entry["workloads"]) <= set(moved["workloads"])
    for cell_name in CHUNKED_CELLS:
        cell = manifest.load_cell(cell_name)
        assert NAME in [m["name"] for m in cell["per_layer"]]
    for cell_name in ("smollm2-1.7b.train.seq2k", "olmo-1b.train.fsdp4",
                      "olmo-1b.serve.chat-steady"):
        cell = manifest.load_cell(cell_name)
        assert NAME not in [m["name"] for m in cell["per_layer"]]


# A window's counter deltas: prompts of 7-15 chunks, of 2-9, one prompt
# of two chunks, a window whose edge fell between a chunk's dispatch and
# its rows' write, the parent's (no such counter), a window without a
# chunk (a model without the call), and a context without the chunks'
# count.
@pytest.mark.parametrize("counters, want", [
    ({"prefill_chunks": 503, "prefill_chunks_unwaited": 457},
     100.0 * 457 / 503),
    ({"prefill_chunks": 400, "prefill_chunks_unwaited": 240}, 60.0),
    ({"prefill_chunks": 2, "prefill_chunks_unwaited": 1}, 50.0),
    ({"prefill_chunks": 7, "prefill_chunks_unwaited": 7}, 100.0),
    ({"prefill_chunks": 503}, None),
    ({"prefill_chunks": 0, "prefill_chunks_unwaited": 0}, None),
    ({"prefill_chunks_unwaited": 5}, None),
], ids=["long_reason", "repo_context", "one_prompt", "edge_in_a_chunk",
        "parent_has_no_counter", "no_chunks", "no_chunk_count"])
def test_the_reader_divides_unwaited_by_chunks_or_finds_nothing(
        counters, want):
    read = manifest.load_reader(NAME)
    got = read({"counters": counters, "trace": None})
    assert got == (pytest.approx(want) if want is not None else None)
    # A context without the key at all (a hand-built one) reads nothing.
    assert read({}) is None


def test_the_reader_reads_an_engines_own_counters():
    """The two snapshots a window subtracts, from an engine over the
    oracle model, which offers no `prefill_chunk`: both counters are
    there from construction and stay 0, so the reader finds nothing."""
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine, TinyLM

    def numbers(stats):
        return {k: v for k, v in stats.items()
                if isinstance(v, (int, float)) and not isinstance(v, bool)}

    eng = InferenceEngine(TinyLM(), EngineConfig(
        max_batch_size=2, block_size=4, num_blocks=64))
    before = numbers(eng.stats())
    assert before["prefill_chunks_unwaited"] == 0 == before["prefill_chunks"]
    streams = [eng.submit([5, 9, 3], 9), eng.submit([2, 2], 9)]
    while eng.step():
        pass
    assert [len(list(s)) for s in streams] == [9, 9]
    after = numbers(eng.stats())
    counters = {k: after[k] - before[k] for k in before}
    assert counters["prefills"] == 2
    assert manifest.load_reader(NAME)({"counters": counters}) is None
