"""`emit_overlapped_pct` (PR 55): of a window's decode tokens, the share
the engine delivered in the shadow of the next decode step, from the
window's counter deltas."""

import pytest

from benchmarks.harness import manifest

NAME = "emit_overlapped_pct"
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


# A window's counter deltas: a closed loop of 8 rows whose prefills flush
# a step's tokens early (7,600 decode tokens, 240 of them before a
# prefill), every token in a step's shadow, none (each step followed by a
# prefill), the parent's (no such counter), a window without a decode
# token, and one without the counters it divides by.
@pytest.mark.parametrize("counters, want", [
    ({"tokens_generated": 7_630, "prefills": 30,
      "tokens_delivered_overlapped": 7_360}, 100.0 * 7_360 / 7_600),
    ({"tokens_generated": 1_010, "prefills": 10,
      "tokens_delivered_overlapped": 1_000}, 100.0),
    ({"tokens_generated": 64, "prefills": 32,
      "tokens_delivered_overlapped": 0}, 0.0),
    ({"tokens_generated": 7_630, "prefills": 30}, None),
    ({"tokens_generated": 12, "prefills": 12,
      "tokens_delivered_overlapped": 0}, None),
    ({"tokens_delivered_overlapped": 5}, None),
], ids=["decode_heavy", "all_in_the_shadow", "none", "parent_has_no_counter",
        "no_decode_token", "no_token_counters"])
def test_the_reader_divides_overlapped_by_decode_tokens_or_finds_nothing(
        counters, want):
    read = manifest.load_reader(NAME)
    got = read({"counters": counters, "trace": None})
    assert got == (pytest.approx(want) if want is not None else None)
    # A context without the key at all (a hand-built one) reads nothing.
    assert read({}) is None


def test_the_reader_reads_an_engines_own_counters():
    """The two snapshots a window subtracts, from an engine that ran:
    every decode step but the last of a batch delivers in the next one's
    shadow, and a request admitted beside a running one flushes a step's
    tokens before its prefill."""
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine, TinyLM

    def numbers(stats):
        return {k: v for k, v in stats.items()
                if isinstance(v, (int, float)) and not isinstance(v, bool)}

    eng = InferenceEngine(TinyLM(), EngineConfig(
        max_batch_size=4, block_size=4, num_blocks=64))
    before = numbers(eng.stats())
    first = eng.submit([5, 9, 3], 9)
    for _ in range(3):
        eng.step()
    late = eng.submit([2, 2], 4)        # its prefill flushes one token
    while eng.step():
        pass
    assert len(list(first)) == 9 and len(list(late)) == 4
    after = numbers(eng.stats())
    counters = {k: after[k] - before[k] for k in before}
    # 8 + 3 decode tokens: one flushed before the late prefill, one at
    # the end of the last step, which no step followed.
    assert counters["tokens_generated"] - counters["prefills"] == 11
    assert counters["tokens_delivered_overlapped"] == 9
    assert manifest.load_reader(NAME)({"counters": counters}) == \
        pytest.approx(100.0 * 9 / 11)
