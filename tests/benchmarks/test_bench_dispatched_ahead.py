"""`decode_dispatched_ahead_pct` (PR 60): of a window's decode steps, the
share the scheduler dispatched before it had read the step before's ids,
from the window's counter deltas."""

import pytest

from benchmarks.harness import manifest

NAME = "decode_dispatched_ahead_pct"
SERVE_CELLS = ["olmo-1b.serve.chat-steady", "olmo-1b.serve.decode-heavy",
               "solar-open2-250b.serve.decode-wide",
               "laguna-s-2.1.serve.repo-context",
               "mimo-v2.5.serve.doc-context",
               "keye-vl-2.0-30b-a3b.serve.long-doc"]


def test_the_six_serve_cells_are_among_the_entrys_cells():
    """Membership, not position: the next PR appends a cell to the list
    and a metric behind this one (ROADMAP B0 a0)."""
    entries = {m["name"]: m for m in manifest.load_manifest()["per_layer"]}
    assert NAME in entries
    entry = entries[NAME]
    assert set(SERVE_CELLS) <= set(entry["workloads"])
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


# A window's counter deltas: every step behind the first of a run ahead,
# a closed loop at its cap most of its steps, an open loop that never
# fills its batch, the parent's (no such counter), a window without a
# decode step, and a context without the steps' count.
@pytest.mark.parametrize("counters, want", [
    ({"paged_steps": 400, "decode_steps_ahead": 399}, 100.0 * 399 / 400),
    ({"paged_steps": 4_100, "decode_steps_ahead": 2_870}, 70.0),
    ({"paged_steps": 3_000, "decode_steps_ahead": 0}, 0.0),
    ({"paged_steps": 4_100}, None),
    ({"paged_steps": 0, "decode_steps_ahead": 0}, None),
    ({"decode_steps_ahead": 5}, None),
], ids=["all_ahead", "decode_heavy", "none", "parent_has_no_counter",
        "no_steps", "no_step_count"])
def test_the_reader_divides_steps_ahead_by_steps_or_finds_nothing(
        counters, want):
    read = manifest.load_reader(NAME)
    got = read({"counters": counters, "trace": None})
    assert got == (pytest.approx(want) if want is not None else None)
    # A context without the key at all (a hand-built one) reads nothing.
    assert read({}) is None


def test_the_reader_reads_an_engines_own_counters():
    """The two snapshots a window subtracts, from an engine over the
    oracle model, which takes no `ahead`: the counters are there from
    construction, every step is read before the next, the share is 0."""
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine, TinyLM

    def numbers(stats):
        return {k: v for k, v in stats.items()
                if isinstance(v, (int, float)) and not isinstance(v, bool)}

    eng = InferenceEngine(TinyLM(), EngineConfig(
        max_batch_size=2, block_size=4, num_blocks=64))
    before = numbers(eng.stats())
    assert before["decode_steps_ahead"] == 0
    assert before["decode_ends_found_late"] == 0
    streams = [eng.submit([5, 9, 3], 9), eng.submit([2, 2], 9)]
    while eng.step():
        pass
    assert [len(list(s)) for s in streams] == [9, 9]
    after = numbers(eng.stats())
    counters = {k: after[k] - before[k] for k in before}
    assert counters["paged_steps"] == 8
    assert manifest.load_reader(NAME)({"counters": counters}) == 0.0
