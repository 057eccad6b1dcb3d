"""`decode_attn_whole_tile_pct` (PR 58): of the live pages a window's
decode steps read through the paged kernel, the share read from a pool
held by planes, from the window's counter deltas."""

import pytest

from benchmarks.harness import manifest

NAME = "decode_attn_whole_tile_pct"
CELLS = ["solar-open2-250b.serve.decode-wide",
         "laguna-s-2.1.serve.repo-context", "mimo-v2.5.serve.doc-context",
         "keye-vl-2.0-30b-a3b.serve.long-doc"]


def test_the_entry_is_among_the_kernels_metrics_of_its_four_cells():
    entries = {m["name"]: m for m in manifest.load_manifest()["per_layer"]}
    assert NAME in entries
    entry = entries[NAME]
    assert set(CELLS) <= set(entry["workloads"])
    assert (entry["source"], entry["layer"], entry["moves"], entry["unit"],
            entry["better"]) == ("program_counter", "Kernels, serve",
                                 "serve_out_tokens_per_s", "%", "higher")
    assert manifest.problems() == []
    for cell_name in CELLS:
        cell = manifest.load_cell(cell_name)
        assert NAME in [m["name"] for m in cell["per_layer"]]
        # It moves a metric the cell reports.
        assert entry["moves"] in [m["name"] for m in cell["end_to_end"]]
    for cell_name in ("smollm2-1.7b.train.seq2k", "olmo-1b.train.fsdp4",
                      "olmo-1b.serve.chat-steady"):
        cell = manifest.load_cell(cell_name)
        assert NAME not in [m["name"] for m in cell["per_layer"]]


# A window's counter deltas: every page from a pool of 4 key/value heads
# (`long-doc`), the global group's pages beside the window group's
# (`doc-context`: 2 layers of 480 pages a row on 4 heads, 5 layers of 9
# on 8), pools of 8 heads alone (`repo-context`, `decode-wide`), the
# parent's (no such counter), a window without a step through the
# kernel, and a context without the counters at all.
@pytest.mark.parametrize("counters, want", [
    ({"decode_kv_pages_read": 15_617_484,
      "decode_kv_pages_read_planes": 15_617_484}, 100.0),
    ({"decode_kv_pages_read": 16 * (2 * 480 + 5 * 9),
      "decode_kv_pages_read_planes": 16 * 2 * 480},
     100.0 * 960 / 1005),
    ({"decode_kv_pages_read": 812_000, "decode_kv_pages_read_planes": 0},
     0.0),
    ({"decode_kv_pages_read": 812_000}, None),
    ({"decode_kv_pages_read": 0, "decode_kv_pages_read_planes": 0}, None),
    ({"decode_kv_pages_read_planes": 5}, None),
], ids=["long_doc", "doc_context", "eight_heads", "parent_has_no_counter",
        "no_step_through_the_kernel", "no_page_counter"])
def test_the_reader_divides_planes_pages_by_pages_or_finds_nothing(
        counters, want):
    read = manifest.load_reader(NAME)
    got = read({"counters": counters, "trace": None})
    assert got == (pytest.approx(want) if want is not None else None)
    assert read({}) is None


def test_the_reader_reads_an_engines_own_counters():
    """The two snapshots a window subtracts, from an engine that ran:
    the model counts a group's live pages under
    `decode_kv_pages_read_planes` where that group's pool is held by
    planes (2 global key/value heads: planes; 8 window ones: rows), and
    `stats()` reports it beside `decode_kv_pages_read`."""
    import json
    import os

    from ray_tpu.ops.paged_attention import by_planes
    from ray_tpu.serve.engine import InferenceEngine

    family = manifest.load_family("mimo_v2")
    with open(os.path.join(manifest.ROOT, "benchmarks", "configs",
                           "mimo-v2.5.json")) as f:
        toy = family.toy_widths(family.widths(json.load(f)))
    served = family.build_serving(
        dict(toy, kv_heads_window=8),
        {"max_seq_len": 256, "engine": {
            "paged_decode": True, "max_batch_size": 3, "block_size": 16,
            "num_blocks": 64, "group_blocks": {"window": 12},
            "max_queue": 64}}, 7)
    model = served["model"]
    assert model.kv_planes == {"global": True, "window": False}
    # Off the chip the steps take the XLA body and count no page: count
    # as on the chip (the counters are the host's arithmetic).
    model._attn_inplace = True
    engine = InferenceEngine(model, served["engine_config"])
    assert engine.cache.with_pools(
        lambda pools: {k: by_planes(v) for k, v in pools.items()}) == \
        {"global": True, "window": False}

    def numbers(stats):
        return {k: v for k, v in stats.items()
                if isinstance(v, (int, float)) and not isinstance(v, bool)}

    before = numbers(engine.stats())
    stream = engine.submit(list(range(3, 43)), 6)       # 40 positions
    while engine.step():
        pass
    assert len(list(stream)) == 6
    after = numbers(engine.stats())
    counters = {k: after[k] - before[k] for k in before}
    # Five decode steps at positions 40-44: 3 global pages a step, and of
    # a window of 16 the 2 pages it still reaches.
    assert counters["decode_kv_pages_read_global"] == 5 * 3
    assert counters["decode_kv_pages_read_planes"] == 5 * 3
    assert counters["decode_kv_pages_read"] == \
        5 * 3 + counters["decode_kv_pages_read_window"]
    assert 0 < counters["decode_kv_pages_read_window"] <= 5 * 2
    assert manifest.load_reader(NAME)({"counters": counters}) == \
        pytest.approx(100.0 * 15 / counters["decode_kv_pages_read"])
