"""`prefill_kv_on_device_pct`: the reader on recorded counters, and its
entry."""

import pytest

from benchmarks.harness import manifest

NAME = "prefill_kv_on_device_pct"
SERVE_CELLS = ["olmo-1b.serve.chat-steady", "olmo-1b.serve.decode-heavy"]


@pytest.mark.parametrize("counters, expected", [
    ({"prefill_kv_device_writes": 68, "prefill_kv_host_writes": 0}, 100.0),
    ({"prefill_kv_device_writes": 3, "prefill_kv_host_writes": 1}, 75.0),
    ({"prefill_kv_device_writes": 0, "prefill_kv_host_writes": 12}, 0.0),
    # No prefill in the window; and the parent, which has no such counter.
    ({"prefill_kv_device_writes": 0, "prefill_kv_host_writes": 0}, None),
    ({"prefills": 68, "cache.host_gathers": 0}, None),
], ids=["all_on_device", "mixed", "all_by_host", "no_prefill", "parent"])
def test_reader_on_recorded_counters(counters, expected):
    assert manifest.load_reader(NAME)({"counters": counters}) == expected


def test_entry_names_the_serve_cells_and_the_gap_tail():
    entry = {m["name"]: m for m in
             manifest.load_manifest()["per_layer"]}[NAME]
    # A later PR appends its serving cells to the list.
    assert set(SERVE_CELLS) <= set(entry.pop("workloads"))
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "KV cache",
        "moves": "serve_itl_p99_ms"}
    for name in SERVE_CELLS:
        cell = manifest.load_cell(name)
        assert NAME in [m["name"] for m in cell["per_layer"]]
    assert manifest.problems() == []
