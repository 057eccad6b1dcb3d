"""`decode_kv_pages_per_fetch` (PR 36): the pages a fetch of the paged
decode kernel brings, from the counters of a traced sub-window."""

import pytest

from benchmarks.harness import manifest

NAME = "decode_kv_pages_per_fetch"
SERVE_CELLS = ["olmo-1b.serve.chat-steady", "olmo-1b.serve.decode-heavy",
               "solar-open2-250b.serve.decode-wide",
               "laguna-s-2.1.serve.repo-context"]


def test_the_entry_names_the_four_serve_cells_and_its_layer():
    entry = {m["name"]: m for m in
             manifest.load_manifest()["per_layer"]}[NAME]
    assert entry["workloads"][:4] == SERVE_CELLS
    assert (entry["source"], entry["layer"], entry["moves"], entry["unit"],
            entry["better"]) == ("program_counter", "Kernels, serve",
                                 "serve_itl_p99_ms", "pages", "higher")
    for cell_name in SERVE_CELLS:
        cell = manifest.load_cell(cell_name)
        assert NAME in [m["name"] for m in cell["per_layer"]]
    for cell_name in ("smollm2-1.7b.train.seq2k", "olmo-1b.train.fsdp4"):
        cell = manifest.load_cell(cell_name)
        assert NAME not in [m["name"] for m in cell["per_layer"]]


# A traced sub-window's counter deltas as a run of `repo-context` records
# them (66 steps of 16 rows at a mean of 181 global and 32.5 window live
# pages; groups of 32 pages), one of `decode-heavy` (8 rows, 20 pages a
# row in groups of 8), the parent's (no such counter), an untraced run's
# and a CPU run's (no step went through the kernel).
@pytest.mark.parametrize("trace_counters, want", [
    ({"decode_kv_pages_read": 225_456, "decode_kv_page_groups_read": 8_448,
      "decode_kv_pages_read_global": 191_136,
      "decode_kv_page_groups_read_global": 6_336,
      "paged_steps": 66}, 225_456 / 8_448),
    ({"decode_kv_pages_read": 64_000, "decode_kv_page_groups_read": 9_600,
      "paged_steps": 400}, 64_000 / 9_600),
    ({"decode_kv_pages_read": 225_456, "paged_steps": 66}, None),
    (None, None),
    ({"decode_kv_pages_read": 0, "decode_kv_page_groups_read": 0,
      "paged_steps": 66}, None),
], ids=["repo_context", "decode_heavy", "parent_has_no_counter",
        "untraced", "cpu_no_kernel_step"])
def test_the_reader_divides_pages_by_groups_or_finds_nothing(trace_counters,
                                                             want):
    read = manifest.load_reader(NAME)
    got = read({"trace_counters": trace_counters, "counters": {}})
    assert got == (pytest.approx(want) if want is not None else None)
    # A context without the key at all (a hand-built one) reads nothing.
    assert read({}) is None
