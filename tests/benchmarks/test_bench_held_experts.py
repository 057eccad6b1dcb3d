"""The Experts layer's two readers of PR 42.
`held_experts_ffn_decode_roofline`: the bytes of the held experts a
traced decode step touched (and the layer's rows in and out), over the
chip's HBM bandwidth and the device time a step spent in the kernel that
read them. `held_experts_kernel_pct`: the share of the model's programs
in the window whose expert layers were traced with that kernel."""

import pytest

from benchmarks.harness import manifest

NAME = "held_experts_ffn_decode_roofline"
SHARE = "held_experts_kernel_pct"
SPARSE_CELLS = ["solar-open2-250b.serve.decode-wide",
                "laguna-s-2.1.serve.repo-context"]
OTHER_CELLS = ["olmo-1b.serve.chat-steady", "olmo-1b.serve.decode-heavy",
               "smollm2-1.7b.train.seq2k", "olmo-1b.train.fsdp4"]
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.mark.parametrize("name, source", [
    (NAME, "device_trace"), (SHARE, "program_counter")])
def test_the_entry_names_the_two_sparse_cells_and_its_layer(name, source):
    per_layer = manifest.load_manifest()["per_layer"]
    entry = {m["name"]: m for m in per_layer}[name]
    # Appended: the last two entries of the list.
    assert [m["name"] for m in per_layer[-2:]] == [SHARE, NAME]
    assert entry["workloads"] == SPARSE_CELLS
    assert (entry["source"], entry["layer"], entry["moves"], entry["unit"],
            entry["better"]) == (source, "Experts",
                                 "serve_out_tokens_per_s", "%", "higher")
    for cell_name in SPARSE_CELLS:
        cell = manifest.load_cell(cell_name)
        assert name in [m["name"] for m in cell["per_layer"]]
        assert entry["moves"] in [m["name"] for m in cell["end_to_end"]]
    for cell_name in OTHER_CELLS:
        cell = manifest.load_cell(cell_name)
        assert name not in [m["name"] for m in cell["per_layer"]]


def _ctx(cell_name, op_s, trace_counters, peak=V5E, spans=None):
    """A traced run's context: `decode_steps` steps by the counters
    unless they say otherwise, as many `decode_step` spans in the trace
    unless `spans` says otherwise."""
    cell = manifest.load_cell(cell_name)
    if trace_counters is not None:
        trace_counters = {"decode_steps": trace_counters["paged_steps"],
                          **trace_counters}
    if spans is None and trace_counters is not None:
        spans = trace_counters["decode_steps"]
    trace = None if op_s is None else {
        "op_s": op_s, "spans": {"decode_step": {
            "count": spans, "host_s": 1.0, "device_busy_s": 0.9}}
        if spans else {}}
    return {"trace": trace, "trace_counters": trace_counters, "peak": peak,
            "counts": manifest.family_of(cell).counts(cell["widths"])}


# `repo-context`: 92 traced steps x 11 expert layers x 14.2 of 32 held
# experts touched = 14,370 (layer, expert) pairs of 3 x 3072 x 1024 bf16
# values, 18.87 MB each: 271.2 GB, 0.3311 s at 819 GB/s. `decode-wide`:
# 150 steps x 4 layers x 13 of 40 experts of 3 x 4096 x 1280.
LAGUNA_S = 14_370 * 3 * 3072 * 1024 * 2 / 819e9
SOLAR_S = 7_800 * 3 * 4096 * 1280 * 2 / 819e9


@pytest.mark.parametrize("cell_name, op_s, trace_counters, want", [
    (SPARSE_CELLS[1],
     {"held_experts_ffn_decode.1": 0.25, "held_experts_ffn_decode.7": 0.15,
      "fusion.12": 0.3, "paged_decode_attention.3": 0.1},
     {"moe_expert_touches": 14_370, "paged_steps": 92},
     100 * LAGUNA_S / 0.40),
    (SPARSE_CELLS[0], {"held_experts_ffn_decode": 0.36, "cond.1": 0.2},
     {"moe_expert_touches": 7_800, "paged_steps": 150},
     100 * SOLAR_S / 0.36),
    # A kernel whose name only begins alike (were a prompt's calls given
    # one of their own) is left out, as `moe_expert_touches` leaves a
    # prompt's experts out.
    (SPARSE_CELLS[1],
     {"held_experts_ffn_decode.1": 0.40, "held_experts_ffn_prompt.2": 0.9,
      "held_experts_ffn_prompt": 0.3},
     {"moe_expert_touches": 14_370, "paged_steps": 92},
     100 * LAGUNA_S / 0.40),
    # The parent: the expert layer is a scan of XLA operations.
    (SPARSE_CELLS[1], {"cond.25.clone.2": 0.055, "cond.41.clone.2": 0.054},
     {"moe_expert_touches": 14_370, "paged_steps": 92}, None),
    # No call of the decode kernel's name in the window.
    (SPARSE_CELLS[1], {"held_experts_ffn_prompt.2": 0.9},
     {"moe_expert_touches": 14_370, "paged_steps": 92}, None),
    (SPARSE_CELLS[1], {"held_experts_ffn_decode.1": 0.40},
     {"paged_steps": 92}, None),
    (SPARSE_CELLS[1], {"held_experts_ffn_decode.1": 0.40},
     {"moe_expert_touches": 0, "paged_steps": 92}, None),
    (SPARSE_CELLS[1], {"held_experts_ffn_decode.1": 0.40},
     {"moe_expert_touches": 14_370, "paged_steps": 92, "decode_steps": 0},
     None),
    (SPARSE_CELLS[1], None, None, None),
    (SPARSE_CELLS[1], {"held_experts_ffn_decode.1": 0.40}, None, None),
], ids=["repo_context", "decode_wide", "other_kernels_left_out",
        "parent_has_no_kernel", "no_decode_call", "no_counter",
        "no_touch", "no_step_counted", "untraced",
        "trace_without_counters"])
def test_the_reader_divides_least_time_by_kernel_time_or_finds_nothing(
        cell_name, op_s, trace_counters, want):
    read = manifest.load_reader(NAME)
    got = read(_ctx(cell_name, op_s, trace_counters))
    assert got == (pytest.approx(want) if want is not None else None)


def test_both_sides_are_taken_a_step():
    """The counters' window is wider than the traced one by the time of
    its two snapshots: 98 steps counted beside 92 `decode_step` spans in
    the trace read what 92 beside 92 read, and a trace without the span
    reads nothing."""
    read = manifest.load_reader(NAME)
    op_s = {"held_experts_ffn_decode.1": 0.40}
    wider = {"moe_expert_touches": 14_370 * 98 // 92, "paged_steps": 98}
    assert read(_ctx(SPARSE_CELLS[1], op_s, wider, spans=92)) == \
        pytest.approx(100 * LAGUNA_S / 0.40, rel=1e-4)
    no_span = _ctx(SPARSE_CELLS[1], op_s, wider)
    no_span["trace"]["spans"] = {}
    assert read(no_span) is None


def test_the_reader_needs_the_chips_peak_and_reads_nothing_without():
    read = manifest.load_reader(NAME)
    full = _ctx(SPARSE_CELLS[1], {"held_experts_ffn_decode.1": 0.40},
                {"moe_expert_touches": 14_370, "paged_steps": 92})
    assert read(full) == pytest.approx(100 * LAGUNA_S / 0.40)
    assert 70 < read(full) < 100
    assert read(dict(full, peak=None)) is None     # a device not in peaks
    assert read({}) is None                        # a hand-built context


def test_the_rows_in_and_out_are_counted_where_the_widths_name_them():
    """16 rows a step x 11 layers x 3,072 x (2 bytes in + 4 out): 3.2 MB
    a step beside 2.9 GB of expert matrices."""
    read = manifest.load_reader(NAME)
    cell = manifest.load_cell(SPARSE_CELLS[1])
    ctx = _ctx(SPARSE_CELLS[1], {"held_experts_ffn_decode.1": 0.40},
               {"moe_expert_touches": 14_370, "paged_steps": 92,
                "decode_rows": 92 * 16})
    bare = read(ctx)
    with_rows = read(dict(ctx, widths=cell["widths"]))
    rows_s = 92 * 16 * 11 * 3072 * 6 / 819e9
    assert with_rows == pytest.approx(100 * (LAGUNA_S + rows_s) / 0.40)
    assert 1.0 < with_rows / bare < 1.002


@pytest.mark.parametrize("counters, want", [
    ({"moe_steps_kernel": 2_400, "moe_steps_scan": 100}, 96.0),
    ({"moe_steps_kernel": 300, "moe_steps_scan": 0}, 100.0),
    # Off the chip every program keeps the scan: present, and 0.
    ({"moe_steps_kernel": 0, "moe_steps_scan": 250}, 0.0),
    # A model without experts ran nothing of either body.
    ({"moe_steps_kernel": 0, "moe_steps_scan": 0}, None),
    # The parent: no such counters.
    ({"paged_steps": 250, "moe_expert_touches": 14_370}, None),
    (None, None),
], ids=["decode_steps_beside_prompts", "all_kernel", "cpu", "no_experts",
        "parent_has_no_counter", "no_counters"])
def test_the_share_of_programs_through_the_kernel(counters, want):
    read = manifest.load_reader(SHARE)
    assert read({"counters": counters}) == (
        pytest.approx(want) if want is not None else None)
    assert read({}) is None
