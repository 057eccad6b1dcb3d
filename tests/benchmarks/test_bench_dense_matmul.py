"""The two readers PR 50 adds to the layer "Kernels, serve".
`stacked_weight_matmul_roofline`: the bytes of the dense model's four
stacks of layer matrices, which a decode step's layer products read once,
over the chip's HBM bandwidth and the device time a step spent in the
kernel that reads them. `dense_matmul_kernel_pct`: the share of the
model's programs in the window whose layer products were traced with that
kernel."""

import pytest

from benchmarks.harness import manifest

NAME = "stacked_weight_matmul_roofline"
SHARE = "dense_matmul_kernel_pct"
DENSE_SERVE_CELLS = ["olmo-1b.serve.chat-steady",
                     "olmo-1b.serve.decode-heavy"]
OTHER_CELLS = ["solar-open2-250b.serve.decode-wide",
               "laguna-s-2.1.serve.repo-context",
               "smollm2-1.7b.train.seq2k", "olmo-1b.train.fsdp4"]
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# `olmo-1b`: 16 layers of 4 x 2048 x 2048 + 3 x 2048 x 8192 values, in
# float32: 4.29 GB, 5.24 ms at 819 GB/s.
STACKS = 16 * (4 * 2048 * 2048 + 3 * 2048 * 8192)
STEP_S = STACKS * 4 / 819e9


@pytest.mark.parametrize("name, source", [
    (NAME, "device_trace"), (SHARE, "program_counter")])
def test_the_entry_names_the_two_dense_serve_cells_and_its_layer(name,
                                                                 source):
    per_layer = manifest.load_manifest()["per_layer"]
    entry = {m["name"]: m for m in per_layer}[name]
    assert entry["workloads"] == DENSE_SERVE_CELLS
    assert (entry["source"], entry["layer"], entry["moves"], entry["unit"],
            entry["better"]) == (source, "Kernels, serve",
                                 "serve_itl_p99_ms", "%", "higher")
    for cell_name in DENSE_SERVE_CELLS:
        cell = manifest.load_cell(cell_name)
        assert name in [m["name"] for m in cell["per_layer"]]
        assert entry["moves"] in [m["name"] for m in cell["end_to_end"]]
    for cell_name in OTHER_CELLS:
        cell = manifest.load_cell(cell_name)
        assert name not in [m["name"] for m in cell["per_layer"]]


def test_the_contract_finds_nothing_to_refuse():
    assert manifest.problems() == []


def _ctx(cell_name, op_s, spans=400, peak=V5E, held=None):
    cell = manifest.load_cell(cell_name)
    trace = None if op_s is None else {
        "op_s": op_s, "spans": {"decode_step": {
            "count": spans, "host_s": 4.0, "device_busy_s": 3.0}}
        if spans else {}}
    return {"trace": trace, "peak": peak,
            "counts": manifest.family_of(cell).counts(cell["widths"], held)}


def test_the_bytes_are_the_four_stacks_at_what_the_replica_holds():
    cell = manifest.load_cell(DENSE_SERVE_CELLS[1])
    params = manifest.family_of(cell).counts(cell["widths"])["params"]
    assert params["matmul"] - params["embedding"] == STACKS
    assert STEP_S == pytest.approx(5.24e-3, rel=1e-3)


@pytest.mark.parametrize("op_s, spans, want", [
    # 400 traced steps, 64 calls each, under four instruction names.
    ({"stacked_weight_matmul_decode.3 f32[16,6144]": 0.55,
      "stacked_weight_matmul_decode.4 f32[16,2048]": 0.20,
      "stacked_weight_matmul_decode.5 f32[16,16384]": 1.10,
      "stacked_weight_matmul_decode.6 f32[16,2048]": 0.55,
      "paged_decode_attention.6 f32[8,16,128]": 0.3, "fusion.63": 0.2},
     400, 100 * STEP_S / (2.40 / 400)),
    # A prompt's calls of the same kernel run under another name and are
    # no part of a decode step's time.
    ({"stacked_weight_matmul_decode.3": 2.40,
      "stacked_weight_matmul_prefill.9 f32[64,6144]": 0.7,
      "stacked_weight_matmul.1": 0.4}, 400,
     100 * STEP_S / (2.40 / 400)),
    # The parent: the products are XLA's, behind its converts.
    ({"convert.7 bf16[16,2048,2,8192]": 2.0, "fusion.87": 0.6}, 400, None),
    # No decode call of the kernel in the window, or no step's span.
    ({"stacked_weight_matmul_prefill.9": 0.7}, 400, None),
    ({"stacked_weight_matmul_decode.3": 2.40}, 0, None),
    (None, 400, None),
], ids=["decode_heavy", "prompts_left_out", "parent_has_no_kernel",
        "no_decode_call", "no_step_span", "untraced"])
def test_the_reader_divides_least_time_by_kernel_time_or_finds_nothing(
        op_s, spans, want):
    read = manifest.load_reader(NAME)
    for cell_name in DENSE_SERVE_CELLS:
        got = read(_ctx(cell_name, op_s, spans))
        assert got == (pytest.approx(want) if want is not None else None)


def test_the_reader_counts_a_weight_at_the_bytes_the_replica_holds():
    """A replica that held its weights in bf16 would read half the
    bytes: the share halves at equal kernel time, it does not pass
    100."""
    read = manifest.load_reader(NAME)
    op_s = {"stacked_weight_matmul_decode.3": 2.40}
    full = read(_ctx(DENSE_SERVE_CELLS[1], op_s))
    assert 80 < full < 100
    half = {"weights": {"dtype": "bfloat16", "bytes_per_value": 2},
            "kv_pool": {"dtype": "float32", "bytes_per_value": 4}}
    assert read(_ctx(DENSE_SERVE_CELLS[1], op_s, held=half)) == \
        pytest.approx(full / 2)


def test_the_reader_needs_the_chips_peak_and_a_dense_family():
    read = manifest.load_reader(NAME)
    op_s = {"stacked_weight_matmul_decode.3": 2.40}
    full = _ctx(DENSE_SERVE_CELLS[0], op_s)
    assert read(full) == pytest.approx(100 * STEP_S / (2.40 / 400))
    assert read(dict(full, peak=None)) is None     # a device not in peaks
    assert read({}) is None                        # a hand-built context
    # Another family's counts name no embedding beside its matrices.
    sparse = _ctx(OTHER_CELLS[0], op_s)
    if "embedding" not in sparse["counts"]["params"]:
        assert read(sparse) is None


@pytest.mark.parametrize("counters, want", [
    ({"dense_steps_kernel": 2_400, "dense_steps_xla": 100}, 96.0),
    ({"dense_steps_kernel": 2_200, "dense_steps_xla": 0}, 100.0),
    # Off the chip every program keeps XLA's product: present, and 0.
    ({"dense_steps_kernel": 0, "dense_steps_xla": 250}, 0.0),
    # A model of another family ran nothing of either body.
    ({"dense_steps_kernel": 0, "dense_steps_xla": 0}, None),
    # The parent: no such counters.
    ({"paged_steps": 250, "decode_attn_inplace_steps": 250}, None),
    (None, None),
], ids=["decode_steps_beside_long_prompts", "all_kernel", "cpu",
        "another_family", "parent_has_no_counter", "no_counters"])
def test_the_share_of_programs_through_the_kernel(counters, want):
    read = manifest.load_reader(SHARE)
    assert read({"counters": counters}) == (
        pytest.approx(want) if want is not None else None)
    assert read({}) is None
