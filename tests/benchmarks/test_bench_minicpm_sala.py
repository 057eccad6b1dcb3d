"""The `minicpm_sala` family's cell through the benchmark's runner on the
CPU at toy widths: the manifest loads with the new entries, the cell
resolves its files by name and, sound, comes out `correct`; with a
reference that selects nothing it comes out not `correct`. And the
family's share of the harness: its counts (the issue's arithmetic), the
readers this PR brings and the older ones the cell joins, the controls'
runner, the family's own limits on numbers, and a tree whose program
lacks the model refusing the cell at once. A metric's `workloads` list is
held by containment: a later PR appends to it."""

import os
import shutil
import time

import numpy as np
import pytest

from benchmarks.harness import manifest, program_trace, serve_cell

import bench_toy as toy

CELL = "minicpm-sala.serve.long-context"
CONFIG = "minicpm-sala"
FAMILY = "minicpm_sala"
NEW_READERS = ("block_sparse_decode_attention_roofline",
               "block_sparse_attention_share_of_step_pct",
               "lightning_step_roofline")
# Older readers the cell joins: a model with state, a pool held by
# planes, prompts in chunks, a selection that reads part of the cache.
JOINED_READERS = ("engine_mean_decode_batch", "state_slots_in_use_pct",
                  "prefill_share_of_window_pct", "prefill_chunked_tokens_pct",
                  "decode_kv_pages_per_fetch", "decode_attn_inplace_pct",
                  "decode_attn_whole_tile_pct", "prefill_state_carried_pct",
                  "sparse_attn_kv_read_pct")


@pytest.fixture
def cluster():
    import ray_tpu

    ray_tpu.init(num_cpus=8, ignore_reinit_error=True)
    yield ray_tpu
    ray_tpu.shutdown()


def _benchmark_copy(tmp_path, edit=None):
    """A root holding a copy of the benchmark's files and BENCHMARK.json
    (what the driver lays over a checkout), the family's source with
    `edit` applied where one is given."""
    root = str(tmp_path)
    shutil.copytree(manifest.bench_dir(), os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(manifest.ROOT, "BENCHMARK.json"), root)
    if edit is not None:
        path = os.path.join(root, "benchmarks", "families", f"{FAMILY}.py")
        with open(path) as f:
            source = f.read()
        edited = edit(source)
        assert edited != source
        with open(path, "w") as f:
            f.write(edited)
    return root


def _toy_cell(root):
    """The cell resolved by name from the copy, at toy widths, with a
    check prompt past the toy `dense_len` (64): the chunk program and the
    decode bucket select."""
    cell = toy.cell(CELL)
    copy = manifest.load_cell(CELL, root)
    assert copy["family"] == FAMILY and copy["settings"] == \
        manifest.load_cell(CELL)["settings"]
    cell["root"] = root
    cell["settings"].update(max_seq_len=256, check_prompts=[16, 100],
                            check_decode_steps=6)
    return cell


def _run(cell, seed):
    return serve_cell.run(cell, seed=seed, seconds=1.0, trace=False,
                          t0=time.time(), expect_platform="cpu",
                          timeout_s=300)


def test_the_manifest_loads_with_the_new_entries():
    assert manifest.problems() == []
    m = manifest.load_manifest()
    config = next(c for c in m["configs"] if c["name"] == CONFIG)
    assert config["reduced"] == ["num_hidden_layers", "mixer_types"]
    assert config["source"] == ("https://huggingface.co/openbmb/"
                                "MiniCPM-SALA/blob/main/config.json")
    assert {"name": CELL, "config": CONFIG, "traffic": "serve.long-context",
            "chips": 1, "why": manifest.load_cell(CELL)["settings"]["why"]} \
        in m["workloads"]
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    cell = manifest.load_cell(CELL)
    assert {e["name"] for e in cell["end_to_end"]} == {
        "serve_itl_p99_ms", "serve_out_tokens_per_s", "setup_s"}
    listed = {e["name"] for e in cell["per_layer"]}
    assert set(NEW_READERS) | set(JOINED_READERS) <= listed
    # The chunk's forward under the block mask is the kernel an older
    # reader takes.
    assert "prefill_selected_attention_share_pct" in listed
    assert {"decode_step_roofline", "emit_overlapped_pct",
            "decode_device_ms_per_step", "device_idle_pct.serve",
            "decode_dispatched_ahead_pct", "kv_host_gathers",
            "prefill_chunks_unwaited_pct",
            "compiles_in_window.serve"} <= listed
    # A dense model reports no expert metric and no other model's kernel.
    assert not {name for name in listed
                if name.startswith(("moe_", "held_experts", "latent_",
                                    "index_scores"))}
    # By containment: a later PR appends behind the cell.
    mine = [e for e in m["per_layer"] if e["name"] in NEW_READERS]
    assert len(mine) == 3 and all(CELL in e["workloads"] for e in mine)
    assert {e["name"]: e["moves"] for e in mine} == {
        "block_sparse_decode_attention_roofline": "serve_itl_p99_ms",
        "block_sparse_attention_share_of_step_pct": "serve_itl_p99_ms",
        "lightning_step_roofline": "serve_out_tokens_per_s"}


def test_the_cells_traffic_and_settings_are_the_issues():
    cell = manifest.load_cell(CELL)
    traffic, settings = cell["traffic"], cell["settings"]
    assert traffic["kind"] == "serve_closed" and traffic["clients"] == 6
    assert traffic["requests"] == 32 and traffic["drain_s"] == 120
    assert traffic["schedule_seed"] == 63
    assert traffic["prompt_len"] == {"dist": "uniform", "min": 24576,
                                     "max": 49152, "step": 2048}
    assert traffic["output_len"] == {"dist": "uniform", "min": 768,
                                     "max": 1024, "step": 1}
    engine = settings["engine"]
    assert engine["max_batch_size"] == 6 and engine["block_size"] == 16
    assert engine["paged_decode"] is True
    # The pool holds what the batch can at the longest context, which
    # is more than the traffic's most.
    assert engine["num_blocks"] * 16 == 6 * settings["max_seq_len"] \
        > 6 * 50176
    assert settings["num_blocks_arithmetic"]
    assert settings["max_seq_len"] == 65536
    assert settings["check_prompts"] == [48, 200, 9216, 34816]
    assert settings["check_decode_steps"] == 20
    assert len(settings["why"]) <= 200
    config = cell["config"]
    assert config["published"]["num_hidden_layers"] == 32
    kinds = config["published"]["mixer_types"]
    assert len(kinds) == 32 and kinds.count("minicpm4") == 8
    assert config["layer_offset"] == 9
    assert config["mixer_types"] == kinds[9:17] == (
        ["minicpm4"] + ["lightning-attn"] * 6 + ["minicpm4"])
    assert config["num_hidden_layers"] == 8
    assert config["vocab_size"] == 73448          # whole
    for key in ("assumed", "arithmetic", "departures", "stands_for"):
        assert config[key]
    assert config["stands_for"].startswith("a pipeline stage of 8 of the 32")
    for reading in ("sparse_config", "compressed_keys", "scores",
                    "selection", "qk_norm", "gates", "output_norm",
                    "lightning", "decay", "mup"):
        assert config["assumed"][reading]
    assert config["sparse_config"] == {
        "kernel_size": 32, "kernel_stride": 16, "block_size": 64,
        "init_blocks": 1, "window_size": 2048, "topk": 64,
        "dense_len": 8192}
    # Every width as published.
    for key, value in (("hidden_size", 4096), ("intermediate_size", 16384),
                       ("num_attention_heads", 32),
                       ("num_key_value_heads", 2), ("head_dim", 128),
                       ("lightning_nh", 32), ("lightning_nkv", 32),
                       ("lightning_head_dim", 128), ("scale_emb", 12),
                       ("scale_depth", 1.4), ("dim_model_base", 256),
                       ("mup_denominator", 32), ("rope_theta", 10000)):
        assert config[key] == value
    # The 13 lengths have their keys in the 32,768 and 65,536 chunk
    # programs; their steps in two table buckets.
    from benchmarks.harness import loadgen

    shapes = loadgen.reachable_shapes(traffic, 16, 6)
    assert len(shapes["prompt_lengths"]) == 13
    assert shapes["decode_tables"] == [2048, 4096]
    assert shapes["longest_context"] == 49152 + 1024 <= 65536
    assert max(shapes["prompt_lengths"]) // 16 + 64 == 3136


def test_counts_are_the_issues_arithmetic():
    cell = manifest.load_cell(CELL)
    family = manifest.family_of(cell)
    counts = family.counts(cell["widths"])
    p = counts["params"]
    assert round(p["mlp"] / 1e6, 1) == 201.3
    assert round(p["sparse_layer"] / 1e6, 1) == 253.8    # with its norms
    assert round(p["lightning_layer"] / 1e6, 1) == 285.2
    assert round(2 * p["head"] / 1e6, 1) == 601.7
    assert round(p["held"] / 1e9, 2) == 2.82         # 5.64 GB in bfloat16
    assert round(p["total"] / 1e9, 2) == 9.48
    # 2 layers x 2 heads x (128 + 128) bfloat16 values a position.
    assert counts["kv_bytes_per_token"] == 2048
    # 6 layers x 32 heads x 128 x 128 float32: 12.6 MB a sequence.
    assert counts["state_bytes_per_sequence"] == 6 * 32 * 128 * 128 * 4
    assert round(counts["state_bytes_per_sequence"] / 1e6, 1) == 12.6
    assert counts["block_sparse"] == {
        "layers": 2, "kv_heads": 2, "compressed_bytes_per_token": 64.0}
    assert counts["lightning"]["layers"] == 6
    # A row at 36k reads 97 and a half blocks, not 576.
    assert family.selected_positions(cell["widths"], 36000) == \
        64 * 65 + 2048 + 32
    assert family.selected_positions(cell["widths"], 5000) == 5001
    live = 6 * 36000
    no_kv = counts["decode_step_bytes"](6, 0)
    # The layers and the head once, six rows' states read and written
    # (and each row's own position).
    assert no_kv == pytest.approx(
        (p["layers_held"] + p["head"]) * 2 + 12 * 12582912 + 6 * 2048)
    assert 5.0e9 < no_kv < 5.3e9
    assert counts["decode_step_bytes"](6, live) - no_kv == pytest.approx(
        6 * 6239 * 2048 + live * 64)
    cost = counts["decode_attention_cost"]("block_sparse", 1000)
    assert cost == {"flops": 4.0 * 16 * 128 * 1000, "bytes": 512000.0}
    with pytest.raises(ValueError):
        counts["decode_attention_cost"]("global", 1)


def test_the_new_readers_read_what_is_there_and_nothing_else(monkeypatch):
    cell = manifest.load_cell(CELL)
    counts = manifest.family_of(cell).counts(cell["widths"])
    peak = cell["peaks"]["TPU v5 lite"]
    steps = 50
    tokens = steps * 6 * 4 * 6240            # rows x (layer, head) x chosen
    ctx = {"counts": counts, "window_s": 40.0, "cell": cell,
           "widths": cell["widths"], "peak": peak,
           "counters": {"paged_steps": steps},
           "trace_counters": {"decode_steps": steps,
                              "decode_kv_tokens_read": tokens,
                              "lightning_state_bytes_moved":
                                  steps * 6 * 2 * 12582912},
           "trace": {"op_s": {"block_sparse_paged_decode_attention": 0.02,
                              "lightning_decode_step.3": 0.012,
                              "flash_prefill_fwd_selected": 0.3,
                              "flash_prefill_fwd_causal": 0.1,
                              "fusion.7": 0.2},
                     "spans": {"decode_step": {"count": steps,
                                               "device_busy_s": 0.5}}}}
    modules = {"jit_prefill_chunk": {"device_s": 1.2, "count": 30},
               "jit_decode_paged": {"device_s": 0.5, "count": steps}}
    monkeypatch.setattr(program_trace, "of_run",
                        lambda ctx: ctx["trace"] and {"modules": modules})
    got = {name: manifest.load_reader(name)(ctx) for name in NEW_READERS}
    # 512 B a chosen position a head at 819 GB/s against 20 ms.
    assert got["block_sparse_decode_attention_roofline"] == pytest.approx(
        100 * (tokens * 512 / 819e9) / 0.02, rel=0.01)
    # 20 ms of the decode programs' 0.5 s (not of the span's, which
    # holds the chunk in front of a step).
    assert got["block_sparse_attention_share_of_step_pct"] == \
        pytest.approx(4.0)
    # 50 steps x 6 rows x 2 x 12.6 MB at 819 GB/s against 12 ms.
    assert got["lightning_step_roofline"] == pytest.approx(
        100 * (steps * 6 * 2 * 12582912 / 819e9) / 0.012, rel=0.01)
    # The forward under the block mask is the kernel the older reader
    # of a chunk's selecting attention takes.
    assert manifest.load_reader("prefill_selected_attention_share_pct")(
        ctx) == pytest.approx(25.0)
    # A program without the counters, a run without a trace, a trace
    # without the kernels (the parent of this PR, the CPU): nothing to
    # read, nothing raised.
    bare = {"counts": counts, "cell": cell, "widths": cell["widths"],
            "peak": peak, "counters": {"paged_steps": 10}, "trace": None,
            "trace_counters": None}
    for name in NEW_READERS:
        assert manifest.load_reader(name)(bare) is None, name
    xla = dict(bare, trace_counters=ctx["trace_counters"],
               trace={"op_s": {"fusion.1": 0.1}, "spans": {
                   "decode_step": {"count": 5, "device_busy_s": 0.1}}})
    monkeypatch.setattr(program_trace, "of_run", lambda ctx: None)
    for name in NEW_READERS:
        assert manifest.load_reader(name)(xla) is None, name
    # Another family's counts and counters: nothing read, nothing raised.
    other = manifest.load_cell("keye-vl-2.0-30b-a3b.serve.long-doc")
    other_ctx = dict(ctx, cell=other, widths=other["widths"],
                     counts=manifest.family_of(other).counts(other["widths"]),
                     counters={"paged_steps": 10},
                     trace_counters={"decode_steps": 5,
                                     "decode_kv_tokens_read": 1000},
                     trace=dict(ctx["trace"], op_s={
                         "flash_prefill_fwd_selected": 0.1,
                         "paged_decode_attention": 0.2}))
    for name in NEW_READERS:
        assert manifest.load_reader(name)(other_ctx) is None, name


def test_the_older_readers_of_the_pool_and_the_state_read_this_cell():
    cell = manifest.load_cell(CELL)
    counts = manifest.family_of(cell).counts(cell["widths"])
    steps = 50
    pages = steps * 6 * 4 * 390               # a head's pages of 16
    ctx = {"counts": counts, "cell": cell, "widths": cell["widths"],
           "peak": cell["peaks"]["TPU v5 lite"], "window_s": 40.0,
           "counters": {"paged_steps": steps, "tokens_generated": 310,
                        "prefills": 10, "prefill_s": 18.0,
                        "decode_attn_inplace_steps": steps,
                        "decode_kv_pages_read": pages,
                        "decode_kv_pages_read_planes": pages,
                        "state_slot_steps_in_use": 5 * steps,
                        "state_slot_steps": 6 * steps,
                        "prefill_chunk_tokens": 9000,
                        "model.prefill_tokens": 9000,
                        "prefill_later_chunks": 40,
                        "prefill_state_chunks": 40,
                        "decode_kv_tokens_read": pages * 16,
                        "decode_index_tokens_scored": steps * 6 * 4 * 36001},
           "trace_counters": {"decode_steps": steps, "decode_rows": 6 * steps,
                              "decode_kv_pages_read": pages,
                              "decode_kv_page_groups_read": pages // 195},
           "trace": {"op_s": {}, "spans": {
               "decode_step": {"count": steps, "device_busy_s": 1.0}}}}
    got = {name: manifest.load_reader(name)(ctx) for name in JOINED_READERS}
    assert all(value is not None for value in got.values()), got
    assert got["engine_mean_decode_batch"] == 6.0
    assert got["state_slots_in_use_pct"] == pytest.approx(100 * 5 / 6)
    assert got["prefill_share_of_window_pct"] == 45.0
    assert got["prefill_chunked_tokens_pct"] == 100.0
    assert got["decode_kv_pages_per_fetch"] == pytest.approx(195.0)
    assert got["decode_attn_inplace_pct"] == 100.0
    assert got["decode_attn_whole_tile_pct"] == 100.0
    assert got["prefill_state_carried_pct"] == 100.0
    # 390 pages of 16 positions of a row's 36,001.
    assert got["sparse_attn_kv_read_pct"] == pytest.approx(
        100 * 390 * 16 / 36001)


def test_the_control_runner_tells_the_sound_engine_from_the_lacking(
        tmp_path, monkeypatch):
    """`families/minicpm_sala_controls.py`, what the chip's controls are
    read with, at toy widths: the sound drive inside the family's limits,
    a reference that selects nothing and an engine whose lightning state
    is kept in bf16 outside them (exit 0 says all of it)."""
    import importlib.util
    import json

    spec = importlib.util.spec_from_file_location(
        "minicpm_sala_controls", os.path.join(
            manifest.ROOT, "benchmarks", "families",
            "minicpm_sala_controls.py"))
    runner = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(runner)
    monkeypatch.chdir(tmp_path)
    assert runner.main(["--seeds", "7", "--toy", "1", "--lengths", "100",
                        "--controls",
                        "every_causal_block,state_bf16"]) == 0
    with open(tmp_path / "chiprun_out" / "minicpm_sala_controls.json") as f:
        lines = json.load(f)
    assert [(line["control"], line["ok"]) for line in lines] == [
        ("sound", True), ("every_causal_block", False),
        ("state_bf16", False)]
    family = manifest.load_family(FAMILY)
    assert lines[0]["selection_overlap"] == 1.0 and lines[0]["worst"] < 1e-4
    assert lines[1]["selection_overlap"] < family.SELECTION_OVERLAP_LIMIT
    assert lines[2]["state_bf16_share"] == 1.0


def test_a_tree_without_the_model_refuses_the_cell_at_once(monkeypatch):
    family = manifest.load_family(FAMILY)
    monkeypatch.setattr(family, "PROGRAM_FILES",
                        ("models/minicpm_sala.py", "serve/engine/no_such.py"))
    with pytest.raises(ValueError, match="lacks serve/engine/no_such.py"):
        family.widths(manifest.load_cell(CELL)["config"])


def test_the_parent_commits_tree_lacks_the_files_the_family_names():
    """What makes the parent fail at once on the new cell: `widths` looks
    for files that this PR brought, before any cluster or chip is
    touched (`manifest.load_cell` calls it)."""
    family = manifest.load_family(FAMILY)
    assert set(family.PROGRAM_FILES) == {
        "models/minicpm_sala.py", "serve/engine/minicpm_sala_model.py",
        "ops/block_sparse_attention.py", "ops/lightning_attention.py"}
    import ray_tpu

    where = os.path.dirname(ray_tpu.__file__)
    assert all(os.path.isfile(os.path.join(where, f))
               for f in family.PROGRAM_FILES)


def test_the_familys_own_limits_read_a_sound_drive_and_a_lowered_one():
    """`own_limits` on numbers: rounding passes; every position off, a
    state off, a state kept in bf16, rows at fp8's mantissa and another
    selection do not."""
    family = manifest.load_family(FAMILY)
    rng = np.random.default_rng(0)
    want = {"logits": rng.normal(size=(25, 32)).astype(np.float32),
            "states": rng.normal(size=(6, 2, 8, 8)).astype(np.float32),
            "kv": rng.normal(size=(2, 25, 2, 2, 8)).astype(np.float32),
            "selected": rng.random((2, 2, 12)) < 0.5}
    served = {"widths": {"toy": 1, "dense_len": 4}, "params": None}
    saved = family.reference
    family.reference = lambda w: lambda params, tokens: want
    try:
        def limits(rows, state, kv=want["kv"] * (1 + 2e-3),
                   kept=want["selected"], n=5):
            return family.own_limits(served, rows, list(range(25)), n, state,
                                     kv, np.pad(kept, ((0, 0), (0, 0),
                                                       (0, 4))))

        noise = rng.normal(size=(21, 32)).astype(np.float32)
        sound = [want["logits"][4 + j] + 0.001 * noise[j] for j in range(21)]
        near = want["states"] * (1 + 1e-3)
        assert limits(sound, near)["ok"]
        lowered = [want["logits"][4 + j] + 0.05 * noise[j]
                   for j in range(21)]
        assert not limits(lowered, near)["ok"]
        assert not limits(sound, want["states"] * 1.3)["ok"]
        # A prompt past `dense_len` is held to the tighter limit of the
        # state, a shorter one (whose few positions read higher) is not.
        between = want["states"] * (1 + (family.LONG_STATE_TOLERANCE
                                         * family.STATE_TOLERANCE) ** 0.5)
        assert not limits(sound, between)["ok"]
        assert limits(sound, between, n=4)["ok"]
        coarse = want["kv"] * (1 + rng.uniform(-1, 1, want["kv"].shape) / 16)
        got = limits(sound, near, coarse.astype(np.float32))
        assert not got["ok"] and got["kv"] > family.KV_TOLERANCE
        in_bf16 = near.view(np.uint32) & np.uint32(0xFFFF0000)
        got = limits(sound, in_bf16.view(np.float32))
        assert not got["ok"] and got["state_bf16_share"] == 1.0
        got = limits(sound, near, kept=~want["selected"])
        assert not got["ok"] and got["selection_overlap"] == 0.0
    finally:
        family.reference = saved


def test_the_references_logits_are_its_last_rows_alone():
    """`reference_logits` hands the harness ``[S, V]`` of which the last
    `REFERENCE_HEAD_ROWS` rows are computed: the rows it compares."""
    family = manifest.load_family(FAMILY)
    cell = toy.cell(CELL)
    served = family.build_serving(
        cell["widths"], dict(cell["settings"], max_seq_len=128), 5)
    tokens = np.random.default_rng(1).integers(2, 500, 50).astype(np.int32)
    rows = family.reference_logits(cell["widths"])(served["params"], tokens)
    assert rows.shape == (50, 500)
    head = family.REFERENCE_HEAD_ROWS
    assert not rows[:50 - head].any() and rows[50 - head:].any(axis=1).all()
    shorter = family.reference_logits(cell["widths"])(served["params"],
                                                      tokens[:40])
    np.testing.assert_allclose(shorter[39], family.reference(
        cell["widths"])(served["params"], tokens[:40])["logits"][-1])


@pytest.mark.cluster
def test_the_cell_resolves_by_name_on_a_copy_and_runs_correct(tmp_path,
                                                             cluster):
    root = _benchmark_copy(tmp_path)
    assert manifest.problems(root) == []
    cell = _toy_cell(root)
    assert cell["widths"]["prefill_chunk_tokens"] == 16
    out = _run(cell, 2 ** 31 + 63)
    assert out["correct"], out
    assert out["failed"] == 0 and out["attempted"] >= 6
    counters = out["ctx"]["counters"]
    assert counters["cache.host_gathers"] == 0
    assert counters["decode_h2d_arrays"] == counters["paged_steps"]
    assert counters["prefill_later_chunks"] == \
        counters["prefill_state_chunks"]
    assert counters["lightning_state_bytes_moved"] > 0
    layer = manifest.read_layer_metrics(cell, out["ctx"])
    assert layer["kv_host_gathers"]["value"] == 0
    assert layer["state_slots_in_use_pct"]["value"] > 0
    assert not any(name.startswith("moe_") for name in layer)
    # Off the chip no operation ran on a device and the XLA body read the
    # pool: the trace readers find nothing.
    for name in NEW_READERS[:2]:
        assert name not in layer


@pytest.mark.cluster
def test_a_reference_that_selects_nothing_comes_out_not_correct(tmp_path,
                                                                cluster):
    root = _benchmark_copy(tmp_path, lambda source: source.replace(
        'if "selection" in w.get("without", ()):', 'if True:'))
    out = _run(_toy_cell(root), 2 ** 31 + 64)
    assert out["correct"] is False
    # By the family's limits (the selections' overlap): the drive past
    # `dense_len` handed its rows back as no numbers, the one below it is
    # sound, so the largest gap that IS a number stays inside the limit.
    gap, limit = out["checks"]["logit_rms_gap"]
    assert limit == manifest.load_family(FAMILY).LOGIT_TOLERANCE
    assert gap != gap or gap < limit
    assert out["failed"] == 0
