"""The `gigachat3_5` family's cell through the benchmark's runner on the
CPU at toy widths: the manifest loads with the new entries, the cell
resolves its files by name and, sound, comes out `correct`; with a
reference without the output gate and one without the decay it comes out
not `correct`. And the family's share of the harness: its counts (the
issue's arithmetic), the four readers this PR brings and the older ones
the cell joins, the controls' runner, and a tree whose program lacks the
model refusing the cell at once."""

import os
import shutil
import time

import pytest

from benchmarks.harness import manifest, program_trace, serve_cell

import bench_toy as toy

CELL = "gigachat3.5-432b-a28b.serve.long-reason"
CONFIG = "gigachat3.5-432b-a28b"
FAMILY = "gigachat3_5"
NEW_READERS = ("latent_decode_attention_roofline",
               "latent_attention_share_of_step_pct",
               "prefill_latent_attention_share_pct",
               "prefill_state_carried_pct")
# Older readers the cell joins: a model with state, a pool held by planes
# whose rows are padded, prompts in chunks, held experts.
JOINED_READERS = ("engine_mean_decode_batch", "state_slots_in_use_pct",
                  "kv_pool_padding_pct", "prefill_share_of_window_pct",
                  "prefill_chunked_tokens_pct", "decode_kv_pages_per_fetch",
                  "decode_attn_inplace_pct", "decode_attn_whole_tile_pct",
                  "moe_assignments_per_expert_step", "moe_load_max_over_mean",
                  "moe_experts_touched_pct", "held_experts_kernel_pct",
                  "held_experts_ffn_decode_roofline")


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
    """The cell resolved by name from the copy, at toy widths."""
    cell = toy.cell(CELL)
    copy = manifest.load_cell(CELL, root)
    assert copy["family"] == FAMILY and copy["settings"] == \
        manifest.load_cell(CELL)["settings"]
    cell["root"] = root
    return cell


def _run(cell, seed):
    return serve_cell.run(cell, seed=seed, seconds=1.0, trace=False,
                          t0=time.time(), expect_platform="cpu",
                          timeout_s=300)


def test_the_manifest_loads_with_the_new_entries():
    assert manifest.problems() == []
    m = manifest.load_manifest()
    config = next(c for c in m["configs"] if c["name"] == CONFIG)
    assert config["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace",
        "full_attention_layers", "n_routed_experts", "vocab_size"]
    assert {"name": CELL, "config": CONFIG, "traffic": "serve.long-reason",
            "chips": 1, "why": manifest.load_cell(CELL)["settings"]["why"]} \
        in m["workloads"]
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    cell = manifest.load_cell(CELL)
    assert {e["name"] for e in cell["end_to_end"]} == {
        "serve_itl_p99_ms", "serve_out_tokens_per_s", "setup_s"}
    listed = {e["name"] for e in cell["per_layer"]}
    assert set(NEW_READERS) | set(JOINED_READERS) <= listed
    assert {"decode_step_roofline", "emit_overlapped_pct",
            "decode_device_ms_per_step", "device_idle_pct.serve",
            "decode_dispatched_ahead_pct", "kv_host_gathers",
            "compiles_in_window.serve"} <= listed
    # The new entries are IN `per_layer`, each this cell's alone (a later
    # PR appends behind them).
    mine = [e for e in m["per_layer"] if e["name"] in NEW_READERS]
    assert len(mine) == 4 and all(CELL in e["workloads"] for e in mine)
    assert [e["workloads"][0] for e in mine] == [CELL] * 4


def test_the_cells_traffic_and_settings_are_the_issues():
    cell = manifest.load_cell(CELL)
    traffic, settings = cell["traffic"], cell["settings"]
    assert traffic["kind"] == "serve_closed" and traffic["clients"] == 32
    assert traffic["requests"] == 128 and traffic["drain_s"] == 90
    assert traffic["schedule_seed"] == 61
    assert traffic["prompt_len"] == {"dist": "uniform", "min": 7168,
                                     "max": 15360, "step": 512}
    assert traffic["output_len"] == {"dist": "uniform", "min": 768,
                                     "max": 1024, "step": 1}
    assert settings["engine"] == {
        "paged_decode": True, "max_batch_size": 32, "block_size": 16,
        "num_blocks": 34816, "max_queue": 256}
    assert settings["max_seq_len"] == 16384
    assert settings["check_prompts"] == [48, 200, 2304, 7168]
    assert settings["check_decode_steps"] == 20
    assert settings["trace_seconds"] == 1.5
    assert len(settings["why"]) <= 200
    config = cell["config"]
    assert config["share_chips"] == 16 and config["experts_held"] == [0, 16]
    assert config["published"]["num_hidden_layers"] == 40
    assert config["published"]["n_routed_experts"] == 256
    assert config["published"]["full_attention_layers"] == list(
        range(3, 40, 4))
    assert config["full_attention_layers"] == [1]
    assert config["first_k_dense_replace"] == 1
    # An eighth of the vocabulary, rounded up to whole lanes.
    assert config["vocab_size"] == -(-128256 // 8 // 128) * 128 == 16128
    for key in ("assumed", "arithmetic", "departures", "stands_for"):
        assert config[key]
    assert "multi-token-prediction" in config["departures"][0]
    for reading in ("norm", "attention_gate", "swiglu_clamp",
                    "gdn_gate_and_beta"):
        assert config["assumed"][reading]
    # Every width as published.
    for key, value in (("hidden_size", 7168), ("kv_lora_rank", 512),
                       ("q_lora_rank", 1536), ("qk_rope_head_dim", 64),
                       ("qk_nope_head_dim", 128), ("v_head_dim", 128),
                       ("intermediate_size", 18432),
                       ("moe_intermediate_size", 2048),
                       ("num_experts_per_tok", 8),
                       ("linear_num_key_heads", 32),
                       ("linear_num_value_heads", 64)):
        assert config[key] == value
    # The 17 lengths have their keys in the 8,192 and 16,384 chunk
    # programs; their steps in two table buckets.
    from benchmarks.harness import loadgen

    shapes = loadgen.reachable_shapes(traffic, 16, 32)
    assert len(shapes["prompt_lengths"]) == 17
    assert shapes["decode_tables"] == [512, 1024]
    assert shapes["longest_context"] == 16384


def test_counts_are_the_issues_arithmetic():
    cell = manifest.load_cell(CELL)
    family = manifest.family_of(cell)
    counts = family.counts(cell["widths"])
    p = counts["params"]
    assert round(p["gdn_layer"] / 1e6, 1) == 235.9
    assert round(p["mla_layer"] / 1e6, 1) == 159.8
    assert round(p["dense_mlp"] / 1e6, 1) == 396.4
    assert round(p["expert"] / 1e6, 1) == 44.0
    assert round(p["router"] / 1e6, 1) == 1.8
    assert round(p["head"] / 1e6, 1) == 115.6
    assert round(p["held"] / 1e6) == 4733        # 9.47 GB in bfloat16
    assert 425e9 < p["total"] < 435e9 and 25e9 < p["active"] < 29e9
    # A position: 576 values as the model counts, 640 as the pool holds.
    assert counts["kv_bytes_per_token"] == 1152
    assert counts["latent"] == {"layers": 1, "row_values": 576,
                                "row_values_held": 640,
                                "bytes_per_token_held": 1280}
    # 4 x (64 x 128 x 128 float32 + 3 x 16,384 bfloat16).
    assert counts["state_bytes_per_sequence"] == 4 * (4 * 2 ** 20
                                                      + 3 * 16384 * 2)
    assert counts["moe"] == {"layers": 4, "experts_held": 16}
    live = 32 * 12000
    no_kv = counts["decode_step_bytes"](32, 0)
    assert counts["decode_step_bytes"](32, live) - no_kv == \
        pytest.approx(live * 1280)
    assert 8.1e9 < no_kv < 8.5e9
    assert 10.0 < counts["experts_touched"](32) < 10.4
    cost = counts["decode_attention_cost"]("latent", live)
    assert cost["bytes"] == live * 1280
    assert cost["flops"] == 2 * 64 * (640 + 512) * live
    with pytest.raises(ValueError):
        counts["decode_attention_cost"]("global", 1)


def test_the_new_readers_read_what_is_there_and_nothing_else(monkeypatch):
    cell = manifest.load_cell(CELL)
    counts = manifest.family_of(cell).counts(cell["widths"])
    peak = cell["peaks"]["TPU v5 lite"]
    steps, pages = 50, 32 * 750 * 50       # 32 rows of 12,000 positions
    ctx = {"counts": counts, "window_s": 40.0, "cell": cell,
           "widths": cell["widths"], "peak": peak,
           "counters": {"prefill_later_chunks": 40,
                        "prefill_state_chunks": 40},
           "trace_counters": {"decode_steps": steps,
                              "decode_latent_pages_read": pages},
           "trace": {"op_s": {"paged_latent_decode_attention": 0.05,
                              "held_experts_ffn_decode": 0.1,
                              "flash_prefill_fwd_causal": 0.1,
                              "fusion.7": 0.2},
                     "spans": {"decode_step": {"count": steps,
                                               "device_busy_s": 1.0}}}}
    modules = {"jit_prefill_chunk": {"device_s": 1.25, "count": 30},
               "jit_decode_paged": {"device_s": 1.0, "count": steps}}
    monkeypatch.setattr(program_trace, "of_run",
                        lambda ctx: ctx["trace"] and {"modules": modules})
    got = {name: manifest.load_reader(name)(ctx) for name in NEW_READERS}
    # 0.49 GB of held rows a step at 819 GB/s against 1 ms a step.
    assert got["latent_decode_attention_roofline"] == pytest.approx(
        100 * (32 * 12000 * 1280 / 819e9) / 1e-3, rel=0.01)
    assert got["latent_attention_share_of_step_pct"] == pytest.approx(5.0)
    assert got["prefill_latent_attention_share_pct"] == pytest.approx(8.0)
    assert got["prefill_state_carried_pct"] == 100.0
    ctx["counters"]["prefill_state_chunks"] = 30
    assert manifest.load_reader("prefill_state_carried_pct")(ctx) == 75.0
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
    for name in NEW_READERS:
        assert manifest.load_reader(name)(xla) is None, name
    # A trace that holds the forward and no prefill program.
    modules.pop("jit_prefill_chunk")
    assert manifest.load_reader(
        "prefill_latent_attention_share_pct")(ctx) is None
    # Another family's counts (no latent group) and counters, whose
    # chunks run the same causal forward: nothing read, nothing raised.
    modules["jit_prefill_chunk"] = {"device_s": 1.25, "count": 30}
    other = manifest.load_cell("mimo-v2.5.serve.doc-context")
    other_ctx = dict(ctx, cell=other, widths=other["widths"],
                     counts=manifest.family_of(other).counts(other["widths"]),
                     counters={"paged_steps": 10},
                     trace_counters={"decode_steps": 5},
                     trace=dict(ctx["trace"], op_s={
                         "flash_prefill_fwd_causal": 0.1,
                         "paged_decode_attention": 0.2}))
    for name in NEW_READERS:
        assert manifest.load_reader(name)(other_ctx) is None, name


def test_the_older_readers_of_the_pool_and_the_state_read_this_cell():
    cell = manifest.load_cell(CELL)
    counts = manifest.family_of(cell).counts(cell["widths"])
    steps, pages = 50, 32 * 750 * 50
    ctx = {"counts": counts, "cell": cell, "widths": cell["widths"],
           "peak": cell["peaks"]["TPU v5 lite"], "window_s": 40.0,
           "counters": {"paged_steps": steps, "tokens_generated": 1610,
                        "prefills": 10, "prefill_s": 18.0,
                        "decode_attn_inplace_steps": steps,
                        "decode_kv_pages_read": pages,
                        "decode_kv_pages_read_planes": pages,
                        "decode_kv_bytes_read_held": pages * 16 * 1280,
                        "decode_kv_bytes_read_model": pages * 16 * 1152,
                        "state_slot_steps_in_use": 31 * steps,
                        "state_slot_steps": 32 * steps,
                        "prefill_chunk_tokens": 9000,
                        "model.prefill_tokens": 9000,
                        "moe_local_assignments": steps * 4 * 16,
                        "moe_expert_touches": steps * 4 * 10,
                        "moe_max_expert_load": steps * 4 * 3,
                        "moe_steps_kernel": 60, "moe_steps_scan": 0},
           "trace_counters": {"decode_steps": steps, "decode_rows": 32 * steps,
                              "decode_kv_pages_read": pages,
                              "decode_kv_page_groups_read": pages // 60,
                              "moe_expert_touches": steps * 4 * 10},
           "trace": {"op_s": {"held_experts_ffn_decode": 0.25},
                     "spans": {"decode_step": {"count": steps,
                                               "device_busy_s": 1.0}}}}
    got = {name: manifest.load_reader(name)(ctx) for name in JOINED_READERS}
    assert all(value is not None for value in got.values()), got
    assert got["engine_mean_decode_batch"] == 32.0
    assert got["state_slots_in_use_pct"] == pytest.approx(100 * 31 / 32)
    # 640 lanes held for a row of 576.
    assert got["kv_pool_padding_pct"] == pytest.approx(100 * 64 / 576)
    assert got["prefill_share_of_window_pct"] == 45.0
    assert got["prefill_chunked_tokens_pct"] == 100.0
    assert got["decode_kv_pages_per_fetch"] == pytest.approx(60.0)
    assert got["decode_attn_inplace_pct"] == 100.0
    assert got["decode_attn_whole_tile_pct"] == 100.0
    assert got["moe_assignments_per_expert_step"] == 1.0
    assert got["moe_experts_touched_pct"] == pytest.approx(62.5)
    assert got["held_experts_kernel_pct"] == 100.0
    # 40 touched experts of 88 MB a step against 5 ms a step.
    assert got["held_experts_ffn_decode_roofline"] == pytest.approx(
        100 * ((40 * 44_040_192 * 2 + 32 * 4 * 7168 * 6) / 819e9) / 5e-3,
        rel=0.01)


def test_the_control_runner_tells_the_sound_engine_from_the_lacking(
        tmp_path, monkeypatch):
    """`families/gigachat3_5_controls.py`, what the chip's controls are
    read with, at toy widths: the sound drive inside the family's limits,
    a reference that lacks the output gate and an engine whose latent
    pool is at fp8's mantissa (8 bits a value) outside them (exit 0 says
    all of it)."""
    import importlib.util
    import json

    spec = importlib.util.spec_from_file_location(
        "gigachat3_5_controls", os.path.join(
            manifest.ROOT, "benchmarks", "families",
            "gigachat3_5_controls.py"))
    runner = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(runner)
    monkeypatch.chdir(tmp_path)
    assert runner.main(["--seeds", "7", "--toy", "1", "--lengths", "40",
                        "--controls", "latent_pool_fp8,no_output_gate"]) == 0
    with open(tmp_path / "chiprun_out" / "gigachat3_5_controls.json") as f:
        lines = json.load(f)
    assert [(line["control"], line["ok"]) for line in lines] == [
        ("sound", True), ("latent_pool_fp8", False),
        ("no_output_gate", False)]
    family = manifest.load_family(FAMILY)
    # The latent pool in 8 bits fails by the rows the cache holds, and at
    # toy widths by every position of the drive too.
    assert lines[1]["latent"] > family.LATENT_TOLERANCE > lines[0]["latent"]
    assert lines[1]["least"] > family.POSITIONS_TOLERANCE
    assert lines[0]["worst"] < 1e-4


def test_a_tree_without_the_model_refuses_the_cell_at_once(monkeypatch):
    family = manifest.load_family(FAMILY)
    monkeypatch.setattr(family, "PROGRAM_FILES",
                        ("models/gigachat35.py", "serve/engine/no_such.py"))
    with pytest.raises(ValueError, match="lacks serve/engine/no_such.py"):
        family.widths(manifest.load_cell(CELL)["config"])


def test_the_parent_commits_tree_lacks_the_files_the_family_names():
    """What makes the parent fail at once on the new cell: `widths` looks
    for files that this PR brought, before any cluster or chip is
    touched (`manifest.load_cell` calls it)."""
    family = manifest.load_family(FAMILY)
    assert set(family.PROGRAM_FILES) == {
        "models/gigachat35.py", "serve/engine/gigachat_model.py",
        "ops/latent_attention.py"}
    import ray_tpu

    where = os.path.dirname(ray_tpu.__file__)
    assert all(os.path.isfile(os.path.join(where, f))
               for f in family.PROGRAM_FILES)


def test_the_familys_own_limits_read_a_sound_drive_and_a_lowered_one():
    """`own_limits` on numbers: rounding passes; every position off, a
    state off and a state kept in bf16 do not."""
    import numpy as np

    family = manifest.load_family(FAMILY)
    rng = np.random.default_rng(0)
    want = rng.normal(size=(25, 32)).astype(np.float32)
    want_state = rng.normal(size=(4, 2, 8, 8)).astype(np.float32)
    want_rows = rng.normal(size=(1, 25, 40)).astype(np.float32)
    served = {"widths": {"toy": 1}, "params": None}
    saved = family.reference
    family.reference = lambda w: lambda params, tokens: (
        want, want_state, want_rows)
    try:
        def limits(rows, state, latents=want_rows * (1 + 2e-3)):
            return family.own_limits(served, rows, list(range(25)), 5, state,
                                     latents)

        noise = rng.normal(size=(21, 32)).astype(np.float32)
        sound = [want[4 + j] + 0.004 * noise[j] for j in range(21)]
        near = want_state * (1 + 1e-3)
        assert limits(sound, near)["ok"]
        lowered = [want[4 + j] + 0.05 * noise[j] for j in range(21)]
        assert not limits(lowered, near)["ok"]
        assert not limits(sound, want_state * 1.3)["ok"]
        # A latent pool at fp8's mantissa: a sixteenth of a value off.
        coarse = want_rows * (1 + rng.uniform(-1, 1, want_rows.shape) / 16)
        got = limits(sound, near, coarse.astype(np.float32))
        assert not got["ok"] and got["latent"] > family.LATENT_TOLERANCE
        in_bf16 = near.view(np.uint32) & np.uint32(0xFFFF0000)
        got = limits(sound, in_bf16.view(np.float32))
        assert not got["ok"] and got["state_bf16_share"] == 1.0
    finally:
        family.reference = saved


@pytest.mark.cluster
def test_the_cell_resolves_by_name_on_a_copy_and_runs_correct(tmp_path,
                                                             cluster):
    root = _benchmark_copy(tmp_path)
    assert manifest.problems(root) == []
    cell = _toy_cell(root)
    assert cell["widths"]["prefill_chunk_tokens"] == 16
    out = _run(cell, 2 ** 31 + 61)
    assert out["correct"], out
    assert out["failed"] == 0 and out["attempted"] >= 8
    counters = out["ctx"]["counters"]
    assert counters["cache.host_gathers"] == 0
    assert counters["decode_h2d_arrays"] == counters["paged_steps"]
    # Prompts of 32 went in chunks of 16 (the toy widths' chunk), and
    # every later chunk began from its sequence's state slot.
    assert counters["prefill_chunk_tokens"] > 0
    assert counters["prefill_later_chunks"] == \
        counters["prefill_state_chunks"] > 0
    layer = manifest.read_layer_metrics(cell, out["ctx"])
    assert layer["prefill_state_carried_pct"]["value"] == 100.0
    assert layer["kv_host_gathers"]["value"] == 0
    assert layer["moe_experts_touched_pct"]["value"] > 0
    assert layer["prefill_chunked_tokens_pct"]["value"] > 0
    assert layer["state_slots_in_use_pct"]["value"] > 0
    # Off the chip no operation ran on a device and the XLA body read the
    # pool: the trace readers find nothing.
    for name in NEW_READERS[:3]:
        assert name not in layer


@pytest.mark.cluster
@pytest.mark.parametrize("control", ["no_output_gate", "no_decay"])
def test_a_reference_without_a_mechanism_comes_out_not_correct(
        tmp_path, cluster, control):
    edits = {
        "no_output_gate": (
            'if "output_gate" not in w.get("without", ()):', 'if False:'),
        "no_decay": ('if "decay" in w.get("without", ()):', 'if True:')}
    root = _benchmark_copy(tmp_path, lambda source: source.replace(
        *edits[control]))
    out = _run(_toy_cell(root), 2 ** 31 + 62)
    assert out["correct"] is False
    gap, limit = out["checks"]["logit_rms_gap"]
    assert limit == manifest.load_family(FAMILY).LOGIT_TOLERANCE
    assert gap != gap or gap > limit        # NaN: by the family's limits
    assert out["failed"] == 0
