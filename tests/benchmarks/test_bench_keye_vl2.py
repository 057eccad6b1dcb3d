"""The `keye_vl2` family's cell through the benchmark's runner on the CPU
at toy widths: the manifest loads with the new entries, the cell resolves
its files by name and, sound, comes out `correct`; with a reference that
attends to every causal key, one that keeps half as many positions and
one without the norms on q and k it comes out not `correct`. And the
family's share of the harness: its counts (the issue's arithmetic), the
five readers this PR brings and the four older ones of the paged kernel
and the pools' padding that the cell joins, and a tree whose program
lacks the model refusing the cell at once."""

import os
import shutil
import time

import pytest

from benchmarks.harness import manifest, program_trace, serve_cell

import bench_toy as toy

CELL = "keye-vl-2.0-30b-a3b.serve.long-doc"
CONFIG = "keye-vl-2.0-30b-a3b"
FAMILY = "keye_vl2"
NEW_READERS = ("sparse_attn_kv_read_pct",
               "sparse_attention_share_of_step_pct", "index_scores_roofline",
               "sparse_decode_attention_roofline",
               "prefill_selected_attention_share_pct")
# Older readers of the paged kernel and the pools' padding: the masked
# walk is that kernel under its own name, and the step counts what they
# read.
JOINED_READERS = ("paged_decode_attention_roofline",
                  "decode_attention_share_of_step_pct",
                  "decode_attn_inplace_pct", "kv_pool_padding_pct")


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
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert {"name": CELL, "config": CONFIG, "traffic": "serve.long-doc",
            "chips": 1, "why": manifest.load_cell(CELL)["settings"]["why"]} \
        in m["workloads"]
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    cell = manifest.load_cell(CELL)
    assert {e["name"] for e in cell["end_to_end"]} == {
        "serve_itl_p99_ms", "serve_out_tokens_per_s", "setup_s"}
    listed = {e["name"] for e in cell["per_layer"]}
    assert set(NEW_READERS) <= listed
    assert {"decode_step_roofline", "held_experts_ffn_decode_roofline",
            "held_experts_kernel_pct", "prefill_share_of_window_pct",
            "prefill_chunked_tokens_pct", "emit_overlapped_pct",
            "moe_experts_touched_pct", "engine_mean_decode_batch",
            "decode_device_ms_per_step", "device_idle_pct.serve"} \
        | set(JOINED_READERS) <= listed
    # The new entries are IN `per_layer`, each this cell's alone (a later
    # PR appends behind them).
    mine = [e for e in m["per_layer"] if e["name"] in NEW_READERS]
    assert len(mine) == 5 and all(e["workloads"] == [CELL] for e in mine)


def test_the_cells_traffic_and_settings_are_the_issues():
    cell = manifest.load_cell(CELL)
    traffic, settings = cell["traffic"], cell["settings"]
    assert traffic["kind"] == "serve_closed" and traffic["clients"] == 16
    assert traffic["requests"] == 128 and traffic["drain_s"] == 60
    assert traffic["schedule_seed"] == 57
    assert traffic["prompt_len"] == {"dist": "uniform", "min": 8448,
                                     "max": 15616, "step": 256}
    assert traffic["output_len"] == {"dist": "uniform", "min": 512,
                                     "max": 768, "step": 1}
    assert settings["engine"] == {
        "paged_decode": True, "max_batch_size": 16, "block_size": 16,
        "num_blocks": 17408, "max_queue": 256}
    assert settings["max_seq_len"] == 16384
    assert settings["check_prompts"] == [48, 200, 2304, 8448]
    assert settings["check_decode_steps"] == 20
    assert settings["trace_seconds"] == 1.5
    assert len(settings["why"]) <= 200
    config = cell["config"]
    assert config["share_chips"] == 8 and config["experts_held"] == [0, 16]
    assert config["published"] == {"num_hidden_layers": 48,
                                   "num_experts": 128, "vocab_size": 151936}
    assert config["vocab_size"] * 8 == 151936
    for key in ("assumed", "arithmetic", "departures", "stands_for"):
        assert config[key]
    # Every length of the mix has its keys in the 16,384 chunk program
    # and its steps in the 1,024-block table bucket.
    from benchmarks.harness import loadgen

    shapes = loadgen.reachable_shapes(traffic, 16, 16)
    assert len(shapes["prompt_lengths"]) == 29
    assert shapes["decode_tables"] == [1024]
    assert shapes["longest_context"] == 16384


def test_counts_are_the_issues_arithmetic():
    cell = manifest.load_cell(CELL)
    family = manifest.family_of(cell)
    counts = family.counts(cell["widths"])
    p = counts["params"]
    assert (p["attention"], p["norms"], p["indexer"], p["router"]) == \
        (18_874_368, 4_352, 2_261_120, 262_144)
    assert p["rest_a_layer"] == 21_401_984 and p["expert"] == 4_718_592
    assert p["published_layer"] == 625_381_760
    assert p["held_layer"] == 96_899_456
    assert p["held"] == 1_240_586_752
    assert round(p["total"] / 1e9, 2) == 30.64
    assert round(p["active"] / 1e9, 2) == 3.46
    # A position's rows: 12 x (2,048 B of KV + 128 B of index key).
    assert counts["kv_bytes_per_token"] == 12 * 2048
    assert counts["index_bytes_per_token"] == 12 * 128
    assert counts["pool_bytes_per_token"] == 26_112
    assert counts["moe"] == {"layers": 12, "experts_held": 16}
    assert counts["index_topk"] == 2048
    # A step of 16 rows at 12,000 positions: every live index key, the KV
    # of 2,048 positions a row.
    live = 16 * 12000
    no_kv = counts["decode_step_bytes"](16, 0)
    assert counts["decode_step_bytes"](16, live) - no_kv == pytest.approx(
        live * 12 * 128 + 16 * 2048 * 12 * 2048)
    assert 1.7e9 < no_kv < 1.8e9
    assert 10.2 < counts["experts_touched"](16) < 10.4
    # Under `topk` a row attends to all it has.
    assert counts["decode_step_bytes"](16, 16 * 100) - no_kv == \
        pytest.approx(16 * 100 * 26_112)
    cost = counts["decode_attention_cost"]("selected", 16 * 2048)
    assert cost["bytes"] == 16 * 2048 * 12 * 2048
    assert cost["flops"] == 4 * 32 * 128 * 12 * 16 * 2048
    cost = counts["index_scores_cost"](live)
    assert cost["bytes"] == live * 12 * 128
    assert cost["flops"] == 2 * 16 * 64 * 12 * live
    with pytest.raises(ValueError):
        counts["decode_attention_cost"]("global", 1)


def test_the_new_readers_read_what_is_there_and_nothing_else(monkeypatch):
    cell = manifest.load_cell(CELL)
    counts = manifest.family_of(cell).counts(cell["widths"])
    peak = cell["peaks"]["TPU v5 lite"]
    live, steps = 16 * 12000 * 12, 50
    ctx = {"counts": counts, "window_s": 40.0, "cell": cell,
           "widths": cell["widths"], "peak": peak,
           "counters": {"decode_index_tokens_scored": live * steps,
                        "decode_kv_tokens_read": live * steps,
                        "decode_kv_tokens_selected": 16 * 2048 * 12 * steps},
           "trace_counters": {
               "decode_steps": steps,
               "decode_index_tokens_scored": live * steps,
               "decode_kv_tokens_selected": 16 * 2048 * 12 * steps},
           "trace": {"op_s": {"paged_index_scores": 0.05,
                              "paged_decode_attention": 0.30,
                              "held_experts_ffn_decode": 0.1,
                              "prefill_index_scores": 0.1,
                              "flash_prefill_fwd_selected": 0.4,
                              "fusion.7": 0.2},
                     "spans": {"decode_step": {"count": steps,
                                               "device_busy_s": 1.0}}}}
    modules = {"jit_prefill_chunk": {"device_s": 1.25, "count": 30},
               "jit_decode_paged": {"device_s": 1.0, "count": steps}}
    monkeypatch.setattr(program_trace, "of_run",
                        lambda ctx: ctx["trace"] and {"modules": modules})
    got = {name: manifest.load_reader(name)(ctx) for name in NEW_READERS}
    assert got["prefill_selected_attention_share_pct"] == pytest.approx(40.0)
    assert got["sparse_attn_kv_read_pct"] == 100.0
    assert got["sparse_attention_share_of_step_pct"] == pytest.approx(35.0)
    # 0.29 GB of index keys a step at 819 GB/s against 1 ms a step.
    assert got["index_scores_roofline"] == pytest.approx(
        100 * (16 * 12000 * 12 * 128 / 819e9) / 1e-3, rel=0.02)
    # 0.81 GB of chosen rows a step against 6 ms a step.
    assert got["sparse_decode_attention_roofline"] == pytest.approx(
        100 * (16 * 2048 * 12 * 2048 / 819e9) / 6e-3, rel=0.02)
    ctx["counters"]["decode_kv_tokens_read"] = 16 * 2048 * 12 * steps
    assert manifest.load_reader("sparse_attn_kv_read_pct")(ctx) == \
        pytest.approx(100 * 2048 / 12000)
    fetch = dict(ctx, trace=dict(ctx["trace"], op_s={
        "sparse_paged_decode_attention": 0.15, "paged_index_scores": 0.05}))
    assert manifest.load_reader(
        "sparse_decode_attention_roofline")(fetch) == pytest.approx(
        2 * got["sparse_decode_attention_roofline"])
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
    for name in NEW_READERS[1:]:
        assert manifest.load_reader(name)(xla) is None, name
    # A trace that holds the chunk's kernels and no prefill program.
    modules.pop("jit_prefill_chunk")
    assert manifest.load_reader(
        "prefill_selected_attention_share_pct")(ctx) is None
    # Another family's counts (no `index_scores_cost`, no group
    # "selected") and counters: nothing read, nothing raised.
    other = manifest.load_cell("mimo-v2.5.serve.doc-context")
    other_ctx = dict(xla, cell=other, widths=other["widths"],
                     counts=manifest.family_of(other).counts(other["widths"]),
                     trace_counters={"decode_steps": 5})
    for name in NEW_READERS:
        assert manifest.load_reader(name)(other_ctx) is None, name


def test_the_older_readers_of_the_walk_and_the_padding_read_this_cell():
    """The masked walk is the paged kernel under its own name: the four
    accepted readers the cell joins read its time, the pages it moved and
    both pools' bytes as held and as the model counts them."""
    cell = manifest.load_cell(CELL)
    counts = manifest.family_of(cell).counts(cell["widths"])
    steps, pages = 50, 16 * 750 * 50      # 16 rows of 12,000 positions
    position = 24576 + 12 * 64 * 2        # a position's bytes, both pools
    ctx = {"counts": counts, "cell": cell, "widths": cell["widths"],
           "peak": cell["peaks"]["TPU v5 lite"],
           "counters": {"paged_steps": steps,
                        "decode_attn_inplace_steps": steps,
                        "decode_kv_bytes_read_held": position + 12 * 64 * 2,
                        "decode_kv_bytes_read_model": position},
           "trace_counters": {"decode_kv_pages_read": pages},
           "trace": {"op_s": {"paged_decode_attention": 0.30,
                              "paged_index_scores": 0.05},
                     "spans": {"decode_step": {"count": steps,
                                               "device_busy_s": 1.0}}}}
    got = {name: manifest.load_reader(name)(ctx) for name in JOINED_READERS}
    # 4.7 GB of live pages a step at 819 GB/s against 6 ms a step.
    assert got["paged_decode_attention_roofline"] == pytest.approx(
        100 * (16 * 12000 * 24576 / 819e9) / 6e-3, rel=0.01)
    assert got["decode_attention_share_of_step_pct"] == pytest.approx(30.0)
    assert got["decode_attn_inplace_pct"] == 100.0
    # 128 lanes held for an index key of 64: 1,536 B on 26,112.
    assert got["kv_pool_padding_pct"] == pytest.approx(100 * 1536 / 26112)


def test_the_control_runner_tells_the_sound_engine_from_the_lacking(
        tmp_path, monkeypatch):
    """`families/keye_vl2_controls.py`, what the chip's controls are read
    with, at toy widths: the sound drive inside the family's limits, a
    reference that lacks the selection and an engine whose keys and
    values are at fp8's mantissa outside them (exit 0 says all of it)."""
    import importlib.util
    import json

    spec = importlib.util.spec_from_file_location(
        "keye_vl2_controls", os.path.join(
            manifest.ROOT, "benchmarks", "families", "keye_vl2_controls.py"))
    runner = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(runner)
    monkeypatch.chdir(tmp_path)
    assert runner.main(["--seeds", "7,8", "--toy", "1", "--lengths", "40",
                        "--controls", "kv_pool_fp8,every_causal_key"]) == 0
    with open(tmp_path / "chiprun_out" / "keye_vl2_controls.json") as f:
        lines = json.load(f)
    assert [(line["seed"], line["control"], line["ok"]) for line in lines] \
        == [(seed, control, control == "sound") for seed in (7, 8)
            for control in ("sound", "kv_pool_fp8", "every_causal_key")]
    assert lines[2]["first_layer_overlap"] < 0.2
    assert lines[1]["median"] > lines[1]["limits"][1]


def test_a_tree_without_the_model_refuses_the_cell_at_once(monkeypatch):
    family = manifest.load_family(FAMILY)
    monkeypatch.setattr(family, "PROGRAM_FILES",
                        ("models/keye_vl2.py", "serve/engine/no_such.py"))
    with pytest.raises(ValueError, match="lacks serve/engine/no_such.py"):
        family.widths(manifest.load_cell(CELL)["config"])


@pytest.mark.cluster
def test_the_cell_resolves_by_name_on_a_copy_and_runs_correct(tmp_path,
                                                             cluster):
    root = _benchmark_copy(tmp_path)
    assert manifest.problems(root) == []
    cell = _toy_cell(root)
    assert cell["widths"]["index_topk"] == 8
    out = _run(cell, 2 ** 31 + 57)
    assert out["correct"], out
    assert out["failed"] == 0 and out["attempted"] >= 8
    counters = out["ctx"]["counters"]
    assert counters["cache.host_gathers"] == 0
    assert counters["decode_h2d_arrays"] == counters["paged_steps"]
    # Every step stands past `topk` (8): the indexer scored every live
    # position and the attention kept 8 a row.
    assert counters["decode_index_tokens_scored"] > \
        counters["decode_kv_tokens_selected"] > 0
    # Prompts of 32 went in chunks of 16 (the toy widths' chunk).
    assert counters["prefill_chunk_tokens"] > 0
    layer = manifest.read_layer_metrics(cell, out["ctx"])
    assert layer["sparse_attn_kv_read_pct"]["value"] == 100.0
    assert layer["kv_host_gathers"]["value"] == 0
    assert layer["moe_experts_touched_pct"]["value"] > 0
    assert layer["prefill_chunked_tokens_pct"]["value"] > 0
    # Off the chip no operation ran on a device: the trace readers find
    # nothing.
    for name in NEW_READERS[1:]:
        assert name not in layer




@pytest.mark.cluster
@pytest.mark.parametrize("control", ["every_causal_key", "topk_halved",
                                     "no_qk_norm"])
def test_a_reference_without_a_mechanism_comes_out_not_correct(
        tmp_path, cluster, control):
    edits = {
        "every_causal_key": (
            'if "selection" in w.get("without", ()) or s <= w["index_topk"]:',
            'if True:'),
        "topk_halved": (
            'kth = -jnp.sort(-scores, axis=-1)[:, w["index_topk"] - 1]',
            'kth = -jnp.sort(-scores, axis=-1)[:, w["index_topk"] // 2 - 1]'),
        "no_qk_norm": (
            'if "qk_norm" not in w.get("without", ()):', 'if False:')}
    root = _benchmark_copy(tmp_path, lambda source: source.replace(
        *edits[control]))
    out = _run(_toy_cell(root), 2 ** 31 + 58)
    assert out["correct"] is False
    gap, limit = out["checks"]["logit_rms_gap"]
    assert limit == manifest.load_family(FAMILY).LOGIT_TOLERANCE
    assert gap != gap or gap > limit        # NaN: by the family's limits
    assert out["failed"] == 0


def test_the_familys_own_limits_read_a_sound_drive_and_a_lowered_one():
    """`own_limits` on numbers: rounding passes, every position off does
    not, nor does a first layer's selection that lost one of sixteen."""
    import numpy as np

    family = manifest.load_family(FAMILY)
    rng = np.random.default_rng(0)
    want = rng.normal(size=(25, 32)).astype(np.float32)
    selected = np.zeros((3, 25), bool)
    selected[:, rng.permutation(25)[:16]] = True
    served = {"widths": None, "params": None}

    def limits(rows, kept=None):
        saved = family.reference_with_selection
        family.reference_with_selection = \
            lambda w: lambda params, tokens: (want, selected)
        try:
            return family.own_limits(served, rows, list(range(25)), 5, kept)
        finally:
            family.reference_with_selection = saved

    noise = rng.normal(size=(21, 32)).astype(np.float32)
    sound = [want[4 + j] + 0.004 * noise[j] for j in range(21)]
    assert limits(sound)["ok"]
    lowered = [want[4 + j] + 0.03 * noise[j] for j in range(21)]
    assert not limits(lowered)["ok"]
    # The engine's selection: positions 0..23 as the table's columns (a
    # table of 32), the query's own position last.
    mine = np.zeros((3, 33), bool)
    mine[:, :24], mine[:, -1] = selected[:, :24], selected[:, 24]
    got = limits(sound, mine)
    assert got["ok"] and got["selection_overlap"] == 1.0
    # A later layer may lose a fifth (its input already differs); the
    # first, whose input both sides share, may not; none may lose half.
    lost = mine.copy()
    lost[1, np.flatnonzero(mine[1])[:3]] = False      # 3 of 16 in layer 1
    got = limits(sound, lost)
    assert got["ok"] and got["selection_overlap"] == pytest.approx(13 / 16)
    lost = mine.copy()
    lost[0, np.flatnonzero(mine[0])[:1]] = False      # 1 of 16 in layer 0
    got = limits(sound, lost)
    assert not got["ok"] and got["first_layer_overlap"] == \
        pytest.approx(15 / 16)
    lost = mine.copy()
    lost[2, np.flatnonzero(mine[2])[:8]] = False      # half of layer 2
    assert not limits(sound, lost)["ok"]
    assert family.drive_limits(48) == (0.012, 0.012)
    assert family.drive_limits(8448)[0] > 0
