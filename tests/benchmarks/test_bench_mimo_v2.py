"""The `mimo_v2` family's cell through the benchmark's runner on the CPU
at toy widths: the manifest loads with the new entries, the cell resolves
its files by name and, sound, comes out `correct`; with a broken decode
step (the window layers' sink left out of the engine's steps) and with a
reference that has no sink it comes out not `correct`. And the family's
share of the harness: its counts (the published sizes, the model's bytes
a position), the three readers this PR brings, and a tree whose program
lacks the model refusing the cell at once."""

import os
import shutil
import time

import pytest

from benchmarks.harness import manifest, serve_cell

import bench_toy as toy

CELL = "mimo-v2.5.serve.doc-context"
FAMILY = "mimo_v2"
NEW_READERS = ("decode_attn_inplace_pct",
               "decode_attention_share_of_step_pct", "kv_pool_padding_pct")


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
    config = m["configs"][-1]
    assert config["name"] == "mimo-v2.5" and config["reduced"] == [
        "num_hidden_layers", "hybrid_layer_pattern", "moe_layer_freq",
        "n_routed_experts", "vocab_size"]
    assert m["workloads"][-1] == {
        "name": CELL, "config": "mimo-v2.5", "traffic": "serve.doc-context",
        "chips": 1, "why": manifest.load_cell(CELL)["settings"]["why"]}
    cell = manifest.load_cell(CELL)
    assert {e["name"] for e in cell["end_to_end"]} == {
        "serve_itl_p99_ms", "serve_out_tokens_per_s", "setup_s"}
    listed = {e["name"] for e in cell["per_layer"]}
    assert set(NEW_READERS) <= listed
    assert {"window_decode_attention_roofline",
            "global_decode_attention_roofline", "decode_step_roofline",
            "held_experts_ffn_decode_roofline", "kv_global_blocks_in_use_pct",
            "kv_window_tokens_held_per_seq", "prefill_share_of_window_pct",
            "moe_experts_touched_pct", "engine_mean_decode_batch"} <= listed
    # The new entries are the last three, and this cell's alone.
    assert [e["name"] for e in m["per_layer"][-3:]] == list(NEW_READERS)
    assert all(e["workloads"] == [CELL] for e in m["per_layer"][-3:])


def test_the_cells_traffic_and_settings_are_the_issues():
    cell = manifest.load_cell(CELL)
    traffic, settings = cell["traffic"], cell["settings"]
    assert traffic["kind"] == "serve_closed" and traffic["clients"] == 16
    assert traffic["requests"] == 128 and traffic["drain_s"] == 60
    assert traffic["prompt_len"] == {"dist": "uniform", "min": 4352,
                                     "max": 7680, "step": 256}
    assert traffic["output_len"] == {"dist": "uniform", "min": 384,
                                     "max": 512, "step": 1}
    assert settings["engine"] == {
        "paged_decode": True, "max_batch_size": 16, "block_size": 16,
        "num_blocks": 10240, "group_blocks": {"window": 160},
        "max_queue": 256}
    assert settings["max_seq_len"] == 8192
    assert settings["check_prompts"] == [48, 200, 1040, 4352]
    assert settings["check_decode_steps"] == 20
    config = cell["config"]
    assert config["share_chips"] == 16 and config["experts_held"] == [0, 16]
    assert config["published"]["n_routed_experts"] == 256
    assert config["published"]["vocab_size"] == 152576 == 8 * 19072
    for key in ("assumed", "arithmetic", "departures", "stands_for"):
        assert config[key]


def test_counts_are_the_published_sizes_and_the_models_bytes():
    cell = manifest.load_cell(CELL)
    family = manifest.family_of(cell)
    counts = family.counts(cell["widths"])
    p = counts["params"]
    assert round(p["total"] / 1e9, 2) == 308.78
    assert round(p["active"] / 1e9, 2) == 15.45
    assert round(p["held"] / 1e9, 2) == 3.43
    assert round(p["global_layer"] / 1e6, 2) == 89.13
    assert round(p["window_layer"] / 1e6, 2) == 94.37
    assert round(p["expert"] / 1e6, 2) == 25.17
    # 2,560 bytes a token and global layer (4 x (192 + 128) x 2), 5,120 a
    # window layer: two global layers, five window layers.
    assert counts["kv_group_bytes_per_token"] == {"global": 2 * 2560,
                                                  "window": 5 * 5120}
    assert counts["kv_bytes_per_token"] == 2 * 2560 + 5 * 5120
    assert counts["moe"] == {"layers": 6, "experts_held": 16}
    assert counts["window"] == 128
    # A step of 16 rows at 6,300 tokens: all of them in the 2 global
    # layers, 128 a row in the 5 window layers.
    live = 16 * 6300
    kv = live * 2 * 2560 + 16 * 128 * 5 * 5120
    no_kv = counts["decode_step_bytes"](16, 0)
    assert counts["decode_step_bytes"](16, live) - no_kv == \
        pytest.approx(kv)
    assert 0.5e9 < kv < 0.6e9 and 3.6e9 < no_kv < 4.0e9
    assert 6.3 < counts["experts_touched"](16) < 6.5
    cost = counts["decode_attention_cost"]("global", live)
    assert cost["bytes"] == live * 2 * 2560
    assert cost["flops"] == 2 * 64 * (192 + 128) * 2 * live
    cost = counts["decode_attention_cost"]("window", 16 * 128)
    assert cost["bytes"] == 16 * 128 * 5 * 5120


def test_the_three_new_readers_read_what_is_there_and_nothing_else():
    cell = manifest.load_cell(CELL)
    counts = manifest.family_of(cell).counts(cell["widths"])
    ctx = {"counts": counts, "window_s": 40.0, "cell": cell, "counters": {
        "paged_steps": 200, "decode_attn_inplace_steps": 200,
        "decode_kv_bytes_read_held": 384 * 7, "decode_kv_bytes_read_model":
        320 * 7},
        "trace": {"op_s": {"paged_decode_attention": 0.030,
                           "paged_window_decode_attention": 0.010,
                           "held_experts_ffn_decode": 0.2},
                  "spans": {"decode_step": {"count": 50,
                                            "device_busy_s": 0.4}}}}
    got = {name: manifest.load_reader(name)(ctx) for name in NEW_READERS}
    assert got["decode_attn_inplace_pct"] == 100.0
    assert got["decode_attention_share_of_step_pct"] == pytest.approx(10.0)
    assert got["kv_pool_padding_pct"] == pytest.approx(20.0)
    ctx["counters"]["decode_attn_inplace_steps"] = 150
    assert manifest.load_reader("decode_attn_inplace_pct")(ctx) == 75.0
    # A program without the counters, a run without a trace, a trace
    # without the kernels (the parent of this PR, the CPU): nothing to
    # read, nothing raised.
    bare = {"counts": counts, "cell": cell, "counters": {"paged_steps": 10},
            "trace": None, "trace_counters": None}
    for name in NEW_READERS:
        assert manifest.load_reader(name)(bare) is None, name
    xla = dict(bare, counters={"paged_steps": 10,
                               "decode_attn_inplace_steps": 0},
               trace={"op_s": {"fusion.1": 0.1}, "spans": {
                   "decode_step": {"count": 5, "device_busy_s": 0.1}}})
    assert manifest.load_reader("decode_attn_inplace_pct")(xla) == 0.0
    assert manifest.load_reader(
        "decode_attention_share_of_step_pct")(xla) is None
    assert manifest.load_reader("kv_pool_padding_pct")(xla) is None


@pytest.mark.cluster
def test_the_cell_resolves_by_name_on_a_copy_and_runs_correct(tmp_path,
                                                             cluster):
    root = _benchmark_copy(tmp_path)
    assert manifest.problems(root) == []
    cell = _toy_cell(root)
    assert cell["settings"]["engine"]["group_blocks"] == {"window": 160}
    assert cell["widths"]["window"] == 16
    out = _run(cell, 2 ** 31 + 53)
    assert out["correct"], out
    assert out["failed"] == 0 and out["attempted"] >= 8
    counters = out["ctx"]["counters"]
    assert counters["cache.host_gathers"] == 0
    # (At toy lengths no block leaves the window of 16 after a prompt
    # of 16 or 32: the prefill stores only the rows the window reaches,
    # and 12 more positions cross no further block. `test_mimo_engine`
    # sees blocks released.)
    assert counters["decode_h2d_arrays"] == counters["paged_steps"]
    layer = manifest.read_layer_metrics(cell, out["ctx"])
    # ceil(16 / 16) + 1 = 2 blocks of 16 a sequence at the most.
    assert 0 < layer["kv_window_tokens_held_per_seq"]["value"] <= 32
    assert 0 < layer["kv_global_blocks_in_use_pct"]["value"] < 100
    assert layer["kv_host_gathers"]["value"] == 0
    assert layer["moe_experts_touched_pct"]["value"] > 0
    # Off the chip no step's attention goes through the kernel, and no
    # operation ran on a device: the trace readers find nothing.
    assert layer["decode_attn_inplace_pct"]["value"] == 0
    for name in ("window_decode_attention_roofline",
                 "global_decode_attention_roofline",
                 "decode_attention_share_of_step_pct",
                 "kv_pool_padding_pct"):
        assert name not in layer


# The engine's decode steps without the window layers' sink: a broken
# decode step (the prefill keeps it, so the first row of a drive is
# sound and the twenty after it are not).
NO_SINK_IN_A_STEP = """
def _steps_without_sink(model):
    build, layers = model._build_decode_paged, model._layers

    def no_sink(params):
        for lp, *rest in layers(params):
            yield ({k: v for k, v in lp.items() if k != "sink"}, *rest)

    def build_without(*key):
        fn = build(*key)            # a jit: traced at its first call

        def step(*args):
            model._layers = no_sink
            try:
                return fn(*args)
            finally:
                model._layers = layers

        return step

    model._build_decode_paged = build_without
    return model
"""

REFERENCE_WITHOUT_SINK = (
    "    without = w.get(\"without\", ())\n    s = y.shape[0]\n",
    "    without = (\"sink\",)\n    s = y.shape[0]\n")


@pytest.mark.cluster
@pytest.mark.parametrize("control", ["a_decode_step_without_the_sink",
                                     "a_reference_without_the_sink"])
def test_a_broken_step_or_a_sinkless_reference_comes_out_not_correct(
        tmp_path, cluster, control):
    if control == "a_decode_step_without_the_sink":
        root = _benchmark_copy(tmp_path, lambda source: source.replace(
            "    model.eos_token = None ",
            "    model = _steps_without_sink(model)\n"
            "    model.eos_token = None ") + NO_SINK_IN_A_STEP)
    else:
        root = _benchmark_copy(tmp_path, lambda source: source.replace(
            *REFERENCE_WITHOUT_SINK))
    out = _run(_toy_cell(root), 2 ** 31 + 54)
    assert out["correct"] is False
    gap, limit = out["checks"]["logit_rms_gap"]
    assert limit == manifest.load_family(FAMILY).LOGIT_TOLERANCE
    assert gap != gap or gap > limit        # NaN: by the family's limits
    assert out["failed"] == 0


def test_the_familys_own_limits_read_a_sound_drive_and_a_lowered_one():
    """`own_limits` on numbers: swaps at a few positions pass, every
    position off does not."""
    import numpy as np

    family = manifest.load_family(FAMILY)
    rng = np.random.default_rng(0)
    want = rng.normal(size=(25, 32)).astype(np.float32)
    served = {"widths": None, "params": None}

    def limits(rows):
        saved = family.reference_logits
        family.reference_logits = lambda w: lambda params, tokens: want
        try:
            return family.own_limits(served, rows, list(range(25)), 5)
        finally:
            family.reference_logits = saved

    noise = rng.normal(size=(21, 32)).astype(np.float32)
    sound = [want[4 + j] + 0.004 * noise[j] for j in range(21)]
    assert limits(sound)["ok"]
    swapped = [row + 0.08 * noise[j] if j in (3, 4, 11) else row
               for j, row in enumerate(sound)]
    got = limits(swapped)
    assert got["ok"] and got["positions"][-1] > 0.06
    lowered = [want[4 + j] + 0.03 * noise[j] for j in range(21)]
    assert not limits(lowered)["ok"]


def test_a_program_without_the_model_refuses_the_cell_at_once(tmp_path):
    """The benchmark's files over a program that lacks the model (how the
    driver tries a new cell on the parent): as a script the benchmark
    exits non-zero at once, before any cluster or chip is touched, and
    every other cell still resolves."""
    import subprocess
    import sys

    root = _benchmark_copy(tmp_path)
    os.makedirs(os.path.join(root, "ray_tpu"))      # a program without it
    with open(os.path.join(root, "ray_tpu", "__init__.py"), "w") as f:
        f.write("def init(*a, **k):\n    raise SystemExit('reached the "
                "cluster')\n")
    started = time.time()
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELL, "--seed",
         "1", "--seconds", "1", "--trace", "0"], cwd=root,
        capture_output=True, text=True, timeout=60)
    assert proc.returncode not in (0, None) and proc.stdout == ""
    assert "ray_tpu lacks models/mimo_v2.py" in proc.stderr
    assert "reached the cluster" not in proc.stderr
    assert time.time() - started < 20
    check = ("import sys; sys.path.insert(0, '.'); "
             "from benchmarks.harness import manifest; "
             "assert manifest.load_cell('olmo-1b.serve.decode-heavy', '.')")
    subprocess.run([sys.executable, "-c", check], cwd=root, check=True,
                   timeout=60)
