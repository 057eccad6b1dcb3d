"""The window-and-global family's cell through the benchmark's runner on
the CPU at toy widths: it resolves its files by name and, sound, comes out
`correct` (`test_bench_cells` runs it with every other cell, and here it
is run on a copy of the benchmark's files); with the engine's window one
block short it comes out not `correct`, and so it does with both KV pools
at a precision below the one the configuration states (fp8's mantissa).
And the family's share of the harness: its counts by group, the readers
of the new counters, and a tree whose program lacks the model refusing
the cell at once."""

import os
import shutil
import time

import pytest

from benchmarks.harness import manifest, serve_cell

import bench_toy as toy

CELL = "laguna-s-2.1.serve.repo-context"
FAMILY = "laguna"


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
    saved = manifest.ROOT
    cell = toy.cell(CELL)
    assert manifest.ROOT == saved
    copy = manifest.load_cell(CELL, root)
    assert copy["family"] == FAMILY and copy["settings"] == \
        manifest.load_cell(CELL)["settings"]
    cell["root"] = root
    return cell


def _run(cell, seed):
    return serve_cell.run(cell, seed=seed, seconds=1.0, trace=False,
                          t0=time.time(), expect_platform="cpu",
                          timeout_s=300)


@pytest.mark.cluster
def test_the_cell_resolves_by_name_on_a_copy_and_runs_correct(tmp_path,
                                                             cluster):
    root = _benchmark_copy(tmp_path)
    assert manifest.problems(root) == []
    cell = _toy_cell(root)
    assert cell["settings"]["engine"]["group_blocks"] == {"window": 640}
    assert cell["settings"]["check_decode_steps"] == 20
    assert cell["widths"]["window"] == 24
    out = _run(cell, 2 ** 31 + 11)
    assert out["correct"], out
    assert out["failed"] == 0 and out["attempted"] >= 8
    counters = out["ctx"]["counters"]
    assert counters["cache.host_gathers"] == 0
    assert counters["kv_window_window_blocks_released"] > 0
    assert counters["decode_h2d_arrays"] == counters["paged_steps"]
    layer = manifest.read_layer_metrics(cell, out["ctx"])
    # ceil(24 / 16) + 1 = 3 blocks of 16 a sequence at the most.
    assert 0 < layer["kv_window_tokens_held_per_seq"]["value"] <= 48
    assert 0 < layer["kv_global_blocks_in_use_pct"]["value"] < 100
    assert layer["prefill_kv_on_device_pct"]["value"] == 100
    assert layer["kv_host_gathers"]["value"] == 0
    assert layer["moe_experts_touched_pct"]["value"] > 0
    # No operation ran on a device here: the trace readers find nothing.
    for name in ("window_decode_attention_roofline",
                 "global_decode_attention_roofline"):
        assert name not in layer


WINDOW_SHORT = (
    "    cfg = model_config(w)\n",
    "    cfg = model_config(dict(w, window=w[\"window\"]\n"
    "                            - settings[\"engine\"][\"block_size\"]))\n")

# Both pools at fp8's 3 mantissa bits: the keys and values a layer hands
# its pool (the prefill's rows and a decode step's) rounded by the bits.
KV_FP8 = """
def _kv_at_fp8(model):
    import jax
    import jax.numpy as jnp

    def keep_3_bits(a):             # e4m3's mantissa, to nearest even
        bits = jax.lax.bitcast_convert_type(a.astype(jnp.float32),
                                            jnp.uint32)
        bits = (bits + jnp.uint32(0x7FFFF) + ((bits >> 20) & 1)) \\
            & jnp.uint32(0xFFF00000)
        return jax.lax.bitcast_convert_type(bits, jnp.float32)

    qkv = model._qkv

    def qkv_fp8(*args):
        q, k, v = qkv(*args)
        return q, keep_3_bits(k), keep_3_bits(v)

    model._qkv = qkv_fp8
    return model
"""


@pytest.mark.cluster
@pytest.mark.parametrize("control", ["window_one_block_short",
                                     "kv_pools_at_fp8"])
def test_a_short_window_or_a_lower_precision_comes_out_not_correct(
        tmp_path, cluster, control):
    """The model under test is built with a window of 8 where the
    reference keeps 24, or stores its keys and values at fp8's mantissa
    where the configuration states bfloat16 (float32 at toy widths);
    the reference keeps the seeded weights and the stated arithmetic."""
    if control == "window_one_block_short":
        root = _benchmark_copy(tmp_path, lambda source: source.replace(
            *WINDOW_SHORT))
    else:
        root = _benchmark_copy(tmp_path, lambda source: source.replace(
            "    model.eos_token = None ",
            "    model = _kv_at_fp8(model)\n    model.eos_token = None ")
            + KV_FP8)
    out = _run(_toy_cell(root), 2 ** 31 + 12)
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
    sound = [want[4 + j] + 0.01 * noise[j] for j in range(21)]
    assert limits(sound)["ok"]
    swapped = [row + 0.08 * noise[j] if j in (3, 4, 11) else row
               for j, row in enumerate(sound)]
    got = limits(swapped)
    assert got["ok"] and got["positions"][-1] > 0.06
    lowered = [want[4 + j] + 0.06 * noise[j] for j in range(21)]
    assert not limits(lowered)["ok"]


# What the chip read a drive length (PERF.md, Findings, PR 35): the
# largest least position and median of the sound drives over the seeds
# run, the smallest of the control's (both KV pools at fp8's mantissa).
CHIP_READINGS = {48: ((0.0091, 0.0119), (0.0242, 0.0308)),
                 528: ((0.0040, 0.0047), (0.0122, 0.0142)),
                 1040: ((0.0031, 0.0039), (0.0102, 0.0112)),
                 4096: ((0.0026, 0.0031), (0.0065, 0.0079))}


@pytest.mark.parametrize("n", sorted(CHIP_READINGS))
def test_a_drives_limits_lie_between_the_chips_two_readings(n):
    """Every check prompt of the cell has limits of its own, each with
    room over the largest sound reading and under the control's least:
    the control fails by each of its drives, not by the shortest alone."""
    family = manifest.load_family(FAMILY)
    assert n in manifest.load_cell(CELL)["settings"]["check_prompts"]
    sound, lowered = CHIP_READINGS[n]
    for limit, low, high in zip(family.drive_limits(n), sound, lowered):
        assert 1.65 * low <= limit <= 0.8 * high
    # The toy prompts are held as the shortest drive is.
    assert family.drive_limits(16) == family.drive_limits(48)


def test_counts_by_group_and_readers_of_the_new_counters():
    cell = manifest.load_cell(CELL)
    family = manifest.family_of(cell)
    counts = family.counts(cell["widths"])
    # 4 KB a token and layer: 3 global layers, 9 window layers.
    assert counts["kv_group_bytes_per_token"] == {"global": 3 * 4096,
                                                  "window": 9 * 4096}
    assert counts["kv_bytes_per_token"] == 12 * 4096
    assert counts["moe"] == {"layers": 11, "experts_held": 32}
    # A step of 16 rows at 2,900 tokens: all of them in the 3 global
    # layers, 512 a row in the 9 window layers.
    live = 16 * 2900
    kv = live * 3 * 4096 + 16 * 512 * 9 * 4096
    no_kv = counts["decode_step_bytes"](16, 0)
    assert counts["decode_step_bytes"](16, live) - no_kv == kv
    assert 0.8e9 < kv < 0.9e9 and 4.5e9 < no_kv < 5.5e9
    # A row shorter than the window reads its own length there.
    assert counts["decode_step_bytes"](2, 200) - counts[
        "decode_step_bytes"](2, 0) == 200 * 12 * 4096
    cost = counts["decode_attention_cost"]("window", 16 * 512)
    assert cost["bytes"] == 16 * 512 * 9 * 4096
    assert cost["flops"] == 4 * 72 * 128 * 9 * 16 * 512
    ctx = {"counts": counts, "window_s": 40.0, "cell": cell, "counters": {
        "paged_steps": 100, "tokens_generated": 1500, "prefills": 20,
        "kv_window_block_steps_in_use": 100 * 15 * 33,
        "kv_window_block_steps": 100 * 640,
        "kv_global_block_steps_in_use": 100 * 2048,
        "kv_global_block_steps": 100 * 8192,
        "prefill_s": 10.0}}
    new = ("kv_window_tokens_held_per_seq", "kv_global_blocks_in_use_pct",
           "prefill_share_of_window_pct")
    got = {name: manifest.load_reader(name)(ctx) for name in new}
    assert got == {
        "kv_window_tokens_held_per_seq": 100 * 15 * 33 * 16 / 1480,
        "kv_global_blocks_in_use_pct": 25.0,
        "prefill_share_of_window_pct": 25.0}
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    traced = dict(ctx, peak=peak, trace={"op_s": {
        "paged_window_decode_attention": 0.010,
        "paged_decode_attention": 0.020}},
        trace_counters={"decode_kv_pages_read_window": 100 * 16 * 32,
                        "decode_kv_pages_read_global": 100 * 16 * 181})
    window = manifest.load_reader("window_decode_attention_roofline")(traced)
    assert window == pytest.approx(
        100 * (100 * 16 * 32 * 16 * 9 * 4096 / 819e9) / 0.010)
    globe = manifest.load_reader("global_decode_attention_roofline")(traced)
    assert globe == pytest.approx(
        100 * (100 * 16 * 181 * 16 * 3 * 4096 / 819e9) / 0.020)
    # A program that has no such counter or kernel (the parent of this
    # family's PR) and a family without the counts: the readers find
    # nothing and leave their metrics out, and do not raise.
    old = {"counts": manifest.load_family().counts(
        manifest.load_cell("olmo-1b.serve.decode-heavy")["widths"]),
        "counters": {"paged_steps": 10, "tokens_generated": 100,
                     "prefills": 3, "prefill_s": 1.0},
        "window_s": 40.0, "cell": cell, "peak": peak,
        "trace": {"op_s": {"paged_decode_attention": 0.02}},
        "trace_counters": {"decode_kv_pages_read": 1000}}
    for name in new[:2] + ("window_decode_attention_roofline",
                           "global_decode_attention_roofline"):
        assert manifest.load_reader(name)(old) is None, name
    # The prefill's clock is older than this family: its share reads on
    # any program, in the cells `BENCHMARK.json` lists for it.
    share = manifest.load_reader("prefill_share_of_window_pct")
    assert share(old) == 2.5
    assert share(dict(old, counters={})) is None


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
    assert "ray_tpu lacks models/laguna.py" in proc.stderr
    assert "reached the cluster" not in proc.stderr
    assert time.time() - started < 20
    check = ("import sys; sys.path.insert(0, '.'); "
             "from benchmarks.harness import manifest; "
             "assert manifest.load_cell('olmo-1b.serve.decode-heavy', '.')")
    subprocess.run([sys.executable, "-c", check], cwd=root, check=True,
                   timeout=60)
