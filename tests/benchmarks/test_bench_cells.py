"""Each cell's driver end to end at toy widths with the expected platform
turned to `cpu`, while `python benchmarks/run.py` itself gives no result
off the chip."""

import subprocess
import sys
import time

import pytest

from benchmarks.harness import manifest, serve_cell, train_cell

import bench_toy as toy

REPO = manifest.ROOT


@pytest.fixture(scope="module")
def cluster():
    import ray_tpu

    ray_tpu.init(num_cpus=8, ignore_reinit_error=True)
    yield ray_tpu
    ray_tpu.shutdown()


@pytest.fixture(scope="module")
def off_chip_script():
    """Started first, collected last: no chip here, so as a script the
    benchmark says so, exits non-zero and prints no result."""
    proc = subprocess.Popen(
        [sys.executable, "benchmarks/run.py", "--workload",
         toy.CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=REPO, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True)
    yield proc
    if proc.poll() is None:
        proc.kill()


@pytest.mark.cluster
# Every second cell is run with the profiler on.
@pytest.mark.parametrize("name,trace", [
    (name, i % 2 == 1)
    for i, name in enumerate(toy.cells_of("serve_open", "serve_closed"))])
def test_serving_cells_at_toy_widths(cluster, off_chip_script, name, trace):
    cell = toy.cell(name)
    out = serve_cell.run(cell, seed=2 ** 31 + 7, seconds=2.0, trace=trace,
                         t0=time.time(), expect_platform="cpu",
                         timeout_s=300)
    assert out["correct"], out
    assert out["device"]["platform"] == "cpu"
    assert out["failed"] == 0 and out["attempted"] >= 8
    ctx = out["ctx"]
    assert ctx["counters"]["compiles"] == 0
    assert ctx["counters"]["paged_steps"] > 0
    assert ctx["counters"]["cache.host_gathers"] == 0
    for metric in cell["end_to_end"]:
        assert out["values"][metric["name"]] > 0, metric["name"]
    assert out["checks"]["logit_rms_gap"][0] <= \
        out["checks"]["logit_rms_gap"][1]
    layer = manifest.read_layer_metrics(cell, ctx)
    assert layer["decode_step_ms"]["value"] > 0
    assert layer["compiles_in_window.serve"]["value"] == 0
    assert layer["serve_stream_hop_p50_ms"]["value"] > 0
    # No operation ran on a device here: the trace readers find nothing
    # to read and their metrics are left out, never filled from the CPU.
    assert "decode_step_roofline" not in layer
    assert "device_idle_pct.serve" not in layer
    if trace:
        assert ctx["trace_counters"]["decode_steps"] > 0
        assert layer["engine_mean_decode_batch"]["value"] > 1


@pytest.mark.cluster
@pytest.mark.parametrize("name,trace", [
    (name, i % 2 == 0) for i, name in enumerate(toy.cells_of("train"))])
def test_training_cells_at_toy_widths(cluster, off_chip_script, name, trace):
    cell = toy.cell(name)
    out = train_cell.run(cell, seed=2 ** 31 + 7, seconds=1.5, trace=trace,
                         t0=time.time(), expect_platform="cpu",
                         timeout_s=300)
    assert out["correct"], out
    assert out["device"]["platform"] == "cpu"
    assert out["values"]["train_tokens_per_s_per_chip"] > 0
    assert out["values"]["setup_s"] > 0
    ctx = out["ctx"]
    assert ctx["counters"]["compiles"] == 0
    assert ctx["counters"]["steps"] == out["attempted"] >= 3
    assert out["checks"]["first_loss_gap"][0] <= \
        out["checks"]["first_loss_gap"][1]
    layer = manifest.read_layer_metrics(cell, ctx)
    assert layer["compiles_in_window.train"]["value"] == 0
    assert 0 <= layer["data_wait_pct"]["value"] < 100
    assert layer["session_report_ms"]["value"] > 0
    assert "train_mfu_pct" not in layer           # a CPU has no peak
    assert "device_idle_pct.train" not in layer
    if trace:
        assert ctx["trace_counters"]["steps"] == 2


def test_off_the_chip_the_command_gives_no_result_line(off_chip_script):
    out, err = off_chip_script.communicate(timeout=120)
    assert off_chip_script.returncode != 0
    assert out == ""
    assert f"needs {manifest.load_cell(toy.CELLS[0])['chips']} TPU chip" \
        in err


def test_the_harness_touches_no_tpu_library_at_import_time():
    code = ("import sys; sys.path.insert(0, %r); "
            "import benchmarks.harness.manifest, benchmarks.harness.loadgen,"
            " benchmarks.harness.stats, benchmarks.harness.flops, "
            "benchmarks.harness.trace, benchmarks.harness.serve_cell, "
            "benchmarks.harness.train_cell; "
            "assert 'jax' not in sys.modules, 'jax imported'" % REPO)
    subprocess.run([sys.executable, "-c", code], check=True, cwd=REPO,
                   timeout=120)
