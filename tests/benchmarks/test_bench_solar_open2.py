"""The hybrid family's cell through the benchmark's runner on the CPU at
toy widths: sound, it comes out `correct` (`test_bench_cells` runs it
with every other cell); with a row decoding from another row's state it
comes out not `correct`, and so it does at a precision below the one the
configuration states (the experts' matrices at fp8's mantissa, the delta
rule's state kept in bf16). And the family's share of the harness: a
tree whose program lacks the model refuses the cell at once."""

import os
import shutil
import time

import pytest

from benchmarks.harness import manifest, serve_cell

import bench_toy as toy

CELL = "solar-open2-250b.serve.decode-wide"
FAMILY = "solar_open2"


@pytest.fixture
def cluster():
    import ray_tpu

    ray_tpu.init(num_cpus=8, ignore_reinit_error=True)
    yield ray_tpu
    ray_tpu.shutdown()


def _family_copy(tmp_path, edit):
    """A root holding the family's file with `edit` applied to its
    source: what the replica loads in place of the real one."""
    families = os.path.join(str(tmp_path), "benchmarks", "families")
    os.makedirs(families)
    with open(os.path.join(manifest.bench_dir(), "families",
                           f"{FAMILY}.py")) as f:
        source = f.read()
    edited = edit(source)
    assert edited != source
    with open(os.path.join(families, f"{FAMILY}.py"), "w") as f:
        f.write(edited)
    return str(tmp_path)


@pytest.mark.cluster
def test_a_row_decoding_from_another_rows_state_comes_out_not_correct(
        tmp_path, cluster):
    """The check's drive with its decode steps handed a stale slot (the
    neighbour of the sequence's own): KV, tokens and positions are
    right, the recurrent state is another row's. The prefill rows still
    agree; `correct` comes out false on the decode rows."""
    root = _family_copy(tmp_path, lambda source: source.replace(
        "pool, [table], [tok], [pos], blocks, offs, block, state,\n"
        "                slots))",
        "pool, [table], [tok], [pos], blocks, offs, block, state,\n"
        "                [s ^ 1 for s in slots]))"))
    cell = toy.cell(CELL)
    cell["root"] = root
    out = serve_cell.run(cell, seed=2 ** 31 + 9, seconds=1.0, trace=False,
                         t0=time.time(), expect_platform="cpu",
                         timeout_s=300)
    assert out["correct"] is False
    gap, limit = out["checks"]["logit_rms_gap"]
    assert gap > 4 * limit
    assert out["failed"] == 0


# The two controls of the configuration's `arithmetic`, as edits of the
# family's `build_serving` in the replica: the model under test is built
# at the lower precision, the reference keeps the seeded weights.
EXPERTS_FP8 = """
def _experts_at_fp8(params):
    import jax
    import jax.numpy as jnp

    def keep_3_bits(a):             # e4m3's mantissa, to nearest even
        bits = jax.lax.bitcast_convert_type(a.astype(jnp.float32),
                                            jnp.uint32)
        bits = (bits + jnp.uint32(0x7FFFF) + ((bits >> 20) & 1)) \\
            & jnp.uint32(0xFFF00000)
        return jax.lax.bitcast_convert_type(bits, jnp.float32).astype(
            a.dtype)

    low = jax.tree.map(lambda a: a, params)
    for layer in low["moe"]:
        for name in ("w_gate", "w_up", "w_down", "shared_gate",
                     "shared_up", "shared_down"):
            layer[name] = keep_3_bits(layer[name])
    return low
"""
STATE_BF16 = """
def _state_kept_in_bf16(params):
    import jax.numpy as jnp

    import ray_tpu.ops.delta_rule as ops

    def kept(s):
        return s.astype(jnp.bfloat16).astype(jnp.float32)

    step, chunked = ops.delta_rule_step, ops.delta_rule_chunked

    def step_bf16(state, *rest):
        o, s = step(kept(state), *rest)
        return o, kept(s)

    def chunked_bf16(*args, **kwargs):
        o, s = chunked(*args, **kwargs)
        return o, kept(s)

    ops.delta_rule_step, ops.delta_rule_chunked = step_bf16, chunked_bf16
    return params
"""


@pytest.mark.cluster
@pytest.mark.parametrize("control, lowered", [
    ("_experts_at_fp8", EXPERTS_FP8), ("_state_kept_in_bf16", STATE_BF16)],
    ids=["experts_at_fp8", "state_kept_in_bf16"])
def test_a_precision_below_the_stated_one_comes_out_not_correct(
        tmp_path, cluster, control, lowered):
    """Through the benchmark's runner: with the held experts (routed and
    shared) at fp8's 3 mantissa bits every position of a drive leaves
    `POSITIONS_TOLERANCE`; with the delta rule's state rounded to
    bf16 after every update the logits read as a sound run's and the
    state's own limit (`STATE_BF16_SHARE`) sees it: the family's drive
    hands back rows that are no numbers. Either way the run is not
    `correct`. (At toy widths, 16 experts and 64 wide, fp8 experts read
    past the harness's own limit too.)"""
    root = _family_copy(tmp_path, lambda source: source.replace(
        "    model = HybridEngineModel(\n        params, cfg,",
        f"    model = HybridEngineModel(\n        {control}(params), cfg,")
        + lowered)
    cell = toy.cell(CELL)
    cell["root"] = root
    out = serve_cell.run(cell, seed=2 ** 31 + 10, seconds=1.0, trace=False,
                         t0=time.time(), expect_platform="cpu",
                         timeout_s=300)
    assert out["correct"] is False
    gap, limit = out["checks"]["logit_rms_gap"]
    assert limit == manifest.family_of(cell).LOGIT_TOLERANCE
    if control == "_state_kept_in_bf16":
        assert gap != gap                   # NaN: by the family's limit
    else:
        assert gap != gap or gap > limit
    assert out["failed"] == 0


def test_the_familys_own_limits_read_a_sound_drive_and_a_lowered_one():
    """`own_limits` on numbers: swaps at three positions of four pass,
    every position off does not; a state that bf16 holds does not."""
    import numpy as np

    family = manifest.family_of(toy.cell(CELL))
    rng = np.random.default_rng(0)
    want = rng.normal(size=(8, 32)).astype(np.float32)
    want_state = rng.normal(size=(2, 3, 4, 4)).astype(np.float32)
    served = {"widths": None, "params": None}

    def limits(rows, state, monkey=family):
        saved = monkey.reference
        monkey.reference = lambda w: lambda params, tokens: (want,
                                                             want_state)
        try:
            return monkey.own_limits(served, rows, list(range(8)), 5, state)
        finally:
            monkey.reference = saved

    noise = rng.normal(size=(4, 32)).astype(np.float32)
    sound = [want[4 + j] + 0.01 * noise[j] for j in range(4)]
    state = want_state * (1 + 1e-3)
    assert limits(sound, state)["ok"]
    swapped = [row + 0.06 * noise[j] for j, row in
               enumerate(sound[:3])] + sound[3:]
    assert limits(swapped, state)["ok"]
    assert limits(swapped, state)["positions"][1] > 0.05
    lowered = [want[4 + j] + 0.07 * noise[j] for j in range(4)]
    assert not limits(lowered, state)["ok"]
    assert not limits(sound, want_state * 1.1)["ok"]
    in_bf16 = (state.view(np.uint32) & 0xFFFF0000).view(np.float32)
    got = limits(sound, in_bf16)
    assert got["state"] < 0.01 and got["state_bf16_share"] == 1.0
    assert not got["ok"]


def test_counts_of_the_toy_widths_and_readers_of_the_new_counters():
    cell = toy.cell(CELL)
    family = manifest.family_of(cell)
    counts = family.counts(cell["widths"])
    assert counts["moe"] == {"layers": 8, "experts_held": 2}
    assert counts["state_bytes_per_sequence"] > 0
    ctx = {"counts": counts, "counters": {
        "paged_steps": 10, "moe_local_assignments": 80,
        "moe_expert_touches": 120, "moe_max_expert_load": 100,
        "state_slot_steps_in_use": 300, "state_slot_steps": 330}}
    got = {m["name"]: manifest.load_reader(m["name"])(ctx)
           for m in cell["per_layer"] if m["name"].startswith(
               ("moe_", "state_"))}
    assert got == {
        "moe_assignments_per_expert_step": 80 / (10 * 8 * 2),
        "moe_load_max_over_mean": 100 * 2 / 80,
        "moe_experts_touched_pct": 100.0 * 120 / (10 * 8 * 2),
        "state_slots_in_use_pct": 100.0 * 300 / 330}
    # A program that has no such counter (the parent of this family's
    # PR): the readers find nothing and leave their metrics out.
    old = {"counts": manifest.load_family().counts(
        manifest.load_cell("olmo-1b.serve.decode-heavy")["widths"]),
        "counters": {"paged_steps": 10}}
    for name in got:
        assert manifest.load_reader(name)(old) is None


def test_a_program_without_the_model_refuses_the_cell_at_once(tmp_path):
    """The benchmark's files over a program that lacks the hybrid model
    (how the driver tries a new cell on the parent): as a script the
    benchmark exits non-zero at once, before any cluster or chip is
    touched, and every other cell still resolves."""
    import subprocess
    import sys

    root = str(tmp_path)
    shutil.copytree(manifest.bench_dir(), os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(manifest.ROOT, "BENCHMARK.json"), root)
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
    assert "ray_tpu lacks models/hybrid_moe.py" in proc.stderr
    assert "reached the cluster" not in proc.stderr
    assert time.time() - started < 20
    check = ("import sys; sys.path.insert(0, '.'); "
             "from benchmarks.harness import manifest; "
             "assert manifest.load_cell('olmo-1b.serve.decode-heavy', '.')")
    subprocess.run([sys.executable, "-c", check], cwd=root, check=True,
                   timeout=60)
