"""BENCHMARK.json and the data files behind it: every name resolves, and a
configuration, a traffic mix, a cell and a per-layer metric can each be
added as new files plus one entry, with no edit to a file that exists."""

import json
import os
import shutil

import pytest

from benchmarks.harness import manifest

REPO = manifest.ROOT


def test_manifest_meets_the_contract_as_far_as_names_and_files_go():
    assert manifest.problems() == []
    m = manifest.load_manifest()
    assert m["command"] == ["python3", "benchmarks/run.py"]
    assert m["paths"] == ["benchmarks", "tests/benchmarks"]
    assert 1 <= m["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in m[group]:
            assert manifest.NAME_RE.match(entry["name"]), entry["name"]
    for metric in m["end_to_end"] + m["per_layer"]:
        assert manifest.UNIT_RE.match(metric["unit"]), metric
    for metric in m["per_layer"]:
        if metric["name"].endswith("_roofline"):
            assert metric["unit"] == "%"


@pytest.mark.parametrize("cell_name", [
    w["name"] for w in manifest.load_manifest()["workloads"]])
def test_every_cell_resolves_its_files_by_name(cell_name):
    cell = manifest.load_cell(cell_name)
    assert cell["widths"]["vocab_size"] > 2      # what the generator reads
    family = manifest.load_family(cell["family"])
    assert family.toy_widths(cell["widths"])["vocab_size"] > 2
    assert 0 < family.LOGIT_TOLERANCE and 0 < family.LOSS_TOLERANCE
    assert cell["traffic"]["kind"] in manifest.KINDS
    assert 1 <= len(cell["settings"]["why"]) <= 200
    assert cell["settings"]["why"] == next(
        w["why"] for w in manifest.load_manifest()["workloads"]
        if w["name"] == cell_name)
    assert "forced_today" in cell["settings"]
    names = {m["name"] for m in cell["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert cell["per_layer"]
    for metric in cell["per_layer"]:
        assert metric["moves"] in names, metric
        assert callable(manifest.load_reader(metric["name"]))
    for name in cell["config"]["reduced"]:
        assert name in cell["config"]["published"]


def test_grouped_query_or_untied_configs_are_refused_not_run_as_something_else():
    config = dict(manifest.load_cell("olmo-1b.serve.chat-steady")["config"])
    assert manifest.model_widths(config)["n_heads"] == 16
    with pytest.raises(ValueError, match="grouped-query"):
        manifest.model_widths(dict(config, num_key_value_heads=4))
    with pytest.raises(ValueError, match="untied"):
        manifest.model_widths(dict(config, tie_word_embeddings=False))
    # A family that has no file is refused by name, not run as the default.
    with pytest.raises(FileNotFoundError):
        manifest.model_widths(dict(config, family="no-such-family"))
    with pytest.raises(ValueError, match="bad family name"):
        manifest.model_widths(dict(config, family="../dense"))


# A second architecture, as the file a later PR would add: the engine's
# `TinyLM`, whose next token is a function of what the cache holds, with
# its oracle (no cache) as the plain reference. The default family refuses
# its configuration and could not describe it: no weights but the hash's
# two coefficients, a KV row of one value, nothing compiled by the model.
TINY_FAMILY = '''
"""TinyLM behind the engine; see serve/engine/model.py."""

# TinyLM's logits are -1e30 off its token and 0 on it: both sides are read
# as one-hot rows, and compared exactly.
LOGIT_TOLERANCE = 0.0
LOSS_TOLERANCE = 0.0
TRACED_CALLS = {"prefill": "prefill", "decode_step": "decode_paged"}


def widths(config):
    if config.get("model_type") != "tiny_oracle":
        raise ValueError("not a tiny_oracle config")
    # One of `vocab_shards` chips' slice: the generator draws ids from it.
    return {"vocab_size": config["vocab_size"] // config["vocab_shards"],
            "hash": config["hash"]}


def toy_widths(w):
    return dict(w)


def counts(w, held=None):
    held = held or {"weights": {"dtype": "float32", "bytes_per_value": 4},
                    "kv_pool": {"dtype": "float32", "bytes_per_value": 4}}
    kv = held["kv_pool"]["bytes_per_value"]
    return {"params": {"total": 2, "matmul": 0, "active": 2, "held": 2},
            "held": held,
            "train_flops_per_token": lambda seq_len: 0.0,
            "decode_step_flops": lambda batch, live: float(live + 2 * batch),
            "decode_step_bytes": lambda batch, live: float(live * kv),
            "kv_bytes_per_token": kv, "state_bytes_per_sequence": 0}


def build_serving(w, settings, seed):
    import jax.numpy as jnp

    from ray_tpu.serve.engine import EngineConfig, TinyLM

    class DeviceTinyLM(TinyLM):
        kv_pool_ns = jnp          # the pool on the device, as a cell's is

    return {"params": {"hash": jnp.asarray(w["hash"], jnp.float32)},
            "model": DeviceTinyLM(vocab_size=w["vocab_size"]),
            "engine_config": EngineConfig(**settings["engine"])}


def warm_bucket(engine, served, batch, table_blocks):
    # TinyLM compiles nothing, but its write into a device pool is one
    # scatter program for each number of rows.
    block = engine.config.block_size
    engine.cache.mutate_pool(lambda pool: served["model"].decode_paged(
        pool, [[0] * table_blocks] * batch, [2] * batch, [0] * batch,
        [0] * batch, list(range(batch)), block))


def drive(engine, served, tokens, steps, sid):
    # TinyLM takes the three calls of every engine model: the default
    # family's drive through the cache serves it as it stands.
    import numpy as np

    from benchmarks.harness import manifest

    got, tokens = manifest.load_family().drive(engine, served, tokens,
                                               steps, sid)
    return [(row > -1.0).astype(np.float32) for row in got], tokens


def decode_step_rows_and_live(args, kwargs):
    positions = args[3]
    return len(positions), sum(int(p) for p in positions)


def reference_logits(w):
    import numpy as np

    def logits(params, tokens):
        a, b = (int(x) for x in np.asarray(params["hash"]))
        tokens = [int(t) for t in tokens]
        out = np.zeros((len(tokens), w["vocab_size"]), np.float32)
        for pos, last in enumerate(tokens):
            h = sum(tokens[:pos]) + a * last + b * pos
            out[pos, 2 + h % (w["vocab_size"] - 2)] = 1.0
        return out
    return logits
'''


@pytest.fixture
def cluster():
    import ray_tpu

    ray_tpu.init(num_cpus=8, ignore_reinit_error=True)
    yield ray_tpu
    ray_tpu.shutdown()


CELL_NAME = "tiny-32.serve.tiny-mix"


def _tree_with_a_second_family(tmp_path, family_source=TINY_FAMILY):
    """A copy of the benchmark with the second family added as files and
    appended entries; returns its root and the digest of what was there."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmarks"),
                    os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    before = _digest(root)

    bdir = os.path.join(root, "benchmarks")
    with open(os.path.join(bdir, "families", "tiny-oracle.py"), "w") as f:
        f.write(family_source)
    _write(bdir, "configs/tiny-32.json", {
        "source": "https://example.org/tiny", "family": "tiny-oracle",
        "model_type": "tiny_oracle", "vocab_size": 128, "vocab_shards": 4,
        "hash": [7, 3], "reduced": ["vocab_shards"],
        "published": {"vocab_shards": 1},
        "stands_for": "one of 4 chips that share the vocabulary"})
    _write(bdir, "traffic/serve.tiny-mix.json", {
        "kind": "serve_closed", "clients": 2, "requests": 12,
        "prompt_len": {"dist": "uniform", "min": 16, "max": 32, "step": 16},
        "output_len": {"dist": "uniform", "min": 4, "max": 8, "step": 1},
        "drain_s": 20, "schedule_seed": 3})
    cell_name = CELL_NAME
    _write(bdir, f"cells/{cell_name}.json", {
        "config": "tiny-32", "traffic": "serve.tiny-mix", "chips": 1,
        "why": "a cell added as files", "forced_today": "nothing",
        "engine": {"paged_decode": True, "max_batch_size": 2,
                   "block_size": 16, "num_blocks": 16},
        "max_seq_len": 64, "check_prompts": [16, 21],
        "check_decode_steps": 2, "trace_seconds": 0.5})
    with open(os.path.join(bdir, "layer_metrics", "tiny_step_bytes.py"),
              "w") as f:
        f.write("def read(ctx):\n"
                "    if not ctx.get('counters', {}).get('paged_steps'):\n"
                "        return None\n"
                "    return ctx['counts']['decode_step_bytes'](2, 40)\n")
    m = manifest.load_manifest(root)
    m["configs"].append({
        "name": "tiny-32", "source": "https://example.org/tiny",
        "file": "benchmarks/configs/tiny-32.json",
        "reduced": ["vocab_shards"], "why": "a second family as files"})
    m["workloads"].append({
        "name": cell_name, "config": "tiny-32",
        "traffic": "serve.tiny-mix", "chips": 1, "why": "as files"})
    for metric in m["end_to_end"]:
        if metric["name"] in ("serve_out_tokens_per_s", "serve_itl_p99_ms"):
            metric["workloads"].append(cell_name)
    m["per_layer"].append({
        "name": "tiny_step_bytes", "unit": "bytes", "better": "lower",
        "source": "program_counter", "layer": "Model step, decode",
        "moves": "serve_out_tokens_per_s", "workloads": [cell_name]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(m, f)

    return root, before


def _run_cell(root, trace):
    import sys
    import time

    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    try:
        import run as bench_run
    finally:
        sys.path.pop(0)
    return bench_run.run_cell(CELL_NAME, 2 ** 31 + 5, 1.5, trace,
                              time.time(), expect_platform="cpu", root=root)


@pytest.mark.cluster
def test_a_config_a_mix_a_cell_and_a_layer_metric_are_added_as_files(
        tmp_path, cluster):
    """And an architecture: a second family, with a configuration of it
    (reduced by other keys than depth), a mix, a cell and a reader of
    `ctx["counts"]`, as files and appended entries only; then the cell is
    run end to end on the CPU."""
    root, before = _tree_with_a_second_family(tmp_path)
    cell_name = CELL_NAME
    assert manifest.problems(root) == []
    cell = manifest.load_cell(cell_name, root)
    assert cell["family"] == "tiny-oracle"
    assert cell["widths"] == {"vocab_size": 32, "hash": [7, 3]}
    assert cell["traffic"]["clients"] == 2
    assert [p["name"] for p in cell["per_layer"]] == ["tiny_step_bytes"]
    for name in cell["config"]["reduced"]:
        assert name in cell["config"]["published"]
    # The default family refuses that configuration.
    with pytest.raises(KeyError):
        manifest.load_family().widths(cell["config"])
    ctx = {"counters": {"paged_steps": 7},
           "counts": manifest.load_family("tiny-oracle", root).counts(
               cell["widths"])}
    got = manifest.read_layer_metrics(cell, ctx)
    assert got == {"tiny_step_bytes": {"value": 160.0, "unit": "bytes"}}
    # A reader that finds nothing to read leaves its metric out.
    assert manifest.read_layer_metrics(cell, {"counters": {}}) == {}

    # The cell, end to end: serve.run, the replica, the engine around a
    # model that is not the default family's, the check, the window.
    for trace in (False, True):
        result = _run_cell(root, trace)
        assert result["correct"], result
        assert result["failed"] == 0 and result["attempted"] >= 4
        assert result["checks"]["logit_rms_gap"] == [0.0, 0.0]
        assert list(result)[-1] == "checks"
        if trace:
            assert result["metrics"] == {"tiny_step_bytes": {
                "value": 160.0, "unit": "bytes"}}
        else:
            assert result["metrics"]["serve_out_tokens_per_s"]["value"] > 0
            assert set(result["metrics"]) == {
                "serve_out_tokens_per_s", "serve_itl_p99_ms", "setup_s"}
    # Not one file that was there has changed (BENCHMARK.json gains the
    # entries: that is the "one entry").
    after = _digest(root)
    assert {k: v for k, v in after.items() if k in before
            and k != "BENCHMARK.json"} == \
        {k: v for k, v in before.items() if k != "BENCHMARK.json"}
    assert len(after) == len(before) + 5


@pytest.mark.cluster
def test_a_decode_step_that_alters_its_tokens_comes_out_not_correct(
        tmp_path, cluster):
    """The rest of a run, with the timed path broken underneath: the
    same cell, its model's decode step putting every row's token one id
    off where it is produced. The prefill rows still agree; `correct`
    comes out false on the decode rows."""
    broken = TINY_FAMILY.replace(
        "        kv_pool_ns = jnp          # the pool on the device, as a "
        "cell's is\n",
        "        kv_pool_ns = jnp\n\n"
        "        def decode(self, kvs, last_tokens, positions):\n"
        "            import numpy as np\n\n"
        "            logits, kv = super().decode(kvs, last_tokens, "
        "positions)\n"
        "            return np.roll(logits, 1, axis=1), kv\n")
    assert broken != TINY_FAMILY
    root, _ = _tree_with_a_second_family(tmp_path, broken)
    result = _run_cell(root, False)
    assert result["correct"] is False
    gap, limit = result["checks"]["logit_rms_gap"]
    assert gap > limit == 0.0
    assert result["failed"] == 0 and result["attempted"] >= 4


def _write(bdir, rel, obj):
    with open(os.path.join(bdir, rel), "w") as f:
        json.dump(obj, f)


def _digest(root):
    import hashlib

    out = {}
    for base, dirs, files in os.walk(root):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in files:
            path = os.path.join(base, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out
