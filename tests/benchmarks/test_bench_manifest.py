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
    assert cell["widths"]["d_model"] == 2048     # both published widths
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


def test_a_config_a_mix_a_cell_and_a_layer_metric_are_added_as_files(tmp_path):
    root = str(tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmarks"),
                    os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    before = _digest(root)

    bdir = os.path.join(root, "benchmarks")
    config = dict(manifest.load_cell("olmo-1b.serve.chat-steady")["config"],
                  num_hidden_layers=4, reduced=["num_hidden_layers"],
                  published={"num_hidden_layers": 16})
    _write(bdir, "configs/dummy-model.json", config)
    _write(bdir, "traffic/serve.dummy-mix.json", {
        "kind": "serve_closed", "clients": 2, "requests": 4,
        "prompt_len": {"dist": "fixed", "value": 32},
        "output_len": {"dist": "fixed", "value": 8}, "drain_s": 5})
    _write(bdir, "cells/dummy-model.serve.dummy-mix.json", {
        "config": "dummy-model", "traffic": "serve.dummy-mix", "chips": 1,
        "why": "a cell added as files", "forced_today": "nothing",
        "engine": {"paged_decode": True, "max_batch_size": 2,
                   "block_size": 16, "num_blocks": 8},
        "max_seq_len": 64, "check_prompts": [32], "check_decode_steps": 1,
        "trace_seconds": 1})
    with open(os.path.join(bdir, "layer_metrics", "dummy_steps.py"),
              "w") as f:
        f.write("def read(ctx):\n    return ctx['counters'].get('steps')\n")
    m = manifest.load_manifest(root)
    m["configs"].append({
        "name": "dummy-model", "source": "https://example.org/dummy",
        "file": "benchmarks/configs/dummy-model.json",
        "reduced": ["num_hidden_layers"], "why": "a configuration as a file"})
    m["workloads"].append({
        "name": "dummy-model.serve.dummy-mix", "config": "dummy-model",
        "traffic": "serve.dummy-mix", "chips": 1, "why": "as files"})
    for metric in m["end_to_end"]:
        if metric["name"] in ("serve_out_tokens_per_s", "serve_itl_p99_ms"):
            metric["workloads"].append("dummy-model.serve.dummy-mix")
    m["per_layer"].append({
        "name": "dummy_steps", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "Engine scheduler",
        "moves": "serve_out_tokens_per_s",
        "workloads": ["dummy-model.serve.dummy-mix"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(m, f)

    assert manifest.problems(root) == []
    cell = manifest.load_cell("dummy-model.serve.dummy-mix", root)
    assert cell["widths"]["n_layers"] == 4
    assert cell["traffic"]["clients"] == 2
    assert [p["name"] for p in cell["per_layer"]] == ["dummy_steps"]
    got = manifest.read_layer_metrics(cell, {"counters": {"steps": 7}}, root)
    assert got == {"dummy_steps": {"value": 7.0, "unit": "count"}}
    # A reader that finds nothing to read leaves its metric out.
    assert manifest.read_layer_metrics(cell, {"counters": {}}, root) == {}
    # Not one file that was there has changed (BENCHMARK.json gains the
    # entries: that is the "one entry").
    after = _digest(root)
    assert {k: v for k, v in after.items() if k in before
            and k != "BENCHMARK.json"} == \
        {k: v for k, v in before.items() if k != "BENCHMARK.json"}


def _write(bdir, rel, obj):
    with open(os.path.join(bdir, rel), "w") as f:
        json.dump(obj, f)


def _digest(root):
    import hashlib

    out = {}
    for base, _dirs, files in os.walk(root):
        for name in files:
            path = os.path.join(base, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out
