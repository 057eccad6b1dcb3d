"""BENCHMARK.json and the data files it names, found by name.

A later PR adds an architecture, a configuration, a traffic mix, a cell
or a per-layer metric as new files plus one entry in BENCHMARK.json;
nothing here (or anywhere else under `benchmarks/`) is edited for it:

    benchmarks/families/<family>.py         what one architecture runs
    benchmarks/configs/<config>.json        the sizes as they are run
    benchmarks/traffic/<mix>.json           kind + parameters of one mix
    benchmarks/cells/<cell>.json            configuration, mix, chips, settings
    benchmarks/layer_metrics/<metric>.py    `read(ctx) -> number | None`

This module never imports JAX.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import Callable, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
KINDS = {"serve_open", "serve_closed", "train"}
# The family of a configuration that names none: the block the benchmark
# began with (`families/dense.py`).
DEFAULT_FAMILY = "dense"


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_manifest(root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def bench_dir(root: str = ROOT) -> str:
    return os.path.join(root, "benchmarks")


_FAMILIES: Dict[str, object] = {}


def load_family(name: str = DEFAULT_FAMILY, root: str = ROOT):
    """The module benchmarks/families/<name>.py: what one architecture
    runs, its reference, counts, tolerances and drive (the names are in
    benchmarks/README.md). Loaded once a process; its top level imports
    neither JAX nor the program."""
    if not NAME_RE.match(name):
        raise ValueError(f"bad family name {name!r}")
    path = os.path.join(bench_dir(root), "families", f"{name}.py")
    if path not in _FAMILIES:
        spec = importlib.util.spec_from_file_location(
            f"bench_family_{re.sub(r'[^A-Za-z0-9_]', '_', name)}", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        _FAMILIES[path] = module
    return _FAMILIES[path]


def family_of(cell: dict):
    """The family module of a cell that `load_cell` resolved."""
    return load_family(cell["family"], cell["root"])


def model_widths(config: dict, root: str = ROOT) -> dict:
    """The widths the configuration's family makes of its published keys:
    opaque here but for `vocab_size`, from which the load generator draws
    token ids. What the family cannot run it refuses."""
    return load_family(config.get("family", DEFAULT_FAMILY),
                       root).widths(config)


def load_cell(name: str, root: str = ROOT) -> dict:
    """Everything one run needs, resolved by name from BENCHMARK.json."""
    manifest = load_manifest(root)
    entry = next((w for w in manifest["workloads"] if w["name"] == name),
                 None)
    if entry is None:
        raise KeyError(f"BENCHMARK.json has no workload {name!r}; it has "
                       f"{[w['name'] for w in manifest['workloads']]}")
    bdir = bench_dir(root)
    config_entry = next(c for c in manifest["configs"]
                        if c["name"] == entry["config"])
    settings = _load_json(os.path.join(bdir, "cells", f"{name}.json"))
    for key in ("config", "traffic", "chips"):
        if settings[key] != entry[key]:
            raise ValueError(f"cells/{name}.json says {key}="
                             f"{settings[key]!r}, BENCHMARK.json says "
                             f"{entry[key]!r}")
    config = _load_json(os.path.join(root, config_entry["file"]))
    traffic = _load_json(os.path.join(bdir, "traffic",
                                      f"{entry['traffic']}.json"))
    if traffic["kind"] not in KINDS:
        raise ValueError(f"traffic {entry['traffic']!r}: unknown kind "
                         f"{traffic['kind']!r}")

    def reported_here(metric: dict) -> bool:
        return "workloads" not in metric or name in metric["workloads"]

    return {
        "name": name, "chips": entry["chips"], "settings": settings,
        "root": root, "config_name": entry["config"], "config": config,
        "family": config.get("family", DEFAULT_FAMILY),
        "widths": model_widths(config, root),
        "traffic_name": entry["traffic"], "traffic": traffic,
        "end_to_end": [m for m in manifest["end_to_end"]
                       if reported_here(m)],
        "per_layer": [m for m in manifest["per_layer"] if reported_here(m)],
        "peaks": _load_json(os.path.join(bdir, "harness", "peaks.json")),
    }


def load_reader(metric_name: str, root: str = ROOT
                ) -> Callable[[dict], Optional[float]]:
    """`read` of benchmarks/layer_metrics/<metric_name>.py."""
    path = os.path.join(bench_dir(root), "layer_metrics",
                        f"{metric_name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_layer_metric_{re.sub(r'[^A-Za-z0-9_]', '_', metric_name)}",
        path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def read_layer_metrics(cell: dict, ctx: dict, root: Optional[str] = None
                       ) -> Dict[str, dict]:
    """Every per-layer metric of the cell whose reader found something
    to read; a reader that returns None leaves its metric out."""
    root = root or cell.get("root", ROOT)
    out = {}
    for metric in cell["per_layer"]:
        value = load_reader(metric["name"], root)(ctx)
        if value is not None:
            out[metric["name"]] = {"value": float(value),
                                   "unit": metric["unit"]}
    return out


def problems(root: str = ROOT) -> List[str]:
    """What the contract would refuse, as far as names and files go."""
    manifest = load_manifest(root)
    bad: List[str] = []
    want = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    if set(manifest) != want:
        bad.append(f"keys {sorted(manifest)} != {sorted(want)}")
    names: Dict[str, List[str]] = {}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in manifest[group]:
            names.setdefault(group, []).append(entry["name"])
            if not NAME_RE.match(entry["name"]):
                bad.append(f"{group}: bad name {entry['name']!r}")
    for group, seen in names.items():
        if len(set(seen)) != len(seen):
            bad.append(f"{group}: duplicate names")
    if set(names["end_to_end"]) & set(names["per_layer"]):
        bad.append("a metric name is both end-to-end and per-layer")
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    if "setup_s" not in e2e:
        bad.append("no setup_s")
    cells = {w["name"]: w for w in manifest["workloads"]}

    def cells_of(metric: dict) -> set:
        return set(metric.get("workloads", cells))

    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if not UNIT_RE.match(metric["unit"]):
            bad.append(f"{metric['name']}: bad unit {metric['unit']!r}")
        if metric["better"] not in ("lower", "higher"):
            bad.append(f"{metric['name']}: better={metric['better']!r}")
        if metric["source"] not in SOURCES:
            bad.append(f"{metric['name']}: source={metric['source']!r}")
        if not cells_of(metric) <= set(cells):
            bad.append(f"{metric['name']}: unknown workload listed")
    for metric in manifest["end_to_end"]:
        if metric["source"] not in ("host_clock", "device_trace"):
            bad.append(f"{metric['name']}: end-to-end source")
        if not 0 < metric["bound"] <= 0.1:
            bad.append(f"{metric['name']}: bound {metric['bound']}")
    for metric in manifest["per_layer"]:
        moved = e2e.get(metric["moves"])
        if moved is None:
            bad.append(f"{metric['name']}: moves unknown "
                       f"{metric['moves']!r}")
        elif not cells_of(metric) <= cells_of(moved):
            bad.append(f"{metric['name']}: reported where "
                       f"{metric['moves']} is not")
        reader = os.path.join(bench_dir(root), "layer_metrics",
                              f"{metric['name']}.py")
        if not os.path.isfile(reader):
            bad.append(f"{metric['name']}: no reader file")
    pairs = set()
    config_names = set(names["configs"])
    for cell in manifest["workloads"]:
        if cell["config"] not in config_names:
            bad.append(f"{cell['name']}: unknown config")
        if (cell["config"], cell["traffic"]) in pairs:
            bad.append(f"{cell['name']}: pair appears twice")
        pairs.add((cell["config"], cell["traffic"]))
        if cell["chips"] not in (1, 4):
            bad.append(f"{cell['name']}: chips={cell['chips']}")
        if not 1 <= len(cell["why"]) <= 200:
            bad.append(f"{cell['name']}: why has {len(cell['why'])} chars")
        if not NAME_RE.match(cell["traffic"]):
            bad.append(f"{cell['name']}: bad traffic name")
        try:
            load_cell(cell["name"], root)
        except Exception as e:  # noqa: BLE001 — listed, not raised
            bad.append(f"{cell['name']}: {type(e).__name__}: {e}")
        reported = [m for m in manifest["end_to_end"]
                    if cell["name"] in cells_of(m)]
        if len(reported) < 2 or not any(
                cell["name"] in cells_of(m)
                for m in manifest["per_layer"]):
            bad.append(f"{cell['name']}: needs setup_s, one more "
                       f"end-to-end and one per-layer metric")
    four = sum(1 for c in manifest["workloads"] if c["chips"] == 4)
    if four > max(1, len(manifest["workloads"]) // 4):
        bad.append(f"{four} four-chip cells")
    used = {c["config"] for c in manifest["workloads"]}
    for config in manifest["configs"]:
        if config["name"] not in used:
            bad.append(f"config {config['name']} has no cell")
        if not any(config["file"].startswith(p.rstrip("/") + "/")
                   for p in manifest["paths"]):
            bad.append(f"config {config['name']}: file outside paths")
    return bad
