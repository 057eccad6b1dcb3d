"""The benchmark's cells at toy widths, for the CPU tests: the same data
files, with the sizes turned down. The cells come from the manifest and
the toy widths from each cell's family, so a cell added later is run at
toy size without an edit here."""

import copy

from benchmarks.harness import manifest

CELLS = [w["name"] for w in manifest.load_manifest()["workloads"]]


def cells_of(*kinds: str) -> list:
    """The manifest's cells whose traffic is of one of `kinds`."""
    return [name for name in CELLS
            if manifest.load_cell(name)["traffic"]["kind"] in kinds]


def cell(name: str) -> dict:
    out = copy.deepcopy(manifest.load_cell(name))
    out["widths"] = manifest.family_of(out).toy_widths(out["widths"])
    traffic, settings = out["traffic"], out["settings"]
    if traffic["kind"] == "train":
        traffic.update(seq_len=64, global_batch=8, dataset_steps=8)
        settings["trace_steps"] = 2
        return out
    if traffic["prompt_len"]["dist"] == "lognormal":
        traffic["prompt_len"].update(min=16, max=48, median=24)
        traffic["output_len"].update(min=4, max=12, median=6)
    else:
        traffic["prompt_len"].update(min=16, max=32)
        traffic["output_len"].update(min=8, max=12)
    traffic.update(rate_per_s=4.0, drain_s=30)
    settings["engine"]["num_blocks"] = 64
    settings.update(max_seq_len=128, check_prompts=[16, 40],
                    trace_seconds=0.5)
    return out
