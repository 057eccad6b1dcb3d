"""The benchmark's cells at toy widths, for the CPU tests: the same data
files, with the sizes turned down."""

import copy

from benchmarks.harness import manifest

WIDTHS = dict(vocab_size=512, d_model=64, n_layers=2, n_heads=4, d_ff=128,
              rope_theta=10000.0)


def cell(name: str) -> dict:
    out = copy.deepcopy(manifest.load_cell(name))
    out["widths"] = dict(WIDTHS)
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
