"""The `keye_vl2` family's check alone, with the controls that must come
out not correct, at the widths of its cell: what the limits of
`keye_vl2.py` (`DRIVE_LIMITS`, `FIRST_LAYER_OVERLAP_LIMIT`,
`SELECTION_OVERLAP_LIMIT`) were set from, to be read again on the chip
whenever the program's arithmetic or the seeding changes. No cell runs
this file and no metric reads it.

    python3 benchmarks/families/keye_vl2_controls.py --seeds 11,12,13 \\
        [--lengths 2304,8448] [--controls every_causal_key,topk_halved,\\
        index_keys_fp8,kv_pool_fp8] [--toy 1]

A seed: the family's serving model and an engine over it in this process
(no cluster), then a drive a prompt length (`keye_vl2.drive`: prefill in
chunks as the scheduler makes them, 20 greedy steps, the last step's
selection probed) held to `own_limits`, sound and under each control:

- ``every_causal_key``, ``topk_halved``: the REFERENCE lacks the
  mechanism (`served["reference_widths"]`: ``without: ["selection"]``,
  `index_topk` halved); the engine is the sound one.
- ``index_keys_fp8``, ``kv_pool_fp8``: the ENGINE at the nearest
  precision below the stated one: the index keys, or the keys and
  values, rounded to fp8's three mantissa bits before they are stored
  and scored (by the bits: the chip's compiler folds a round trip
  through a narrower dtype away).

One JSON line a drive on standard output (least, median and worst of the
positions' gaps, the selections' overlap a layer, `ok`), and all of them
in ``chiprun_out/keye_vl2_controls.json``. About 5 chip-minutes a seed
with all four controls at both lengths past `index_topk`.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

CELL = "keye-vl-2.0-30b-a3b.serve.long-doc"
REFERENCE_CONTROLS = {
    "every_causal_key": lambda w: dict(w, without=["selection"]),
    "topk_halved": lambda w: dict(w, index_topk=w["index_topk"] // 2),
}
ENGINE_CONTROLS = {"index_keys_fp8": "_index", "kv_pool_fp8": "_qkv"}


def to_fp8_mantissa(x):
    """float32 values rounded (to nearest, ties to even) to three
    mantissa bits, by the bits."""
    import jax
    import jax.numpy as jnp

    u = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    u = ((u + jnp.uint32(0x0007FFFF) + ((u >> 20) & jnp.uint32(1)))
         & jnp.uint32(0xFFF00000))
    return jax.lax.bitcast_convert_type(u, jnp.float32)


def lower_precision(model, piece: str):
    """Have `model` round what `piece` (``_index``: the index key,
    ``_qkv``: keys and values) hands the pools, and forget its compiled
    programs. Returns the call that undoes it."""
    from ray_tpu.serve.engine.model import _JitLRU

    sound = getattr(model, piece)

    def rounded(*args):
        a, b, c = sound(*args)
        if piece == "_index":                  # (queries, key, weights)
            return a, to_fp8_mantissa(b), c
        return a, to_fp8_mantissa(b), to_fp8_mantissa(c)    # (q, k, v)

    def forget():
        model._prefill_jit, model._decode_paged_jit = _JitLRU(32), _JitLRU(32)

    def undo():
        delattr(model, piece)
        forget()

    setattr(model, piece, rounded)
    forget()
    return undo


def _short(readings: dict) -> dict:
    return {"least": readings["positions"][0], "median": readings["median"],
            "worst": readings["positions"][-1], "limits": readings["limits"],
            "first_layer_overlap": readings["first_layer_overlap"],
            "selection_overlap": readings["selection_overlap"],
            "by_layer": [round(x, 4) for x in
                         readings["selection_overlap_by_layer"]],
            "ok": readings["ok"]}


def main(argv=None) -> int:
    import numpy as np

    from benchmarks.harness import manifest
    from ray_tpu.serve.engine import InferenceEngine

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--lengths", default=None)
    parser.add_argument("--controls", default=",".join(
        list(REFERENCE_CONTROLS) + list(ENGINE_CONTROLS)))
    parser.add_argument("--toy", type=int, default=0)
    args = parser.parse_args(argv)
    family = manifest.load_family("keye_vl2")
    cell = manifest.load_cell(CELL)
    widths, settings = cell["widths"], cell["settings"]
    if args.toy:        # a CPU's size: the runner itself, not the limits
        widths = family.toy_widths(widths)
        settings = dict(settings, engine=dict(settings["engine"],
                                              num_blocks=64),
                        check_prompts=[12, 40])
    lengths = ([int(n) for n in args.lengths.split(",")] if args.lengths
               else settings["check_prompts"])
    controls = [c for c in args.controls.split(",") if c]
    steps = settings["check_decode_steps"]
    lines = []

    def drive(served, engine, prompt, seed, control):
        family.drive(engine, served, prompt, steps, f"{control}-{len(prompt)}")
        line = dict(seed=seed, n=len(prompt), control=control,
                    **_short(served["own_limits"][-1]))
        lines.append(line)
        print(json.dumps(line), flush=True)

    for seed in (int(s) for s in args.seeds.split(",")):
        began = time.time()
        served = family.build_serving(widths, settings, seed)
        engine = InferenceEngine(served["model"], served["engine_config"])
        rng = np.random.default_rng([seed, 57])
        prompts = [rng.integers(2, widths["vocab_size"], n).tolist()
                   for n in lengths]
        selecting = [p for p in prompts if len(p) > widths["index_topk"]]
        for prompt in prompts:
            drive(served, engine, prompt, seed, "sound")
        for control in controls:
            if control in REFERENCE_CONTROLS:
                served["reference_widths"] = \
                    REFERENCE_CONTROLS[control](widths)
                undo = lambda: served.pop("reference_widths")  # noqa: E731
            else:
                undo = lower_precision(served["model"],
                                       ENGINE_CONTROLS[control])
            for prompt in selecting:
                drive(served, engine, prompt, seed, control)
            undo()
        print(f"seed {seed}: {time.time() - began:.0f} s", file=sys.stderr,
              flush=True)
        del engine, served
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/keye_vl2_controls.json", "w") as f:
        json.dump(lines, f, indent=1)
    sound = [line["ok"] for line in lines if line["control"] == "sound"]
    lacking = [line["ok"] for line in lines if line["control"] != "sound"]
    return 0 if all(sound) and not any(lacking) else 1


if __name__ == "__main__":
    sys.exit(main())
