"""The `gigachat3_5` family's check alone, with the controls that must
come out not correct, at the widths of its cell: what the limits of
`gigachat3_5.py` (`POSITIONS_TOLERANCE`, `STATE_TOLERANCE`,
`LATENT_TOLERANCE`) were set from, to be read again on the chip whenever the program's arithmetic or
the seeding changes. No cell runs this file and no metric reads it.

    python3 benchmarks/families/gigachat3_5_controls.py --seeds 11,12,13 \\
        [--lengths 200,2304] [--controls latent_pool_fp8,experts_fp8,\\
        no_output_gate,no_decay] [--toy 1]

A seed: the family's serving model and an engine over it in this process
(no cluster), then a drive a prompt length (`gigachat3_5.drive`: prefill
whole or in chunks as the scheduler makes them, the check's greedy steps)
held to `own_limits`, sound and under each control:

- ``no_output_gate``, ``no_decay``: the REFERENCE lacks the mechanism
  (`served["reference_widths"]`: ``without``); the engine is the sound
  one.
- ``latent_pool_fp8``, ``experts_fp8``: the ENGINE at the nearest
  precision below the stated one: the latent and the rotary key rounded
  to fp8's three mantissa bits before they are stored and attended, or
  the routed and shared experts' outputs' operands so rounded (by the
  bits: the chip's compiler folds a round trip through a narrower dtype
  away).

A seed runs in a process of its own (the weights and both pools are two
thirds of the chip: a second build beside what the first left cannot be
placed), one after the other; this process stays off JAX unless it is
given one seed. One JSON line a drive on standard output (least, median and worst of the
positions' gaps, the state's and the latent rows' gaps, `ok`), and all of them in
``chiprun_out/gigachat3_5_controls.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

CELL = "gigachat3.5-432b-a28b.serve.long-reason"
REFERENCE_CONTROLS = {
    "no_output_gate": lambda w: dict(w, without=["output_gate"]),
    "no_decay": lambda w: dict(w, without=["decay"]),
}
ENGINE_CONTROLS = {"latent_pool_fp8": "_latent_inputs",
                   "experts_fp8": "_experts_of"}


def lower_precision(model, piece: str):
    """Have `model` round what `piece` hands on (``_latent_inputs``: the
    latent and the rotary key; ``_experts_of``: the expert layer's normed
    input), and forget its compiled programs. Returns the call that
    undoes it."""
    from benchmarks.families.keye_vl2_controls import to_fp8_mantissa
    from ray_tpu.serve.engine.model import _JitLRU

    sound = getattr(model, piece)

    def rounded(*args):
        if piece == "_experts_of":              # (y, mp, valid)
            return sound(to_fp8_mantissa(args[0]), *args[1:])
        q_nope, q_r, c_kv, k_r = sound(*args)
        return q_nope, q_r, to_fp8_mantissa(c_kv), to_fp8_mantissa(k_r)

    def forget():
        model._prefill_jit, model._decode_paged_jit = _JitLRU(32), _JitLRU(32)

    def undo():
        delattr(model, piece)
        forget()

    setattr(model, piece, rounded)
    forget()
    return undo


def _short(readings: dict) -> dict:
    positions = readings["positions"]
    return {"least": positions[0], "median": positions[len(positions) // 2],
            "worst": positions[-1], "state": readings["state"],
            "latent": readings["latent"],
            "state_bf16_share": readings["state_bf16_share"],
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
    seeds = [int(s) for s in args.seeds.split(",")]
    if len(seeds) > 1:
        return _a_process_a_seed(seeds, argv if argv is not None
                                 else sys.argv[1:])
    family = manifest.load_family("gigachat3_5")
    cell = manifest.load_cell(CELL)
    widths, settings = cell["widths"], cell["settings"]
    if args.toy:        # a CPU's size: the runner itself, not the limits
        widths = family.toy_widths(widths)
        settings = dict(settings, engine=dict(settings["engine"],
                                              num_blocks=64,
                                              max_batch_size=4),
                        check_prompts=[12, 40], check_decode_steps=6)
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

    for seed in seeds:
        began = time.time()
        served = family.build_serving(widths, settings, seed)
        engine = InferenceEngine(served["model"], served["engine_config"])
        rng = np.random.default_rng([seed, 61])
        prompts = [rng.integers(2, widths["vocab_size"], n).tolist()
                   for n in lengths]
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
            for prompt in prompts:
                drive(served, engine, prompt, seed, control)
            undo()
        print(f"seed {seed}: {time.time() - began:.0f} s", file=sys.stderr,
              flush=True)
    return _verdict(lines)


def _verdict(lines: list) -> int:
    """All drives into the file; 0 when every sound drive is inside the
    family's limits and every control outside."""
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/gigachat3_5_controls.json", "w") as f:
        json.dump(lines, f, indent=1)
    sound = [line["ok"] for line in lines if line["control"] == "sound"]
    lacking = [line["ok"] for line in lines if line["control"] != "sound"]
    return 0 if sound and all(sound) and not any(lacking) else 1


def _a_process_a_seed(seeds: list, argv: list) -> int:
    """This file once a seed, each in a child that holds the chip alone;
    the children's lines passed on and gathered."""
    import subprocess

    rest = [a for i, a in enumerate(argv)
            if a != "--seeds" and (i == 0 or argv[i - 1] != "--seeds")
            and not a.startswith("--seeds=")]
    lines = []
    for seed in seeds:
        child = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--seeds", str(seed)]
            + rest, stdout=subprocess.PIPE, text=True)
        for line in child.stdout.splitlines():
            print(line, flush=True)
            if line.startswith("{"):
                lines.append(json.loads(line))
    return _verdict(lines)


if __name__ == "__main__":
    sys.exit(main())
