"""The `minicpm_sala` family's check alone, with the controls that must
come out not correct, at the widths of its cell: what the limits of
`minicpm_sala.py` (`POSITIONS_TOLERANCE`, `KV_TOLERANCE`,
`STATE_TOLERANCE`, `STATE_BF16_SHARE`, `FIRST_LAYER_OVERLAP_LIMIT`,
`SELECTION_OVERLAP_LIMIT`) were set from, to be read again on the chip
whenever the program's arithmetic or the seeding changes. No cell runs
this file and no metric reads it.

    python3 benchmarks/families/minicpm_sala_controls.py --seeds 11,12 \\
        [--lengths 9216,34816] [--controls every_causal_block,\\
        topk_halved,decay_dropped,kv_pool_fp8,state_bf16,\
        lightning_products_bf16] [--toy 1]

A seed: the family's serving model and an engine over it in this process
(no cluster), then a drive a prompt length (`minicpm_sala.drive`: prefill
in chunks as the scheduler makes them, 20 greedy steps, the last step's
selection probed) held to `own_limits`, sound and under each control:

- ``every_causal_block``, ``topk_halved``, ``decay_dropped``: the
  REFERENCE lacks the mechanism (`served["reference_widths"]`: ``without:
  ["selection"]``, `topk` halved, ``without: ["decay"]``: lambda = 1);
  the engine is the sound one.
- ``kv_pool_fp8``, ``state_bf16``, ``lightning_products_bf16``: the
  ENGINE at the nearest precision below the stated one: the keys and
  values rounded to fp8's three mantissa bits before they are stored,
  compressed and attended; the lightning state rounded to bf16's seven
  after every update (by the bits: the chip's compiler folds a round
  trip through a narrower dtype away); the lightning layers' own
  products with both operands in bf16 (the chunk's at the default
  precision, which on the chip is one bf16 pass; the step's q, k and v
  rounded by the bits), the state itself still float32, which the bit
  test cannot see and `LONG_STATE_TOLERANCE` has to.
- ``compressed_scores_fp8`` (not among the defaults: a MEASUREMENT, not
  held to come out not correct): the compressed keys' scores with both
  operands at fp8's mantissa. It moves a block or two of a drive's 97
  across the threshold, which is what a sound drive's rounding does
  too (PERF.md 7): the overlap of one query's selection cannot tell it.

One JSON line a drive on standard output (least, median and worst of the
positions' gaps, the state's and the rows' gaps, the selections' overlap
a layer, `ok`), and all of them in
``chiprun_out/minicpm_sala_controls.json``.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

CELL = "minicpm-sala.serve.long-context"
REFERENCE_CONTROLS = {
    "every_causal_block": lambda w: dict(w, without=["selection"]),
    "topk_halved": lambda w: dict(w, topk=w["topk"] // 2),
    "decay_dropped": lambda w: dict(w, without=["decay"]),
}
ENGINE_CONTROLS = ("kv_pool_fp8", "state_bf16", "lightning_products_bf16")
MEASURED_ALONE = ("compressed_scores_fp8",)


def keep_mantissa(x, bits: int):
    """float32 values rounded (to nearest, ties to even) to `bits`
    mantissa bits, by the bits."""
    import jax
    import jax.numpy as jnp

    drop = 23 - bits
    u = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    u = ((u + jnp.uint32((1 << (drop - 1)) - 1) + ((u >> drop)
                                                    & jnp.uint32(1)))
         & jnp.uint32((0xFFFFFFFF >> drop) << drop))
    return jax.lax.bitcast_convert_type(u, jnp.float32)


def lower_precision(model, control: str):
    """Have `model` compute at the precision below the stated one and
    forget its compiled programs. Returns the call that undoes it."""
    import jax

    from ray_tpu.ops import block_sparse_attention, lightning_attention
    from ray_tpu.serve.engine.model import _JitLRU

    def forget():
        model._prefill_jit, model._decode_paged_jit = _JitLRU(32), _JitLRU(32)

    if control == "kv_pool_fp8":
        sound = model._sparse_qkv

        def rounded(y, mp):
            q, k, v = sound(y, mp)
            return q, keep_mantissa(k, 3), keep_mantissa(v, 3)

        model._sparse_qkv = rounded

        def undo():
            del model._sparse_qkv
            forget()
    elif control == "compressed_scores_fp8":
        scores = block_sparse_attention.compressed_scores

        def at_fp8(q, ck, n_valid):
            return scores(keep_mantissa(q, 3).astype(ck.dtype),
                          keep_mantissa(ck, 3).astype(ck.dtype), n_valid)

        block_sparse_attention.compressed_scores = at_fp8

        def undo():
            block_sparse_attention.compressed_scores = scores
            forget()
    elif control == "lightning_products_bf16":
        step, highest = (lightning_attention.lightning_step_in_pool,
                         lightning_attention._HIGHEST)

        def step_on_bf16(pool, layer, q, k, v, g):
            return step(pool, layer,
                        *(keep_mantissa(x, 7) for x in (q, k, v)), g)

        lightning_attention.lightning_step_in_pool = step_on_bf16
        lightning_attention._HIGHEST = jax.lax.Precision.DEFAULT

        def undo():
            lightning_attention.lightning_step_in_pool = step
            lightning_attention._HIGHEST = highest
            forget()
    else:
        step, chunked = (lightning_attention.lightning_step_in_pool,
                         lightning_attention.lightning_chunked)

        def with_state_in_bf16(fn):
            # (The step hands back the whole pool: rounding a state that
            # is rounded leaves it.)
            def run(*args, **kwargs):
                o, state = fn(*args, **kwargs)
                return o, keep_mantissa(state, 7)
            return run

        lightning_attention.lightning_step_in_pool = with_state_in_bf16(step)
        lightning_attention.lightning_chunked = with_state_in_bf16(chunked)

        def undo():
            lightning_attention.lightning_step_in_pool = step
            lightning_attention.lightning_chunked = chunked
            forget()
    forget()
    return undo


def _short(readings: dict) -> dict:
    return {"least": readings["positions"][0], "median": readings["median"],
            "worst": readings["positions"][-1], "state": readings["state"],
            "kv": readings["kv"],
            "state_bf16_share": readings["state_bf16_share"],
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
    family = manifest.load_family("minicpm_sala")
    cell = manifest.load_cell(CELL)
    widths, settings = cell["widths"], cell["settings"]
    if args.toy:        # a CPU's size: the runner itself, not the limits
        widths = family.toy_widths(widths)
        settings = dict(settings, engine=dict(settings["engine"],
                                              num_blocks=64),
                        max_seq_len=256, check_prompts=[12, 100])
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
        rng = np.random.default_rng([seed, 63])
        prompts = [rng.integers(2, widths["vocab_size"], n).tolist()
                   for n in lengths]
        selecting = [p for p in prompts if len(p) > widths["dense_len"]]
        for prompt in prompts:
            drive(served, engine, prompt, seed, "sound")
        for control in controls:
            if control not in (*REFERENCE_CONTROLS, *ENGINE_CONTROLS,
                               *MEASURED_ALONE):
                raise SystemExit(f"no control {control!r}")
            if control in REFERENCE_CONTROLS:
                served["reference_widths"] = \
                    REFERENCE_CONTROLS[control](widths)
                undo = lambda: served.pop("reference_widths")  # noqa: E731
            else:
                undo = lower_precision(served["model"], control)
            for prompt in selecting:
                drive(served, engine, prompt, seed, control)
            undo()
            del undo        # it holds the model, and so the weights
        print(f"seed {seed}: {time.time() - began:.0f} s", file=sys.stderr,
              flush=True)
        del engine, served      # the next seed's weights need the room
        gc.collect()
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/minicpm_sala_controls.json", "w") as f:
        json.dump(lines, f, indent=1)
    sound = [line["ok"] for line in lines if line["control"] == "sound"]
    lacking = [line["ok"] for line in lines
               if line["control"] not in ("sound", *MEASURED_ALONE)]
    return 0 if all(sound) and not any(lacking) else 1


if __name__ == "__main__":
    sys.exit(main())
