"""One run of one benchmark cell.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the repo root, on a machine that holds the chips the cell asks for.
A new process each run: it starts the cluster (`ray_tpu.init`), never
imports JAX itself, and exits non-zero with no result line when the
process that owns the chips reports anything but a TPU. The last line of
its standard output is the result: the cell's end-to-end metrics with
`--trace 0`, its per-layer metrics, the device's busy time and a
breakdown with `--trace 1`.

`--sweep-rates a,b,c` (an open-loop serving cell only) runs one window per
rate on one replica and prints what each gave: how a knee is found when a
cell is defined. It prints no result line.
"""

import time

T0 = time.time()          # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t0: float, expect_platform: str = "tpu",
             sweep_rates: tuple = (), root: str = ROOT) -> dict:
    """Run the cell on a cluster that is already up and return the
    result object (the tests call this with `expect_platform="cpu"`, and
    with the `root` of a copy they have added files to). `checks`, its
    last key, holds every number that decided `correct` beside its
    limit."""
    from benchmarks.harness import manifest

    cell = manifest.load_cell(workload, root)
    if cell["traffic"]["kind"] == "train":
        from benchmarks.harness import train_cell

        outcome = train_cell.run(cell, seed=seed, seconds=seconds,
                                 trace=trace, t0=t0,
                                 expect_platform=expect_platform)
    else:
        from benchmarks.harness import serve_cell

        outcome = serve_cell.run(cell, seed=seed, seconds=seconds,
                                 trace=trace, t0=t0,
                                 expect_platform=expect_platform,
                                 sweep_rates=sweep_rates)
    if outcome.get("sweep"):
        return outcome
    device = dict(outcome["device"])
    device.pop("memory_stats", None)      # printed on the set-up line
    result = {"correct": outcome["correct"],
              "attempted": outcome["attempted"],
              "failed": outcome["failed"], "device": device}
    if not trace:
        result["metrics"] = {
            m["name"]: {"value": float(outcome["values"][m["name"]]),
                        "unit": m["unit"]}
            for m in cell["end_to_end"]}
    else:
        ctx = outcome["ctx"]
        for name, value in outcome["values"].items():
            print(f"traced run, not judged: {name}={value}", flush=True)
        result["metrics"] = manifest.read_layer_metrics(cell, ctx)
        reduced = ctx.get("trace")
        if reduced:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            result["breakdown"] = reduced["breakdown"]
    result["checks"] = outcome["checks"]
    return result


def print_worker_logs(log_dir: str, files: int = 4, lines: int = 60) -> None:
    """The machine is thrown away after the run: the tail of the newest
    worker logs (the chip's owner among them) is the only evidence."""
    import glob

    logs = sorted(glob.glob(os.path.join(log_dir, "worker-*.log")),
                  key=os.path.getmtime)[-files:]
    for path in logs + [os.path.join(log_dir, "raylet.err")]:
        try:
            with open(path, errors="replace") as f:
                tail = f.readlines()[-lines:]
        except OSError:
            continue
        print(f"---- tail of {path}", file=sys.stderr)
        sys.stderr.writelines(tail)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--sweep-rates", default="")
    args = parser.parse_args(argv)
    sweep = tuple(float(r) for r in args.sweep_rates.split(",") if r)

    import ray_tpu
    from benchmarks.harness import manifest

    cell = manifest.load_cell(args.workload)
    ray_tpu.init()
    try:
        chips = int(ray_tpu.cluster_resources().get("TPU", 0))
        if chips < cell["chips"]:
            raise RuntimeError(
                f"{args.workload} needs {cell['chips']} TPU chip(s); this "
                f"host has {chips} (ray_tpu.cluster_resources() found no "
                f"/dev/accel* or /dev/vfio/<n> device). The benchmark runs "
                f"on a TPU or not at all.")
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), T0, sweep_rates=sweep)
        if "jax" in sys.modules:
            raise RuntimeError("the harness process imported jax; it "
                               "must stay off the chip")
    except BaseException as e:  # noqa: BLE001 — reported, then exit 1
        traceback.print_exc()
        print_worker_logs(ray_tpu._private_node().log_dir)
        print(f"benchmark FAILED: {type(e).__name__}: {e}",
              file=sys.stderr, flush=True)
        return 1
    finally:
        ray_tpu.shutdown()
    if not result.get("sweep"):
        for name, (value, limit) in result["checks"].items():
            print(f"compared: {name}={value} limit={limit}",
                  file=sys.stderr, flush=True)
        print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
