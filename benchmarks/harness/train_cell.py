"""A training cell: `JaxTrainer.fit` -> one worker leasing the cell's
chips -> `get_mesh()` -> the seeded sharded weights and the loss of the
configuration's family (`benchmarks/families/<name>.py`) ->
`iter_jax_batches` -> `make_train_step`, with `train.report` every step
as Ray Train users write it. The loop below is the benchmark's own, built
on `chip_smoke.py`'s; this (the harness's) process never imports JAX.
"""

from __future__ import annotations

import os
import shutil
import tempfile
import threading
import time
import numpy as np

from benchmarks.harness import loadgen, manifest
from benchmarks.harness import trace as trace_mod
from benchmarks.harness.serve_cell import (CompileCounter, _require,
                                           device_facts, trace_directory)


def _train_loop(config: dict) -> None:
    """Runs in the worker that leased the chips."""
    import jax
    import optax
    from jax.sharding import NamedSharding, PartitionSpec

    from benchmarks.harness import trace
    from ray_tpu import train
    from ray_tpu.parallel.spmd import make_train_step

    compiles = CompileCounter()
    seconds, seq, batch = config["seconds"], config["seq"], config["batch"]
    family = manifest.load_family(config["family"], config["root"])
    trainer = config["trainer"]
    optimizer = optax.adamw(trainer["learning_rate"])
    shape = tuple(trainer["mesh"]) if trainer["mesh"] else None
    mesh = train.get_mesh(shape, devices=(
        jax.devices()[:int(np.prod(shape))] if shape else None))
    built = family.build_training(config["widths"], trainer, seq,
                                  config["seed"], mesh)
    params = built["params"]
    # Adam's moments are placed like the parameters they belong to. Left to
    # itself `jit(optimizer.init)` hands back replicated zeros (nothing in
    # them depends on a sharded input): 9.4 GB a chip for olmo-1b, and the
    # step no longer loads (first four-chip run of PR 23).
    replicated = NamedSharding(mesh, PartitionSpec())
    opt_shardings = optax.tree_utils.tree_map_params(
        optimizer, lambda _, sharding: sharding,
        jax.eval_shape(optimizer.init, params),
        jax.tree.map(lambda p: p.sharding, params),
        transform_non_params=lambda _: replicated)
    opt_state = jax.jit(optimizer.init, out_shardings=opt_shardings)(params)
    step = make_train_step(built["loss_fn"], optimizer)
    shard = train.get_dataset_shard("train")

    def epochs():
        while True:    # the window may outlast the dataset: go round again
            yield from shard.iter_jax_batches(batch_size=batch, mesh=mesh,
                                              drop_last=True)

    batches = epochs()
    probe = next(batches)
    # Pallas kernels lower to this custom call; XLA attention leaves none.
    attention = ("pallas_flash" if "tpu_custom_call" in step.lower(
        params, opt_state, probe).as_text() else "xla")
    reference_loss = family.reference_loss(config["widths"])(
        params, probe["tokens"])
    warm_losses = []
    for i in range(config["warm_steps"]):     # one repeated probe batch
        params, opt_state, loss = step(params, opt_state, probe)
        warm_losses.append(float(loss))
        train.report({"warm_step": i + 1, "loss": warm_losses[-1]})
    # Everything the window runs is compiled; the data iterator is hot.
    first = next(batches)
    jax.block_until_ready((params, opt_state, first))
    compiles_at_open = compiles.count

    tracing = config["trace_dir"] is not None
    trace_from = 2                 # steps into the window before tracing
    window_span = None
    t_open_wall, t_open = time.time(), time.perf_counter()
    steps, data_wait_s, report_s, pending = 0, 0.0, 0.0, None
    batch_now = first
    while True:
        if tracing and steps == trace_from:
            jax.profiler.start_trace(
                config["trace_dir"], profiler_options=trace.start_options())
        if tracing and steps == trace_from + 1:
            # One step after the profiler started, so that its own
            # start-up is not read as device idle time.
            window_span = jax.profiler.TraceAnnotation("bench:window")
            window_span.__enter__()
            traced_from = (steps, data_wait_s, report_s)
        with jax.profiler.TraceAnnotation("bench:dispatch_step"):
            params, opt_state, loss = step(params, opt_state, batch_now)
        steps += 1
        if pending is not None:
            # The loss of the step before: the host stays one step ahead
            # of the device, and reads a number that is already there.
            with jax.profiler.TraceAnnotation("bench:read_loss"):
                value = float(pending)
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench:train.report"):
                train.report({"step": steps - 1, "loss": value})
            report_s += time.perf_counter() - t
        pending = loss
        if tracing and steps == trace_from + 1 + config["trace_steps"]:
            window_span.__exit__(None, None, None)
            traced_to = (steps, data_wait_s, report_s)
            jax.profiler.stop_trace()
            window_span = None
        if time.perf_counter() - t_open >= seconds:
            break
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench:next(batches)"):
            batch_now = next(batches)
        data_wait_s += time.perf_counter() - t
    jax.block_until_ready((params, opt_state, loss))
    window_s = time.perf_counter() - t_open
    compiles_in_window = compiles.count - compiles_at_open
    last_loss = float(loss)

    trace_counters = None
    if tracing and window_span is None and steps > trace_from + 1:
        trace_counters = {
            "steps": traced_to[0] - traced_from[0],
            "data_wait_s": traced_to[1] - traced_from[1],
            "report_s": traced_to[2] - traced_from[2]}
    placement = sorted({len({s.device for s in leaf.addressable_shards})
                        for leaf in jax.tree.leaves(params)})
    first_device = jax.local_devices()[0]
    state_bytes = sum(
        shard.data.nbytes for leaf in jax.tree.leaves((params, opt_state))
        for shard in leaf.addressable_shards if shard.device == first_device)
    train.report({"summary": {
        "device": device_facts(), "mesh": dict(mesh.shape),
        "mesh_devices": int(mesh.size),
        "attention": attention, "reference_loss": reference_loss,
        "warm_losses": warm_losses, "last_loss": last_loss,
        "param_leaf_device_counts": placement,
        "state_bytes_on_first_device": int(state_bytes),
        "t_open_wall": t_open_wall, "window_s": window_s, "steps": steps,
        "tokens": steps * batch * seq, "data_wait_s": data_wait_s,
        "report_s": report_s, "compiles": compiles_in_window,
        "compiles_before_window": compiles_at_open,
        "trace_counters": trace_counters,
    }})


def run(cell: dict, *, seed: int, seconds: float, trace: bool, t0: float,
        expect_platform: str = "tpu", timeout_s: float = 1100.0) -> dict:
    """One run of a training cell; returns what `run.py` prints."""
    from ray_tpu.data import from_numpy
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    settings, traffic, widths = cell["settings"], cell["traffic"], \
        cell["widths"]
    chips = cell["chips"] if expect_platform == "tpu" else 0
    batch, seq = traffic["global_batch"], traffic["seq_len"]
    n_steps = traffic["dataset_steps"]
    rows = loadgen.rng_for(seed, 5).integers(
        0, widths["vocab_size"], (n_steps * batch, seq + 1), dtype=np.int32)
    dataset = from_numpy({"tokens": rows}, parallelism=n_steps)
    storage = tempfile.mkdtemp(prefix="ray_tpu_bench_")
    trace_dir = trace_directory(cell["name"]) if trace else None
    trainer = JaxTrainer(
        _train_loop,
        train_loop_config={
            "family": cell["family"], "root": cell["root"],
            "widths": widths, "seq": seq, "batch": batch, "seed": seed,
            "seconds": seconds, "warm_steps": traffic["warm_steps"],
            "trainer": settings["trainer"],
            "trace_steps": settings["trace_steps"],
            "trace_dir": trace_dir},
        scaling_config=ScalingConfig(num_workers=1, use_tpu=chips > 0,
                                     chips_per_worker=chips),
        run_config=RunConfig(name=f"bench_{os.getpid()}",
                             storage_path=storage),
        datasets={"train": dataset})
    box: dict = {}

    def fit():
        try:
            box["result"] = trainer.fit()
        except BaseException as e:  # noqa: BLE001 — re-raised below
            box["error"] = e

    # `fit` blocks without a limit of its own.
    thread = threading.Thread(target=fit, daemon=True)
    thread.start()
    thread.join(timeout_s)
    shutil.rmtree(storage, ignore_errors=True)
    _require(not thread.is_alive(),
             f"JaxTrainer.fit did not finish within {timeout_s:.0f} s")
    if "error" in box:
        raise box["error"]
    summaries = [m["summary"] for m in box["result"].metrics_history
                 if "summary" in m]
    _require(bool(summaries), "the train loop reported no summary")
    s = summaries[-1]
    device = s["device"]
    _require(device["platform"] == expect_platform,
             f"the worker ran on {device['platform']}, expected "
             f"{expect_platform}")
    _require(chips == 0 or device["count"] == chips,
             f"the worker saw {device['count']} devices, the cell asks "
             f"for {chips}")
    n_chips = max(1, device["count"] if chips else 1)
    rate = s["tokens"] / s["window_s"] / n_chips
    print(f"setup: device={device} mesh={s['mesh']} "
          f"attention={s['attention']} state_bytes_on_first_device="
          f"{s['state_bytes_on_first_device']} compiles_or_cache_fetches="
          f"{s['compiles_before_window']} warm_losses="
          f"{[round(x, 4) for x in s['warm_losses']]} reference_loss="
          f"{s['reference_loss']:.4f}", flush=True)
    print(f"window: steps={s['steps']} window_s={s['window_s']:.3f} "
          f"tokens={s['tokens']} step_ms="
          f"{s['window_s'] / s['steps'] * 1e3:.2f} data_wait_s="
          f"{s['data_wait_s']:.3f} report_s={s['report_s']:.3f} "
          f"last_loss={s['last_loss']:.4f} compiles={s['compiles']}",
          flush=True)
    family = manifest.family_of(cell)
    tolerance = family.LOSS_TOLERANCE
    problems = []
    losses = s["warm_losses"]
    if not abs(losses[0] - s["reference_loss"]) <= tolerance:
        problems.append(
            f"first loss {losses[0]:.5f} against the reference's "
            f"{s['reference_loss']:.5f}: off by more than "
            f"{tolerance}")
    if not (np.isfinite(losses + [s["last_loss"]]).all()
            and losses[-1] < losses[0]):
        problems.append(f"loss not finite and falling on the repeated "
                        f"probe batch: {losses}, then {s['last_loss']}")
    if s["compiles"]:
        problems.append(f"{s['compiles']} programs compiled inside the "
                        f"window")
    if s["param_leaf_device_counts"] != [s["mesh_devices"]]:
        problems.append(f"parameter leaves sit on "
                        f"{s['param_leaf_device_counts']} of the mesh's "
                        f"{s['mesh_devices']} devices")
    expected = settings.get("expect_attention")
    if expect_platform == "tpu" and expected and s["attention"] != expected:
        problems.append(f"attention is {s['attention']}, the cell is "
                        f"defined on {expected}")
    for problem in problems:
        print(f"NOT CORRECT: {problem}", flush=True)
    peak = cell["peaks"].get(device["kind"])
    reduced = (trace_mod.reduce_in_subprocess(trace_dir)
               if s["trace_counters"] else None)
    ctx = {"cell": cell, "kind": "train", "widths": widths, "peak": peak,
           "window_s": s["window_s"], "chips": n_chips, "seq_len": seq,
           "global_batch": batch, "tokens_per_s_per_chip": rate,
           "counters": {"steps": s["steps"], "tokens": s["tokens"],
                        "data_wait_s": s["data_wait_s"],
                        "report_s": s["report_s"],
                        "compiles": s["compiles"]},
           "trace": reduced, "trace_counters": s["trace_counters"],
           "counts": family.counts(widths)}
    checks = {
        "first_loss_gap": [abs(losses[0] - s["reference_loss"]), tolerance],
        "loss_fall_on_probe": [losses[0] - losses[-1], 0],
        "compiles_in_window": [s["compiles"], 0]}
    return {"correct": not problems, "attempted": s["steps"], "failed": 0,
            "values": {"train_tokens_per_s_per_chip": rate,
                       "setup_s": s["t_open_wall"] - t0},
            "ctx": ctx, "device": device, "checks": checks}
