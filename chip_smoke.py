"""The quickest proof that the system still starts on the chip.

Drives both main paths once, through the entry points a user calls:
training at the widths of the 503M dense model, serving at the
benchmark's serving widths (`SERVE_WIDTHS`), depth 12 both (depth and
weights are the only cuts: the weights are random, made from a seed):

- train: `JaxTrainer.fit` -> Tune trial actor -> placement group -> one
  worker that leases every local chip -> `jax.distributed` -> mesh ->
  `make_train_step`, fed by a `ray_tpu.data` iterator;
- serve: `serve.run` -> replica actor holding `{"TPU": 1}` ->
  `InferenceEngine` -> device KV pool ->
  `TransformerEngineModel` (its decode step attends over the pool's
  pages in place, through the Pallas kernel), asked through a streaming
  handle and the HTTP proxy.

A chip belongs to one process at a time, so this process never touches
JAX: every device fact below is reported by the worker that owned the
chip. Run it from the repo root: `python chip_smoke.py`. It exits non-zero
unless every check passed on a TPU, and prints the result as the last
line of its standard output.
"""

from __future__ import annotations

import collections
import glob
import json
import os
import statistics
import sys
import threading
import time
import traceback
import urllib.request

import numpy as np

import ray_tpu
from ray_tpu import serve
from ray_tpu.data import from_numpy
from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

# bench.py's model, the one configuration the repo has profiled on a chip.
WIDTHS = dict(vocab_size=32768, d_model=1536, n_layers=12, n_heads=12,
              d_ff=6144)
# What the serving section runs: the same block at the benchmark's serving
# widths (`olmo-1b`: 16 heads of 128), which the paged decode kernel takes
# (`ops.paged_attention.kernel_eligible`: a multiple of 8 heads of 128).
SERVE_WIDTHS = dict(WIDTHS, d_model=2048, n_heads=16, d_ff=8192)
SEED = 0


class SmokeFailure(RuntimeError):
    """A check failed; the message says which."""


def _bounded(fn, timeout_s: float, what: str):
    """Run `fn()` on a daemon thread and wait at most `timeout_s`: the
    entry points below block without a limit of their own."""
    box = {}

    def run():
        try:
            box["value"] = fn()
        except BaseException as e:  # noqa: BLE001 — re-raised below
            box["error"] = e

    t = threading.Thread(target=run, daemon=True, name=what)
    t.start()
    t.join(timeout_s)
    if t.is_alive():
        raise SmokeFailure(f"{what} did not finish within {timeout_s:.0f} s")
    if "error" in box:
        raise box["error"]
    return box["value"]


def _require(ok: bool, message: str) -> None:
    if not ok:
        raise SmokeFailure(message)


def _device_facts() -> dict:
    """The device as JAX reports it to the process that owns the chip."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------
def _train_loop(config: dict) -> None:
    """Runs in the worker that leased the chips."""
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu import train
    from ray_tpu.models.transformer import (TransformerConfig, init_params,
                                            lm_loss, param_specs)
    from ray_tpu.ops.attention import flash_tiles
    from ray_tpu.parallel.spmd import init_sharded, make_train_step

    t_mesh = time.perf_counter()
    events = collections.Counter()   # JAX's own monitoring events, by name
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _secs, **_kw: events.update([event]))
    jax.monitoring.register_event_listener(
        lambda event, **_kw: events.update([event]))

    device = _device_facts()
    cfg = TransformerConfig(
        **config["widths"], max_seq_len=config["seq"], dtype=jnp.bfloat16,
        remat=True, remat_policy="save_attn_qkv")
    optimizer = optax.adamw(1e-4)
    shard = train.get_dataset_shard("train")

    for shape in config["mesh_shapes"]:
        mesh = train.get_mesh(shape)
        params = init_sharded(
            lambda: init_params(jax.random.PRNGKey(config["seed"]), cfg),
            param_specs(cfg), mesh)
        opt_state = jax.jit(optimizer.init)(params)
        step = make_train_step(
            lambda p, b: lm_loss(p, b, cfg, mesh=mesh), optimizer)
        placement = sorted({
            len({s.device for s in leaf.addressable_shards})
            for leaf in jax.tree.leaves(params)})
        attention = None
        losses, step_s = [], []
        batches = shard.iter_jax_batches(
            batch_size=config["batch"], mesh=mesh, drop_last=True)
        for batch in batches:
            if attention is None:
                # Pallas kernels lower to this custom call; XLA attention
                # leaves none. (`attention()` picks without a word.)
                lowered = step.lower(params, opt_state, batch).as_text()
                attention = ("pallas_flash" if "tpu_custom_call" in lowered
                             else "xla")
            t0 = time.perf_counter()
            params, opt_state, loss = step(params, opt_state, batch)
            losses.append(float(loss))   # the host needs it: syncs the step
            step_s.append(time.perf_counter() - t0)
            if len(losses) == 1:
                # From loop entry (first mesh): backend start-up, init
                # and every compile on the way to one finished step.
                first_step_s = time.perf_counter() - t_mesh
            train.report({"mesh": dict(mesh.shape), "step": len(losses),
                          "loss": losses[-1]})
        del params, opt_state, step
        train.report({"summary": {
            "device": device, "mesh": dict(mesh.shape), "losses": losses,
            "param_leaf_device_counts": placement,
            "attention": attention,
            "flash_tiles": (repr(flash_tiles(config["seq"], cfg.head_dim))
                            if attention == "pallas_flash" else None),
            "first_step_s": round(first_step_s, 2),
            "warm_step_s": round(statistics.median(step_s[1:]), 4),
            # Programs compiled or fetched from the persistent cache,
            # and how many of them were fetched.
            "compiles": events["/jax/core/compile/backend_compile_duration"],
            "cache_hits": events["/jax/compilation_cache/cache_hits"],
            "compile_cache": jax.config.jax_compilation_cache_dir,
        }})
        t_mesh = time.perf_counter()


def train_phase(widths: dict, *, expect_platform: str, chips: int,
                batch: int = 16, seq: int = 1024, steps: int = 5,
                timeout_s: float = 600.0) -> dict:
    """`steps` steps of `batch` x `seq` through `JaxTrainer.fit` on one
    worker that leases `chips` chips (0: a chip-less worker, for the CPU
    tests). With more than one device it runs the default mesh and then
    an explicit all-FSDP one. Returns the last mesh's summary."""
    t0 = time.perf_counter()
    rng = np.random.default_rng(SEED)
    rows = rng.integers(0, widths["vocab_size"], (batch, seq + 1),
                        dtype=np.int32)
    # The same batch every step: a falling loss is then a property of the
    # optimizer, not of the data.
    dataset = from_numpy({"tokens": np.tile(rows, (steps, 1))},
                         parallelism=steps)
    trainer = JaxTrainer(
        _train_loop,
        train_loop_config={
            "widths": widths, "batch": batch, "seq": seq, "seed": SEED,
            # None: whatever get_mesh() makes of the leased devices.
            "mesh_shapes": ([None] if chips <= 1
                            else [None, (chips, 1, 1, 1)])},
        scaling_config=ScalingConfig(num_workers=1, use_tpu=chips > 0,
                                     chips_per_worker=chips),
        run_config=RunConfig(name=f"chip_smoke_{os.getpid()}",
                             storage_path="/tmp/ray_tpu_chip_smoke"),
        datasets={"train": dataset})
    result = _bounded(trainer.fit, timeout_s, "JaxTrainer.fit")
    summaries = [m["summary"] for m in result.metrics_history
                 if "summary" in m]
    _require(bool(summaries), "the train loop reported no summary")
    for s in summaries:
        dev, losses = s["device"], s["losses"]
        where = f"train mesh={s['mesh']}"
        _require(dev["platform"] == expect_platform,
                 f"{where}: worker ran on {dev['platform']}, "
                 f"expected {expect_platform}")
        _require(chips == 0 or dev["count"] == chips,
                 f"{where}: worker saw {dev['count']} devices, "
                 f"leased {chips}")
        _require(len(losses) == steps,
                 f"{where}: {len(losses)} steps ran, expected {steps}")
        _require(all(np.isfinite(losses)), f"{where}: losses {losses}")
        _require(losses[-1] < losses[0],
                 f"{where}: loss did not fall on a repeated batch: {losses}")
        _require(s["param_leaf_device_counts"] == [dev["count"]],
                 f"{where}: param leaves sit on "
                 f"{s['param_leaf_device_counts']} of {dev['count']} devices")
        if expect_platform == "tpu" and dev["count"] == 1:
            _require(s["attention"] == "pallas_flash",
                     f"{where}: the one-chip step holds no Pallas flash "
                     f"call (attention={s['attention']})")
        print(f"train: platform={dev['platform']} "
              f"device_kind={dev['kind']!r} devices={dev['count']} "
              f"mesh={s['mesh']} attention={s['attention']} "
              f"flash_tiles={s['flash_tiles']} "
              f"losses={[round(x, 4) for x in losses]} "
              f"first_step_s={s['first_step_s']} "
              f"warm_step_s={s['warm_step_s']} compiles={s['compiles']} "
              f"cache_hits={s['cache_hits']} "
              f"compile_cache={s['compile_cache']}", flush=True)
    last = dict(summaries[-1])
    last["cold_wall_s"] = round(time.perf_counter() - t0, 1)
    print(f"train: cold_wall_s={last['cold_wall_s']}", flush=True)
    return last


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------
def _smoke_deployment(chips: int):
    @serve.deployment(
        name="chip_smoke", max_ongoing_requests=32,
        ray_actor_options={"resources": {"TPU": 1}} if chips else {})
    class SmokeLM:
        """Runs in the replica that leased the chip."""

        def __init__(self, widths: dict, max_seq_len: int, seed: int):
            import jax

            from ray_tpu.models.transformer import (TransformerConfig,
                                                    init_params)
            from ray_tpu.serve.engine import (EngineConfig,
                                              InferenceEngine,
                                              TransformerEngineModel)

            t0 = time.perf_counter()
            cfg = TransformerConfig(**widths, max_seq_len=max_seq_len)
            params = jax.jit(
                lambda: init_params(jax.random.PRNGKey(seed), cfg))()
            self.model = TransformerEngineModel(params, cfg,
                                                max_batch_size=8)
            # Random weights give no token the meaning "end of sequence".
            self.model.eos_token = None
            self.engine = InferenceEngine(self.model, EngineConfig(
                max_batch_size=8, block_size=16, num_blocks=512))
            self.engine.start()
            jax.block_until_ready(params)
            self.init_s = time.perf_counter() - t0

        def _submit(self, req: dict):
            return self.engine.submit(req["prompt"], req["max_new_tokens"])

        def generate(self, req: dict):
            yield from self._submit(req)

        async def __call__(self, req: dict):
            return [tok async for tok in self._submit(req)]

        def report(self) -> dict:
            pool_platforms = self.engine.cache.with_pool(
                lambda pool: sorted({d.platform for d in pool.devices()}))
            return {
                "device": _device_facts(),
                "pool_platforms": pool_platforms,
                "stats": self.engine.stats(),
                "jit_compiles": self.model.jit_compiles,
                "init_s": round(self.init_s, 1),
            }

    return SmokeLM


def serve_phase(widths: dict, *, expect_platform: str, chips: int,
                prompt_lens=(96, 400), new_tokens: int = 32,
                max_seq_len: int = 1024, timeout_s: float = 600.0) -> dict:
    """Eight streamed requests and one HTTP request against a replica
    that leases one chip (`chips` 0: a chip-less replica, for the CPU
    tests). Prompts come in two lengths so the engine compiles few shape
    buckets."""
    t0 = time.perf_counter()
    deadline = t0 + timeout_s
    rng = np.random.default_rng(SEED + 1)
    requests = [
        {"prompt": rng.integers(2, widths["vocab_size"],
                                prompt_lens[i % 2]).tolist(),
         "max_new_tokens": new_tokens}
        for i in range(9)]
    app = _smoke_deployment(chips).bind(widths, max_seq_len, SEED)
    try:
        handle = _bounded(
            lambda: serve.run(app, route_prefix="/chip_smoke",
                              _blocking_timeout_s=timeout_s / 2),
            timeout_s / 2 + 30, "serve.run")
        port = serve.start()

        streams = [[] for _ in requests[:8]]
        errors = []

        def consume(i: int) -> None:
            try:
                gen = handle.options(
                    stream=True, method_name="generate").remote(requests[i])
                for tok in gen:
                    streams[i].append(tok)
            except BaseException as e:  # noqa: BLE001 — reported below
                errors.append(f"stream {i}: {e!r}")

        threads = [threading.Thread(target=consume, args=(i,), daemon=True)
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(max(0.0, deadline - time.perf_counter()))
        _require(not errors, "; ".join(errors))
        _require(not any(t.is_alive() for t in threads),
                 f"streams still open after {timeout_s:.0f} s: "
                 f"{[len(s) for s in streams]} tokens so far")

        http = urllib.request.Request(
            f"http://127.0.0.1:{port}/chip_smoke",
            data=json.dumps(requests[8]).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(http, timeout=120) as reply:
            _require(reply.status == 200, f"HTTP status {reply.status}")
            streams.append(json.loads(reply.read()))

        report = handle.options(method_name="report").remote().result(
            timeout_s=60)
    finally:
        serve.shutdown()

    dev, stats = report["device"], report["stats"]
    _require(dev["platform"] == expect_platform,
             f"serve: replica ran on {dev['platform']}, "
             f"expected {expect_platform}")
    for i, toks in enumerate(streams):
        _require(len(toks) == new_tokens
                 and all(0 <= t < widths["vocab_size"] for t in toks),
                 f"serve: request {i} gave {len(toks)} tokens: {toks}")
    cache = stats["cache"]
    _require(cache["host_gathers"] == 0,
             f"serve: {cache['host_gathers']} host gathers on the paged path")
    _require(cache["pool_residency"] == "device"
             and report["pool_platforms"] == [expect_platform],
             f"serve: KV pool is {cache['pool_residency']} on "
             f"{report['pool_platforms']}")
    _require(stats["paged"] and stats["paged_steps"] > 0,
             f"serve: paged_steps={stats['paged_steps']}")
    # On the chip (`SERVE_WIDTHS`) every step's attention reads the
    # pool's pages in place through the Pallas kernel; off it, none does.
    inplace = stats["decode_attn_inplace_steps"]
    _require(inplace == (stats["paged_steps"]
                         if expect_platform == "tpu" else 0),
             f"serve: {inplace} of {stats['paged_steps']} paged steps "
             f"attended over the pool's pages in place")
    out = {"device": dev, "jit_compiles": report["jit_compiles"],
           "paged_steps": stats["paged_steps"],
           "attn_inplace_steps": inplace,
           "kv_pages_read_per_step": round(
               stats["decode_kv_pages_read"] / max(inplace, 1), 2),
           "replica_init_s": report["init_s"],
           "cold_wall_s": round(time.perf_counter() - t0, 1)}
    print(f"serve: platform={dev['platform']} device_kind={dev['kind']!r} "
          f"devices={dev['count']} requests={len(streams)} "
          f"tokens={sum(len(s) for s in streams)} "
          f"paged_steps={out['paged_steps']} host_gathers=0 "
          f"attn_inplace_steps={inplace} "
          f"kv_pages_read_per_step={out['kv_pages_read_per_step']} "
          f"pool=device/{report['pool_platforms'][0]} "
          f"jit_compiles={out['jit_compiles']} "
          f"replica_init_s={out['replica_init_s']} "
          f"cold_wall_s={out['cold_wall_s']}", flush=True)
    return out


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------
def _wait_for_chips(total: float, timeout_s: float = 60.0) -> None:
    """The raylet hands chips back only once the process that held them
    has exited; the next phase starts after that."""
    deadline = time.monotonic() + timeout_s
    while ray_tpu.available_resources().get("TPU", 0.0) < total:
        _require(time.monotonic() < deadline,
                 f"chips still held {timeout_s:.0f} s after the phase ended: "
                 f"{ray_tpu.available_resources()}")
        time.sleep(0.5)


def _print_worker_logs(log_dir: str, files: int = 4, lines: int = 60) -> None:
    """The machine is thrown away after the run: the tail of the newest
    worker logs (the chip's owner among them) is the only evidence."""
    logs = sorted(glob.glob(os.path.join(log_dir, "worker-*.log")),
                  key=os.path.getmtime)[-files:]
    for path in logs + [os.path.join(log_dir, "raylet.err"),
                        os.path.join(log_dir, "gcs.err")]:
        try:
            with open(path, errors="replace") as f:
                tail = f.readlines()[-lines:]
        except OSError:
            continue
        print(f"---- tail of {path}", file=sys.stderr)
        sys.stderr.writelines(tail)


def main() -> int:
    ray_tpu.init()
    log_dir = ray_tpu._private_node().log_dir
    try:
        chips = int(ray_tpu.cluster_resources().get("TPU", 0))
        _require(chips > 0,
                 "no TPU chip on this host: ray_tpu.cluster_resources() has "
                 "no 'TPU' (no /dev/accel* or /dev/vfio/<n> device found). "
                 "This script runs the 503M model on a TPU or not at all.")
        trained = train_phase(WIDTHS, expect_platform="tpu", chips=chips)
        _wait_for_chips(chips)
        served = serve_phase(SERVE_WIDTHS, expect_platform="tpu",
                             chips=chips)
        _require(trained["device"]["kind"] == served["device"]["kind"],
                 f"phases saw different devices: {trained['device']} "
                 f"and {served['device']}")
        _require("jax" not in sys.modules,
                 "the driver imported jax; it must stay off the chip")
        result = {"ok": True, "device": trained["device"]}
    except BaseException as e:  # noqa: BLE001 — reported, then exit 1
        traceback.print_exc()
        _print_worker_logs(log_dir)
        print(f"chip_smoke FAILED: {type(e).__name__}: {e}",
              file=sys.stderr, flush=True)
        return 1
    finally:
        ray_tpu.shutdown()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
