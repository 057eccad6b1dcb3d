"""A serving cell: `serve.run` -> one replica holding the chip ->
`InferenceEngine(paged_decode=True)` -> the engine model of the
configuration's family (`benchmarks/families/<name>.py`: weights, model,
warm-up, drive, reference, tolerance), asked through streaming
`DeploymentHandle`s from this (the harness's) process.

The shape is `chip_smoke.py`'s: this process never imports JAX, and every
device fact is reported by the replica that owns the chip. The deployment
class below is the benchmark's own; the program under it is untouched.
"""

from __future__ import annotations

import os
import re
import shutil
import threading
import time
from typing import Dict, Iterator, List, Optional, Tuple

import numpy as np

from benchmarks.harness import loadgen, manifest, stats
from benchmarks.harness import trace as trace_mod

DEPLOYMENT = "bench_lm"

def device_facts() -> dict:
    """The device as JAX reports it to the process that owns the chip."""
    import jax

    devices = jax.local_devices()
    stats_by_device = [d.memory_stats() or {} for d in devices]
    # Live buffers plus what loaded programs reserve for their own scratch
    # (a train step's activations are there, not among the buffers).
    peaks = [s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0)
             for s in stats_by_device]
    fullest = max(range(len(devices)), key=lambda i: peaks[i])
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(peaks[fullest]),
            "memory_stats": {k: int(v) for k, v in
                             stats_by_device[fullest].items()
                             if isinstance(v, (int, float))}}


class CompileCounter:
    """Programs compiled, or fetched from the persistent cache, in this
    process: JAX's own monitoring event, as `chip_smoke.py` takes it."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, _secs, **_kw):
        if event == self.EVENT:
            self.count += 1


def held_bytes(params, engine) -> dict:
    """The dtypes the replica holds: of the weights (the one most of
    their bytes are in) and of the KV pool. The family's counts take
    their bytes a value from this, not from a constant."""
    import jax

    by_dtype: dict = {}
    for leaf in jax.tree.leaves(params):
        by_dtype[leaf.dtype] = by_dtype.get(leaf.dtype, 0) + leaf.nbytes
    weights = np.dtype(max(by_dtype, key=by_dtype.get))
    pool = np.dtype(engine.cache.with_pool(lambda pool: pool.dtype))
    return {"weights": {"dtype": str(weights),
                        "bytes_per_value": weights.itemsize},
            "kv_pool": {"dtype": str(pool),
                        "bytes_per_value": pool.itemsize}}


def check_against_reference(family, engine, served: dict, widths: dict,
                            prompt_lengths, steps: int, seed: int,
                            reference_widths: Optional[dict] = None) -> dict:
    """The family's drive (prefill, then `steps` decode steps through the
    engine's cache, as the scheduler makes them) against the family's
    plain reference on the same weights: logits, not tokens (with random
    weights the largest logit changes on rounding). `reference_widths`
    hands the reference other widths than the engine runs: how the tests
    see the check fail."""
    ref = family.reference_logits(reference_widths or widths)
    tolerance = family.LOGIT_TOLERANCE
    rng = np.random.default_rng([seed, 12])
    errors, largest = [], []
    for i, n in enumerate(prompt_lengths):
        prompt = rng.integers(2, widths["vocab_size"], n).tolist()
        got, tokens = family.drive(engine, served, prompt, steps,
                                   f"bench-check-{i}")
        want = np.asarray(ref(served["params"],
                              np.asarray(tokens, np.int32)))
        for j, row in enumerate(got):
            expect = want[n - 1 + j]
            scale = np.sqrt(np.mean(expect * expect))
            errors.append(float(np.sqrt(np.mean((row - expect) ** 2))
                                / scale))
            largest.append(float(np.max(np.abs(row - expect)) / scale))
    return {"max_error": max(errors), "errors": errors,
            "largest_single_logit": max(largest),
            "tolerance": tolerance,
            "ok": bool(np.isfinite(errors + largest).all()
                       and max(errors) <= tolerance
                       and max(largest) <= 5 * tolerance)}


def make_deployment(chips: int):
    from ray_tpu import serve

    @serve.deployment(
        name=DEPLOYMENT, max_ongoing_requests=512,
        ray_actor_options={"resources": {"TPU": 1}} if chips else {})
    class BenchLM:
        """Runs in the replica that leased the chip."""

        def __init__(self, spec: dict):
            from ray_tpu.serve.engine import InferenceEngine

            self.compiles = CompileCounter()
            self.spec = spec
            self.family = manifest.load_family(spec["family"], spec["root"])
            self.served = self.family.build_serving(
                spec["widths"], spec["settings"], spec["seed"])
            self.model = self.served["model"]
            self.engine = InferenceEngine(self.model,
                                          self.served["engine_config"])
            self.traced = {"decode_steps": 0, "decode_rows": 0,
                           "decode_live_tokens": 0}
            if spec["trace"]:
                self._annotate()
            self.engine.start()

        # -- spans, from the benchmark's side of each call --------------
        def _annotate(self) -> None:
            """Host spans on the profiler's clock around the calls into
            the model and the scheduler; only a `--trace 1` run has them."""
            import jax

            model, engine, traced = self.model, self.engine, self.traced
            calls = self.family.TRACED_CALLS
            rows_and_live = self.family.decode_step_rows_and_live
            prefill = getattr(model, calls["prefill"])
            decode = getattr(model, calls["decode_step"])
            step = engine.step

            def traced_prefill(*args, **kwargs):
                with jax.profiler.TraceAnnotation("bench:prefill"):
                    return prefill(*args, **kwargs)

            def traced_decode(*args, **kwargs):
                rows, live = rows_and_live(args, kwargs)
                traced["decode_steps"] += 1
                traced["decode_rows"] += rows
                traced["decode_live_tokens"] += live
                with jax.profiler.TraceAnnotation("bench:decode_step"):
                    return decode(*args, **kwargs)

            def traced_step():
                with jax.profiler.TraceAnnotation("bench:engine_step"):
                    return step()

            setattr(model, calls["prefill"], traced_prefill)
            setattr(model, calls["decode_step"], traced_decode)
            engine.step = traced_step

        # -- set-up: warm every shape the mix can reach, then check -----
        def prepare(self, shapes: dict) -> dict:
            t0 = time.perf_counter()
            rng = np.random.default_rng([self.spec["seed"], 11])
            vocab = self.spec["widths"]["vocab_size"]
            streams = [self.engine.submit(
                rng.integers(2, vocab, n).tolist(), 1)
                for n in shapes["prompt_lengths"]]
            for stream in streams:
                if len(list(stream)) != 1:
                    raise RuntimeError("a warm-up prefill gave no token")
            t1 = time.perf_counter()
            for nb in shapes["decode_tables"]:
                for b in shapes["decode_batches"]:
                    self.family.warm_bucket(self.engine, self.served, b, nb)
            t2 = time.perf_counter()
            settings = self.spec["settings"]
            check = self._check(settings["check_prompts"],
                                settings["check_decode_steps"])
            return {"device": device_facts(), "check": check,
                    "held": held_bytes(self.served["params"], self.engine),
                    "warm_prefill_s": t1 - t0, "warm_decode_s": t2 - t1,
                    "check_s": time.perf_counter() - t2,
                    "compiles": self.compiles.count,
                    "pool_platforms": self.engine.cache.with_pool(
                        lambda pool: sorted(
                            {d.platform for d in pool.devices()}))}

        def _check(self, prompt_lengths: List[int], steps: int) -> dict:
            return check_against_reference(
                self.family, self.engine, self.served, self.spec["widths"],
                prompt_lengths, steps, self.spec["seed"])

        # -- the served path ---------------------------------------------
        def generate(self, req: dict):
            for tok in self.engine.submit(req["prompt"],
                                          req["max_new_tokens"]):
                yield tok, time.time()

        # -- counters ----------------------------------------------------
        def snapshot(self) -> dict:
            """Counts and clocks that only grow; the runner takes their
            difference over a window."""
            s = self.engine.stats()
            gauges = {"running", "waiting", "ttft_p50_ms"}
            out = {k: v for k, v in s.items()
                   if isinstance(v, (int, float)) and not isinstance(v, bool)
                   and k not in gauges}
            out.update({f"cache.{k}": s["cache"][k] for k in (
                "host_gathers", "pool_updates", "cow_copies", "adoptions")})
            out.update({
                # A model that compiles nothing of its own has no such
                # counter; JAX's own event (`compiles`) still counts.
                **{f"model.{name}": getattr(self.model, name, 0) for name in (
                    "prefill_tokens", "prefill_calls", "decode_calls",
                    "jit_compiles")},
                "compiles": self.compiles.count, **self.traced})
            return out

        def state(self) -> dict:
            s = self.engine.stats()
            return {"device": device_facts(), "running": s["running"],
                    "waiting": s["waiting"], "paged": s["paged"],
                    "pool_residency": s["cache"].get("pool_residency")}

        # -- the traced window -------------------------------------------
        def trace_window(self, trace_dir: str, lead_s: float,
                         seconds: float) -> dict:
            import jax

            from benchmarks.harness import trace

            time.sleep(lead_s)
            jax.profiler.start_trace(
                trace_dir, profiler_options=trace.start_options())
            time.sleep(0.5)
            before = self.snapshot()
            with jax.profiler.TraceAnnotation("bench:window"):
                time.sleep(seconds)
            after = self.snapshot()
            jax.profiler.stop_trace()
            return {k: after[k] - before[k] for k in after}

    return BenchLM


# ---------------------------------------------------------------------------
# the harness's side
# ---------------------------------------------------------------------------
class CellFailure(RuntimeError):
    """The cell could not be measured; the message says why."""


def _require(ok: bool, message: str) -> None:
    if not ok:
        raise CellFailure(message)


def trace_directory(cell_name: str) -> str:
    path = os.path.join(manifest.ROOT, ".bench_out", "trace", cell_name)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path, exist_ok=True)
    return path


def summarize(kind: str, outcomes: List[loadgen.Outcome], t_open: float,
              t_close: float) -> dict:
    """Client-side samples of one window. A failed, shed or timed-out
    request counts in `failed` and misses every latency: its time to
    first token is the time until the run gave up on it."""
    t_end = max([t_close] + [o.token_times[-1] for o in outcomes
                             if o.token_times])
    # A closed loop's requests in flight at the close run on to their
    # end: what they receive after it is outside the window.
    lo, hi = (t_open, t_close) if kind == "serve_closed" \
        else (-np.inf, np.inf)
    ttft, gaps, hops, received = [], [], [], 0
    for o in outcomes:
        ttft.append(stats.ttft_ms(o.due, o.token_times) if o.ok
                    else (t_end - o.due) * 1e3)
        gaps.extend(stats.gaps_ms(o.token_times, lo, hi))
        hops.extend((r - s) * 1e3 for r, s in
                    zip(o.token_times, o.server_stamps) if lo <= r <= hi)
        received += sum(1 for t in o.token_times
                        if t_open <= t <= t_close)
    return {
        "attempted": len(outcomes),
        "failed": sum(1 for o in outcomes if not o.ok),
        "errors": sorted({o.error for o in outcomes if o.error})[:3],
        "ttft_ms": ttft, "gaps_ms": gaps, "hop_ms": hops,
        "late_ms": stats.lateness_ms((o.due for o in outcomes),
                                     (o.sent for o in outcomes)),
        "window_s": t_close - t_open,
        "out_tokens_in_window": received,
    }


_PERCENTILE_METRIC = re.compile(r"^serve_(ttft|itl)_p(\d+)_ms$")


def end_to_end(sample: dict, names: List[str]) -> Dict[str, float]:
    """The cell's client-side metrics by name: `serve_ttft_p<q>_ms` and
    `serve_itl_p<q>_ms` are percentiles of the window's samples, so a
    later benchmark PR moves a percentile by changing an entry."""
    out = {}
    for name in names:
        match = _PERCENTILE_METRIC.match(name)
        if match:
            values = sample["ttft_ms" if match.group(1) == "ttft"
                            else "gaps_ms"]
            out[name] = stats.percentile(values, int(match.group(2)))
        elif name == "serve_out_tokens_per_s":
            out[name] = sample["out_tokens_in_window"] / sample["window_s"]
    return out


def describe(sample: dict) -> str:
    def row(values, qs):
        return " ".join(f"p{q}={stats.percentile(values, q):.1f}"
                        for q in qs) + \
            f" mean={sum(values) / max(1, len(values)):.1f} (n={len(values)})"

    return (f"requests={sample['attempted']} failed={sample['failed']} "
            f"ttft_ms {row(sample['ttft_ms'], (50, 75, 90, 95))} "
            f"itl_ms {row(sample['gaps_ms'], (50, 90, 95, 99))} "
            f"out_tokens_per_s="
            f"{sample['out_tokens_in_window'] / sample['window_s']:.1f} "
            f"loadgen_late_ms {row(sample['late_ms'], (50, 95))} "
            f"errors={sample['errors']}")


def run(cell: dict, *, seed: int, seconds: float, trace: bool, t0: float,
        expect_platform: str = "tpu", sweep_rates: Tuple[float, ...] = (),
        timeout_s: float = 1100.0) -> dict:
    """One run of a serving cell; returns what `run.py` prints. With
    `sweep_rates` (open loop only) it runs one window per rate on the same
    replica and prints each: how the knee was found, never a result."""
    from ray_tpu import serve

    settings, traffic, widths = cell["settings"], cell["traffic"], \
        cell["widths"]
    chips = cell["chips"] if expect_platform == "tpu" else 0
    kind = traffic["kind"]
    shapes = loadgen.reachable_shapes(
        traffic, settings["engine"]["block_size"],
        settings["engine"]["max_batch_size"])
    _require(shapes["longest_context"] <= settings["max_seq_len"],
             f"the mix reaches context {shapes['longest_context']}, the "
             f"cell's max_seq_len is {settings['max_seq_len']}")
    family = manifest.family_of(cell)
    spec = {"family": cell["family"], "root": cell["root"],
            "widths": widths, "settings": settings, "seed": seed,
            "trace": trace}
    app = make_deployment(chips).bind(spec)
    try:
        handle = serve.run(app, route_prefix="/bench",
                           _blocking_timeout_s=timeout_s / 2)

        def call(method: str, *args, timeout: float = 120.0):
            return handle.options(method_name=method).remote(
                *args).result(timeout_s=timeout)

        prepared = call("prepare", shapes, timeout=timeout_s)
        device = prepared["device"]
        _require(device["platform"] == expect_platform,
                 f"the replica ran on {device['platform']}, expected "
                 f"{expect_platform}")
        _require(chips == 0 or device["count"] == chips,
                 f"the replica saw {device['count']} devices, the cell "
                 f"asks for {chips}")
        _require(prepared["pool_platforms"] == [expect_platform],
                 f"the KV pool is on {prepared['pool_platforms']}")
        print(f"setup: device={device} warm_prefill_s="
              f"{prepared['warm_prefill_s']:.1f} warm_decode_s="
              f"{prepared['warm_decode_s']:.1f} check_s="
              f"{prepared['check_s']:.1f} compiles_or_cache_fetches="
              f"{prepared['compiles']} held={prepared['held']} "
              f"check={prepared['check']}", flush=True)

        streaming = handle.options(stream=True, method_name="generate")

        def send(request: loadgen.Request) -> Iterator[Tuple[int, float]]:
            yield from streaming.remote({
                "prompt": request.prompt,
                "max_new_tokens": request.max_new_tokens})

        # The handle, the router and the stream path, once, before the
        # window: two short concurrent requests.
        vocab = widths["vocab_size"]
        warm = loadgen.plan(traffic, seed + 1, 1.0, vocab, rate_per_s=2.0)[:2]
        for r in warm:
            r.due, r.max_new_tokens = 0.0, 4
        _, _, warmed = loadgen.run_open_loop(send, warm, drain_s=60)
        _require(all(o.ok for o in warmed),
                 f"warm-up requests failed: {[o.error for o in warmed]}")

        def window(rate: Optional[float], seed: int = seed):
            requests = loadgen.plan(traffic, seed, seconds, vocab,
                                    rate_per_s=rate)
            before = call("snapshot")
            t_open = time.time()
            at_close: dict = {}
            if kind == "serve_open":
                span = loadgen.run_open_loop(
                    send, requests, traffic["drain_s"],
                    on_close=lambda: at_close.update(call("state")))
            else:
                span = loadgen.run_closed_loop(
                    send, requests, traffic["clients"], seconds,
                    traffic["drain_s"])
            after = call("snapshot")
            counters = {k: after[k] - before[k] for k in after}
            sample = summarize(kind, span[2], span[0], span[1])
            sample["at_close"] = at_close
            return t_open, sample, counters, call("state")

        if sweep_rates:
            _require(kind == "serve_open", "only an open loop has a rate")
            for i, rate in enumerate(sweep_rates):
                # Another seed each window: the same one would replay
                # the same token stream and hit the prefix cache.
                _, sample, counters, _ = window(rate, seed + 101 * i)
                print(f"sweep rate={rate}: {describe(sample)} "
                      f"queue_at_close={sample['at_close']['waiting']} "
                      f"running_at_close={sample['at_close']['running']} "
                      f"compiles={counters['compiles']} "
                      f"model_compiles={counters['model.jit_compiles']} "
                      f"preemptions={counters['preemptions']} "
                      f"prefix_hit_tokens={counters['prefix_hit_tokens']} "
                      f"mean_batch="
                      f"{(counters['tokens_generated'] - counters['prefills']) / max(1, counters['paged_steps']):.2f}",
                      flush=True)
            return {"sweep": True}

        traced: dict = {}
        tracer = None
        if trace:
            trace_dir = trace_directory(cell["name"])
            tracer = threading.Thread(
                target=lambda: traced.update(counters=call(
                    "trace_window", trace_dir, min(3.0, 0.15 * seconds),
                    float(settings["trace_seconds"]),
                    timeout=seconds + 300)),
                daemon=True)
            tracer.start()
        t_open, sample, counters, state = window(None)
        setup_s = t_open - t0
        if tracer is not None:
            tracer.join(seconds + 300)
            traced["trace_dir"] = trace_dir
        device = state["device"]
    finally:
        serve.shutdown()
    if "trace_dir" in traced:
        traced["trace"] = trace_mod.reduce_in_subprocess(traced["trace_dir"])

    print(f"window: {describe(sample)}", flush=True)
    print(f"counters: {counters}", flush=True)
    problems = []
    if not prepared["check"]["ok"]:
        problems.append(f"logits off the reference: {prepared['check']}")
    if counters["compiles"] or counters["model.jit_compiles"]:
        problems.append(f"{counters['compiles']} programs compiled inside "
                        f"the window")
    if sample["failed"]:
        problems.append(f"{sample['failed']} requests failed: "
                        f"{sample['errors']}")
    if counters["cache.host_gathers"]:
        problems.append("host gathers on the paged path")
    if not state["paged"] or counters["paged_steps"] <= 0:
        problems.append("no paged decode step ran")
    for problem in problems:
        print(f"NOT CORRECT: {problem}", flush=True)
    check = prepared["check"]
    checks = {
        "logit_rms_gap": [check["max_error"], check["tolerance"]],
        "largest_logit_gap": [check["largest_single_logit"],
                              5 * check["tolerance"]],
        "compiles_in_window": [counters["compiles"]
                               + counters["model.jit_compiles"], 0],
        "failed_requests": [sample["failed"], 0],
        "host_gathers": [counters["cache.host_gathers"], 0]}
    values = dict(end_to_end(sample, [m["name"] for m in cell["end_to_end"]]),
                  setup_s=setup_s)
    ctx = {"cell": cell, "kind": kind, "widths": widths,
           "counts": family.counts(widths, prepared["held"]),
           "peak": cell["peaks"].get(device["kind"]),
           "window_s": sample["window_s"], "counters": counters,
           "client": {key: sample[key] for key in (
               "ttft_ms", "gaps_ms", "hop_ms", "late_ms")},
           "trace": traced.get("trace"),
           "trace_counters": traced.get("counters")}
    return {"correct": not problems, "attempted": sample["attempted"],
            "failed": sample["failed"], "values": values, "ctx": ctx,
            "device": device, "checks": checks}
