"""Runtime microbenchmarks: tasks/s, actor calls/s, put/get latency.

Reference equivalent: `python/ray/_private/ray_perf.py` — the numbers the
reference budgets at 50-300 µs/task (SURVEY §3.2). Run directly:

    python -m ray_tpu.perf              # cluster mode (multi-process)
    python -m ray_tpu.perf --local     # local mode (in-process)
    python -m ray_tpu.perf --attribute # + submit-path breakdown
    python -m ray_tpu.perf --ring      # worker-direct dispatch rings
                                       # (tasks_ring_per_s + honesty
                                       # counters, round 10; round 16
                                       # adds the caller-thread phase:
                                       # tasks_ring_caller_per_s vs the
                                       # loop-hop rate, same cluster)
    python -m ray_tpu.perf --timeline [FILE]
                                       # flight-recorder capture: task
                                       # burst -> merged driver+worker
                                       # Chrome trace (round 12)
    python -m ray_tpu.perf --flight-overhead
                                       # recorder-on vs off tasks/s
    python -m ray_tpu.perf --metrics-overhead
                                       # metrics pipeline on vs off
                                       # tasks/s + push/interval counts
                                       # (round 17)

`--attribute` turns on the per-call attribution profiler
(core/attribution.py) for the driver AND every worker it spawns, then
folds the spans into the output under "attribution": where each
submitted task's time went (encode / lease wait / frame write / push
round trip / worker decode / worker execute), the inline-vs-remote
dispatch split (`submit.inline` / `submit.remote` counts + the
`inline.*` caller-thread stage split), lease batch sizes
(`lease.batch_size`), plus a wire-decode microbench comparing the
validated and post-handshake fast decoders.
That breakdown is what makes the NEXT task-plane regression a lookup
instead of an archaeology project (PROFILE.md has the round-6 table).

Prints one JSON object; also importable (`run_microbench`) so bench.py
and tests can embed the numbers.
"""

from __future__ import annotations

import json
import time
from typing import Any, Dict, List


def _noop():
    return None


def _p50(samples: List[float]) -> float:
    s = sorted(samples) or [float("nan")]
    return s[len(s) // 2]


def _p95(samples: List[float]) -> float:
    s = sorted(samples) or [float("nan")]
    return s[min(len(s) - 1, int(len(s) * 0.95))]


def wire_decode_bench(n: int = 3000) -> Dict[str, float]:
    """Validated vs fast-path decode of a representative TaskSpec, in
    microseconds per message (the worker pays exactly one of these per
    pushed task, fast after the schema handshake)."""
    import msgpack

    from ray_tpu.core.wire import TaskSpec, from_wire, from_wire_fast, to_wire

    payload = msgpack.unpackb(msgpack.packb(to_wire(TaskSpec(
        task_id="ab" * 16, job_id="cd" * 8, name="bench", fn_key="k" * 40,
        args=b"x" * 200, resources={"CPU": 1.0}, owner="127.0.0.1:1")),
        use_bin_type=True), raw=False)
    t0 = time.perf_counter()
    for _ in range(n):
        from_wire(payload)
    t1 = time.perf_counter()
    for _ in range(n):
        from_wire_fast(payload)
    t2 = time.perf_counter()
    return {"validated_us": round((t1 - t0) / n * 1e6, 2),
            "fast_us": round((t2 - t1) / n * 1e6, 2)}


class _Counter:
    def __init__(self):
        self.n = 0

    def inc(self):
        self.n += 1
        return self.n

    async def ainc(self):
        self.n += 1
        return self.n


class _ChainStage:
    def step(self, x):
        return x + 1

    def echo(self, x):
        return x


def run_microbench(local_mode: bool = False,
                   scale: float = 1.0,
                   attribute: bool = False) -> Dict[str, Any]:
    """Returns {metric: value} — throughputs in ops/s, latencies in ms."""
    import numpy as np

    import ray_tpu

    import os

    if attribute:
        from ray_tpu.core import attribution

        # Before init so spawned workers inherit the env flag.
        attribution.enable()
        attribution.reset()
    # More workers than cores just adds scheduler contention on small
    # hosts (every process shares the core with the driver + raylet).
    ncpu = min(4, max(2, os.cpu_count() or 1))
    ray_tpu.init(local_mode=local_mode,
                 **({} if local_mode else {"num_cpus": ncpu}),
                 ignore_reinit_error=True)
    # Two handles on the same function: the default one is
    # inline-eligible (the round-8 same-process fast path), the
    # `_metadata` one opts out so the REMOTE plane keeps being measured
    # — `tasks_per_s` must keep meaning "leased-worker dispatch rate",
    # not become an alias of the inline rate.
    noop = ray_tpu.remote(_noop)
    noop_remote = ray_tpu.remote(_metadata={"inline": False})(_noop)
    out: Dict[str, Any] = {"mode": "local" if local_mode else "cluster"}

    # Warmup (worker spawn, function export).
    ray_tpu.get([noop_remote.remote() for _ in range(10)], timeout=120)

    # 1. Task throughput: N in-flight no-ops, batched get (best of 2
    # rounds — the first round also warms the pipelined lease pool).
    n = max(1, int(1000 * scale))
    best = 0.0
    for _ in range(2):
        t0 = time.perf_counter()
        ray_tpu.get([noop_remote.remote() for _ in range(n)], timeout=300)
        dt = time.perf_counter() - t0
        best = max(best, n / dt)
    out["tasks_per_s"] = round(best, 1)

    # 1b. Inline-eligible tiny-task burst (round 8): the remote rounds
    # above warmed the per-fn exec EMA (exec_us rides every reply), so
    # the default handle now dispatches inline — same ObjectRef
    # semantics, no lease, no push. In local mode the dispatch tiers
    # don't exist; report the same burst for comparability.
    best = 0.0
    for _ in range(2):
        t0 = time.perf_counter()
        ray_tpu.get([noop.remote() for _ in range(n)], timeout=300)
        dt = time.perf_counter() - t0
        best = max(best, n / dt)
    out["tasks_inline_per_s"] = round(best, 1)

    # 2. Sequential task round-trip p50 (submit -> result).
    lat = []
    for _ in range(max(1, int(50 * scale))):
        t0 = time.perf_counter()
        ray_tpu.get(noop_remote.remote(), timeout=60)
        lat.append(time.perf_counter() - t0)
    out["task_roundtrip_p50_ms"] = round(_p50(lat) * 1e3, 3)

    # 3. Actor method calls: sequential p50 + pipelined throughput.
    counter_cls = ray_tpu.remote(num_cpus=0)(_Counter)
    counter = counter_cls.remote()
    ray_tpu.get(counter.inc.remote(), timeout=120)
    lat = []
    for _ in range(max(1, int(50 * scale))):
        t0 = time.perf_counter()
        ray_tpu.get(counter.inc.remote(), timeout=60)
        lat.append(time.perf_counter() - t0)
    out["actor_call_p50_ms"] = round(_p50(lat) * 1e3, 3)
    n = max(1, int(500 * scale))
    t0 = time.perf_counter()
    ray_tpu.get([counter.inc.remote() for _ in range(n)], timeout=300)
    dt = time.perf_counter() - t0
    out["actor_calls_per_s"] = round(n / dt, 1)

    # 4. Object plane: 10 MB put + get (zero-copy read path); p50 AND
    # p95 of 8 samples — the round-5 verdict found a 12x spread hiding
    # behind single samples, so the variance itself is now a reported
    # number (BENCH notes carry both).
    arr = np.zeros(10 * 1024 * 1024 // 4, np.float32)
    puts, gets = [], []
    for i in range(8):
        t0 = time.perf_counter()
        ref = ray_tpu.put(arr)
        puts.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        ray_tpu.get(ref, timeout=60)
        gets.append(time.perf_counter() - t0)
        del ref
        time.sleep(0.1)  # segment-pool refill runs off the hot path
    out["put_10mb_ms"] = round(_p50(puts) * 1e3, 2)
    out["get_10mb_ms"] = round(_p50(gets) * 1e3, 2)
    out["put_10mb_p95_ms"] = round(_p95(puts) * 1e3, 2)
    out["get_10mb_p95_ms"] = round(_p95(gets) * 1e3, 2)
    # Bandwidth view of the same numbers (round-7 data-plane guards):
    # MB moved per second of p50 latency — the single shm write (put)
    # and the zero-copy view materialization (get).
    mb = arr.nbytes / 1e6
    out["put_bw_MBps"] = round(mb / max(_p50(puts), 1e-9), 1)
    out["get_bw_MBps"] = round(mb / max(_p50(gets), 1e-9), 1)

    # 5. Compiled graphs vs lazy DAG: the same 3-actor chain through
    # dag.execute (3 actor tasks/call) and experimental_compile
    # (persistent loops + channels; no per-call task plane). Pipelined
    # per-call cost with a bounded in-flight window — the serving shape
    # the compiled plane exists for.
    from ray_tpu.dag import InputNode

    stage_cls = ray_tpu.remote(num_cpus=0)(_ChainStage)
    stages = [stage_cls.remote() for _ in range(3)]
    ray_tpu.get([s.step.remote(0) for s in stages], timeout=120)
    with InputNode() as inp:
        dag = stages[2].step.bind(
            stages[1].step.bind(stages[0].step.bind(inp)))
    n = max(1, int(200 * scale))

    t0 = time.perf_counter()
    ray_tpu.get([dag.execute(i) for i in range(n)], timeout=600)
    dt = time.perf_counter() - t0
    out["dag_chain_calls_per_s"] = round(n / dt, 1)
    out["dag_chain_call_ms"] = round(dt / n * 1e3, 3)

    compiled = dag.experimental_compile(max_in_flight=16)
    ray_tpu.get(compiled.execute(0), timeout=120)  # warm the loops
    t0 = time.perf_counter()
    refs = [compiled.execute(i) for i in range(n)]
    for r in refs:
        ray_tpu.get(r, timeout=600)
    dt = time.perf_counter() - t0
    out["cgraph_calls_per_s"] = round(n / dt, 1)
    out["cgraph_call_ms"] = round(dt / n * 1e3, 3)
    out["cgraph_vs_dag_speedup"] = round(
        out["dag_chain_call_ms"] / max(out["cgraph_call_ms"], 1e-9), 1)
    compiled.teardown()

    # 6. Array-channel bandwidth: a 2-stage compiled chain moving a 4 MB
    # tensor per execution over `.with_channel("array")` edges (blob-
    # framed pushes, zero-copy landing). MB/s of end-to-end pipeline.
    with InputNode() as inp:
        adag = stages[1].echo.bind(
            stages[0].echo.bind(inp).with_channel("array")
        ).with_channel("array")
    acomp = adag.experimental_compile(max_in_flight=4)
    tensor = np.zeros(4 * 1024 * 1024 // 4, np.float32)
    ray_tpu.get(acomp.execute(tensor), timeout=120)  # warm
    n = max(4, int(24 * scale))
    t0 = time.perf_counter()
    arefs = [acomp.execute(tensor) for _ in range(n)]
    for r in arefs:
        ray_tpu.get(r, timeout=600)
    dt = time.perf_counter() - t0
    out["array_chan_MBps"] = round(n * tensor.nbytes / 1e6 / dt, 1)
    acomp.teardown()
    for s in stages:
        ray_tpu.kill(s)

    ray_tpu.kill(counter)
    if attribute:
        from ray_tpu.core import attribution

        out["attribution"] = attribution.snapshot()
        out["attribution"]["wire_decode_bench"] = wire_decode_bench()
    return out


def run_ring_microbench(scale: float = 1.0,
                        rounds: int = 3) -> Dict[str, Any]:
    """Worker-direct dispatch ring bench (round 10): boots its OWN
    cluster with `submit_ring` on (the flag snapshots at runtime
    construction), measures the remote tiny-task burst riding the
    driver->worker rings, and reports the honesty counters next to the
    rate: enqueues vs doorbells (the steady-state zero-syscall claim —
    doorbells must be ≪ enqueues under load), replies that came back
    over the twin ring, and fallbacks (zero on the happy path).
    Fold-best of `rounds` bursts, same convention as the perf guards.

    Round 16 runs the SAME cluster through two phases so the caller-
    thread tier is compared against the loop-hop ring path with every
    box-noise variable held constant: phase 1 flips the caller tier
    off on the live runtime (the flag is read per-submit on the caller
    thread, nothing is cached), phase 2 flips it back on, warms the
    caller registry (offers only happen on loop-path publishes with
    the flag up), and measures the caller-enqueue burst plus its own
    honesty counters: caller enqueues vs loop-hop fallbacks (the <5%
    bound), ProducerLatch handoffs, and SPSC producer violations
    (must be 0 — both the attribution counter and the writers' own
    re-entrancy sentinels are reported).

    Returns:
      tasks_ring_per_s        : loop-hop remote tiny-task rate
      tasks_ring_caller_per_s : caller-thread-enqueue rate, same ring
      ring_caller_vs_loop     : the caller-tier win (ratio of the two)
      ring_enq / ring_doorbell / ring_reply / ring_fallback : phase-1
      caller_enq / caller_fallback / caller_handoffs /
      caller_violations       : phase-2 honesty counters
      ring_engaged / caller_engaged : tier actually exercised
    """
    import os

    import ray_tpu
    from ray_tpu.core import attribution
    from ray_tpu.core.config import ray_config

    ray_tpu.shutdown()
    saved_cfg = dict(ray_config()._values)
    prev_attr = attribution.enabled
    attribution.enable()
    ncpu = min(4, max(2, os.cpu_count() or 1))
    ray_tpu.init(num_cpus=ncpu, _system_config={
        "submit_ring": True, "task_inline_execution": False,
        "task_caller_dispatch": True})
    out: Dict[str, Any] = {}
    try:
        rt = ray_tpu.core.worker.current_runtime()
        noop = ray_tpu.remote(_noop)
        ray_tpu.get([noop.remote() for _ in range(10)], timeout=120)
        n = max(1, int(1000 * scale))

        # -- phase 1: loop-hop ring path (caller tier off) -------------
        rt._caller_dispatch = False
        attribution.reset()
        best = 0.0
        for _ in range(max(1, rounds)):
            t0 = time.perf_counter()
            ray_tpu.get([noop.remote() for _ in range(n)], timeout=300)
            best = max(best, n / (time.perf_counter() - t0))
        out["tasks_ring_per_s"] = round(best, 1)
        snap = attribution.snapshot()
        for label, key in (("ring.direct_enq", "ring_enq"),
                           ("ring.doorbell", "ring_doorbell"),
                           ("ring.reply", "ring_reply"),
                           ("ring.fallback", "ring_fallback")):
            out[key] = snap.get(label, {}).get("count", 0)
        out["ring_engaged"] = any(
            isinstance(st, dict) and st.get("live")
            for st in rt._worker_rings.values())

        # -- phase 2: caller-thread enqueue, same cluster same rings ---
        rt._caller_dispatch = True
        # Warm burst populates _caller_rings (registry offers ride
        # loop-path publishes) so the measured bursts hit the tier.
        ray_tpu.get([noop.remote() for _ in range(min(n, 100))],
                    timeout=300)
        attribution.reset()
        best = 0.0
        for _ in range(max(1, rounds)):
            t0 = time.perf_counter()
            ray_tpu.get([noop.remote() for _ in range(n)], timeout=300)
            best = max(best, n / (time.perf_counter() - t0))
        out["tasks_ring_caller_per_s"] = round(best, 1)
        snap = attribution.snapshot()
        for label, key in (("submit.caller_enq", "caller_enq"),
                           ("submit.caller_fallback", "caller_fallback"),
                           ("ring.handoff", "caller_handoffs"),
                           ("ring.producer_violation",
                            "caller_violations")):
            out[key] = snap.get(label, {}).get("count", 0)
        # The writers' own re-entrancy sentinels, independent of the
        # attribution plumbing: a violation that raced past a count
        # still shows here.
        out["caller_violations"] += sum(
            getattr(st.get("writer"), "producer_violations", 0)
            for st in rt._worker_rings.values()
            if isinstance(st, dict))
        out["caller_engaged"] = out["caller_enq"] > 0
        out["ring_caller_vs_loop"] = round(
            out["tasks_ring_caller_per_s"]
            / max(out["tasks_ring_per_s"], 1e-9), 2)
    finally:
        ray_tpu.shutdown()
        if not prev_attr:
            attribution.disable()
        # _system_config overrides land in the process-global Config:
        # restore so a later init in this process gets its own flags.
        ray_config()._values.clear()
        ray_config()._values.update(saved_cfg)
    return out


def run_timeline_capture(path: str = "ray_tpu_timeline.json",
                         scale: float = 1.0) -> Dict[str, Any]:
    """`python -m ray_tpu.perf --timeline`: bracket a remote task burst
    with the (always-on) flight recorder and write the MERGED Chrome
    trace — driver ring + every raylet's + every worker's, clock-skew
    aligned — to `path` (open in Perfetto / chrome://tracing).

    Boots its own ring-enabled cluster (inline off) so the trace shows
    all three planes: task events (driver push_rtt + worker exec),
    ring primitive traffic, lease churn, plus a forced gc.collect()
    so collector pauses are visibly on the same timeline.
    """
    import gc
    import os

    import ray_tpu
    from ray_tpu.core import flight
    from ray_tpu.core.config import ray_config

    ray_tpu.shutdown()
    saved_cfg = dict(ray_config()._values)
    ncpu = min(4, max(2, os.cpu_count() or 1))
    ray_tpu.init(num_cpus=ncpu, _system_config={
        "submit_ring": True, "task_inline_execution": False})
    out: Dict[str, Any] = {}
    try:
        noop = ray_tpu.remote(_noop)
        ray_tpu.get([noop.remote() for _ in range(10)], timeout=120)
        n = max(1, int(400 * scale))
        t0 = time.perf_counter()
        ray_tpu.get([noop.remote() for _ in range(n)], timeout=300)
        out["tasks_per_s"] = round(n / (time.perf_counter() - t0), 1)
        gc.collect()  # at least one gc event inside the window

        rt = ray_tpu.core.worker.current_runtime()
        records = [flight.dump(window_s=120.0)]

        async def _collect():
            dumps = []
            for node in await rt._gcs.get_nodes():
                if not node.get("alive", True):
                    continue
                try:
                    client = await rt._raylet_client(node["address"])
                    dumps.append(await client.call(
                        "dump_flight_record", window_s=120.0,
                        timeout=10.0))
                except Exception:  # noqa: BLE001 — skip a dead node
                    pass
            return dumps

        for res in rt._loop.run(_collect(), timeout=30):
            if isinstance(res, dict):
                records.extend(res.get("records", []))
        flight.write_chrome_trace(records, path)
        cats: set = set()
        roles: set = set()
        total = 0
        for rec in records:
            roles.add(rec.get("role"))
            for ev in rec.get("events", ()):
                cats.add(ev[2])
                total += 1
        out.update({
            "timeline_path": os.path.abspath(path),
            "timeline_events": total,
            "timeline_processes": len(records),
            "timeline_roles": sorted(r for r in roles if r),
            "timeline_categories": sorted(cats),
        })
    finally:
        ray_tpu.shutdown()
        ray_config()._values.clear()
        ray_config()._values.update(saved_cfg)
    return out


def run_flight_overhead_bench(scale: float = 1.0,
                              bursts: int = 4) -> Dict[str, Any]:
    """Recorder-on vs recorder-off remote tasks/s — the "cheap when
    on" pin for the flight recorder (guarded at <=10% delta in
    `tests/test_perf_guards.py::test_flight_recorder_overhead`).

    Two sequential clusters (the worker processes read the recorder
    flag from their inherited env at spawn, so it cannot be toggled on
    a live cluster), fold-best of `bursts` same-size bursts on each —
    the same flake discipline as every other guard on a box whose
    stall episodes swing single bursts 2-3x.
    """
    import os

    import ray_tpu
    from ray_tpu.core import flight

    out: Dict[str, Any] = {}
    prev_env = os.environ.get(flight.ENV_FLAG)
    prev_enabled = flight.enabled
    ncpu = min(4, max(2, os.cpu_count() or 1))
    n = max(1, int(800 * scale))

    def measure() -> float:
        noop = ray_tpu.remote(_metadata={"inline": False})(_noop)
        ray_tpu.get([noop.remote() for _ in range(10)], timeout=120)
        best = 0.0
        for _ in range(max(1, bursts)):
            t0 = time.perf_counter()
            ray_tpu.get([noop.remote() for _ in range(n)], timeout=300)
            best = max(best, n / (time.perf_counter() - t0))
        return round(best, 1)

    try:
        ray_tpu.shutdown()
        flight.enable()
        ray_tpu.init(num_cpus=ncpu, ignore_reinit_error=True)
        out["tasks_per_s_flight_on"] = measure()
        ray_tpu.shutdown()
        flight.disable()
        ray_tpu.init(num_cpus=ncpu, ignore_reinit_error=True)
        out["tasks_per_s_flight_off"] = measure()
    finally:
        ray_tpu.shutdown()
        if prev_env is None:
            os.environ.pop(flight.ENV_FLAG, None)
        else:
            os.environ[flight.ENV_FLAG] = prev_env
        flight.enabled = prev_enabled
    out["flight_ratio"] = round(
        out["tasks_per_s_flight_on"]
        / max(out["tasks_per_s_flight_off"], 1e-9), 3)
    return out


def run_metrics_overhead_bench(scale: float = 1.0,
                               bursts: int = 4) -> Dict[str, Any]:
    """Metrics-pipeline-on vs -off remote tasks/s — the "cheap when on"
    pin for the round-17 pushed time-series pipeline (guarded at <=10%
    delta in `tests/test_perf_guards.py::test_metrics_pipeline_overhead`).

    Same discipline as the flight-overhead bench: two sequential
    clusters (workers inherit the env flag at spawn), fold-best of
    `bursts` bursts per side. Before tearing down the ON cluster we
    scrape every raylet's `metrics_push_stats` so the guard can also
    assert the structural invariant: one heartbeat interval produces at
    most one metrics push RPC per node (pushes <= intervals).
    """
    import os

    import ray_tpu
    from ray_tpu.core import metrics_ts

    out: Dict[str, Any] = {}
    prev_env = os.environ.get(metrics_ts.ENV_FLAG)
    prev_enabled = metrics_ts.enabled
    ncpu = min(4, max(2, os.cpu_count() or 1))
    n = max(1, int(800 * scale))

    def measure() -> float:
        noop = ray_tpu.remote(_metadata={"inline": False})(_noop)
        ray_tpu.get([noop.remote() for _ in range(10)], timeout=120)
        best = 0.0
        for _ in range(max(1, bursts)):
            t0 = time.perf_counter()
            ray_tpu.get([noop.remote() for _ in range(n)], timeout=300)
            best = max(best, n / (time.perf_counter() - t0))
        return round(best, 1)

    def scrape_push_stats() -> List[Dict[str, Any]]:
        rt = ray_tpu.core.worker.current_runtime()

        async def _collect():
            stats = []
            for node in await rt._gcs.get_nodes():
                if not node.get("alive", True):
                    continue
                try:
                    client = await rt._raylet_client(node["address"])
                    stats.append(await client.call(
                        "metrics_push_stats", timeout=10.0))
                except Exception:  # noqa: BLE001 — skip a dead node
                    pass
            return stats

        return [s for s in rt._loop.run(_collect(), timeout=30)
                if isinstance(s, dict)]

    try:
        ray_tpu.shutdown()
        metrics_ts.enable()
        ray_tpu.init(num_cpus=ncpu, ignore_reinit_error=True)
        out["tasks_per_s_metrics_on"] = measure()
        stats = scrape_push_stats()
        out["push_pushes"] = sum(s.get("pushes", 0) for s in stats)
        out["push_intervals"] = sum(s.get("intervals", 0) for s in stats)
        out["push_nodes"] = len(stats)
        out["push_recorder_dropped"] = sum(
            s.get("recorder_dropped", 0) for s in stats)
        ray_tpu.shutdown()
        metrics_ts.disable()
        ray_tpu.init(num_cpus=ncpu, ignore_reinit_error=True)
        out["tasks_per_s_metrics_off"] = measure()
    finally:
        ray_tpu.shutdown()
        if prev_env is None:
            os.environ.pop(metrics_ts.ENV_FLAG, None)
        else:
            os.environ[metrics_ts.ENV_FLAG] = prev_env
        metrics_ts.enabled = prev_enabled
    out["metrics_ratio"] = round(
        out["tasks_per_s_metrics_on"]
        / max(out["tasks_per_s_metrics_off"], 1e-9), 3)
    return out


def run_simcluster_bench(n_nodes: int = 100,
                         scale: float = 1.0) -> Dict[str, Any]:
    """Control-plane throughput at N simulated nodes (ISSUE 14): lease
    grants/s through the real spillback policy and placement-group
    creations/s through the real 2PC, measured against one real
    GcsServer with `n_nodes` in-process raylets (core/simcluster.py).
    No OS processes, no sockets — the numbers isolate the control
    plane's own code from box fork/exec noise, so a regression here is
    a scheduling/GCS-path regression, full stop.

    Round 15 adds the WAL-checkpoint measurement (ROADMAP 3c): with the
    node table + PG records + a KV payload populated, kill -9 the GCS
    and time the restart (storage load, WAL replay, resumption scans) —
    `gcs_restart_ms`, guarded by a fold-best ceiling in
    tests/test_perf_guards.py."""
    import asyncio
    import os
    import tempfile

    from ray_tpu.core.simcluster import SimCluster

    n_tasks = max(50, int(400 * scale))
    n_pgs = max(8, int(40 * scale))
    n_kv = max(50, int(200 * scale))

    # At 1000 nodes the compressed sim timers themselves become the
    # load: the heartbeat volume + full-table view refreshes saturate
    # the one event loop, heartbeats fall behind the health deadline,
    # and the false-death/re-register storm never converges (PROFILE
    # round 11). Scale the timers with N like a real deployment would.
    big = n_nodes > 200
    sim_config = ({"raylet_heartbeat_period_ms": 1000,
                   "cluster_view_refresh_ms": 10000,
                   "health_check_period_ms": 2000,
                   "health_check_failure_threshold": 10} if big else None)

    async def bench(storage_path: str) -> Dict[str, Any]:
        cluster = SimCluster(num_nodes=n_nodes, seed=0,
                             storage_path=storage_path,
                             config=sim_config)
        await cluster.start()
        try:
            assert await cluster.wait_until(
                lambda: cluster.registered_count() == n_nodes, timeout=60)
            # Warm the cluster views so spillback has a world model.
            await asyncio.gather(*(cluster.driver.submit_task()
                                   for _ in range(20)))

            t0 = time.perf_counter()
            await asyncio.gather(*(cluster.driver.submit_task()
                                   for _ in range(n_tasks)))
            lease_dt = time.perf_counter() - t0

            t0 = time.perf_counter()
            created = await asyncio.gather(
                *(cluster.driver.create_placement_group(
                    [{"CPU": 1.0}] * 4, strategy="SPREAD")
                  for _ in range(n_pgs)))
            await asyncio.gather(
                *(cluster.driver.remove_placement_group(pg_id)
                  for pg_id, _ in created))
            pg_dt = time.perf_counter() - t0

            assert not cluster.driver.lost
            assert all(state == "CREATED" for _, state in created), (
                [s for _, s in created])
            leaked = cluster.leaked_reservations()

            # -- WAL checkpoint round 2 (ROADMAP 3c): restart time ----
            # Populate "large tables": a KV payload on top of the live
            # node table (every put is write-through, so this also
            # exercises WAL append + fsync), plus standing PGs.
            standing = [
                await cluster.driver.create_placement_group(
                    [{"CPU": 1.0}] * 2, strategy="PACK")
                for _ in range(max(4, n_pgs // 4))]
            payload = os.urandom(4096)
            for i in range(n_kv):
                await cluster.driver._gcs.kv_put(
                    f"bench/restart/{i}".encode(), payload)
            await cluster.gcs.flush_now()
            wal_bytes = 0
            for p in (storage_path, storage_path + ".wal"):
                if os.path.exists(p):
                    wal_bytes += os.path.getsize(p)
            t0 = time.perf_counter()
            cluster.kill_gcs()
            await cluster.restart_gcs()
            restart_ms = (time.perf_counter() - t0) * 1e3
            recovered_nodes = sum(
                1 for n in cluster.gcs.nodes.values() if n.get("alive"))
            recovered_kv = sum(
                1 for k in cluster.gcs.kv if k.startswith("bench/"))
            assert recovered_kv == n_kv, (recovered_kv, n_kv)
            for pg_id, _ in standing:
                await cluster.driver.remove_placement_group(pg_id)
            return {
                "sim_nodes": n_nodes,
                "lease_grants_per_s": round(n_tasks / lease_dt, 1),
                "placements_per_s": round(n_pgs / pg_dt, 1),
                "sim_leaked_reservations": len(leaked),
                "gcs_restart_ms": round(restart_ms, 1),
                "gcs_storage_bytes": wal_bytes,
                "gcs_restart_recovered_nodes": recovered_nodes,
                "gcs_restart_kv_rows": n_kv,
            }
        finally:
            await cluster.stop()

    with tempfile.TemporaryDirectory() as td:
        return asyncio.run(bench(os.path.join(td, "gcs.pkl")))


def run_ha_bench(scale: float = 1.0, n_nodes: int = 0) -> Dict[str, Any]:
    """HA control plane (ISSUE 18): quorum write-through throughput and
    client-observed failover latency on a 3-replica GCS.

    `ha_failover_ms` is the number that matters: wall time from kill -9
    of the LEADER (mid task burst) to the first quorum-ACKED write on
    whoever wins the election — election + promotion recovery + client
    redirect, measured where a user feels it. Several failover rounds
    run and the best is reported (fold-best: scheduling noise only ever
    inflates). The merged per-term leader map rides along so the guard
    can assert election SAFETY (exactly one leader per term) on every
    run, not just speed."""
    import asyncio
    import os
    import tempfile

    from ray_tpu.core.simcluster import SimCluster

    n_nodes = n_nodes or max(20, int(100 * scale))
    n_writes = max(30, int(200 * scale))
    failover_rounds = 2

    async def bench(storage_path: str) -> Dict[str, Any]:
        cluster = SimCluster(num_nodes=n_nodes, num_gcs=3, seed=0,
                             storage_path=storage_path)
        await cluster.start()
        try:
            assert await cluster.wait_until(
                lambda: cluster.gcs is not None
                and cluster.registered_count() == n_nodes, timeout=60)

            # Replicated write-through throughput: every put is a WAL
            # append + quorum commit before the ack.
            payload = os.urandom(512)
            t0 = time.perf_counter()
            for i in range(n_writes):
                await cluster.driver._gcs.kv_put(
                    f"ha/bench/{i}".encode(), payload)
            write_dt = time.perf_counter() - t0

            fo_ms = []
            for _ in range(failover_rounds):
                burst = asyncio.ensure_future(asyncio.gather(
                    *(cluster.driver.submit_task(hold_s=0.002)
                      for _ in range(50))))
                await asyncio.sleep(0.05)  # land the kill mid-burst
                t0 = time.perf_counter()
                killed = cluster.kill_leader()
                assert killed is not None
                await cluster.driver._gcs.kv_put(b"ha/failover", payload)
                fo_ms.append((time.perf_counter() - t0) * 1e3)
                results = await burst
                assert all(results), "task lost across failover"
                await cluster.restart_gcs(killed)
                assert await cluster.wait_until(
                    lambda: cluster.gcs is not None and all(
                        g is not None
                        for g in cluster.gcs_replicas.values()),
                    timeout=30)
                await asyncio.sleep(0.3)  # rejoined replica catches up

            # Election-safety observables, merged across replicas.
            leaders_by_term: Dict[str, str] = {}
            split_brain = 0
            elections = 0
            for g in cluster.gcs_replicas.values():
                if g is None or g.replication is None:
                    continue
                elections += g.replication.elections
                for term, ldr in g.replication.leaders_by_term.items():
                    if leaders_by_term.setdefault(str(term), ldr) != ldr:
                        split_brain += 1
            status = cluster.gcs.replication.status()
            return {
                "sim_nodes": n_nodes,
                "ha_replicas": 3,
                "ha_failover_ms": round(min(fo_ms), 1),
                "ha_failover_rounds_ms": [round(x, 1) for x in fo_ms],
                "ha_write_through_per_s": round(n_writes / write_dt, 1),
                "ha_elections": elections,
                "ha_replication_lag": status["replication_lag"],
                "ha_term": status["term"],
                "ha_leaders_by_term": leaders_by_term,
                "ha_split_brain_terms": split_brain,
            }
        finally:
            await cluster.stop()

    with tempfile.TemporaryDirectory() as td:
        return asyncio.run(bench(os.path.join(td, "gcs.pkl")))


def run_llm_serve_bench(scale: float = 1.0) -> Dict[str, Any]:
    """LLM-serving scenario: the continuous-batching engine vs the
    `@serve.batch`-style static policy on the SAME mixed-length
    workload, plus shedding behavior under 2x overload.

    Both sides run the identical `InferenceEngine` loop (same KV-cache
    manager, same bookkeeping, same deterministic TinyLM with a 1 ms
    simulated model-dispatch cost per prefill/decode call) — only the
    admission policy differs, so the ratio measures iteration-level
    scheduling itself: static pays the batch's long pole at shrinking
    occupancy (28 near-empty decode calls for one 32-token straggler),
    continuous refills those slots from the queue.

    Returns:
      llm_engine_tok_s / llm_static_tok_s : generated tokens per second
      llm_engine_vs_static               : the continuous-batching win
      llm_ttft_p50_ms                    : submit -> first-token median
      llm_overload_shed / llm_overload_p99_ms : 2x-overload behavior
        behind the proxy's admission gate (sheds counted pre-queue;
        p99 of SERVED requests must stay bounded)
      llm_prefix_warm_vs_cold            : prefix-sharing win — the
        SAME shared-system-prompt workload through the identical loop
        with sharing on (warm: one prefill, every conversation adopts
        the prompt's blocks) vs off (cold: every request re-prefills),
        with warm/cold TTFT p50s, llm_prefix_hit_tokens and
        llm_prefix_cow_copies riding along
      llm_paged_steps / llm_paged_host_gathers : the mixed workload's
        engine ran its decode steps through `decode_paged` and gathered
        no sequence's KV on the host
    """
    import numpy as np  # noqa: F401  (engine dependency, imported early)

    from ray_tpu.serve._private.proxy import _AdmissionGate
    from ray_tpu.serve.engine import (EngineConfig, EngineOverloadedError,
                                      InferenceEngine, TinyLM)

    out: Dict[str, Any] = {}

    def workload():
        reqs = []
        for i in range(max(8, int(48 * scale))):
            if i % 8 == 0:
                reqs.append(([3 + (i % 11), 5, 7], 32))    # long pole
            else:
                reqs.append(([2 + (i % 13), 4], 4))        # short
        return reqs

    step_cost = 0.001
    for policy in ("continuous", "static"):
        eng = InferenceEngine(
            TinyLM(step_delay_s=step_cost),
            EngineConfig(max_batch_size=8, block_size=8, num_blocks=96,
                         max_queue=256, policy=policy))
        reqs = workload()
        t0 = time.perf_counter()
        streams = [eng.submit(p, n) for p, n in reqs]
        while eng.step():
            pass
        dt = time.perf_counter() - t0
        tokens = eng.tokens_generated
        assert all(s.finished for s in streams)
        key = "llm_engine" if policy == "continuous" else "llm_static"
        out[f"{key}_tok_s"] = round(tokens / dt, 1)
        out[f"{key}_steps"] = eng.steps
        if policy == "continuous":
            st = eng.stats()
            out["llm_ttft_p50_ms"] = st["ttft_p50_ms"]
            out["llm_paged_steps"] = st["paged_steps"]
            out["llm_paged_host_gathers"] = st["cache"]["host_gathers"]
    out["llm_engine_vs_static"] = round(
        out["llm_engine_tok_s"] / max(out["llm_static_tok_s"], 1e-9), 2)

    # -- 2x overload through the admission gate ------------------------
    # Service capacity ~ max_batch tokens per step_cost; offer double
    # that arrival rate for a fixed window. The gate caps in-flight at
    # the engine's own bound, so excess arrivals shed in microseconds
    # and the p99 of SERVED requests stays a function of queue bound x
    # service time, not of the offered load.
    eng = InferenceEngine(
        TinyLM(step_delay_s=step_cost),
        EngineConfig(max_batch_size=8, block_size=8, num_blocks=96,
                     max_queue=16, policy="continuous"))
    eng.start()
    gate = _AdmissionGate(max_inflight=24)
    capacity_rps = 8 / (4 * step_cost)     # ~batch/step per short req
    offered_rps = 2 * capacity_rps
    window_s = 1.2
    interval = 1.0 / offered_rps
    shed = 0
    done: list = []
    lock_t0 = time.perf_counter()
    submitted = []
    next_at = lock_t0
    while time.perf_counter() - lock_t0 < window_s:
        now = time.perf_counter()
        if now < next_at:
            time.sleep(min(next_at - now, 0.001))
            continue
        next_at += interval
        inflight = eng.batch_occupancy() + eng.queue_depth()
        if gate.check(inflight) is not None:
            shed += 1
            continue
        try:
            submitted.append((time.perf_counter(),
                              eng.submit([5, 9], 4)))
        except EngineOverloadedError:
            shed += 1
    for t_sub, stream in submitted:
        for _ in stream:
            pass
        # finished_at is stamped by the engine thread at retirement, so
        # the latency is submit -> completion, not submit -> drain.
        done.append(stream.finished_at - t_sub)
    eng.stop()
    lat = sorted(done)
    out["llm_overload_shed"] = shed
    out["llm_overload_served"] = len(done)
    out["llm_overload_p99_ms"] = round(
        lat[min(len(lat) - 1, int(len(lat) * 0.99))] * 1e3, 1) \
        if lat else None

    # -- prefix-sharing workload: shared system prompt, N convos ------
    # A fleet-wide 80-token system prompt (5 full 16-token blocks)
    # fronts every conversation; per-token prefill cost makes the
    # compute half of sharing measurable. Warm = prefix_sharing on
    # (first admission prefills the prompt once, later ones adopt its
    # blocks and prefill only their 3-token tail); cold = sharing off
    # through the IDENTICAL loop, so the ratio measures prefix reuse
    # itself. Two truncated re-asks (mid-block proper prefixes of the
    # shared doc) exercise the full-hit + COW path.
    sys_prompt = [7 + (i % 19) for i in range(80)]

    def prefix_workload():
        reqs = [(sys_prompt + [2 + (i % 9), 3 + (i % 5), 4 + (i % 7)],
                 8) for i in range(max(6, int(24 * scale)))]
        reqs += [(sys_prompt[:76], 8), (sys_prompt[:70], 8)]
        return reqs

    for mode, sharing in (("warm", True), ("cold", False)):
        eng = InferenceEngine(
            TinyLM(step_delay_s=step_cost,
                   prefill_token_delay_s=0.0004),
            EngineConfig(max_batch_size=8, block_size=16,
                         num_blocks=160, max_queue=256,
                         prefix_sharing=sharing))
        reqs = prefix_workload()
        t0 = time.perf_counter()
        streams = [eng.submit(p, n) for p, n in reqs]
        while eng.step():
            pass
        dt = time.perf_counter() - t0
        assert all(s.finished for s in streams)
        st = eng.stats()
        out[f"llm_prefix_{mode}_tok_s"] = round(
            eng.tokens_generated / dt, 1)
        out[f"llm_prefix_{mode}_ttft_p50_ms"] = st["ttft_p50_ms"]
        if sharing:
            out["llm_prefix_hit_tokens"] = eng.prefix_hit_tokens
            out["llm_prefix_cow_copies"] = eng.cache.cow_copies
    out["llm_prefix_warm_vs_cold"] = round(
        out["llm_prefix_warm_tok_s"]
        / max(out["llm_prefix_cold_tok_s"], 1e-9), 2)
    out["llm_prefix_ttft_cold_over_warm"] = round(
        out["llm_prefix_cold_ttft_p50_ms"]
        / max(out["llm_prefix_warm_ttft_p50_ms"], 1e-9), 2)
    return out


def run_fleet_bench(scale: float = 1.0) -> Dict[str, Any]:
    """Multi-replica serving fleet: cross-replica prefix shipping and
    conversation recovery, 3 in-process `InferenceEngine` replicas
    behind the KV-cache-aware `ServeFleet` router.

    Phase 1 — warm-everywhere vs cold-per-replica: the SAME burst of
    shared-system-prompt conversations (80-token prompt, 5 sealed
    16-token blocks, simulated per-token prefill cost) through the
    identical fleet twice. Cold: KV-aware routing and shipping OFF —
    pure least-loaded spread, each replica pays its own full system-
    prompt prefill. Warm: routing + shipping ON after one warm-up
    conversation on one replica — overload spill moves excess
    conversations to cold replicas, but each spill ships the sealed
    prompt chain first, so the spilled conversation prefills only its
    3-token tail. The ratio measures the fleet layer itself (the
    per-replica engines are identical, local prefix sharing on in both).

    Phase 2 — recovery: a seeded `crash_after` kills a replica on its
    nth streamed token mid-decode; the fleet migrates the conversation
    to a survivor which re-prefills through its radix index and
    continues. Recovery latency = kill -> first post-recovery token;
    the output is asserted token-for-token against the no-fault oracle.

    Returns:
      fleet_warm_tok_s / fleet_cold_tok_s / fleet_warm_vs_cold
      fleet_cold_ttft_p50_ms      : TTFT when every replica re-prefills
      fleet_remote_warm_ttft_p50_ms : TTFT of conversations whose
        prefix was shipped in (must beat cold re-prefill)
      fleet_ttft_cold_over_remote : the shipping TTFT win
      fleet_prefix_ships / fleet_prefix_ship_tokens
      fleet_recovery_ms           : replica kill -> first survivor token
      fleet_recoveries / fleet_lost_conversations
    """
    from ray_tpu.core.faults import FaultPlan
    from ray_tpu.serve.engine import EngineConfig, TinyLM
    from ray_tpu.serve.fleet import FleetConfig, ServeFleet

    out: Dict[str, Any] = {}
    sys_prompt = [7 + (i % 19) for i in range(80)]
    n_convs = max(9, int(12 * scale))
    max_new = 16

    def econf() -> EngineConfig:
        return EngineConfig(max_batch_size=8, block_size=16,
                            num_blocks=160, max_queue=256)

    def model():
        return TinyLM(vocab_size=64, prefill_token_delay_s=0.0008)

    def run_phase(kv_routing: bool, shipping: bool):
        fleet = ServeFleet(FleetConfig(
            model_factory=model, num_replicas=3,
            engine_config=econf(), shipping=shipping,
            kv_routing=kv_routing, digest_max_age_s=0.01))
        fleet.start()
        try:
            if shipping:
                # One warm-up conversation seals the prompt on exactly
                # one replica; the measured burst then finds the fleet
                # in its steady state: one holder, two cold peers.
                warm = fleet.submit(sys_prompt + [2, 3, 4], 4,
                                    session_id="warmup")
                for _ in warm.stream:
                    pass
                time.sleep(0.05)   # let the holder's digest publish
            t0 = time.perf_counter()
            convs = [fleet.submit(
                sys_prompt + [2 + (i % 9), 3 + (i % 5), 4 + (i % 7)],
                max_new, session_id=f"s{i}") for i in range(n_convs)]
            tokens = 0
            for c in convs:
                tokens += sum(1 for _ in c.stream)
            dt = time.perf_counter() - t0
            ttfts = sorted((c.first_token_at - c.submitted_at)
                           for c in convs if c.first_token_at)
            shipped_ttfts = sorted(
                (c.first_token_at - c.submitted_at)
                for c in convs if c.shipped and c.first_token_at)
            return (tokens / dt, ttfts, shipped_ttfts,
                    fleet.prefix_ships, fleet.prefix_ship_tokens,
                    fleet.lost_conversations)
        finally:
            fleet.stop()

    cold_tok_s, cold_ttfts, _, _, _, cold_lost = run_phase(
        kv_routing=False, shipping=False)
    warm_tok_s, _, ship_ttfts, ships, ship_tokens, warm_lost = \
        run_phase(kv_routing=True, shipping=True)
    out["fleet_cold_tok_s"] = round(cold_tok_s, 1)
    out["fleet_warm_tok_s"] = round(warm_tok_s, 1)
    out["fleet_warm_vs_cold"] = round(warm_tok_s / max(cold_tok_s,
                                                       1e-9), 2)
    out["fleet_cold_ttft_p50_ms"] = round(
        cold_ttfts[len(cold_ttfts) // 2] * 1e3, 1) if cold_ttfts else None
    out["fleet_remote_warm_ttft_p50_ms"] = round(
        ship_ttfts[len(ship_ttfts) // 2] * 1e3, 1) if ship_ttfts else None
    out["fleet_ttft_cold_over_remote"] = (
        round(out["fleet_cold_ttft_p50_ms"]
              / max(out["fleet_remote_warm_ttft_p50_ms"], 1e-9), 2)
        if ship_ttfts and cold_ttfts else None)
    out["fleet_prefix_ships"] = ships
    out["fleet_prefix_ship_tokens"] = ship_tokens

    # -- phase 2: seeded kill mid-decode, recovery on a survivor -------
    plan = FaultPlan(seed=19)
    fleet = ServeFleet(FleetConfig(
        model_factory=lambda: TinyLM(vocab_size=64,
                                     step_delay_s=0.002),
        num_replicas=3, engine_config=econf(),
        digest_max_age_s=0.01, fault_plan=plan))
    t_kill: list = []

    def kill(dst: str):
        t_kill.append(time.perf_counter())
        fleet.kill_replica(dst)

    plan.crash_after("replica-0", 8, method="token", on_crash=kill)
    fleet.start()
    try:
        conv = fleet.submit(sys_prompt + [5], 40, session_id="r0")
        got = list(conv.stream)
        want = TinyLM(vocab_size=64).oracle(sys_prompt + [5], 40)
        assert got == want, "recovered stream diverged from oracle"
        assert conv.recovered_token_at is not None and t_kill
        out["fleet_recovery_ms"] = round(
            (conv.recovered_token_at - t_kill[0]) * 1e3, 1)
        out["fleet_recoveries"] = fleet.recoveries
        out["fleet_lost_conversations"] = (
            cold_lost + warm_lost + fleet.lost_conversations)
    finally:
        fleet.stop()
    return out


def format_attribution(attr: Dict[str, Any]) -> str:
    """Human table for `python -m ray_tpu.perf --attribute`."""
    lines = [f"{'stage':28s} {'count':>8s} {'mean_us':>10s} "
             f"{'total_ms':>10s} {'max_us':>10s}"]
    for label, s in attr.items():
        if label == "wire_decode_bench":
            continue
        if "mean_us" not in s:
            # Dimensionless distribution (attribution.value — e.g.
            # lease.batch_size): mean/max in the sample's own units.
            lines.append(f"{label:28s} {s['count']:>8d} "
                         f"{s['mean']:>10.1f} {s['total']:>10.1f} "
                         f"{s['max']:>10.1f}")
            continue
        lines.append(f"{label:28s} {s['count']:>8d} {s['mean_us']:>10.1f} "
                     f"{s['total_ms']:>10.1f} {s['max_us']:>10.1f}")
    bench = attr.get("wire_decode_bench")
    if bench:
        lines.append(f"{'wire decode (validated)':28s} {'-':>8s} "
                     f"{bench['validated_us']:>10.2f}")
        lines.append(f"{'wire decode (fast path)':28s} {'-':>8s} "
                     f"{bench['fast_us']:>10.2f}")
    return "\n".join(lines)


def main() -> None:
    import argparse

    p = argparse.ArgumentParser()
    p.add_argument("--local", action="store_true")
    p.add_argument("--scale", type=float, default=1.0)
    p.add_argument("--attribute", action="store_true",
                   help="profile the submit path per stage and include "
                        "the breakdown in the output JSON")
    p.add_argument("--llm-serve", action="store_true",
                   help="run ONLY the in-process LLM-serving scenario "
                        "(continuous vs static batching, TTFT, 2x-"
                        "overload shedding); no cluster is booted")
    p.add_argument("--fleet", action="store_true",
                   help="run ONLY the multi-replica serving-fleet "
                        "scenario (KV-aware routing, cross-replica "
                        "prefix shipping warm-vs-cold, seeded replica "
                        "kill -> conversation recovery); no cluster is "
                        "booted")
    p.add_argument("--ring", action="store_true",
                   help="run ONLY the worker-direct dispatch-ring "
                        "bench (boots a ring-enabled cluster, measures "
                        "tasks_ring_per_s + the enqueue/doorbell/"
                        "fallback honesty counters, then the caller-"
                        "thread phase: tasks_ring_caller_per_s + "
                        "caller enqueue/fallback/handoff/violation "
                        "counters on the same cluster)")
    p.add_argument("--timeline", nargs="?", const="ray_tpu_timeline.json",
                   default=None, metavar="FILE",
                   help="bracket a task burst with the flight recorder "
                        "and write the merged driver+raylet+worker "
                        "Chrome-trace JSON to FILE (default "
                        "ray_tpu_timeline.json); open in Perfetto")
    p.add_argument("--flight-overhead", action="store_true",
                   help="measure recorder-on vs recorder-off tasks/s "
                        "(the <=10%% 'cheap when on' pin)")
    p.add_argument("--metrics-overhead", action="store_true",
                   help="measure metrics-pipeline-on vs -off tasks/s "
                        "plus per-node push/interval counters (the "
                        "round-17 <=10%% pin + the one-push-per-"
                        "heartbeat structural invariant)")
    p.add_argument("--simcluster", action="store_true",
                   help="run ONLY the simulated-raylet control-plane "
                        "bench: lease grants/s and placement-group "
                        "creations/s at --sim-nodes in-process nodes "
                        "against a real GcsServer; no cluster processes")
    p.add_argument("--sim-nodes", type=int, default=100,
                   help="node count for --simcluster (default 100)")
    p.add_argument("--ha", action="store_true",
                   help="run ONLY the HA control-plane bench: quorum "
                        "write-through throughput and leader kill -9 -> "
                        "first-acked-write failover latency on a "
                        "3-replica GCS, plus the merged one-leader-per-"
                        "term safety observables; no cluster processes")
    args = p.parse_args()
    import ray_tpu

    if args.ha:
        print(json.dumps(run_ha_bench(scale=args.scale)))
        return
    if args.simcluster:
        print(json.dumps(run_simcluster_bench(n_nodes=args.sim_nodes,
                                              scale=args.scale)))
        return
    if args.llm_serve:
        print(json.dumps(run_llm_serve_bench(scale=args.scale)))
        return
    if args.fleet:
        print(json.dumps(run_fleet_bench(scale=args.scale)))
        return
    if args.ring:
        print(json.dumps(run_ring_microbench(scale=args.scale)))
        return
    if args.timeline is not None:
        print(json.dumps(run_timeline_capture(path=args.timeline,
                                              scale=args.scale)))
        return
    if args.flight_overhead:
        print(json.dumps(run_flight_overhead_bench(scale=args.scale)))
        return
    if args.metrics_overhead:
        print(json.dumps(run_metrics_overhead_bench(scale=args.scale)))
        return

    result = run_microbench(local_mode=args.local, scale=args.scale,
                            attribute=args.attribute)
    print(json.dumps(result))
    if args.attribute:
        import sys

        print(format_attribution(result["attribution"]), file=sys.stderr)
    ray_tpu.shutdown()


if __name__ == "__main__":
    main()
