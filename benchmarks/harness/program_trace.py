"""The program's own spans and the device's programs, from the trace a
cell's `--trace 1` run wrote.

`trace.py` reduces the device's op line under the benchmark's `bench:`
spans. This module reads what the program itself puts on the profiler's
clock: the host events whose names start with `rt:` (`flight.span`, one
line a thread: the engine loop's phases, the model's host side of a call,
a trainer's data wait and report) and the device plane's module line (one
event a run of a jitted program, named `jit_<function>(<id>)`). Per span
name it gives the count, the host time, the self time (duration less what
child spans on the same thread cover) and the device-idle time inside
that self time; per module name the count and the device-busy time
inside its events; and the share of the window's device-idle time that
lies in the self time of some named span other than `engine.step`, whose
self time is "the loop, no phase named".

A program without such spans (the parent of the PR that added them), a
run with no operation on a device (the CPU tests) or no trace at all
gives None, and the readers leave their metrics out.

`load` needs JAX's `ProfileData`; the harness process stays off JAX, so
`reduced` runs it in a child pinned to the CPU and caches the result as
JSON beside the trace: the three readers parse once.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import Dict, List, Optional, Sequence, Tuple

from benchmarks.harness import manifest
from benchmarks.harness.trace import (DEVICE_PLANE, OP_LINE, WINDOW_SPAN,
                                      Event, Interval, clip, find_xplane,
                                      gaps, overlap, total, union,
                                      window_of)

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SPAN_PREFIX = "rt:"
MODULE_LINE = "XLA Modules"
# The loop's container: idle time in its self time has no phase's name.
UNNAMED = "engine.step"


def module_name(event_name: str) -> str:
    """`jit_decode_paged(4381957236)` -> `jit_decode_paged`."""
    return event_name.partition("(")[0]


def load(path: str) -> dict:
    """{"threads": {line: [Event]}, "ops": [Event], "modules": [Event],
    "window": Interval | None}: the `rt:` spans by host thread (prefix
    dropped), the op and module events of the first device, and the
    benchmark's window."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    threads: Dict[str, List[Event]] = {}
    devices: Dict[int, dict] = {}
    windows: List[Event] = []
    for plane in data.planes:
        match = DEVICE_PLANE.match(plane.name)
        for number, line in enumerate(plane.lines):
            if match:
                if line.name in (OP_LINE, MODULE_LINE):
                    devices.setdefault(int(match.group(1)), {})[
                        line.name] = [
                        (e.name, int(e.start_ns),
                         int(e.start_ns + e.duration_ns))
                        for e in line.events]
                continue
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    threads.setdefault(
                        f"{plane.name}/{number}:{line.name}", []).append(
                        (e.name[len(SPAN_PREFIX):], int(e.start_ns),
                         int(e.start_ns + e.duration_ns)))
                elif e.name == WINDOW_SPAN:
                    windows.append((e.name, int(e.start_ns),
                                    int(e.start_ns + e.duration_ns)))
    first = devices[min(devices)] if devices else {}
    ops = first.get(OP_LINE, [])
    return {"threads": threads, "ops": ops,
            "modules": [(module_name(n), s, e)
                        for n, s, e in first.get(MODULE_LINE, [])],
            "window": window_of({"spans": windows, "devices": {0: ops}})}


def self_intervals(spans: Sequence[Event]) -> List[Tuple[str, Interval,
                                                         List[Interval]]]:
    """For the spans of ONE thread: (name, interval, the parts of it no
    child span covers). A span is another's child when it lies inside
    it; spans of one thread nest and never cross."""
    out: List[Tuple[str, Interval, List[Interval]]] = []
    stack: List[Tuple[str, int, int, List[Interval]]] = []

    def close() -> None:
        name, start, end, children = stack.pop()
        out.append((name, (start, end),
                    gaps(union(children), start, end)))

    for name, start, end in sorted(spans, key=lambda e: (e[1], -e[2])):
        while stack and start >= stack[-1][2]:
            close()
        if stack:
            stack[-1][3].append((start, min(end, stack[-1][2])))
        stack.append((name, start, end, []))
    while stack:
        close()
    return out


def reduce(threads: Dict[str, List[Event]], ops: Sequence[Event],
           modules: Sequence[Event], window: Optional[Interval]
           ) -> Optional[dict]:
    """Plain arithmetic on `(name, start_ns, end_ns)` tuples; None when
    no operation ran on the device inside the window."""
    if window is None:
        return None
    lo, hi = window
    busy = union(clip(((s, e) for _, s, e in ops), lo, hi))
    if not busy:
        return None
    ns = 1e-9
    idle = gaps(busy, lo, hi)
    spans: Dict[str, dict] = {}
    named: List[Interval] = []
    for events in threads.values():
        for name, (start, end), own in self_intervals(events):
            if not lo <= start < hi:
                continue
            own = clip(own, lo, hi)
            stat = spans.setdefault(name, {
                "count": 0, "host_s": 0.0, "self_s": 0.0,
                "device_idle_s": 0.0})
            stat["count"] += 1
            stat["host_s"] += (end - start) * ns
            stat["self_s"] += total(own) * ns
            stat["device_idle_s"] += sum(
                overlap(idle, a, b) for a, b in own) * ns
            if name != UNNAMED:
                named.extend(own)
    # Two threads can be inside named spans at once: an idle instant
    # counts once.
    named = union(named)
    idle_s = total(idle) * ns
    attributed_s = sum(overlap(named, a, b) for a, b in idle) * ns
    by_module: Dict[str, dict] = {}
    for name, start, end in modules:
        if not lo <= start < hi:
            continue
        stat = by_module.setdefault(name, {"count": 0, "device_s": 0.0})
        stat["count"] += 1
        stat["device_s"] += overlap(busy, start, min(end, hi)) * ns
    return {"window_s": (hi - lo) * ns, "device_idle_s": idle_s,
            "idle_attributed_s": attributed_s,
            # No share without a span of the program to attribute to.
            "idle_attributed_share": (attributed_s / idle_s
                                      if idle_s and spans else None),
            "spans": spans, "modules": by_module}


def reduce_dir(trace_dir: str) -> Optional[dict]:
    path = find_xplane(trace_dir)
    if not path:
        return None
    loaded = load(path)
    return reduce(loaded["threads"], loaded["ops"], loaded["modules"],
                  loaded["window"])


def cache_path(trace_dir: str) -> Optional[str]:
    path = find_xplane(trace_dir)
    return path[:-len(".xplane.pb")] + ".program_trace.json" if path \
        else None


def reduced(trace_dir: str, timeout_s: float = 300.0) -> Optional[dict]:
    """`reduce_dir` in a child pinned to the CPU (as
    `trace.reduce_in_subprocess`), once a trace: the result is kept as
    JSON beside the `.xplane.pb`."""
    cached = cache_path(trace_dir)
    if cached is None:
        return None
    if not os.path.isfile(cached):
        done = subprocess.run(
            [sys.executable, "-m", "benchmarks.harness.program_trace",
             trace_dir], cwd=REPO,
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
            stdout=subprocess.PIPE, text=True, timeout=timeout_s,
            check=True)
        with open(cached, "w") as f:
            f.write(done.stdout.strip().splitlines()[-1])
    with open(cached) as f:
        return json.load(f)


def of_run(ctx: dict) -> Optional[dict]:
    """What a layer-metric reader takes: the reduction of the trace this
    run wrote, None for a run that traced no device."""
    if not ctx.get("trace"):
        return None
    return reduced(os.path.join(manifest.ROOT, ".bench_out", "trace",
                                ctx["cell"]["name"]))


def module_seconds(reduction: dict, prefix: str) -> Tuple[float, int]:
    """Device seconds and runs of the programs whose name starts with
    `prefix` (`jit_prefill` takes `jit_prefill_cached` and
    `jit_prefill_paged` with it)."""
    found = [m for name, m in reduction["modules"].items()
             if name.startswith(prefix)]
    return (sum(m["device_s"] for m in found),
            sum(m["count"] for m in found))


if __name__ == "__main__":
    print(json.dumps(reduce_dir(sys.argv[1])))
