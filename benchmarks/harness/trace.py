"""From a profiler trace (`.xplane.pb`) to the few numbers the metrics
read. Kept with the benchmark so that every PR reduces a trace the same
way.

A trace has planes (one per device, one for the host), planes have lines,
lines have events with a start and a duration in nanoseconds. Device work
is the events of the device planes' op line ("XLA Ops" on a TPU). On a
mesh the compiler turns gathers and scatters into asynchronous ring
steps: their transfers are the collective-named events of the "Async XLA
Ops" line (the op line holds only their start and done stubs), so
collective time is the union over both lines. The benchmark's own host
spans are `jax.profiler.TraceAnnotation`s whose names start with `bench:`.
The traced window is the `bench:window` span when there is one, else the
extent of the device events.

`load` needs JAX's `ProfileData` (no device); the rest is plain Python
on `(name, start_ns, end_ns)` tuples. The harness reduces a trace in a
process of its own (`reduce_in_subprocess`), pinned to the CPU: the
process that owns the chip only writes the file.
"""

from __future__ import annotations

import glob
import json
import os
import re
import subprocess
import sys
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, int, int]           # name, start_ns, end_ns
Interval = Tuple[int, int]

SPAN_PREFIX = "bench:"
WINDOW_SPAN = "bench:window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|collective-permute|"
    r"all-to-all|collective-broadcast|async-collective)")
# Ops that only contain other ops of the same line (a scanned layer stack
# is one `while`): they count as busy time, not as operations of their own.
CONTAINER = re.compile(r"^(while|conditional|call)[.\d]*( |$)")
_ARRAY_TYPE = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")


def op_name(event_name: str) -> str:
    """`%fusion.77 = f32[16,8,512,2,16,128]{...} fusion(...)` ->
    `fusion.77 f32[16,8,512,2,16,128]`: the instruction and the first
    array type of its result, which is what tells two programs' `fusion.77`
    apart."""
    head, _, rest = event_name.partition(" = ")
    shape = _ARRAY_TYPE.search(rest)
    return head.lstrip("%") + (f" {shape.group(0)}" if shape else "")


def start_options():
    """Profiler options for a benchmark trace: the Python tracer off (it
    records every Python call, half a million events in eight seconds of
    serving, and slows the host it measures); host TraceMe spans and the
    device stay on."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    return options


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def load(path: str) -> dict:
    """{"devices": {index: [Event]}, "transfers": {index: [Event]},
    "spans": [Event]}: the op events of every device plane, the
    collective transfers of its async line, and the benchmark's own host
    spans."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[int, List[Event]] = {}
    transfers: Dict[int, List[Event]] = {}
    spans: List[Event] = []
    for plane in data.planes:
        match = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if match and line.name in (OP_LINE, ASYNC_LINE):
                events = [(op_name(e.name), int(e.start_ns),
                           int(e.start_ns + e.duration_ns))
                          for e in line.events]
                if line.name == OP_LINE:
                    devices[int(match.group(1))] = events
                else:
                    transfers[int(match.group(1))] = [
                        e for e in events if COLLECTIVE.match(e[0])]
            elif not match:
                spans.extend(
                    (e.name, int(e.start_ns),
                     int(e.start_ns + e.duration_ns))
                    for e in line.events if e.name.startswith(SPAN_PREFIX))
    return {"devices": devices, "transfers": transfers,
            "spans": sorted(spans, key=lambda e: e[1])}


# -- interval arithmetic ------------------------------------------------
def union(intervals: Iterable[Interval]) -> List[Interval]:
    merged: List[List[int]] = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def clip(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def total(intervals: Iterable[Interval]) -> int:
    return sum(b - a for a, b in intervals)


def overlap(merged: Sequence[Interval], lo: int, hi: int) -> int:
    """Length of [lo, hi] covered by a sorted disjoint list."""
    return total(clip(merged, lo, hi))


def gaps(merged: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    """The idle intervals of [lo, hi]: what a sorted disjoint list does
    not cover."""
    out, at = [], lo
    for a, b in clip(merged, lo, hi):
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def window_of(trace: dict) -> Optional[Interval]:
    windows = [(s, e) for name, s, e in trace["spans"]
               if name == WINDOW_SPAN]
    if windows:
        return windows[-1]
    events = [e for evs in trace["devices"].values() for e in evs]
    if not events:
        return None
    return min(e[1] for e in events), max(e[2] for e in events)


def label_gap(gap: Interval, spans: Sequence[Event]) -> str:
    """What the host was doing in an idle gap: the benchmark span that
    overlaps it most (the innermost on a tie), else `no_span`."""
    best, best_cover, best_len = "no_span", 0, 0
    for name, start, end in spans:
        if name == WINDOW_SPAN:
            continue
        cover = min(end, gap[1]) - max(start, gap[0])
        if cover > best_cover or (cover == best_cover and cover > 0
                                  and end - start < best_len):
            best, best_cover, best_len = name, cover, end - start
    return best[len(SPAN_PREFIX):] if best != "no_span" else best


def reduce(trace: dict, top: int = 10) -> Optional[dict]:
    """The numbers the per-layer readers and the result line take from a
    trace; None when no operation ran on a device (a CPU run)."""
    window = window_of(trace)
    if window is None or not trace["devices"]:
        return None
    lo, hi = window
    ns = 1e-9
    busy = {}
    for index, events in trace["devices"].items():
        busy[index] = union(clip(((s, e) for _, s, e in events), lo, hi))
    if not any(busy.values()):
        return None
    first = min(trace["devices"])
    events = trace["devices"][first]
    op_ns: Dict[str, int] = {}
    for name, start, end in events:
        covered = min(end, hi) - max(start, lo)
        if covered > 0 and not CONTAINER.match(name):
            op_ns[name] = op_ns.get(name, 0) + covered
    collective = union(clip(
        ((s, e) for name, s, e in
         events + trace.get("transfers", {}).get(first, [])
         if COLLECTIVE.match(name)), lo, hi))
    compute = union(clip(((s, e) for name, s, e in events
                          if not COLLECTIVE.match(name)
                          and not CONTAINER.match(name)), lo, hi))
    exposed = total(collective) - sum(
        overlap(compute, a, b) for a, b in collective)
    gap_ns: Dict[str, int] = {}
    for gap in gaps(busy[first], lo, hi):
        label = label_gap(gap, trace["spans"])
        gap_ns[label] = gap_ns.get(label, 0) + gap[1] - gap[0]
    span_stats: Dict[str, dict] = {}
    for name, start, end in trace["spans"]:
        if name == WINDOW_SPAN or not lo <= start < hi:
            continue
        stat = span_stats.setdefault(name[len(SPAN_PREFIX):], {
            "count": 0, "host_s": 0.0, "device_busy_s": 0.0})
        stat["count"] += 1
        stat["host_s"] += (end - start) * ns
        stat["device_busy_s"] += overlap(busy[first], start, end) * ns

    def ranked(table: Dict[str, int]) -> List[list]:
        return [[name, value * ns] for name, value in
                sorted(table.items(), key=lambda kv: -kv[1])[:top]]

    return {
        "window_s": (hi - lo) * ns,
        "busy_s": sum(total(b) for b in busy.values()) * ns / len(busy),
        "busy_s_by_device": {str(i): total(b) * ns
                             for i, b in sorted(busy.items())},
        "op_s": {name: value * ns for name, value in op_ns.items()},
        "collective_s": total(collective) * ns,
        "collective_exposed_s": exposed * ns,
        "spans": span_stats,
        "breakdown": {"device_ops": ranked(op_ns),
                      "idle_gaps": ranked(gap_ns)},
    }


def reduce_dir(trace_dir: str) -> Optional[dict]:
    path = find_xplane(trace_dir)
    return reduce(load(path)) if path else None


def reduce_in_subprocess(trace_dir: str, timeout_s: float = 300.0
                         ) -> Optional[dict]:
    """`reduce_dir` in a child pinned to the CPU, for a harness process
    that stays off JAX."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    done = subprocess.run(
        [sys.executable, "-m", "benchmarks.harness.trace", trace_dir],
        cwd=root, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        stdout=subprocess.PIPE, text=True, timeout=timeout_s, check=True)
    return json.loads(done.stdout.strip().splitlines()[-1])


if __name__ == "__main__":
    print(json.dumps(reduce_dir(sys.argv[1])))
