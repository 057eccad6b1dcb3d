"""A token's way from the device's last operation to the stream path,
from the trace a cell's `--trace 1` run wrote.

`program_trace.py` reads the program's `rt:` spans. This module reads
what lies between a decode step's end on the device and the host's
having its ids, and who else ran meanwhile: the `st:` events (the
category `stream` of `flight.span`: a streamed item's `item.submit`,
packaging it and handing it to the IO loop, and `item.ack_wait` on its
request's thread; the IO loop's own share, `item.rpc`, is in the
program's ring alone and not read here; the program makes them while a
profile runs in its process, so a traced run has them and a judged run
pays nothing), by thread; the engine loop's
`rt:model.decode.logits_wait` and `rt:model.decode.dispatch` spans; and
the first device's `jit_decode_paged` module events with its op line.
All inside `bench:window`.

A decode step's **pickup interval** runs from the end of the last device
operation inside its module event to the end of the `logits_wait` span
that holds that instant: the device is done and the loop does not have
the ids yet. A step whose wait ended before the device did, or began
after it (the step's `meanwhile` outlasted the device), has none; a
prefill chunk's wait has another name and is not read.

**The two clocks.** The interval has one end on the device's clock and
one on the host's, and a profile does not line the two up: in the first
trace read (`olmo-1b.serve.decode-heavy`, PR 59) every decode program
began on the device's clock 1.4-1.7 ms BEFORE the host had returned from
the call that dispatched it. A program cannot start before the runtime
has put it in the device's queue, so `device_clock_shift` moves the
device's events later by the least time that puts (all but a hundredth
of) the decode programs' starts at or behind the ends of the runtime's
`DoEnqueueProgram` events; in a trace without those, behind the ends of
the loop's dispatch spans (the jitted call returns before its program
is queued where the runtime queues it from a thread of its own, as it
did in that trace; a call that returns later would make this shift too
long). What is left in the interval is then an upper bound: too long by
the shortest time from the queue to the program's start. The unshifted
seconds stay in the result beside it (`pickup_unshifted_s`). A device
clock that runs LATE cannot be told from a slow launch and is left
alone.

From them: the pickup intervals' count and seconds; the window's
device-idle seconds inside them; per `st:` name the count, the seconds
and the seconds inside pickup intervals; and for the union of every
`st:` WORK event on a thread other than the engine loop's (`ack_wait` is
a wait: its thread is blocked and runs nothing) the seconds, the seconds
inside pickup intervals and inside the loop's dispatch spans.

Every walk is one pass over sorted lists (`inside`, two pointers).
A program without `st:` events (the parent of the PR that added them),
a run with no operation on a device or no trace gives None, and the
readers leave their metrics out.

As `program_trace.py`: `load` needs JAX's `ProfileData`, so `reduced`
runs it in a child pinned to the CPU and caches the result as JSON
beside the trace, with the seconds the child took; the readers parse
once.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import Dict, List, Optional, Sequence, Tuple

from benchmarks.harness import manifest
from benchmarks.harness.program_trace import (MODULE_LINE, REPO,
                                              module_name)
from benchmarks.harness.trace import (DEVICE_PLANE, OP_LINE, WINDOW_SPAN,
                                      Event, Interval, clip, find_xplane,
                                      gaps, total, union, window_of)

STREAM_PREFIX = "st:"
WAIT_SPAN = "rt:model.decode.logits_wait"
DISPATCH_SPAN = "rt:model.decode.dispatch"
DECODE_MODULE = "jit_decode_paged"
# The TPU runtime's own host event around handing a program to the
# device's queue (host tracer level 2), on whichever of its threads.
ENQUEUE = "DoEnqueueProgram"
# `st:` events that are waits: the thread inside one runs nothing.
WAITS = frozenset({"item.ack_wait"})
# The events whose seconds are the interpreter time an item costs the
# process that produced it, and the one counted as "an item".
ITEM_WORK = ("item.submit",)
ITEM = "item.submit"


def load(path: str) -> dict:
    """{"stream": {line: [Event]}, "engine": {line: [Event]}, "enqueues":
    [Event], "ops": [Event], "modules": [Event], "window": Interval |
    None}: the `st:` events by host thread (prefix dropped), the decode
    step's wait and dispatch spans by host thread, the runtime's
    enqueue events of any thread, the first device's op events and its
    `jit_decode_paged` module events, and the benchmark's window."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    stream: Dict[str, List[Event]] = {}
    engine: Dict[str, List[Event]] = {}
    devices: Dict[int, dict] = {}
    windows: List[Event] = []
    enqueues: List[Event] = []
    for plane in data.planes:
        match = DEVICE_PLANE.match(plane.name)
        for number, line in enumerate(plane.lines):
            if match:
                if line.name == OP_LINE:
                    devices.setdefault(int(match.group(1)), {})[OP_LINE] = [
                        ("", int(e.start_ns),
                         int(e.start_ns + e.duration_ns))
                        for e in line.events]
                elif line.name == MODULE_LINE:
                    devices.setdefault(int(match.group(1)), {})[
                        MODULE_LINE] = [
                        (DECODE_MODULE, int(e.start_ns),
                         int(e.start_ns + e.duration_ns))
                        for e in line.events
                        if module_name(e.name) == DECODE_MODULE]
                continue
            thread = f"{plane.name}/{number}:{line.name}"
            for e in line.events:
                name = e.name
                if name.startswith(STREAM_PREFIX):
                    into = stream.setdefault(thread, [])
                    name = name[len(STREAM_PREFIX):]
                elif name in (WAIT_SPAN, DISPATCH_SPAN):
                    into = engine.setdefault(thread, [])
                elif name == WINDOW_SPAN:
                    into = windows
                elif name == ENQUEUE:
                    into = enqueues
                else:
                    continue
                into.append((name, int(e.start_ns),
                             int(e.start_ns + e.duration_ns)))
    first = devices[min(devices)] if devices else {}
    ops = first.get(OP_LINE, [])
    return {"stream": stream, "engine": engine, "enqueues": enqueues,
            "ops": ops,
            "modules": first.get(MODULE_LINE, []),
            "window": window_of({"spans": windows, "devices": {0: ops}})}


def inside(intervals: Sequence[Interval], merged: Sequence[Interval]
           ) -> int:
    """Summed over `intervals` (sorted by start; they may overlap one
    another), the length of each that lies in `merged` (sorted and
    disjoint). One pass: a pointer into `merged` that only moves on, and
    from it a look ahead as far as the interval reaches."""
    covered, at, n = 0, 0, len(merged)
    for a, b in intervals:
        while at < n and merged[at][1] <= a:
            at += 1
        k = at
        while k < n and merged[k][0] < b:
            covered += min(b, merged[k][1]) - max(a, merged[k][0])
            k += 1
    return covered


def pickup_intervals(ops: Sequence[Interval], steps: Sequence[Interval],
                     waits: Sequence[Interval]) -> List[Interval]:
    """For each decode step (a module event; sorted, as `ops` and
    `waits` are): from the end of its last device operation to the end
    of the wait that holds that instant, where one does."""
    out: List[Interval] = []
    op, wait = 0, 0
    for start, end in steps:
        while op < len(ops) and ops[op][0] < start:
            op += 1
        done = None
        while op < len(ops) and ops[op][0] < end:
            done = ops[op][1] if done is None else max(done, ops[op][1])
            op += 1
        if done is None:
            continue
        while wait < len(waits) and waits[wait][1] <= done:
            wait += 1
        if wait < len(waits) and waits[wait][0] <= done:
            out.append((done, waits[wait][1]))
    return out


def device_clock_shift(steps: Sequence[Interval],
                       launches: Sequence[Interval]) -> int:
    """How far the device's events have to move (later, in ns) so that
    no decode program starts before the host launched it: each step
    (sorted) is set beside the launch (sorted: the runtime's enqueue
    events, or the loop's dispatch spans) whose start lies nearest its
    own, and the shift is what brings the hundredth-lowest of `step
    start - launch end` up to zero; 0 where that is none. Another
    program's launch set beside a step by mistake lies farther from it
    than its own and only makes that step's number larger."""
    if not steps or not launches:
        return 0
    lags, at = [], 0
    for start, _ in steps:
        while (at + 1 < len(launches)
               and abs(launches[at + 1][0] - start)
               <= abs(launches[at][0] - start)):
            at += 1
        lags.append(start - launches[at][1])
    lags.sort()
    return max(0, -lags[len(lags) // 100])


def reduce(stream: Dict[str, List[Event]], engine: Dict[str, List[Event]],
           ops: Sequence[Event], modules: Sequence[Event],
           window: Optional[Interval], enqueues: Sequence[Event] = ()
           ) -> Optional[dict]:
    """Plain arithmetic on `(name, start_ns, end_ns)` tuples: the `st:`
    events by thread (prefix dropped), the threads' `rt:` wait and
    dispatch spans (prefix kept: `WAIT_SPAN`, `DISPATCH_SPAN`), the
    device's op events and its module events (`program_trace`'s names),
    the runtime's enqueue events where the trace has them.
    None when no operation ran on the device inside the window or the
    program put no `st:` event there."""
    if window is None:
        return None
    lo, hi = window

    def windowed(intervals) -> List[Interval]:
        """Those that begin inside the window, cut at its end, sorted."""
        return sorted((a, min(b, hi)) for a, b in intervals
                      if lo <= a < hi and min(b, hi) > a)

    spans = [e for events in engine.values() for e in events]
    dispatches = sorted((s, e) for name, s, e in spans
                        if name == DISPATCH_SPAN)
    steps = sorted((s, e) for name, s, e in modules if name == DECODE_MODULE)
    shift = device_clock_shift(
        steps, sorted((s, e) for _, s, e in enqueues) or dispatches)
    unshifted = sorted((s, e) for _, s, e in ops)
    ops = [(s + shift, e + shift) for s, e in unshifted]
    busy = union(clip(ops, lo, hi))
    by_name: Dict[str, List[Interval]] = {}
    work: List[Interval] = []
    for thread, events in stream.items():
        for name, start, end in events:
            by_name.setdefault(name, []).append((start, end))
            if name not in WAITS and thread not in engine:
                work.append((start, end))
    by_name = {name: windowed(found) for name, found in by_name.items()}
    if not busy or not any(by_name.values()):
        return None
    ns = 1e-9
    waits = sorted((s, e) for name, s, e in spans if name == WAIT_SPAN)
    dispatches = union(windowed(dispatches))
    decode_steps = windowed((s + shift, e + shift) for s, e in steps)
    pickups = clip(pickup_intervals(ops, decode_steps, waits), lo, hi)
    idle = gaps(busy, lo, hi)
    work = union(windowed(work))
    return {
        "window_s": (hi - lo) * ns,
        "device_idle_s": total(idle) * ns,
        "decode_steps": len(decode_steps),
        "pickups": len(pickups),
        "pickup_s": total(pickups) * ns,
        "device_clock_shift_s": shift * ns,
        "pickup_unshifted_s": total(clip(pickup_intervals(
            unshifted, windowed(steps), waits), lo, hi)) * ns,
        "idle_in_pickup_s": inside(idle, pickups) * ns,
        # At least one other thread inside an `st:` work event.
        "contended_s": inside(work, pickups) * ns,
        "work_s": total(work) * ns,
        "dispatch_s": total(dispatches) * ns,
        "work_in_dispatch_s": inside(work, dispatches) * ns,
        "stream_threads": sum(1 for t in stream if t not in engine),
        "events": {name: {"count": len(found),
                          "seconds": total(found) * ns,
                          "in_pickup_s": inside(found, pickups) * ns}
                   for name, found in sorted(by_name.items())}}


def reduce_dir(trace_dir: str) -> Optional[dict]:
    path = find_xplane(trace_dir)
    if not path:
        return None
    loaded = load(path)
    return reduce(loaded["stream"], loaded["engine"], loaded["ops"],
                  loaded["modules"], loaded["window"], loaded["enqueues"])


def cache_path(trace_dir: str) -> Optional[str]:
    path = find_xplane(trace_dir)
    return path[:-len(".xplane.pb")] + ".stream_trace.json" if path \
        else None


def reduced(trace_dir: str, timeout_s: float = 300.0) -> Optional[dict]:
    """`reduce_dir` in a child pinned to the CPU, once a trace: the
    result is kept as JSON beside the `.xplane.pb`, with the seconds
    the child took (`child_s`)."""
    cached = cache_path(trace_dir)
    if cached is None:
        return None
    if not os.path.isfile(cached):
        t0 = time.perf_counter()
        done = subprocess.run(
            [sys.executable, "-m", "benchmarks.harness.stream_trace",
             trace_dir], cwd=REPO,
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
            stdout=subprocess.PIPE, text=True, timeout=timeout_s,
            check=True)
        result = json.loads(done.stdout.strip().splitlines()[-1])
        if result is not None:
            result["child_s"] = time.perf_counter() - t0
        with open(cached, "w") as f:
            json.dump(result, f)
    with open(cached) as f:
        return json.load(f)


def of_run(ctx: dict) -> Optional[dict]:
    """What a layer-metric reader takes: the reduction of the trace this
    run wrote; None for a run that traced no device and for a program
    without the events."""
    if not ctx.get("trace"):
        return None
    return reduced(os.path.join(manifest.ROOT, ".bench_out", "trace",
                                ctx["cell"]["name"]))


def item_seconds(reduction: dict, names: Sequence[str]
                 ) -> Tuple[float, int]:
    """Seconds of the named events, and the items they were spent on."""
    events = reduction["events"]
    return (sum(events[n]["seconds"] for n in names if n in events),
            events.get(ITEM, {}).get("count", 0))


if __name__ == "__main__":
    print(json.dumps(reduce_dir(sys.argv[1])))
