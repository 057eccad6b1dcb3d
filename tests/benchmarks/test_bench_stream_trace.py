"""`stream_trace`: a decode step's pickup interval and the stream path's
`st:` events, reduced from a trace; the six readers that rest on it.
Plain arithmetic on hand-made `(name, start_ns, end_ns)` tuples."""

import json
import os
import random
import time

import pytest

from benchmarks.harness import manifest, program_trace, stream_trace

SERVE_CELLS = {
    "olmo-1b.serve.chat-steady", "olmo-1b.serve.decode-heavy",
    "solar-open2-250b.serve.decode-wide", "laguna-s-2.1.serve.repo-context",
    "mimo-v2.5.serve.doc-context", "keye-vl-2.0-30b-a3b.serve.long-doc"}
NEW_METRICS = {
    "decode_ids_pickup_ms": ("ms", "device_trace", "Model step, decode"),
    "device_idle_in_pickup_pct": ("%", "device_trace", "Device, serve"),
    "decode_pickup_contended_pct": ("%", "device_trace",
                                    "Serve handle, router, replica"),
    "stream_item_cpu_ms": ("ms", "program_span",
                           "Serve handle, router, replica"),
    "stream_item_ack_wait_ms": ("ms", "program_span",
                                "Serve handle, router, replica"),
    "engine_pending_wait_ms": ("ms", "program_span", "Engine scheduler")}

WAIT, DISPATCH = stream_trace.WAIT_SPAN, stream_trace.DISPATCH_SPAN
NS = 1e-9
WINDOW = (0, 1000)
# One decode step: dispatched 100-140, on the device 150-400 (its last
# operation ends at 400), the loop waiting for its ids 140-600. The
# pickup interval is 400-600: d = 200.
ENGINE = {"loop": [(DISPATCH, 100, 140), (WAIT, 140, 600)]}
OPS = [("fusion.1 f32[8]", 150, 300), ("fusion.2 f32[8]", 300, 400)]
MODULES = [("jit_decode_paged", 150, 405)]


def _reduce(stream, engine=ENGINE, ops=OPS, modules=MODULES, window=WINDOW):
    return stream_trace.reduce(stream, engine, ops, modules, window)


def test_a_steps_pickup_runs_from_the_devices_end_to_the_waits_end():
    got = _reduce({"exec-1": [("item.package", 500, 600)]})
    assert got["decode_steps"] == 1 and got["pickups"] == 1
    assert got["pickup_s"] == pytest.approx(200 * NS)
    assert got["window_s"] == pytest.approx(1000 * NS)
    # Idle: 0-150 and 400-1000; inside the pickup interval, 400-600.
    assert got["device_idle_s"] == pytest.approx(750 * NS)
    assert got["idle_in_pickup_s"] == pytest.approx(200 * NS)
    # The event covers half of the interval.
    assert got["contended_s"] == pytest.approx(100 * NS)
    assert got["events"] == {"item.package": {
        "count": 1, "seconds": pytest.approx(100 * NS),
        "in_pickup_s": pytest.approx(100 * NS)}}
    assert got["dispatch_s"] == pytest.approx(40 * NS)
    assert got["work_in_dispatch_s"] == 0
    assert got["stream_threads"] == 1


def test_a_wait_over_all_of_the_interval_is_no_thread_running():
    got = _reduce({"exec-1": [("item.ack_wait", 350, 700)]})
    assert got["contended_s"] == 0 and got["work_s"] == 0
    # It is still counted, as what it is.
    assert got["events"]["item.ack_wait"] == {
        "count": 1, "seconds": pytest.approx(350 * NS),
        "in_pickup_s": pytest.approx(200 * NS)}


def test_two_threads_inside_work_events_at_once_count_once():
    got = _reduce({
        "exec-1": [("item.package", 380, 450), ("item.submit", 450, 470),
                   ("item.ack_wait", 470, 900)],
        "exec-2": [("item.package", 440, 500)],
        "io": [("item.send", 480, 520), ("item.acked", 580, 640)]})
    # The union inside 400-600: 400-520 and 580-600.
    assert got["contended_s"] == pytest.approx(140 * NS)
    # 380-520 and 580-640.
    assert got["work_s"] == pytest.approx(200 * NS)
    events = got["events"]
    assert events["item.package"]["count"] == 2
    assert events["item.package"]["seconds"] == pytest.approx(130 * NS)
    assert events["item.package"]["in_pickup_s"] == pytest.approx(110 * NS)
    assert events["item.acked"]["in_pickup_s"] == pytest.approx(20 * NS)
    assert got["stream_threads"] == 3
    seconds, items = stream_trace.item_seconds(
        got, ("item.package", "item.submit", "item.send", "item.acked"))
    assert items == 1      # counted by `item.submit`
    assert seconds == pytest.approx((130 + 20 + 40 + 60) * NS)


def test_an_event_on_the_engine_loops_own_thread_is_no_other_thread():
    got = _reduce({"loop": [("item.package", 400, 600)],
                   "exec-1": [("item.submit", 590, 610)]})
    assert got["contended_s"] == pytest.approx(10 * NS)
    assert got["stream_threads"] == 1


@pytest.mark.parametrize("engine, why", [
    # The wait ended before the device did.
    ({"loop": [(DISPATCH, 100, 140), (WAIT, 140, 390)]}, "ended before"),
    # The step's `meanwhile` outlasted the device: the wait began later.
    ({"loop": [(DISPATCH, 100, 140), (WAIT, 420, 600)]}, "began after"),
    # A prefill chunk's wait is not a decode step's.
    ({"loop": [("rt:model.prefill.logits_wait", 140, 600)]}, "a prefill's"),
    ({}, "no engine span at all")])
def test_a_step_without_a_wait_that_holds_its_end_has_no_interval(engine,
                                                                   why):
    got = _reduce({"exec-1": [("item.package", 500, 600)]}, engine=engine)
    assert got["decode_steps"] == 1 and got["pickups"] == 0, why
    assert got["pickup_s"] == 0 and got["contended_s"] == 0
    assert got["events"]["item.package"]["in_pickup_s"] == 0


def _device_clock_early(by):
    """The same step as the device's clock shows it when it runs `by`
    ns ahead of the host's."""
    return ([(n, s - by, e - by) for n, s, e in OPS],
            [(n, s - by, e - by) for n, s, e in MODULES])


def test_a_device_clock_that_runs_early_is_moved_behind_the_dispatch():
    stream = {"exec-1": [("item.package", 500, 600)]}
    ops, modules = _device_clock_early(60)
    # The program seems to start at 90, and its dispatch returned at 140.
    got = _reduce(stream, ops=ops, modules=modules)
    assert got["device_clock_shift_s"] == pytest.approx(50 * NS)
    # Unshifted, the interval reads 340-600; shifted, 390-600: too long
    # by the 10 ns from the dispatch's return to the program's start,
    # which no trace can tell from the clocks' distance.
    assert got["pickup_unshifted_s"] == pytest.approx(260 * NS)
    assert got["pickup_s"] == pytest.approx(210 * NS)
    assert got["idle_in_pickup_s"] == pytest.approx(210 * NS)
    assert got["contended_s"] == pytest.approx(100 * NS)
    # A clock that agrees, or runs late, is left alone.
    for by in (0, -40):
        ops, modules = _device_clock_early(by)
        got = _reduce(stream, ops=ops, modules=modules)
        assert got["device_clock_shift_s"] == 0
        assert got["pickup_s"] == got["pickup_unshifted_s"] == \
            pytest.approx((200 + by) * NS)


def test_the_runtimes_enqueue_events_are_the_launch_where_the_trace_has_them():
    stream = {"exec-1": [("item.package", 500, 600)]}
    ops, modules = _device_clock_early(60)
    # The program was queued at 120-125, while the call that dispatched
    # it returned only at 140: it may have started at 125, not at 140.
    enqueues = [("DoEnqueueProgram", 20, 25),       # another program's
                ("DoEnqueueProgram", 120, 125)]
    got = stream_trace.reduce(stream, ENGINE, ops, modules, WINDOW, enqueues)
    assert got["device_clock_shift_s"] == pytest.approx(35 * NS)
    assert got["pickup_s"] == pytest.approx(225 * NS)
    assert stream_trace.ENQUEUE == "DoEnqueueProgram"


def test_the_shift_is_the_hundredth_lowest_lag_of_the_nearest_dispatch():
    dispatches = [(i * 1000, i * 1000 + 50) for i in range(200)]
    steps = [(i * 1000 - 150, i * 1000 + 700) for i in range(200)]
    assert stream_trace.device_clock_shift(steps, dispatches) == 200
    # Two steps of two hundred (a hundredth) that began much earlier
    # still, by some fault of the trace, do not set it.
    steps[7], steps[90] = (7000 - 400, 7700), (90_000 - 450, 90_700)
    assert stream_trace.device_clock_shift(steps, dispatches) == 200
    # A step more than dispatches (the window's edge) finds its nearest.
    assert stream_trace.device_clock_shift(steps + [(200_000, 200_700)],
                                           dispatches) == 200
    assert stream_trace.device_clock_shift([], dispatches) == 0
    assert stream_trace.device_clock_shift(steps, []) == 0


def test_another_programs_run_is_no_decode_step():
    got = _reduce({"exec-1": [("item.package", 500, 600)]},
                  modules=[("jit_prefill_chunk", 150, 405)])
    assert got["decode_steps"] == 0 and got["pickups"] == 0


def test_what_lies_outside_the_window_is_left_out():
    stream = {"exec-1": [("item.package", -50, -10),    # before it
                         ("item.package", 500, 600),
                         ("item.package", 990, 1100),   # cut at its end
                         ("item.package", 1200, 1300)]}  # behind it
    engine = {"loop": ENGINE["loop"] + [(WAIT, 1150, 1500)]}
    ops = OPS + [("fusion.1 f32[8]", 1160, 1400)]
    modules = MODULES + [("jit_decode_paged", 1160, 1405)]
    got = _reduce(stream, engine=engine, ops=ops, modules=modules)
    assert got["decode_steps"] == 1 and got["pickups"] == 1
    assert got["pickup_s"] == pytest.approx(200 * NS)
    assert got["events"]["item.package"] == {
        "count": 2, "seconds": pytest.approx(110 * NS),
        "in_pickup_s": pytest.approx(100 * NS)}
    # A step that began in the window and whose ids came after it: the
    # interval is cut at the window's end.
    late = _reduce(stream, engine={"loop": [(WAIT, 140, 1300)]})
    assert late["pickup_s"] == pytest.approx(600 * NS)


def test_nothing_to_read_gives_none():
    some = {"exec-1": [("item.package", 500, 600)]}
    assert _reduce(some, window=None) is None
    assert _reduce(some, ops=[]) is None              # no device (CPU run)
    assert _reduce({}) is None                        # the parent: no `st:`
    assert _reduce({"exec-1": [("item.package", 2000, 2100)]}) is None
    assert stream_trace.reduce_dir("/nonexistent") is None
    assert stream_trace.reduced("/nonexistent") is None
    assert stream_trace.of_run({"trace": None, "cell": {"name": "x"}}) \
        is None


def test_inside_is_the_overlap_of_each_interval_with_a_disjoint_list():
    merged = [(2, 4), (8, 12), (30, 40)]
    assert stream_trace.inside([(0, 10), (5, 20)], merged) == 4 + 4
    assert stream_trace.inside([(0, 100)], merged) == 2 + 4 + 10
    assert stream_trace.inside([(4, 8), (12, 30), (40, 50)], merged) == 0
    assert stream_trace.inside([], merged) == 0
    assert stream_trace.inside([(0, 10)], []) == 0
    rng = random.Random(5)
    points = sorted(rng.sample(range(10_000), 400))
    merged = list(zip(points[::2], points[1::2]))
    intervals = sorted((a, a + rng.randrange(1, 300))
                       for a in rng.sample(range(10_000), 300))
    slow = sum(max(0, min(b, d) - max(a, c))
               for a, b in intervals for c, d in merged)
    assert stream_trace.inside(intervals, merged) == slow


def _synthetic(steps, threads):
    """`steps` decode steps of 10 us, each with a dispatch, a wait and
    40 device operations, beside `threads` request threads and an IO
    loop that each put five events a step."""
    engine, ops, modules = [], [], []
    stream = {f"exec-{t}": [] for t in range(threads)}
    stream["io"] = []
    for i in range(steps):
        t0 = i * 10_000
        engine += [(DISPATCH, t0, t0 + 500), (WAIT, t0 + 600, t0 + 9_500)]
        ops += [("fusion", t0 + 700 + 200 * k, t0 + 880 + 200 * k)
                for k in range(40)]
        modules.append(("jit_decode_paged", t0 + 700, t0 + 8_700))
        for t in range(threads):
            at = t0 + 8_000 + 97 * t
            stream[f"exec-{t}"] += [
                ("item.package", at, at + 40), ("item.submit", at + 40,
                                                at + 90),
                ("item.ack_wait", at + 90, at + 900)]
            stream["io"] += [("item.send", at + 100, at + 150),
                             ("item.acked", at + 800, at + 850)]
    return stream, {"loop": engine}, ops, modules, (0, steps * 10_000)


def test_fifty_thousand_events_reduce_in_under_two_seconds():
    stream, engine, ops, modules, window = _synthetic(steps=625, threads=16)
    assert sum(len(v) for v in stream.values()) == 50_000
    t0 = time.perf_counter()
    got = stream_trace.reduce(stream, engine, ops, modules, window)
    assert time.perf_counter() - t0 < 2.0
    assert got["decode_steps"] == got["pickups"] == 625
    # Device done at t0 + 8,680, ids at t0 + 9,500.
    assert got["pickup_s"] == pytest.approx(625 * 820 * NS)
    assert got["events"]["item.ack_wait"]["count"] == 10_000
    assert 0 < got["contended_s"] <= got["pickup_s"]
    assert got["idle_in_pickup_s"] == pytest.approx(got["pickup_s"])


def _as_the_profile_holds_them(stream, engine):
    """Host events by thread under their full names, as a trace has
    them, and what `program_trace.load` keeps of them: the names that
    start with its prefix, the prefix dropped."""
    full = {thread: list(events) for thread, events in engine.items()}
    for thread, events in stream.items():
        full.setdefault(thread, []).extend(
            (stream_trace.STREAM_PREFIX + n, s, e) for n, s, e in events)
    prefix = program_trace.SPAN_PREFIX
    return {thread: [(n[len(prefix):], s, e) for n, s, e in events
                     if n.startswith(prefix)]
            for thread, events in full.items()}


def test_the_st_events_do_not_reach_program_traces_reduction():
    stream, engine, ops, modules, window = _synthetic(steps=20, threads=4)
    engine = {"loop": engine["loop"] + [
        ("rt:engine.step", i * 10_000, (i + 1) * 10_000) for i in range(20)]}
    without = program_trace.reduce(_as_the_profile_holds_them({}, engine),
                                   ops, modules, window)
    threads = _as_the_profile_holds_them(stream, engine)
    assert {t for t, events in threads.items() if events} == {"loop"}
    with_them = program_trace.reduce(threads, ops, modules, window)
    assert with_them["spans"] == without["spans"]
    assert with_them["idle_attributed_share"] == \
        without["idle_attributed_share"]
    assert set(with_them["spans"]) == {
        "engine.step", "model.decode.dispatch", "model.decode.logits_wait"}
    # Not 100: the request threads' events, nearly always open, would
    # make it so under `rt:`.
    assert 0 < with_them["idle_attributed_share"] < 1


# -- a small recorded trace ---------------------------------------------------
RECORDED = os.path.join(manifest.bench_dir(), "harness", "testdata",
                        "serve_program_spans.xplane.pb")


def test_the_recorded_trace_of_a_program_without_the_events():
    """`test_bench_program_trace.py`'s 0.36 s of `chat-steady` on one
    v5e chip, PR 24's program: one decode step, no `st:` event."""
    loaded = stream_trace.load(RECORDED)
    assert loaded["stream"] == {}
    (thread, spans), = loaded["engine"].items()
    assert [name for name, _, _ in spans] == [DISPATCH, WAIT]
    assert [name for name, _, _ in loaded["modules"]] == ["jit_decode_paged"]
    assert len(loaded["ops"]) == 1181
    assert loaded["window"] == program_trace.load(RECORDED)["window"]
    # The parent's side of a traced pair: nothing to read.
    assert loaded["enqueues"] == []     # the recording was cut to spans
    assert stream_trace.reduce(loaded["stream"], loaded["engine"],
                               loaded["ops"], loaded["modules"],
                               loaded["window"]) is None
    # With one event of a request's thread across the wait's end, the
    # step's interval is there: the device's last operation ended
    # 2.51 ms before the loop had the ids, all of it idle chip.
    end = spans[1][2]
    got = stream_trace.reduce(
        {"exec-1": [("item.package", end - 1000, end + 500)]},
        loaded["engine"], loaded["ops"], loaded["modules"], loaded["window"])
    assert got["decode_steps"] == got["pickups"] == 1
    assert got["pickup_s"] == pytest.approx(0.002512588)
    assert got["device_clock_shift_s"] == 0     # it began 56 ms behind
    assert got["idle_in_pickup_s"] == pytest.approx(0.002512588)
    assert got["contended_s"] == pytest.approx(1000 * NS)
    # The window's idle time as `program_trace` has it.
    assert got["device_idle_s"] == pytest.approx(0.323559329)


# -- the readers --------------------------------------------------------------
REDUCTION = {
    "window_s": 8.0, "device_idle_s": 2.0, "decode_steps": 800,
    "pickups": 750, "pickup_s": 1.05, "device_clock_shift_s": 0.0015,
    "pickup_unshifted_s": 2.2, "idle_in_pickup_s": 0.9,
    "contended_s": 0.42, "work_s": 1.5, "dispatch_s": 0.4,
    "work_in_dispatch_s": 0.1, "stream_threads": 17, "child_s": 12.5,
    "events": {
        "item.submit": {"count": 12000, "seconds": 0.72, "in_pickup_s": 0.2},
        "item.ack_wait": {"count": 11999, "seconds": 30.0,
                          "in_pickup_s": 9.0}}}


def _reader(name):
    return manifest.load_reader(name)


def test_readers_do_the_arithmetic_their_entries_say(monkeypatch, capsys):
    monkeypatch.setattr(stream_trace, "of_run", lambda ctx: REDUCTION)
    ctx = {"trace": {"busy_s": 6.0}, "cell": {"name": "x"},
           "counters": {"pending_wait_s": 13.2,
                        "pending_wait_tokens": 12000}}
    assert _reader("decode_ids_pickup_ms")(ctx) == pytest.approx(1.4)
    assert _reader("device_idle_in_pickup_pct")(ctx) == pytest.approx(45.0)
    assert _reader("decode_pickup_contended_pct")(ctx) == pytest.approx(40.0)
    assert _reader("stream_item_cpu_ms")(ctx) == pytest.approx(
        0.72 / 12000 * 1e3)
    assert _reader("stream_item_ack_wait_ms")(ctx) == pytest.approx(
        30.0 / 11999 * 1e3)
    assert _reader("engine_pending_wait_ms")(ctx) == pytest.approx(1.1)
    # The table, a line of its own: every event and the child's seconds.
    (line,) = [ln for ln in capsys.readouterr().out.splitlines()
               if ln.startswith("stream path ")]
    head, _, rest = line.partition(" pickups: ")
    table = json.loads(head.partition(": ")[2])
    assert table["item.ack_wait"] == [11999, 30.0, 9.0]
    assert json.loads(rest)["child_s"] == 12.5
    assert json.loads(rest)["device_clock_shift_s"] == 0.0015


def test_a_share_is_of_a_time_that_contains_it():
    stream, engine, ops, modules, window = _synthetic(steps=50, threads=16)
    got = stream_trace.reduce(stream, engine, ops, modules, window)
    assert got["contended_s"] <= got["pickup_s"]
    assert got["idle_in_pickup_s"] <= got["device_idle_s"]
    assert got["idle_in_pickup_s"] <= got["pickup_s"]


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_a_reader_with_nothing_to_read_gives_none(name):
    # No trace and no counters; a parent's trace (no `st:` event: the
    # reduction is None); a parent's counters.
    for ctx in ({"trace": None, "cell": {"name": "x"}},
                {"trace": None, "cell": {"name": "x"},
                 "counters": {"paged_steps": 100, "stream_wake_s": 0.1}}):
        assert _reader(name)(ctx) is None


def test_readers_of_a_run_whose_reduction_is_none_give_none(monkeypatch):
    monkeypatch.setattr(stream_trace, "of_run", lambda ctx: None)
    ctx = {"trace": {"busy_s": 6.0}, "cell": {"name": "x"}, "counters": {}}
    for name in NEW_METRICS:
        assert _reader(name)(ctx) is None, name


def test_a_reduction_without_intervals_leaves_the_interval_metrics_out(
        monkeypatch):
    empty = dict(REDUCTION, pickups=0, pickup_s=0.0, idle_in_pickup_s=0.0,
                 contended_s=0.0)
    monkeypatch.setattr(stream_trace, "of_run", lambda ctx: empty)
    ctx = {"trace": {"busy_s": 6.0}, "cell": {"name": "x"}, "counters": {}}
    assert _reader("decode_ids_pickup_ms")(ctx) is None
    assert _reader("decode_pickup_contended_pct")(ctx) is None
    assert _reader("device_idle_in_pickup_pct")(ctx) == 0.0
    assert _reader("stream_item_ack_wait_ms")(ctx) == pytest.approx(
        30.0 / 11999 * 1e3)


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_the_entry_is_among_the_manifests_with_the_serve_cells(name):
    entries = {m["name"]: m for m in manifest.load_manifest()["per_layer"]}
    assert name in entries
    entry = entries[name]
    unit, source, layer = NEW_METRICS[name]
    assert (entry["unit"], entry["source"], entry["layer"]) == \
        (unit, source, layer)
    assert entry["better"] == "lower"
    assert entry["moves"] == "serve_itl_p99_ms"
    # Among them: a later PR may append its cells.
    assert SERVE_CELLS <= set(entry["workloads"])
    assert callable(_reader(name))


def test_the_manifest_has_no_problem_and_the_cells_carry_the_metrics():
    assert manifest.problems() == []
    for cell_name in sorted(SERVE_CELLS):
        cell = manifest.load_cell(cell_name)
        assert NEW_METRICS.keys() <= {m["name"] for m in cell["per_layer"]}
        assert "serve_itl_p99_ms" in {m["name"] for m in cell["end_to_end"]}
