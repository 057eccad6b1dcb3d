"""A streamed item's way from the process that yields it to its owner,
stage by stage in the flight ring (`core/flight.py`, category `stream`):
`item.submit` -> `item.ack_wait` on the request's executor thread,
`item.rpc` (the round trip, in the ring alone) on the worker's IO loop,
`item.recv` / `item.get` in the owner's process; and the prefix under
which a category's spans reach the profiler. The category is made on
demand: while `flight.watch("stream")` holds or a JAX profile runs."""

import pytest

from ray_tpu.core import flight

EXECUTOR = ("item.submit", "item.ack_wait")
IO_LOOP = ("item.rpc",)
OLDER_CATEGORIES = ("task", "lease", "ring", "gc", "loop", "stall", "engine",
                    "model", "train")


@pytest.fixture(scope="module", autouse=True)
def watching():
    """The whole module watches the category, and so do the workers its
    cluster spawns (they read the env `watch` sets)."""
    flight.watch("stream")
    yield
    flight.unwatch("stream")


@pytest.fixture(scope="module")
def cluster(watching):
    import ray_tpu

    ray_tpu.init(num_cpus=2, ignore_reinit_error=True)
    yield ray_tpu
    ray_tpu.shutdown()


@pytest.fixture(scope="module")
def streamer(cluster):
    @cluster.remote
    class Streamer:
        def items(self, n):
            for i in range(n):
                yield i * 3

        def recorder(self, on):
            from ray_tpu.core import flight

            (flight.enable if on else flight.disable)()
            flight.reset()

        def watch(self, on):
            from ray_tpu.core import flight

            (flight.watch if on else flight.unwatch)("stream")
            flight.reset()

        def ring(self):
            from ray_tpu.core import flight

            return flight.snapshot(categories={"stream"})

    return Streamer.remote()


def streamed(ray, actor, n):
    """The items of one streamed call, fetched as the serve handle
    fetches them, and the call's task id."""
    from ray_tpu.serve.handle import _get_item

    refs = list(actor.items.options(num_returns="streaming").remote(n))
    task = refs[0].hex()[:12]
    assert all(r.hex()[:12] == task for r in refs)
    return [_get_item(r) for r in refs], task


def by_label(events):
    out = {}
    for t, tid, _category, label, dur_us, arg in events:
        out.setdefault(label, []).append((t, tid, dur_us, arg))
    return out


def test_an_items_stages_are_in_both_rings(cluster, streamer, recorder):
    cluster.get(streamer.recorder.remote(True))
    values, task = streamed(cluster, streamer, 4)
    assert values == [0, 3, 6, 9]
    args = [f"{task}#{n}" for n in (1, 2, 3, 4)]

    worker = by_label(cluster.get(streamer.ring.remote()))
    assert set(worker) == set(EXECUTOR + IO_LOOP)
    for label in EXECUTOR + IO_LOOP:
        assert [arg for _, _, _, arg in worker[label]] == args, label
    executor = {tid for label in EXECUTOR for _, tid, _, _ in worker[label]}
    io_loop = {tid for label in IO_LOOP for _, tid, _, _ in worker[label]}
    assert len(executor) == 1 and len(io_loop) == 1
    assert executor != io_loop
    for n in range(4):
        submit, wait = (worker[label][n] for label in EXECUTOR)
        (rpc,) = (worker[label][n] for label in IO_LOOP)
        # One after the other on the request's thread ...
        assert submit[0] <= wait[0]
        # ... the round trip inside the two: begun after the hand-over
        # began, answered before the thread went on.
        assert submit[0] <= rpc[0]
        assert rpc[0] + rpc[2] * 1e-6 <= wait[0] + wait[2] * 1e-6 + 1e-3
    # The next item is packaged only when the last was acknowledged.
    starts = [t for t, _, _, _ in worker["item.submit"]]
    ends = [t + dur * 1e-6 for t, _, dur, _ in worker["item.ack_wait"]]
    assert all(end <= nxt + 1e-3 for end, nxt in zip(ends, starts[1:]))

    owner = by_label(flight.snapshot(categories={"stream"}))
    assert set(owner) == {"item.recv", "item.get"}
    assert [arg for _, _, _, arg in owner["item.recv"]] == args
    assert [arg for _, _, _, arg in owner["item.get"]] == args


def test_with_the_recorder_off_there_is_no_stream_event(cluster, streamer,
                                                         recorder):
    cluster.get(streamer.recorder.remote(False))
    flight.disable()
    try:
        values, _ = streamed(cluster, streamer, 4)
        assert values == [0, 3, 6, 9]
        assert cluster.get(streamer.ring.remote()) == []
        assert flight.snapshot(categories={"stream"}) == []
    finally:
        flight.enable()
        cluster.get(streamer.recorder.remote(True))


def test_with_nobody_watching_there_is_no_stream_event(cluster, streamer,
                                                       recorder):
    cluster.get(streamer.watch.remote(False))
    flight.unwatch("stream")
    try:
        assert not flight.watched("stream")
        values, _ = streamed(cluster, streamer, 4)
        assert values == [0, 3, 6, 9]
        assert cluster.get(streamer.ring.remote()) == []
        assert flight.snapshot(categories={"stream"}) == []
        with flight.span("stream", "item.submit", None) as sp:
            pass
        assert sp.dur == 0.0
        # An older category is made whoever watches.
        with flight.span("engine", "some.label"):
            pass
        assert [e[3] for e in flight.snapshot()] == ["some.label"]
    finally:
        flight.watch("stream")
        cluster.get(streamer.watch.remote(True))


class _Profile:
    """Stands in for `jax.profiler.TraceAnnotation` of a process in which
    a profile is, or is not, running."""

    def __init__(self, running):
        self.running = running

    def is_enabled(self):
        return self.running


def test_a_running_profile_watches_the_stream_events(recorder, monkeypatch):
    flight.unwatch("stream")
    try:
        monkeypatch.setattr(flight, "_annotation", _Profile(False))
        assert not flight.watched("stream")
        assert flight.stream_arg("ab" * 20 + "00000003") is None
        monkeypatch.setattr(flight, "_annotation", _Profile(True))
        assert flight.watched("stream")
        assert flight.stream_arg("ab" * 20 + "00000003") == "abababababab#3"
        flight.disable()
        assert not flight.watched("stream")
    finally:
        flight.enable()
        flight.watch("stream")


def test_watching_reaches_the_processes_spawned_after_it():
    import os

    assert os.environ[flight.ENV_WATCH] == "stream"
    flight.unwatch("stream")
    try:
        assert os.environ[flight.ENV_WATCH] == ""
    finally:
        flight.watch("stream")


def test_with_the_recorder_off_a_stream_span_reads_no_clock(monkeypatch):
    class NoClock:
        def __getattr__(self, name):
            raise AssertionError(f"time.{name} read with the recorder off")

    was = flight.enabled
    flight.disable()
    try:
        monkeypatch.setattr(flight, "time", NoClock())
        assert flight.stream_arg("ab" * 20 + "00000003") is None
        with flight.span("stream", "item.submit", None) as sp:
            pass
        assert sp.dur == 0.0
    finally:
        monkeypatch.undo()
        if was:
            flight.enable()


def test_a_stream_arg_is_the_task_and_the_items_index(recorder):
    from ray_tpu.core.ids import ObjectID, TaskID

    task = TaskID(bytes(range(TaskID.SIZE)))
    oid = ObjectID.for_return(task, 7).hex()
    assert flight.stream_arg(oid) == f"{task.hex()[:12]}#7"


class _Annotations:
    """Stands in for `jax.profiler.TraceAnnotation`: keeps the names."""

    def __init__(self):
        self.names = []

    def __call__(self, name):
        self.names.append(name)
        return self

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.mark.parametrize("category", ("stream",) + OLDER_CATEGORIES)
def test_the_profilers_prefix_follows_from_the_category(category, recorder,
                                                         monkeypatch):
    seen = _Annotations()
    monkeypatch.setattr(flight, "_annotation", seen)
    with flight.span(category, "some.label"):
        pass
    (name,) = seen.names
    if category == "stream":
        assert name == "st:some.label"
    else:
        assert name == f"rt:{category}.some.label"
    (event,) = [e for e in flight.snapshot() if e[3] == "some.label"]
    assert event[2] == category
