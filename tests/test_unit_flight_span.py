"""`flight.span`: the one way program code times an interval. It feeds
the ring, a float and (when JAX is imported) the profiler's host plane
from the same two clock reads, and with the recorder off it does nothing
at all."""

import pytest

from ray_tpu.core import flight


def test_span_records_one_event_and_feeds_its_accumulator(recorder):
    clocks = {"x_s": 0.0, "y_s": 0.0}
    with flight.span("engine", "x", 3, clocks, "x_s") as outer:
        with flight.span("engine", "y", into=clocks, key="y_s") as inner:
            inner.arg = "set inside"
    events = {e[3]: e for e in flight.snapshot(categories={"engine"})}
    assert set(events) == {"x", "y"}
    assert events["x"][5] == 3 and events["y"][5] == "set inside"
    # The float holds what the span itself measured, not a second clock.
    assert clocks == {"x_s": outer.dur, "y_s": inner.dur}
    assert 0.0 <= inner.dur <= outer.dur
    # The ring event starts where the span did and lasts as long.
    x, y = events["x"], events["y"]
    assert x[0] <= y[0]
    assert y[0] + y[4] * 1e-6 <= x[0] + x[4] * 1e-6 + 2e-6
    assert x[4] == int(outer.dur * 1e6)


def test_span_accumulates_over_uses_and_survives_an_exception(recorder):
    clocks = {"k": 0.0}
    first = flight.span("model", "a", into=clocks, key="k")
    with first:
        pass
    with pytest.raises(ValueError):
        with flight.span("model", "a", into=clocks, key="k") as second:
            raise ValueError("inside")
    assert clocks["k"] == first.dur + second.dur
    assert [e[3] for e in flight.snapshot(categories={"model"})] == \
        ["a", "a"]


def test_span_with_the_recorder_off_records_nothing_and_reads_no_clock(
        recorder, monkeypatch):
    flight.disable()

    def no_clock():
        raise AssertionError("a span read the clock with the recorder off")

    monkeypatch.setattr(flight.time, "monotonic", no_clock)
    monkeypatch.setattr(flight, "_trace_annotation", no_clock)
    clocks = {"k": 0.0}
    with flight.span("engine", "x", 1, clocks, "k") as sp:
        pass
    monkeypatch.undo()
    assert sp.dur == 0.0 and clocks == {"k": 0.0}
    flight.enable()
    assert flight.snapshot(categories={"engine"}) == []


def test_span_is_a_profiler_annotation_once_jax_is_imported(recorder,
                                                            monkeypatch):
    """`rt:<category>.<label>`, entered before the clock is read and left
    after it, so the profiler's interval contains the ring's."""
    seen = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            seen.append(("enter", self.name))

        def __exit__(self, *exc):
            seen.append(("exit", self.name))

    monkeypatch.setattr(flight, "_annotation", Annotation)
    with flight.span("engine", "prefill.match"):
        seen.append("body")
    assert seen == [("enter", "rt:engine.prefill.match"), "body",
                    ("exit", "rt:engine.prefill.match")]


def test_span_finds_the_real_annotation_class_without_importing_jax(
        recorder, monkeypatch):
    import sys

    monkeypatch.setattr(flight, "_annotation", None)
    monkeypatch.setitem(sys.modules, "jax", None)      # "not imported"
    with flight.span("engine", "x"):
        pass
    assert flight._annotation is None
    monkeypatch.undo()
    import jax

    monkeypatch.setattr(flight, "_annotation", None)
    with flight.span("engine", "x"):
        pass
    assert flight._annotation is jax.profiler.TraceAnnotation
