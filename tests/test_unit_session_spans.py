"""The trainer session's two stalls as `flight.span`s: the wait for data
feeds the step's `data_wait_s` telemetry from the span's own clock, a
report shows the executor's turn apart from the session's work, and a
`profile_steps` trace starts with the Python tracer off."""

import threading

import pytest

from ray_tpu.air import session as air_session
from ray_tpu.core import flight


def _session(**kw):
    return air_session._TrainSession(world_rank=0, local_rank=0,
                                     world_size=1, node_rank=0, **kw)


def _contains(outer, inner, slack=2e-6):
    return (outer[0] <= inner[0] + slack
            and inner[0] + inner[4] * 1e-6
            <= outer[0] + outer[4] * 1e-6 + slack)


def test_data_wait_and_report_are_spans_and_feed_the_telemetry(recorder):
    s = _session(trial_name="spans")
    batches = air_session._TimedIter(iter([1, 2, 3]), s)
    assert [next(batches), next(batches)] == [1, 2]
    waits = [e for e in flight.snapshot(categories={"train"})
             if e[3] == "data_wait"]
    assert len(waits) == 2
    # One clock: the telemetry is the spans' own sum.
    assert s._waits["data_wait_s"] == pytest.approx(
        sum(e[4] for e in waits) * 1e-6, abs=3e-6)
    waited = s._waits["data_wait_s"]

    seen = []

    def executor():
        seen.append(s.result_queue.get(timeout=30))
        s.continue_event.set()

    t = threading.Thread(target=executor)
    t.start()
    s.report({"loss": 1.0})
    t.join(timeout=30)
    assert not t.is_alive()
    telemetry = seen[0]["telemetry"]
    assert telemetry["data_wait_s"] == pytest.approx(
        min(waited, telemetry["step_time_s"]))
    assert s._waits["data_wait_s"] == 0.0           # reset at the boundary
    events = {e[3]: e for e in flight.snapshot(categories={"train"})}
    assert set(events) == {"data_wait", "report", "report.wait"}
    assert _contains(events["report"], events["report.wait"])
    assert events["report"][5] == 1                 # arg: the step it closes
    with pytest.raises(StopIteration):
        next(batches), next(batches)


def test_with_the_recorder_off_the_wait_reads_as_compute(recorder):
    flight.disable()
    s = _session()
    assert next(air_session._TimedIter(iter([7]), s)) == 7
    assert s._waits["data_wait_s"] == 0.0
    flight.enable()
    assert flight.snapshot(categories={"train"}) == []


def test_profile_steps_trace_starts_with_the_python_tracer_off(
        recorder, monkeypatch, tmp_path):
    import jax

    started = []
    monkeypatch.setattr(
        jax.profiler, "start_trace",
        lambda log_dir, **kw: started.append((log_dir, kw)))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    s = _session(trial_name="prof", profile_steps=(1, 1),
                 profile_dir=str(tmp_path))
    assert s._profiling and len(started) == 1
    log_dir, kw = started[0]
    assert log_dir == s._profile_trace_dir
    options = kw["profiler_options"]
    assert options.python_tracer_level == 0
    assert options.host_tracer_level == 2           # `rt:` spans stay on
