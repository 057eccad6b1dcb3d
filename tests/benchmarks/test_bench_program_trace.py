"""`program_trace`: the program's `rt:` spans and the device's programs,
reduced from a trace; and the readers of the engine loop's clocks. Plain
arithmetic on synthetic tuples first, then a recorded trace."""

import json
import os

import pytest

from benchmarks.harness import manifest, phases, program_trace

SERVE_CELLS = ["olmo-1b.serve.chat-steady", "olmo-1b.serve.decode-heavy"]
NEW_METRICS = {
    "engine_host_ms_per_step": SERVE_CELLS,
    "engine_sample_ms_per_step": SERVE_CELLS,
    "engine_emit_ms_per_step": SERVE_CELLS,
    "engine_gauges_ms_per_step": SERVE_CELLS,
    "engine_host_cpu_share": SERVE_CELLS,
    "engine_queue_wait_ms": SERVE_CELLS[:1],
    "decode_host_prep_ms": SERVE_CELLS,
    "decode_device_ms_per_step": SERVE_CELLS,
    "prefill_device_ms_per_ktok": SERVE_CELLS[:1],
    "prefill_kv_roundtrip_ms_per_ktok": SERVE_CELLS[:1],
    "stream_wake_ms": SERVE_CELLS,
    "device_idle_attributed_pct": SERVE_CELLS,
}

# One decode step of 100 ns on the engine's thread; the device runs
# 35-75.
STEP = [("engine.step", 0, 100), ("engine.decode", 10, 90),
        ("engine.tables", 10, 20), ("model.decode", 20, 80),
        ("model.decode.logits_wait", 30, 80), ("engine.sample", 80, 85),
        ("engine.emit", 85, 90), ("engine.gauges", 92, 98)]


def test_self_time_is_a_span_less_what_its_children_cover():
    got = {name: (interval, own) for name, interval, own in
           program_trace.self_intervals(STEP)}
    assert got["engine.step"] == ((0, 100), [(0, 10), (90, 92), (98, 100)])
    assert got["engine.decode"] == ((10, 90), [])          # all children
    assert got["model.decode"] == ((20, 80), [(20, 30)])
    assert got["model.decode.logits_wait"] == ((30, 80), [(30, 80)])
    # Order of the input does not matter; siblings that touch do not nest.
    again = {name: own for name, _, own in
             program_trace.self_intervals(list(reversed(STEP)))}
    assert again == {name: own for name, (_, own) in got.items()}
    two = program_trace.self_intervals([("a", 0, 5), ("a", 5, 9)])
    assert [own for _, _, own in two] == [[(0, 5)], [(5, 9)]]


def test_idle_time_goes_to_the_span_whose_self_time_it_lies_in():
    got = program_trace.reduce(
        {"engine": STEP}, ops=[("fusion.1 f32[8]", 35, 60),
                               ("fusion.2 f32[8]", 60, 75)],
        modules=[("jit_decode_paged", 35, 76)], window=(0, 100))
    ns = 1e-9
    assert got["window_s"] == pytest.approx(100 * ns)
    assert got["device_idle_s"] == pytest.approx(60 * ns)
    spans = got["spans"]
    assert {n: round(s["device_idle_s"] / ns) for n, s in spans.items()} \
        == {"engine.step": 14, "engine.decode": 0, "engine.tables": 10,
            "model.decode": 10, "model.decode.logits_wait": 10,
            "engine.sample": 5, "engine.emit": 5, "engine.gauges": 6}
    assert spans["model.decode.logits_wait"]["self_s"] == \
        pytest.approx(50 * ns)
    assert spans["engine.decode"]["host_s"] == pytest.approx(80 * ns)
    assert spans["engine.decode"]["self_s"] == 0
    assert all(s["count"] == 1 for s in spans.values())
    # All but the 14 ns in the step's own self time has a phase's name.
    assert got["idle_attributed_s"] == pytest.approx(46 * ns)
    assert got["idle_attributed_share"] == pytest.approx(46 / 60)
    assert got["modules"] == {"jit_decode_paged": {
        "count": 1, "device_s": pytest.approx(40 * ns)}}


def test_an_idle_instant_under_two_threads_spans_counts_once():
    got = program_trace.reduce(
        {"engine": [("engine.park", 0, 50)],
         "trainer": [("train.data_wait", 20, 60)]},
        ops=[("fusion.1 f32[8]", 60, 100)], modules=[], window=(0, 100))
    assert got["device_idle_s"] == pytest.approx(60e-9)
    assert got["spans"]["engine.park"]["device_idle_s"] == \
        pytest.approx(50e-9)
    assert got["spans"]["train.data_wait"]["device_idle_s"] == \
        pytest.approx(40e-9)
    assert got["idle_attributed_share"] == pytest.approx(1.0)


def test_spans_and_modules_are_counted_in_the_window_they_start_in():
    got = program_trace.reduce(
        {"engine": [("engine.step", -20, 10), ("engine.step", 10, 50),
                    ("engine.gauges", 40, 50), ("engine.step", 95, 140)]},
        ops=[("fusion.1 f32[8]", -5, 30), ("fusion.1 f32[8]", 90, 130)],
        modules=[("jit_decode_paged", -5, 30), ("jit_prefill", 90, 130),
                 ("jit_prefill_cached", 131, 140)], window=(0, 100))
    assert got["spans"]["engine.step"]["count"] == 2
    assert got["spans"]["engine.step"]["self_s"] == pytest.approx(35e-9)
    assert got["modules"] == {"jit_prefill": {
        "count": 1, "device_s": pytest.approx(10e-9)}}
    assert program_trace.module_seconds(got, "jit_prefill") == \
        (pytest.approx(10e-9), 1)
    assert program_trace.module_seconds(got, "jit_decode_paged") == (0, 0)


def test_nothing_to_read_gives_none():
    assert program_trace.reduce({}, [], [], None) is None
    assert program_trace.reduce({"t": STEP}, [], [], (0, 100)) is None
    # A device that ran, under a program with no span of its own (the
    # parent of the PR that added them): no share to report.
    bare = program_trace.reduce({}, [("fusion.1 f32[8]", 10, 20)],
                                [("jit_run", 10, 20)], (0, 100))
    assert bare["idle_attributed_share"] is None
    assert bare["modules"] == {"jit_run": {
        "count": 1, "device_s": pytest.approx(10e-9)}}
    assert program_trace.reduce_dir("/nonexistent") is None
    assert program_trace.reduced("/nonexistent") is None
    assert program_trace.of_run({"trace": None, "cell": {"name": "x"}}) \
        is None
    assert program_trace.module_name("jit_decode_paged(438195)") == \
        "jit_decode_paged"


# -- the readers --------------------------------------------------------------
def _reader(name):
    return manifest.load_reader(name)


def test_new_entries_name_their_cells_layers_and_sources():
    entries = {m["name"]: m for m in manifest.load_manifest()["per_layer"]}
    assert NEW_METRICS.keys() <= entries.keys()
    for name, cells in NEW_METRICS.items():
        # A later PR appends its cells; these come first.
        assert entries[name]["workloads"][:len(cells)] == cells, name
        assert entries[name]["moves"] == (
            "serve_itl_p99_ms" if len(cells) == 2 else "serve_ttft_p95_ms")
        assert callable(_reader(name))
    assert {entries[n]["source"] for n in NEW_METRICS
            if "device" in n} == {"device_trace"}
    layers = {m["layer"] for m in entries.values()}
    assert {entries[n]["layer"] for n in NEW_METRICS} <= {
        m["layer"] for n, m in entries.items() if n not in NEW_METRICS}, \
        layers
    assert manifest.problems() == []


COUNTERS = {
    "paged_steps": 100, "steps": 110, "prefills": 4,
    "model.prefill_tokens": 1000, "loop_s": 10.0, "thread_cpu_s": 1.5,
    "queue_wait_s": 0.8, "stream_wake_s": 0.35, "stream_wake_tokens": 700,
    "phase.park_s": 2.0, "phase.tables_s": 0.01, "phase.other_s": 0.02,
    "phase.reap_s": 0.01, "phase.admit_s": 0.01, "phase.capacity_s": 0.02,
    "phase.prefill_match_s": 0.01, "phase.prefill_kv_write_s": 0.25,
    "phase.prefill_seal_s": 0.01, "phase.sample_s": 0.03,
    "phase.emit_s": 0.06, "phase.gauges_s": 0.011,
    "phase.model_prefill_prep_s": 0.01,
    "phase.model_prefill_dispatch_s": 0.02,
    "phase.model_prefill_wait_s": 0.4, "phase.model_prefill_kv_d2h_s": 0.15,
    "phase.model_decode_prep_s": 0.04,
    "phase.model_decode_dispatch_s": 0.05,
    "phase.model_decode_wait_s": 4.6}


def test_counter_readers_do_the_arithmetic_their_entries_say():
    ctx = {"counters": dict(COUNTERS), "trace": None,
           "trace_counters": None, "cell": {"name": "x"}}
    host = 0.01 + 0.02 + 0.01 + 0.01 + 0.02 + 0.03 + 0.06 + 0.011 \
        + 0.04 + 0.05
    assert _reader("engine_host_ms_per_step")(ctx) == \
        pytest.approx(host / 100 * 1e3)
    assert _reader("engine_sample_ms_per_step")(ctx) == pytest.approx(0.3)
    assert _reader("engine_emit_ms_per_step")(ctx) == pytest.approx(0.6)
    assert _reader("engine_gauges_ms_per_step")(ctx) == pytest.approx(0.1)
    assert _reader("engine_host_cpu_share")(ctx) == \
        pytest.approx(100 * 1.5 / (10.0 - 2.0 - 0.4 - 4.6))
    assert _reader("engine_queue_wait_ms")(ctx) == pytest.approx(200.0)
    assert _reader("decode_host_prep_ms")(ctx) == pytest.approx(1.0)
    assert _reader("prefill_kv_roundtrip_ms_per_ktok")(ctx) == \
        pytest.approx(400.0)
    assert _reader("stream_wake_ms")(ctx) == pytest.approx(0.5)
    # No trace: the trace readers find nothing and do not raise.
    for name in ("decode_device_ms_per_step", "prefill_device_ms_per_ktok",
                 "device_idle_attributed_pct"):
        assert _reader(name)(ctx) is None, name
    assert phases.seconds(COUNTERS, ["sample", "emit"]) == \
        pytest.approx(0.09)
    assert phases.seconds(COUNTERS, ["sample", "no_such"]) is None
    assert len(phases.names(COUNTERS)) == 19


def test_on_a_program_without_the_clocks_every_new_reader_gives_none():
    """The parent of the PR that added them: its `stats()` has no
    `phase.*`, no `loop_s`; the line then leaves the metrics out."""
    old = {k: v for k, v in COUNTERS.items()
           if k in ("paged_steps", "steps", "prefills",
                    "model.prefill_tokens")}
    ctx = {"counters": old, "trace": None, "trace_counters": None,
           "cell": {"name": "x"}}
    for name in NEW_METRICS:
        assert _reader(name)(ctx) is None, name
    cell = manifest.load_cell(SERVE_CELLS[0])
    ctx.update(client={"hop_ms": [1.0], "ttft_ms": [1.0], "gaps_ms": [1.0],
                       "late_ms": [0.0]}, widths=cell["widths"], peak=None,
               window_s=1.0)
    ctx["counters"].update({"decode_s": 1.0, "prefill_s": 1.0,
                            "compiles": 0, "cache.host_gathers": 0,
                            "tokens_generated": 10})
    line = manifest.read_layer_metrics(cell, ctx)
    assert not NEW_METRICS.keys() & line.keys()
    assert "decode_step_ms" in line


# -- a small recorded trace ---------------------------------------------------
RECORDED = os.path.join(manifest.bench_dir(), "harness", "testdata",
                        "serve_program_spans.xplane.pb")


def test_reduce_the_recorded_trace_of_two_engine_steps():
    """0.36 s of `olmo-1b.serve.chat-steady` on one v5e chip (PR 24's
    first traced chip run of the program with its own spans, cut to the
    device's op and module lines and the `rt:`/`bench:` spans): a step
    that admits a long prompt and decodes once, a park, an idle step."""
    loaded = program_trace.load(RECORDED)
    assert list(loaded["threads"]) == ["/host:CPU/0:python3"]  # one thread
    assert len(loaded["threads"]["/host:CPU/0:python3"]) == 30
    assert len(loaded["ops"]) == 1181
    assert [name for name, _, _ in loaded["modules"]] == [
        "jit_convert_element_type", "jit_prefill", "jit_dynamic_slice",
        "jit_scatter", "jit_decode_paged"]
    got = program_trace.reduce(loaded["threads"], loaded["ops"],
                               loaded["modules"], loaded["window"])
    assert got["window_s"] == pytest.approx(0.364344419)
    assert got["device_idle_s"] == pytest.approx(0.323559329)
    spans = got["spans"]
    assert {n: s["count"] for n, s in spans.items()} == {
        "engine.step": 2, "engine.reap": 2, "engine.admit": 2,
        "engine.gauges": 2, "engine.sample": 2, "engine.emit": 2,
        "engine.park": 1, "engine.capacity": 1, "engine.decode": 1,
        "engine.tables": 1, "engine.model_step": 1, "model.decode": 1,
        "model.decode.prep": 1, "model.decode.dispatch": 1,
        "model.decode.logits_wait": 1, "engine.prefill": 1,
        "engine.prefill.match": 1, "model.prefill": 1,
        "model.prefill.prep": 1, "model.prefill.dispatch": 1,
        "model.prefill.logits_wait": 1, "model.prefill.kv_d2h": 1,
        "engine.prefill.kv_write": 1, "engine.prefill.seal": 1}
    # The prompt KV's way back into the pool holds half the idle time,
    # its way out and the park most of the rest.
    assert spans["engine.prefill.kv_write"]["device_idle_s"] == \
        pytest.approx(0.158665256)
    assert spans["model.prefill.kv_d2h"]["device_idle_s"] == \
        pytest.approx(0.044385999)
    assert spans["engine.park"]["device_idle_s"] == \
        pytest.approx(0.046781006)
    # Containers keep almost nothing for themselves.
    assert spans["engine.step"]["host_s"] == pytest.approx(0.317424923)
    assert spans["engine.step"]["self_s"] == pytest.approx(0.00007223)
    assert spans["engine.admit"]["self_s"] == pytest.approx(0.00105547)
    assert spans["engine.prefill"]["host_s"] == pytest.approx(0.244402949)
    assert spans["engine.prefill"]["self_s"] == pytest.approx(0.006979449)
    assert got["idle_attributed_s"] == pytest.approx(0.323348609)
    assert got["idle_attributed_share"] == pytest.approx(0.99934874)
    # Self times partition the thread's spanned time: nothing twice.
    assert sum(s["self_s"] for s in spans.values()) == pytest.approx(
        spans["engine.step"]["host_s"] + spans["engine.park"]["host_s"])
    assert got["modules"]["jit_prefill"] == {
        "count": 1, "device_s": pytest.approx(0.030475819)}
    assert got["modules"]["jit_decode_paged"] == {
        "count": 1, "device_s": pytest.approx(0.008843527)}
    assert program_trace.module_seconds(got, "jit_prefill") == \
        (pytest.approx(0.030475819), 1)
    # The module's name selects the same device time as the benchmark's
    # own span around the call.
    from benchmarks.harness import trace

    outside = trace.reduce(trace.load(RECORDED))["spans"]
    # (Here that span also holds the prompt KV's scatter, which ran once
    # its upload had arrived.)
    assert outside["decode_step"]["device_busy_s"] == pytest.approx(
        got["modules"]["jit_decode_paged"]["device_s"]
        + got["modules"]["jit_scatter"]["device_s"])
    assert "jit_run" not in got["modules"]


def test_the_reduction_runs_in_a_child_once_and_is_kept_beside_the_trace(
        tmp_path, monkeypatch):
    import shutil

    run_dir = tmp_path / ".bench_out" / "trace" / "a-cell"
    profile = run_dir / "plugins" / "profile" / "2026_09_27_04_03_36"
    profile.mkdir(parents=True)
    shutil.copy(RECORDED, profile / "host.xplane.pb")
    monkeypatch.setattr(manifest, "ROOT", str(tmp_path))
    ctx = {"trace": {"busy_s": 1.0}, "cell": {"name": "a-cell"},
           "trace_counters": {"model.prefill_tokens": 448},
           "counters": {}}
    assert _reader("decode_device_ms_per_step")(ctx) == \
        pytest.approx(8.843527)
    kept = profile / "host.program_trace.json"
    assert json.loads(kept.read_text())["modules"]["jit_prefill"]["count"] \
        == 1
    assert _reader("prefill_device_ms_per_ktok")(ctx) == \
        pytest.approx(0.030475819 / 448 * 1e6)
    # The second and third reader took the kept result: a changed copy
    # shows through.
    changed = json.loads(kept.read_text())
    changed["idle_attributed_share"] = 0.5
    kept.write_text(json.dumps(changed))
    assert _reader("device_idle_attributed_pct")(ctx) == pytest.approx(50.0)
    assert _reader("device_idle_attributed_pct")(
        dict(ctx, trace=None)) is None


def test_the_span_table_is_printed_on_a_line_of_its_own(tmp_path,
                                                        monkeypatch, capsys):
    reduction = program_trace.reduce(
        {"engine": STEP}, ops=[("fusion.1 f32[8]", 35, 75)],
        modules=[("jit_decode_paged", 35, 76)], window=(0, 100))
    monkeypatch.setattr(program_trace, "of_run", lambda ctx: reduction)
    assert _reader("device_idle_attributed_pct")({}) == \
        pytest.approx(100 * 46 / 60)
    lines = capsys.readouterr().out.splitlines()
    assert len(lines) == 1 and lines[0].startswith("program spans ")
    assert '"engine.tables": [1, ' in lines[0]
    assert '"jit_decode_paged": {"count": 1' in lines[0]


# -- a whole cell at toy widths, on the CPU ------------------------------------
@pytest.fixture(scope="module")
def cluster():
    import ray_tpu

    ray_tpu.init(num_cpus=8, ignore_reinit_error=True)
    yield ray_tpu
    ray_tpu.shutdown()


@pytest.mark.cluster
def test_a_traced_toy_cell_reads_the_clocks_and_leaves_the_device_out(
        cluster):
    """Through `serve.run`, the replica and the streaming handle: the
    counter readers give numbers, the trace readers find no device
    operation in a CPU run's trace and give None."""
    import time

    import bench_toy as toy

    from benchmarks.harness import serve_cell

    cell = toy.cell(SERVE_CELLS[0])
    out = serve_cell.run(cell, seed=2 ** 31 + 11, seconds=2.0, trace=True,
                         t0=time.time(), expect_platform="cpu",
                         timeout_s=300)
    assert out["correct"], out
    ctx = out["ctx"]
    counters = ctx["counters"]
    assert len(phases.names(counters)) == 19
    assert all(counters[f"phase.{name}_s"] >= 0
               for name in phases.names(counters))
    # Over the window the phases account for the loop's wall time.
    assert phases.seconds(counters, phases.names(counters)) == \
        pytest.approx(counters["loop_s"], rel=0.02)
    assert counters["stream_wake_tokens"] > 0 and counters["prefills"] > 0
    assert ctx["trace_counters"]["loop_s"] > 0
    layer = manifest.read_layer_metrics(cell, ctx)
    for name in ("engine_host_ms_per_step", "engine_sample_ms_per_step",
                 "engine_emit_ms_per_step", "engine_gauges_ms_per_step",
                 "engine_queue_wait_ms", "decode_host_prep_ms",
                 "prefill_kv_roundtrip_ms_per_ktok", "stream_wake_ms"):
        assert layer[name]["value"] > 0, name
    assert 0 < layer["engine_host_cpu_share"]["value"] <= 110
    for name in ("decode_device_ms_per_step", "prefill_device_ms_per_ktok",
                 "device_idle_attributed_pct"):
        assert name not in layer
    assert ctx["trace"] is None        # no device operation in the trace
