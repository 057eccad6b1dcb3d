"""The engine loop's own account of its time: `phase.*` in `stats()`
partition `loop_s`; the spans behind them nest in the flight ring; queue
wait and stream wake are measured where they happen; and the device
programs carry their functions' names. Counts and containment only: no
assertion on how long anything took."""

import threading
import time

import pytest

from ray_tpu.core import flight
from ray_tpu.serve.engine import EngineConfig, InferenceEngine, TinyLM

PHASES = {
    "park", "tables", "other", "reap", "admit", "capacity",
    "prefill_match", "prefill_kv_write", "prefill_seal", "sample", "emit",
    "gauges", "model_prefill_prep", "model_prefill_dispatch",
    "model_prefill_wait", "model_prefill_kv_d2h", "model_decode_prep",
    "model_decode_dispatch", "model_decode_wait"}
CLOCKS = {f"phase.{p}_s" for p in PHASES} | {
    "loop_s", "thread_cpu_s", "queue_wait_s", "stream_wake_s",
    "stream_wake_tokens"}


def _clocks(engine):
    stats = engine.stats()
    return {k: stats[k] for k in CLOCKS}


def _phase_sum(clocks):
    return sum(v for k, v in clocks.items() if k.startswith("phase."))


def test_phases_are_there_from_the_start_only_grow_and_sum_to_the_loop(
        recorder):
    eng = InferenceEngine(TinyLM(), EngineConfig(
        block_size=4, num_blocks=256, max_batch_size=4))
    first = _clocks(eng)              # raises KeyError if one is missing
    assert set(first.values()) == {0}
    assert {k for k in eng.stats() if k.startswith("phase.")} == \
        {f"phase.{p}_s" for p in PHASES}
    streams = [eng.submit([3 + i, 5, 7, 9, 2 + i], 60) for i in range(8)]
    last, steps = first, 0
    while eng.step():
        steps += 1
        now = _clocks(eng)
        assert all(now[k] >= last[k] for k in CLOCKS), (last, now)
        last = now
    assert steps > 100
    assert all(len(list(s)) == 60 for s in streams)
    done = _clocks(eng)
    # Nothing unattributed, nothing counted twice.
    assert _phase_sum(done) == pytest.approx(done["loop_s"], rel=0.02)
    assert done["loop_s"] > 0 and done["thread_cpu_s"] > 0
    for phase in ("reap", "admit", "capacity", "tables", "sample", "emit",
                  "gauges", "prefill_match", "prefill_kv_write",
                  "prefill_seal", "other"):
        assert done[f"phase.{phase}_s"] > 0, phase
    # The older clocks are the same spans under their older names.
    stats = eng.stats()
    assert stats["kv_gather_s"] == pytest.approx(done["phase.tables_s"],
                                                 abs=1e-6)
    assert stats["decode_s"] == pytest.approx(
        stats["kv_gather_s"] + stats["model_step_s"], abs=3e-6)
    assert stats["model_step_s"] > 0 and stats["prefill_s"] > 0


def test_the_hosted_loop_counts_its_park_in_loop_s(recorder):
    eng = InferenceEngine(TinyLM(), EngineConfig(block_size=4,
                                                 num_blocks=64))
    eng.start()
    try:
        assert list(eng.submit([3, 4, 5], 5)) == TinyLM().oracle([3, 4, 5],
                                                                 5)
        deadline = time.monotonic() + 10
        while (eng.stats()["phase.park_s"] == 0
               and time.monotonic() < deadline):
            time.sleep(0.01)
    finally:
        eng.stop()
    done = _clocks(eng)
    assert done["phase.park_s"] > 0
    assert _phase_sum(done) == pytest.approx(done["loop_s"], rel=0.02)
    assert any(e[3] == "park" for e in
               flight.snapshot(categories={"engine"}))


def test_clocks_stand_still_with_the_recorder_off(recorder):
    flight.disable()
    eng = InferenceEngine(TinyLM(), EngineConfig(block_size=4,
                                                 num_blocks=64))
    stream = eng.submit([3, 4, 5, 6], 8)
    while eng.step():
        pass
    assert len(list(stream)) == 8
    assert set(_clocks(eng).values()) == {0}
    assert eng.stats()["decode_s"] == 0 == eng.stats()["prefill_s"]
    flight.enable()
    assert flight.snapshot(categories={"engine"}) == []


def _contains(outer, inner, slack_us=2):
    """Ring events (t, tid, cat, label, dur_us, arg): durations are cut to
    whole microseconds, hence the slack."""
    return (outer[0] <= inner[0] + slack_us * 1e-6
            and inner[0] + inner[4] * 1e-6
            <= outer[0] + outer[4] * 1e-6 + slack_us * 1e-6)


def test_engine_spans_nest_in_the_flight_ring(recorder):
    eng = InferenceEngine(TinyLM(), EngineConfig(
        block_size=4, num_blocks=64, max_batch_size=2))
    eng.submit([3, 5, 7, 9, 2, 4, 6, 8], 1)    # one prefill, one step
    assert eng.step() is False                 # finished at its first token
    events = flight.snapshot(categories={"engine"})
    by_label = {}
    for ev in events:
        by_label.setdefault(ev[3], []).append(ev)
    assert {label: len(evs) for label, evs in by_label.items()} == {
        "step": 1, "reap": 1, "admit": 1, "queue_wait": 1, "prefill": 1,
        "prefill.match": 1, "prefill.kv_write": 1, "prefill.seal": 1,
        "sample": 1, "emit": 1, "gauges": 1}
    one = {label: evs[0] for label, evs in by_label.items()}
    for child in ("reap", "admit", "gauges"):
        assert _contains(one["step"], one[child]), child
    assert _contains(one["admit"], one["prefill"])
    for child in ("prefill.match", "prefill.kv_write", "prefill.seal",
                  "sample", "emit"):
        assert _contains(one["prefill"], one[child]), child
    # The prefill's arg is what `/api/timeline` and test_unit_engine read;
    # the request's id rides the wait that ends where the prefill starts.
    assert one["prefill"][5] == "tokens=8 prefix_hit=0"
    assert one["queue_wait"][5] == "seq-0"
    assert one["queue_wait"][0] + one["queue_wait"][4] * 1e-6 <= \
        one["prefill"][0] + 1e-3

    flight.reset()
    streams = [eng.submit([3, 5, 7, 9, 2 + i], 3) for i in range(2)]
    while eng.step():
        pass
    assert all(len(list(s)) == 3 for s in streams)
    decode = [e for e in flight.snapshot(categories={"engine"})
              if e[3] == "decode"]
    assert decode and all(e[5] == 2 for e in decode)      # arg: the batch
    inside = [e for e in flight.snapshot(categories={"engine"})
              if e[3] in ("tables", "model_step", "sample") and e[5] == 2]
    assert len(inside) == 3 * len(decode)
    assert all(any(_contains(d, e) for d in decode) for e in inside)
    # A step's tokens go out from inside the next step's model call; the
    # last step's, which no step follows, after it and inside its
    # `engine.step`.
    emits = [e for e in flight.snapshot(categories={"engine"})
             if e[3] == "emit" and e[5] == 2]
    model_steps = [e for e in inside if e[3] == "model_step"]
    assert len(emits) == len(decode) == 2
    assert _contains(model_steps[1], emits[0])
    assert not any(_contains(d, emits[1]) for d in decode)
    steps = [e for e in flight.snapshot(categories={"engine"})
             if e[3] == "step"]
    assert any(_contains(s, emits[1]) and _contains(s, decode[1])
               for s in steps)
    assert [e[5] for e in steps][-1] is None               # the idle one
    assert all(any(_contains(s, d) for s in steps) for d in decode)


def test_queue_wait_is_the_time_a_request_was_held_in_the_queue(recorder):
    eng = InferenceEngine(TinyLM(), EngineConfig(
        block_size=4, num_blocks=64, max_batch_size=1))
    held = 0.05
    first = eng.submit([3, 4, 5], 4)
    second = eng.submit([6, 7, 8], 2)
    eng.step()                      # admits `first`; the batch is full
    assert eng.stats()["prefills"] == 1
    early = eng.stats()["queue_wait_s"]
    time.sleep(held)                # `second` waits for the slot
    while eng.step():
        pass
    assert len(list(first)) == 4 and len(list(second)) == 2
    stats = eng.stats()
    assert stats["prefills"] == 2
    assert stats["queue_wait_s"] - early >= held
    waits = {e[5]: e[4] for e in flight.snapshot(categories={"engine"})
             if e[3] == "queue_wait"}
    assert set(waits) == {"seq-0", "seq-1"}
    assert waits["seq-1"] >= held * 1e6 > waits["seq-0"]


def test_a_preempted_sequence_waits_from_its_preemption(recorder):
    eng = InferenceEngine(TinyLM(), EngineConfig(
        block_size=4, num_blocks=6, max_batch_size=2,
        prefix_sharing=False))
    streams = [eng.submit([3 + i, 4, 5, 6], 12) for i in range(2)]
    while eng.step():
        pass
    assert all(len(list(s)) == 12 for s in streams)
    stats = eng.stats()
    assert stats["preemptions"] >= 1
    assert stats["prefills"] == 2 + stats["preemptions"]
    waits = [e for e in flight.snapshot(categories={"engine"})
             if e[3] == "queue_wait"]
    assert len(waits) == stats["prefills"]
    assert sum(e[4] for e in waits) * 1e-6 == pytest.approx(
        stats["queue_wait_s"], abs=1e-5 * len(waits))


def test_stream_wake_is_push_to_pickup_of_each_token(recorder):
    eng = InferenceEngine(TinyLM(), EngineConfig(block_size=4,
                                                 num_blocks=64))
    late = 0.05
    stream = eng.submit([3, 4, 5], 6)
    while eng.step():
        pass
    assert eng.stats()["stream_wake_tokens"] == 0       # nobody has read
    time.sleep(late)                                    # a late consumer
    assert len(list(stream)) == 6
    stats = eng.stats()
    assert stats["stream_wake_tokens"] == 6
    assert stats["stream_wake_s"] >= 6 * late

    # A consumer that is already waiting picks each token up as it comes.
    eng.start()
    try:
        got = []
        stream = eng.submit([6, 7, 8], 5)
        reader = threading.Thread(target=lambda: got.extend(stream))
        reader.start()
        reader.join(timeout=30)
        assert not reader.is_alive() and len(got) == 5
    finally:
        eng.stop()
    assert eng.stats()["stream_wake_tokens"] == 11


def test_async_consumers_are_stamped_too(recorder):
    import asyncio

    eng = InferenceEngine(TinyLM(), EngineConfig(block_size=4,
                                                 num_blocks=64))
    stream = eng.submit([3, 4, 5], 4)
    while eng.step():
        pass

    async def read():
        return [tok async for tok in stream]

    assert len(asyncio.run(read())) == 4
    assert eng.stats()["stream_wake_tokens"] == 4


def test_submit_keeps_the_trace_id_of_the_span_that_submitted(recorder,
                                                              tmp_path):
    from ray_tpu.util import tracing

    eng = InferenceEngine(TinyLM(), EngineConfig(block_size=4,
                                                 num_blocks=64))
    was = tracing.tracing_enabled()
    tracing.enable_tracing(str(tmp_path))
    try:
        with tracing.span("serve.replica") as ctx:
            inside = eng.submit([3, 4, 5], 2)
        outside = eng.submit([6, 7, 8], 2)
    finally:
        tracing._enabled = was
    while eng.step():
        pass
    assert len(list(inside)) == 2 and len(list(outside)) == 2
    args = [e[5] for e in flight.snapshot(categories={"engine"})
            if e[3] == "queue_wait"]
    assert args == [f"seq-0 trace={ctx['trace_id']}", "seq-1"]


# -- the model's side, and the device programs' names ------------------------
@pytest.fixture(scope="module")
def tiny_transformer():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import TransformerConfig, init_params

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=2,
                            n_heads=2, d_ff=64, max_seq_len=128,
                            dtype=jnp.float32)
    return init_params(jax.random.PRNGKey(0), cfg), cfg


def test_the_transformer_model_splits_its_calls_and_the_engine_reads_it(
        recorder, tiny_transformer):
    """The prompt KV stays on the device when `prefill` returns, so
    `kv_d2h` stands still."""
    from ray_tpu.serve.engine import TransformerEngineModel

    params, cfg = tiny_transformer
    model = TransformerEngineModel(params, cfg, max_batch_size=2)
    eng = InferenceEngine(model, EngineConfig(
        max_batch_size=2, block_size=4, num_blocks=32))
    assert set(model.phase.values()) == {0.0}
    streams = [eng.submit([2, 3, 4, 5 + i], 6) for i in range(2)]
    while eng.step():
        pass
    assert all(len(list(s)) == 6 for s in streams)
    done = _clocks(eng)
    ran = {"model_prefill_prep", "model_prefill_dispatch",
           "model_prefill_wait", "model_decode_prep",
           "model_decode_dispatch", "model_decode_wait"}
    for phase in ("model_prefill_prep", "model_prefill_dispatch",
                  "model_prefill_wait", "model_prefill_kv_d2h",
                  "model_decode_prep", "model_decode_dispatch",
                  "model_decode_wait"):
        seconds = model.phase[phase[len("model_"):] + "_s"]
        assert done[f"phase.{phase}_s"] == seconds, phase
        assert (seconds > 0) == (phase in ran), phase
    assert _phase_sum(done) == pytest.approx(done["loop_s"], rel=0.02)
    labels = [e[3] for e in flight.snapshot(categories={"model"})]
    assert labels.count("prefill") == 2
    assert labels.count("prefill.kv_d2h") == 0
    assert labels.count("decode") == labels.count("decode.prep") == \
        labels.count("decode.dispatch") == \
        labels.count("decode.logits_wait") == eng.paged_steps > 0
    # Each model call lies inside the engine span that made it.
    ring = flight.snapshot()
    model_calls = [e for e in ring if e[2] == "model"
                   and e[3] in ("prefill", "decode")]
    parents = [e for e in ring if e[2] == "engine"
               and e[3] in ("prefill", "model_step")]
    assert all(any(_contains(p, c) for p in parents) for c in model_calls)
    s = eng.stats()
    assert (s["prefill_kv_device_writes"], s["prefill_kv_host_writes"]) \
        == (2, 0)


def _module_name(jitted, *args):
    import re

    text = jitted.lower(*args).as_text()
    return re.search(r"module @(\w+)", text).group(1)


def test_device_programs_are_named_after_their_functions(tiny_transformer):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ray_tpu.models.transformer import lm_loss
    from ray_tpu.parallel.spmd import make_train_step
    from ray_tpu.serve.engine import TransformerEngineModel

    params, cfg = tiny_transformer
    model = TransformerEngineModel(params, cfg, max_batch_size=2)
    i32 = jnp.int32
    # The model's programs take its own tree (two stacks re-laid).
    laid = model._params
    assert _module_name(model._build_prefill(8), laid,
                        jnp.zeros((8,), i32), i32(3)) == "jit_prefill"
    pool = jnp.zeros((16, 4) + model.kv_token_shape, jnp.float32)
    assert _module_name(
        model._build_prefill_paged(8, 2, 4), laid, jnp.zeros((8,), i32),
        i32(4), i32(3), pool, jnp.zeros((2,), i32)) == "jit_prefill_paged"
    assert _module_name(
        model._build_decode_paged(2, 2, 4), pool, laid,
        jnp.zeros((2, 5 + 2), i32),
        jnp.zeros((2,), i32)) == "jit_decode_paged"

    optimizer = optax.sgd(0.1)
    step = make_train_step(lambda p, b: lm_loss(p, b, cfg), optimizer)
    batch = {"tokens": jnp.asarray(np.ones((2, 9)), i32)}
    lowered = step.lower(params, optimizer.init(params), batch)
    text = lowered.as_text(debug_info=True)
    assert "module @jit_train_step" in text
    # The layers' scopes reach the lowered program's op names
    # (`mlp/mul`; under the gradient `jvp(embed)/...`).
    for scope in ("embed", "attn", "mlp", "lm_head"):
        assert f'"{scope}/' in text or f"({scope})/" in text, scope


# Toy engines of the three model classes that dispatch to a device, each
# as its family builds it (`benchmarks/families`): the dense model, the
# hybrid with per-sequence state, and a model of two layer groups.
_TOY_ENGINES = {
    "dense": ("dense", "olmo-1b", {}),
    "hybrid": ("solar_open2", "solar-open2-250b", {}),
    "layer_groups": ("laguna", "laguna-s-2.1",
                     {"group_blocks": {"window": 12}}),
}


@pytest.mark.parametrize("kind", sorted(_TOY_ENGINES))
def test_meanwhile_runs_once_a_step_between_its_dispatch_and_its_wait(
        recorder, kind):
    import json
    import os

    from benchmarks.harness import manifest

    family_name, config, engine = _TOY_ENGINES[kind]
    family = manifest.load_family(family_name)
    with open(os.path.join(manifest.ROOT, "benchmarks", "configs",
                           f"{config}.json")) as f:
        widths = family.toy_widths(family.widths(json.load(f)))
    # A batch of three slots that holds two rows: never full, so every
    # step is read inside the call that dispatched it (behind a full
    # batch a step is read inside the NEXT call, PR 60:
    # `test_unit_engine_ahead.py`).
    served = family.build_serving(
        widths, {"max_seq_len": 128, "engine": dict(
            engine, max_batch_size=3, block_size=16, num_blocks=32)}, 7)
    eng = InferenceEngine(served["model"], served["engine_config"])
    delivering = eng._in_shadow

    def recorded():
        with flight.span("test", "meanwhile"):
            delivering()

    eng._in_shadow = recorded
    streams = [eng.submit([3, 5, 7, 9, 2 + i], 5) for i in range(2)]
    while eng.step():
        pass
    assert all(len(list(s)) == 5 for s in streams)
    ring = flight.snapshot()
    by_label = {}
    for ev in ring:
        if ev[2] in ("model", "test"):
            by_label.setdefault(ev[3], []).append(ev)
    calls = by_label["decode"]
    assert len(calls) == eng.paged_steps == 4
    for label in ("decode.dispatch", "meanwhile", "decode.logits_wait"):
        assert len(by_label[label]) == len(calls), label
    def end(event):
        return event[0] + event[4] * 1e-6

    for call, dispatch, meanwhile, wait in zip(
            calls, by_label["decode.dispatch"], by_label["meanwhile"],
            by_label["decode.logits_wait"]):
        assert all(_contains(call, e) for e in (dispatch, meanwhile, wait))
        assert end(dispatch) <= meanwhile[0] + 2e-6
        assert end(meanwhile) <= wait[0] + 2e-6
    # The streams' tokens and the gauges went out there, and nowhere in
    # the model's own three phases.
    emits = [e for e in ring if e[2] == "engine" and e[3] == "emit"
             and e[5] == 2]
    assert sum(any(_contains(m, e) for m in by_label["meanwhile"])
               for e in emits) == 3
    assert eng.stats()["tokens_delivered_overlapped"] == 6
    assert _phase_sum(_clocks(eng)) == pytest.approx(
        _clocks(eng)["loop_s"], rel=0.02)
    # A caller that gives none gets a step and nothing else.
    flight.reset()
    family.warm_bucket(eng, served, 2, 1)
    labels = [e[3] for e in flight.snapshot() if e[2] in ("model", "test")]
    assert labels.count("decode.dispatch") == 1 == \
        labels.count("decode.logits_wait")
    assert "meanwhile" not in labels
