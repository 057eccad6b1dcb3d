"""A decode step in flight (PR 60): behind a full batch none of whose rows
is known to end, the scheduler dispatches the next step before it has
read the last one's ids (`ahead`: `serve/engine/model.py`).

`AheadLM` is the device-model double: `TinyLM`'s arithmetic behind the
protocol of a model whose step runs on a device. Its step comes back as
an object whose ids are "on the device" until somebody looks, it takes a
row's token from the step before's ids where a place is given, and it
keeps a record of every call and every read, in order. Everything a
stream receives is held against `TinyLM.oracle`: a token lost, doubled or
emitted behind an end changes it.
"""

import json
import os
import threading

import numpy as np
import pytest

from ray_tpu.serve.engine import (EngineConfig, EngineStoppedError,
                                  InferenceEngine, TinyLM)

pytestmark = pytest.mark.unit


class _Step:
    """What `DecodeStep` is to a device model: `ids` are read on the
    first look (the model's record says when), `on_device` is what the
    next step's program takes."""

    def __init__(self, ids, number: int, record: list, fail: bool):
        self.on_device = np.asarray(ids, np.int64)
        self.number = number
        self._record, self._fail, self._read = record, fail, False

    def __len__(self):
        return len(self.on_device)

    @property
    def ids(self):
        if not self._read:
            if self._fail:
                raise RuntimeError(f"step {self.number}: the ids are lost")
            self._read = True
            self._record.append(("read", self.number))
        return self.on_device


class AheadLM(TinyLM):
    """`fail_dispatch` / `fail_read`: the number of the decode call (from
    0) that raises when called, or whose ids raise when read."""

    def __init__(self, *args, fail_dispatch=None, fail_read=None, **kwargs):
        super().__init__(*args, **kwargs)
        self.record = []        # ("call", number, kind, rows) | ("read", n)
        self.calls = []         # a dict a call: what the host handed in
        self._fail_dispatch, self._fail_read = fail_dispatch, fail_read

    def decode_paged(self, pool, block_tables, last_tokens, positions,
                     write_blocks, write_offs, block_size, *,
                     meanwhile=None, ahead=None):
        number = len(self.calls)
        before, sources = ahead if ahead is not None else (None, None)
        kind = ("plain" if ahead is None
                else "first" if before is None else "ahead")
        self.calls.append({"kind": kind, "rows": len(last_tokens),
                           "last_tokens": list(last_tokens),
                           "positions": list(positions),
                           "sources": None if sources is None
                           else list(sources)})
        if number == self._fail_dispatch:
            raise RuntimeError(f"step {number}: the dispatch failed")
        # The device's side: the tokens where they are, without a read.
        tokens = [int(before.on_device[src])
                  if before is not None and src >= 0 else int(tok)
                  for tok, src in zip(last_tokens,
                                      sources or [-1] * len(last_tokens))]
        logits, pool = super().decode_paged(
            pool, block_tables, tokens, positions, write_blocks, write_offs,
            block_size)
        self.record.append(("call", number, kind, len(tokens)))
        step = _Step(np.argmax(logits, axis=-1), number, self.record,
                     number == self._fail_read)
        if meanwhile is not None:
            meanwhile()
        if ahead is None:
            step.ids
        elif before is not None:
            before.ids
        return step, pool


def _engine(model, batch=4, blocks=64, **config):
    config.setdefault("block_size", 4)
    return InferenceEngine(model, EngineConfig(
        max_batch_size=batch, num_blocks=blocks, **config))


def _drive(engine, max_steps=10000):
    steps = 0
    while engine.step():
        steps += 1
        assert steps < max_steps, "engine failed to converge"
    return steps


REQUESTS = [([5, 9, 3], 12), ([2, 2], 9), ([7], 14), ([4, 4, 4, 4], 11),
            ([3, 8], 10), ([6, 6, 6], 13), ([9], 12), ([2, 5, 7, 3, 4], 9)]


def _run(model, requests=REQUESTS, **engine):
    eng = _engine(model, **engine)
    streams = [eng.submit(p, n) for p, n in requests]
    _drive(eng)
    assert all(s.finished for s in streams)
    return eng, [s.tokens_so_far() for s in streams]


# ---------------------------------------------------------------------------
# same tokens, whichever way the steps went out
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("eos_period", [0, 5], ids=["no_eos", "eos"])
def test_a_full_batchs_tokens_are_the_oracles_and_a_never_full_batchs(
        eos_period):
    oracle = TinyLM(eos_period=eos_period)
    want = [oracle.oracle(p, n) for p, n in REQUESTS]
    full_model = AheadLM(eos_period=eos_period)
    full, got_full = _run(full_model, batch=4)
    wide_model = AheadLM(eos_period=eos_period)
    wide, got_wide = _run(wide_model, batch=16)
    assert got_full == want
    assert got_wide == want
    # The batch of 4 ran at its cap and went ahead; the batch of 16 never
    # held 16 rows and every step of it was read before the next.
    assert full.stats()["decode_steps_ahead"] > 0
    assert {c["kind"] for c in full_model.calls} >= {"first", "ahead"}
    assert wide.stats()["decode_steps_ahead"] == 0
    assert {c["kind"] for c in wide_model.calls} == {"plain"}
    for eng in (full, wide):
        stats = eng.stats()
        assert stats["tokens_generated"] == sum(len(t) for t in want)
        assert stats["running"] == stats["waiting"] == 0
        assert eng._ahead is None and not eng._pending


def test_a_step_ahead_takes_every_token_from_the_step_before():
    """What the host hands a step ahead: no token (zeros), every row a
    position further than the step in flight, its place in that step's
    ids; the step before is read only once the next is dispatched."""
    model = AheadLM()
    eng, _ = _run(model, requests=[([5, 9, 3], 8), ([2, 2], 8)], batch=2)
    kinds = [c["kind"] for c in model.calls]
    assert kinds[0] == "first" and "ahead" in kinds
    for before, call in zip(model.calls, model.calls[1:]):
        if call["kind"] == "ahead":
            assert call["last_tokens"] == [0, 0]
            assert call["sources"] == [0, 1]
            assert call["positions"] == [p + 1 for p in before["positions"]]
        if call["kind"] == "first":
            assert call["sources"] == [-1, -1]
    # Step n is read right behind the call of step n + 1, where that
    # went ahead: dispatch first, then the wait.
    for at, event in enumerate(model.record):
        if event[0] == "call" and event[2] == "ahead":
            assert model.record[at + 1] == ("read", event[1] - 1)
    assert eng.stats()["decode_steps_ahead"] == kinds.count("ahead")


def test_a_row_that_ends_by_its_length_stops_the_steps_ahead_there():
    """Ends the host can foresee are never found late: the step that
    brings a row's last token is read before anything follows it, so the
    engine makes the calls, row for row, that an engine which never goes
    ahead makes, and drops nothing."""
    requests = [([5, 9, 3], 5), ([2, 2], 9), ([7], 4), ([4, 4], 7),
                ([3, 8], 6), ([6], 3)]
    ahead_model, plain_model = AheadLM(), AheadLM()
    ahead, got = _run(ahead_model, requests=requests, batch=2)
    plain = _engine(plain_model, batch=2)
    plain._takes_ahead = False
    streams = [plain.submit(p, n) for p, n in requests]
    _drive(plain)
    assert got == [s.tokens_so_far() for s in streams]
    assert got == [TinyLM().oracle(p, n) for p, n in requests]
    assert [c["rows"] for c in ahead_model.calls] == \
        [c["rows"] for c in plain_model.calls]
    assert [c["positions"] for c in ahead_model.calls] == \
        [c["positions"] for c in plain_model.calls]
    stats = ahead.stats()
    assert stats["decode_steps_ahead"] > 0
    assert stats["decode_ends_found_late"] == 0
    assert stats["paged_steps"] == plain.stats()["paged_steps"]
    assert {c["kind"] for c in plain_model.calls} == {"plain"}


def test_an_eos_inside_a_step_in_flight_drops_one_token_and_emits_none():
    """The model's `eos_token` among a step's ids is no end the host
    foresees: the row's next step is on the device already. Its token
    there is dropped: the stream ends on the eos, the step's other rows
    lose nothing, and the blocks come back when that step is read."""
    requests = [([5, 9, 3], 30), ([2, 2], 30), ([7], 30), ([4, 4, 4], 30)]
    oracle = TinyLM(eos_period=11)
    want = [oracle.oracle(p, n) for p, n in requests]
    assert [len(w) for w in want] == [25, 15, 15, 6]
    assert all(w[-1] == oracle.eos_token for w in want)
    model = AheadLM(eos_period=11)
    eng, got = _run(model, requests=requests, batch=4, prefix_sharing=False)
    assert got == want
    stats = eng.stats()
    late = stats["decode_ends_found_late"]
    assert late >= 1
    # Every call's rows made a token; all but the dropped ones joined a
    # sequence.
    made = stats["prefills"] + sum(c["rows"] for c in model.calls)
    assert stats["tokens_generated"] == made - late == \
        sum(len(w) for w in want)
    for tokens in got:
        assert oracle.eos_token not in tokens[:-1]
    assert stats["cache"]["free_blocks"] == stats["cache"]["num_blocks"]


# ---------------------------------------------------------------------------
# whatever changes the batch reads the step in flight first
# ---------------------------------------------------------------------------
def _until_in_flight(eng, max_steps=50):
    for _ in range(max_steps):
        assert eng.step()
        if eng._ahead is not None:
            return
    raise AssertionError("no decode step was left in flight")


def test_a_cancel_with_a_step_in_flight_loses_and_doubles_nothing():
    requests = [([5, 9, 3], 20), ([2, 2], 20), ([7], 20), ([4, 4], 20)]
    want = [TinyLM().oracle(p, n) for p, n in requests]
    model = AheadLM()
    eng = _engine(model, batch=4)
    streams = [eng.submit(p, n) for p, n in requests]
    _until_in_flight(eng)
    eng.step()
    assert eng._ahead is not None
    streams[1].cancel()
    late = eng.submit([8, 8], 6)          # takes the cancelled row's place
    _drive(eng)
    got = [s.tokens_so_far() for s in streams]
    for i in (0, 2, 3):
        assert got[i] == want[i]
    # The cancelled stream has a prefix of its tokens, each once, and
    # has ended; the step in flight when it was cancelled was read before
    # the row left, and its token of that step emitted.
    assert 0 < len(got[1]) < 20 and got[1] == want[1][:len(got[1])]
    assert streams[1].finished
    assert late.tokens_so_far() == TinyLM().oracle([8, 8], 6)
    assert eng.stats()["decode_ends_found_late"] == 0
    # No step went out ahead over a batch that held the cancelled row
    # once the cancel was there.
    assert eng._ahead is None


def test_a_preemption_waits_for_the_step_in_flight():
    """A full batch that runs out of blocks: the step ahead needs a
    preemption, so the step in flight is read first and the loop preempts
    as it always has; every stream still gets the oracle's tokens."""
    requests = [([5, 9, 3, 1], 24), ([2, 2, 6, 6], 24), ([7, 3, 3, 3], 24)]
    model = AheadLM()
    eng, got = _run(model, requests=requests, batch=3, blocks=16,
                    prefix_sharing=False)
    assert got == [TinyLM().oracle(p, n) for p, n in requests]
    stats = eng.stats()
    assert stats["preemptions"] > 0
    assert stats["decode_steps_ahead"] > 0
    assert stats["decode_ends_found_late"] == 0
    assert stats["cache"]["free_blocks"] == stats["cache"]["num_blocks"]


def test_stop_with_a_step_in_flight_delivers_it_and_then_fails_the_streams():
    requests = [([5, 9, 3], 20), ([2, 2], 20)]
    want = [TinyLM().oracle(p, n) for p, n in requests]
    model = AheadLM()
    eng = _engine(model, batch=2)
    streams = [eng.submit(p, n) for p, n in requests]
    _until_in_flight(eng)
    eng.step()
    in_flight = eng._ahead[0].number
    assert ("read", in_flight) not in model.record
    eng.stop()
    assert ("read", in_flight) in model.record
    calls = len(model.calls)
    for stream, tokens in zip(streams, want):
        got = stream.tokens_so_far()
        # The prefill's token and one of every step dispatched.
        assert got == tokens[:1 + calls]
        with pytest.raises(EngineStoppedError):
            list(stream)
    assert eng._ahead is None and eng.stats()["running"] == 0


def test_drain_returns_behind_the_last_step_in_flight():
    requests = [([5, 9, 3], 16), ([2, 2], 16), ([7], 16), ([4, 4], 16)]
    model = AheadLM(step_delay_s=0.001)
    eng = _engine(model, batch=2)
    eng.start()
    try:
        streams = [eng.submit(p, n) for p, n in requests]
        got = [None] * len(streams)

        def consume(i):
            got[i] = list(streams[i])

        threads = [threading.Thread(target=consume, args=(i,))
                   for i in range(len(streams))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        assert eng.drain(10)
    finally:
        eng.stop()
    assert got == [TinyLM().oracle(p, n) for p, n in requests]
    assert eng.stats()["decode_steps_ahead"] > 0
    assert eng._ahead is None


@pytest.mark.parametrize("where", ["dispatch", "read"])
def test_a_failing_step_fails_its_streams_behind_their_tokens(where):
    """The call of a step ahead fails (the step in flight can still be
    read: its tokens come first), or the ids of a step in flight are
    lost (what was delivered before stays): every stream has a prefix of
    the oracle's tokens, each once, then the error."""
    requests = [([5, 9, 3], 20), ([2, 2], 20)]
    want = [TinyLM().oracle(p, n) for p, n in requests]
    model = AheadLM(**{f"fail_{where}": 3})
    eng = _engine(model, batch=2)
    streams = [eng.submit(p, n) for p, n in requests]
    _drive(eng)
    # Calls 0-2 made a token each; a failed dispatch leaves step 2 to be
    # read, lost ids take step 3's tokens with them (and step 4's, which
    # was dispatched over them).
    for stream, tokens in zip(streams, want):
        assert stream.tokens_so_far() == tokens[:4]
        with pytest.raises(RuntimeError, match="step 3"):
            list(stream)
    stats = eng.stats()
    assert stats["running"] == stats["waiting"] == 0
    assert eng._ahead is None and not eng._pending
    # The loop survives: a request behind the failure runs to its end.
    again = eng.submit([6, 6], 5)
    _drive(eng)
    assert again.tokens_so_far() == TinyLM().oracle([6, 6], 5)


def test_the_counters_count_what_happened():
    """`decode_steps_ahead` the calls that took `before`,
    `decode_ends_found_late` the dropped tokens, and a step's tokens
    still count as delivered beside a busy device."""
    requests = [([5, 9, 3], 30), ([2, 2], 30)]
    model = AheadLM(eos_period=11)
    eng, got = _run(model, requests=requests, batch=2)
    stats = eng.stats()
    kinds = [c["kind"] for c in model.calls]
    assert stats["paged_steps"] == len(kinds)
    assert stats["decode_steps_ahead"] == kinds.count("ahead") > 0
    dropped = (stats["prefills"] + sum(c["rows"] for c in model.calls)
               - stats["tokens_generated"])
    assert stats["decode_ends_found_late"] == dropped
    decoded = stats["tokens_generated"] - stats["prefills"]
    # A step's tokens go out from the next call's `meanwhile`, ahead or
    # not; only what nothing followed was flushed beside an idle device.
    flushed = decoded - stats["tokens_delivered_overlapped"]
    assert 0 < flushed <= 2 * 2 * (kinds.count("plain") + 1)


def test_a_model_without_the_keyword_is_never_called_with_it():
    seen = []

    class Plain(TinyLM):
        def decode_paged(self, *args, **kwargs):
            seen.append(sorted(kwargs))
            return super().decode_paged(*args, **kwargs)

    eng, got = _run(Plain(), batch=4)
    assert got == [TinyLM().oracle(p, n) for p, n in REQUESTS]
    assert seen and all(keys == ["meanwhile"] for keys in seen)
    assert eng.stats()["decode_steps_ahead"] == 0
    # The oracle model itself has no such parameter either.
    eng, _ = _run(TinyLM(), batch=4)
    assert not eng._takes_ahead and eng.stats()["decode_steps_ahead"] == 0


# ---------------------------------------------------------------------------
# the device models: a bucket's ONE program, whichever way it is called
# ---------------------------------------------------------------------------
TOY_ENGINES = {
    "dense": ("olmo-1b.json", {}),
    "solar_open2": ("solar-open2-250b.json", {}),
    "laguna": ("laguna-s-2.1.json", {"group_blocks": {"window": 12}}),
    "mimo_v2": ("mimo-v2.5.json", {"group_blocks": {"window": 12}}),
    "keye_vl2": ("keye-vl-2.0-30b-a3b.json", {}),
}


def _toy_engine(family_name: str):
    from benchmarks.harness import manifest

    family = manifest.load_family(family_name)
    config_file, more = TOY_ENGINES[family_name]
    with open(os.path.join(manifest.ROOT, "benchmarks", "configs",
                           config_file)) as f:
        widths = family.toy_widths(family.widths(json.load(f)))
    served = family.build_serving(
        widths, {"max_seq_len": 128, "engine": dict(
            paged_decode=True, max_batch_size=2, block_size=16,
            num_blocks=32, max_queue=16, **more)}, 7)
    model = served["model"]
    if hasattr(model, "prefill_chunk_tokens"):
        model.prefill_chunk_tokens = 1024       # these prompts go whole
    return model, InferenceEngine(model, served["engine_config"])


@pytest.mark.parametrize("family_name", sorted(TOY_ENGINES))
def test_a_bucket_compiles_one_program_whichever_way_it_is_called(
        family_name):
    """A full batch through the scheduler (the first step of a run, then
    steps ahead), against the same requests through an engine that never
    goes ahead: the same tokens, and not one program more. Then a plain
    call and a call ahead into one bucket by hand."""
    rng = np.random.default_rng(3)
    prompts = [rng.integers(2, 60, 5).tolist() for _ in range(2)]
    tokens, compiles, ahead = {}, {}, {}
    for way in ("ahead", "read_first"):
        model, eng = _toy_engine(family_name)
        assert eng._takes_ahead
        eng._takes_ahead = way == "ahead"
        streams = [eng.submit(p, 7) for p in prompts]
        _drive(eng)
        tokens[way] = [s.tokens_so_far() for s in streams]
        compiles[way] = model.jit_compiles
        ahead[way] = eng.stats()["decode_steps_ahead"]
        assert all(len(t) == 7 for t in tokens[way])
    assert tokens["ahead"] == tokens["read_first"]
    assert compiles["ahead"] == compiles["read_first"]
    assert ahead["ahead"] >= 4 and ahead["read_first"] == 0

    # By hand, as the benchmark's warm-up calls it (plain), then ahead of
    # that step's ids: the bucket is compiled once.
    state = ((eng.cache._state, []) if eng.cache._state is not None
             else ())
    table = ({name: (0, [0]) for name in ("global", "window")}
             if eng.cache.grouped else [0])
    pools = eng.cache.with_pools(lambda pools: pools) \
        if eng.cache.grouped else eng.cache.with_pool(lambda pool: pool)
    none = {"global": [], "window": []}
    writes = (none, none) if eng.cache.grouped else ([], [])

    def call(pools, state, **keywords):
        out = model.decode_paged(pools, [table, table], [3, 4], [15, 15],
                                 *writes, 16, *state, **keywords)
        return out[0], out[1], ((out[2], []) if state else ())

    before = model.jit_compiles
    first, pools, state = call(pools, state, ahead=(None, [-1, -1]))
    built = model.jit_compiles - before
    assert built <= 1
    second, pools, state = call(pools, state, ahead=(first, [0, 1]))
    plain, pools, state = call(pools, state)
    assert model.jit_compiles == before + built
    # The step ahead took its tokens from the first one's ids; a plain
    # call over the same tokens samples what it sampled.
    again, pools, state = call(pools, state, ahead=(None, [-1, -1]))
    np.testing.assert_array_equal(first.ids, again.ids)
    assert len(second.ids) == len(plain.ids) == 2
