"""The yardstick's arithmetic on hand-made samples: percentiles, due-time
latency, lateness, the traffic generator, FLOP and byte counts, and the
reduction of a trace."""

import os

import numpy as np
import pytest

from benchmarks.harness import flops, loadgen, manifest, stats, trace


def test_percentile_is_linear_between_closest_ranks():
    xs = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert stats.percentile(xs, 0) == 10.0
    assert stats.percentile(xs, 50) == 30.0
    assert stats.percentile(xs, 90) == pytest.approx(46.0)
    assert stats.percentile(xs, 100) == 50.0
    assert stats.percentile([], 50) is None
    rng = np.random.default_rng(0).normal(size=101).tolist()
    for q in (5, 50, 90, 95):
        assert stats.percentile(rng, q) == pytest.approx(
            float(np.percentile(rng, q)))


def test_quartile_spread_is_the_contracts():
    values = [100.0, 101.0, 102.0, 103.0, 104.0, 110.0]
    import statistics
    q1, _, q3 = statistics.quantiles(values, n=4)
    assert stats.quartile_spread(values) == pytest.approx(
        (q3 - q1) / statistics.median(values))


def test_latency_runs_from_the_due_time_and_lateness_is_reported():
    # Due at 10.0, sent late at 10.3, first token at 10.5.
    assert stats.ttft_ms(10.0, [10.5, 10.6, 10.9]) == pytest.approx(500.0)
    assert stats.ttft_ms(10.0, []) is None
    assert stats.gaps_ms([10.5, 10.6, 10.9]) == pytest.approx([100.0, 300.0])
    # Only the gaps whose token arrived inside [lo, hi].
    assert stats.gaps_ms([10.5, 10.6, 10.9], lo=10.0, hi=10.7) == \
        pytest.approx([100.0])
    assert stats.lateness_ms([10.0, 11.0], [10.3, 10.9]) == \
        pytest.approx([300.0, 0.0])


@pytest.mark.parametrize("mix", ["serve.chat-steady", "serve.decode-heavy"])
def test_every_seed_gets_the_same_schedule_and_other_tokens(mix):
    import json

    with open(os.path.join(manifest.bench_dir(), "traffic",
                           f"{mix}.json")) as f:
        traffic = json.load(f)
    a = loadgen.plan(traffic, 7, 40.0, 50304)
    b = loadgen.plan(traffic, 2 ** 31 + 11, 40.0, 50304)   # > 32 signed bits
    again = loadgen.plan(traffic, 7, 40.0, 50304)
    assert [r.prompt for r in a] == [r.prompt for r in again]
    # The schedule (sizes, arrivals, their order) is the mix's; the seed
    # draws the token ids.
    assert [(len(r.prompt), r.max_new_tokens, r.due) for r in a] == \
        [(len(r.prompt), r.max_new_tokens, r.due) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in b]
    other = loadgen.plan(dict(traffic, schedule_seed=1), 7, 40.0, 50304)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in other]
    assert sorted(len(r.prompt) for r in a) == \
        sorted(len(r.prompt) for r in other)
    grid = set(loadgen.length_grid(traffic["prompt_len"]))
    assert {len(r.prompt) for r in a} <= grid
    assert all(2 <= t < 50304 for r in a for t in r.prompt)
    lo, hi = traffic["output_len"]["min"], traffic["output_len"]["max"]
    assert all(lo <= r.max_new_tokens <= hi for r in a)
    if traffic["kind"] == "serve_open":
        assert len(a) == round(traffic["rate_per_s"] * 40.0)
        assert a[0].due == 0.0 and a[-1].due < 40.0
        assert np.all(np.diff([r.due for r in a]) >= 0)


def test_arrival_gaps_have_the_asked_mean_and_shape():
    for kind, cv in (("poisson", 1.0), ("gamma", 3.0), ("uniform", 0.0)):
        gaps = loadgen.arrival_gaps(kind, 2.5, 400, cv=cv or 1.0)
        assert gaps.sum() == pytest.approx(400 / 2.5)
        assert np.std(gaps) / np.mean(gaps) == pytest.approx(cv, abs=0.35)


def test_reachable_shapes_cover_the_paged_buckets():
    traffic = {"prompt_len": {"dist": "uniform", "min": 32, "max": 640,
                              "step": 16},
               "output_len": {"dist": "uniform", "min": 16, "max": 256}}
    shapes = loadgen.reachable_shapes(traffic, block_size=16, max_batch=8)
    assert shapes["prompt_lengths"][0] == 32
    assert shapes["prompt_lengths"][-1] == 640
    assert len(shapes["prompt_lengths"]) == 39
    assert shapes["decode_batches"] == [1, 2, 4, 8]
    assert shapes["decode_tables"] == [4, 8, 16, 32, 64]
    assert shapes["longest_context"] == 896


def test_loops_against_a_fake_server():
    def send(request):
        for i in range(request.max_new_tokens):
            yield i, 0.0

    traffic = {"kind": "serve_open", "rate_per_s": 100.0,
               "prompt_len": {"dist": "fixed", "value": 4},
               "output_len": {"dist": "fixed", "value": 3}}
    requests = loadgen.plan(traffic, 1, 0.2, 100)
    t_open, t_close, outcomes = loadgen.run_open_loop(send, requests, 5.0)
    assert len(outcomes) == 20 and all(o.ok for o in outcomes)
    assert all(o.sent >= o.due - 1e-3 for o in outcomes)
    assert 0.15 < t_close - t_open < 1.0

    def broken(request):
        yield 1, 0.0
        raise RuntimeError("shed")

    _, _, outcomes = loadgen.run_open_loop(broken, requests[:2], 5.0)
    assert [o.ok for o in outcomes] == [False, False]
    assert "shed" in outcomes[0].error

    closed = dict(traffic, kind="serve_closed", requests=4, clients=2)
    t_open, t_close, outcomes = loadgen.run_closed_loop(
        send, loadgen.plan(closed, 1, 0.2, 100), 2, 0.2, 5.0)
    assert t_close - t_open == pytest.approx(0.2)
    assert len(outcomes) > 4 and all(o.ok for o in outcomes)


# -- FLOPs and bytes, against hand counts ---------------------------------
OLMO = dict(vocab_size=50304, d_model=2048, n_layers=16, n_heads=16,
            d_ff=8192)
SMOL = dict(vocab_size=49152, d_model=2048, n_layers=6, n_heads=32,
            d_ff=8192)


def test_parameter_counts_by_hand():
    # One block: 4 * 2048^2 (q, k, v, o) + 3 * 2048 * 8192 (gate, up, down).
    per_layer = 4 * 2048 * 2048 + 3 * 2048 * 8192
    assert per_layer == 67_108_864
    olmo = flops.param_counts(OLMO)
    assert olmo["matmul"] == 16 * per_layer + 50304 * 2048 == 1_176_764_416
    assert olmo["total"] == olmo["matmul"] + 16 * 2 * 2048 + 2048
    smol = flops.param_counts(SMOL)
    assert smol["matmul"] == 6 * per_layer + 49152 * 2048 == 503_316_480
    assert manifest.load_cell("olmo-1b.train.fsdp4")["widths"] == dict(
        OLMO, rope_theta=10000.0)
    assert manifest.load_cell("smollm2-1.7b.train.seq2k")["widths"] == dict(
        SMOL, rope_theta=130000.0)


def test_train_flops_per_token_by_hand():
    # 6 N, plus per layer 3 * (QK^T + PV) = 3 * 2 * 2 * d * (S+1)/2.
    attention = 16 * 3 * 2 * 2048 * 2049
    assert flops.train_flops_per_token(OLMO, 2048) == \
        6 * 1_176_764_416 + attention
    assert flops.train_flops_per_token(OLMO, 2048) / 1e9 == \
        pytest.approx(7.463, abs=1e-3)
    assert flops.train_flops_per_token(SMOL, 2048) / 1e9 == \
        pytest.approx(3.171, abs=1e-3)


def test_decode_step_bytes_and_kv_rows_by_hand():
    assert flops.kv_bytes_per_token(OLMO) == 16 * 2 * 16 * 128 * 4 == 262_144
    assert flops.kv_bytes_per_token(dict(SMOL, n_layers=24)) == 393_216
    weights = flops.param_counts(OLMO)["total"] * 4
    assert flops.decode_step_bytes(OLMO, 0) == weights
    assert flops.decode_step_bytes(OLMO, 8 * 300) == weights + 2400 * 262_144
    assert flops.decode_step_flops(OLMO, 8, 2400) == \
        2 * 1_176_764_416 * 8 + 4 * 2048 * 16 * 2400
    peak = manifest.load_cell("olmo-1b.train.fsdp4")["peaks"]["TPU v5 lite"]
    # 4.7 GB of weights at 819 GB/s: 5.7 ms, and bytes bound it.
    assert flops.roofline_seconds(
        flops.decode_step_flops(OLMO, 8, 0), weights, peak) == \
        pytest.approx(weights / 819e9)
    assert weights / 819e9 == pytest.approx(5.75e-3, rel=0.01)


def test_flash_attention_cost_by_hand():
    cost = flops.flash_attention_cost(8, 32, 2048, 64)
    one = 2 * 8 * 32 * 64 * 2048 * 2049 / 2      # one causal S x S x hd matmul
    assert cost["flops"] == 7 * one
    assert cost["bytes"] == 12 * 8 * 32 * 2048 * 64 * 2


# -- the reduction of a trace ----------------------------------------------
def test_interval_arithmetic():
    assert trace.union([(0, 5), (3, 8), (10, 12), (12, 13), (20, 20)]) == \
        [(0, 8), (10, 13)]
    assert trace.clip([(0, 8), (10, 13)], 5, 11) == [(5, 8), (10, 11)]
    assert trace.total([(0, 8), (10, 13)]) == 11
    assert trace.gaps([(2, 4), (6, 7)], 0, 10) == [(0, 2), (4, 6), (7, 10)]
    assert trace.overlap([(0, 8), (10, 13)], 6, 12) == 4


def hand_made_trace():
    ms = 1_000_000
    device0 = [("fusion.1", 0 * ms, 10 * ms), ("all-gather.3", 8 * ms, 14 * ms),
               ("fusion.1", 20 * ms, 30 * ms), ("custom-call.7", 30 * ms,
                                                35 * ms)]
    device1 = [("fusion.1", 0 * ms, 20 * ms)]
    spans = [("bench:window", 0, 40 * ms),
             ("bench:train.report", 14 * ms, 19 * ms),
             ("bench:next(batches)", 35 * ms, 40 * ms),
             ("bench:dispatch_step", 19 * ms, 21 * ms)]
    return {"devices": {0: device0, 1: device1}, "spans": spans}


def test_reduce_a_hand_made_trace():
    got = trace.reduce(hand_made_trace())
    assert got["window_s"] == pytest.approx(0.040)
    # Device 0 busy [0,14) + [20,35) = 29 ms, device 1 20 ms: mean 24.5.
    assert got["busy_s_by_device"] == {"0": pytest.approx(0.029),
                                       "1": pytest.approx(0.020)}
    assert got["busy_s"] == pytest.approx(0.0245)
    assert got["op_s"]["fusion.1"] == pytest.approx(0.020)
    assert got["collective_s"] == pytest.approx(0.006)
    # The gather ran under fusion.1 for 2 of its 6 ms.
    assert got["collective_exposed_s"] == pytest.approx(0.004)
    gaps = dict(got["breakdown"]["idle_gaps"])
    assert gaps == {"train.report": pytest.approx(0.006),
                    "next(batches)": pytest.approx(0.005)}
    assert got["breakdown"]["device_ops"][0] == ["fusion.1",
                                                 pytest.approx(0.020)]
    assert got["spans"]["train.report"] == {
        "count": 1, "host_s": pytest.approx(0.005), "device_busy_s": 0.0}
    assert got["spans"]["dispatch_step"]["device_busy_s"] == \
        pytest.approx(0.001)


def test_a_trace_with_no_device_operation_reduces_to_nothing():
    assert trace.reduce({"devices": {}, "spans": []}) is None
    assert trace.reduce({"devices": {0: []}, "spans": [
        ("bench:window", 0, 10)]}) is None
    assert trace.reduce_dir("/nonexistent") is None


# -- a small recorded trace -------------------------------------------------
RECORDED = os.path.join(manifest.bench_dir(), "harness", "testdata",
                        "serve_one_chip.xplane.pb")


def test_op_names_keep_the_instruction_and_its_first_array_type():
    assert trace.op_name(
        "%fusion.77 = f32[16,8,512,2,16,128]{5,4,3,2,1,0:T(8,128)} "
        "fusion(pred[8,512]{1,0} %p)") == "fusion.77 f32[16,8,512,2,16,128]"
    assert trace.op_name("%while.11 = (s32[]{:T(128)}, f32[8,2048]{1,0}) "
                         "while(%t)") == "while.11 s32[]"
    assert trace.CONTAINER.match("while.11 s32[]")
    assert not trace.CONTAINER.match("fusion.77 f32[16]")
    assert trace.COLLECTIVE.match("all-gather-start.3 f32[4,8]")
    assert trace.op_name("bench:window") == "bench:window"


def test_reduce_the_recorded_trace_of_a_quarter_second_of_serving():
    """0.25 s of `olmo-1b.serve.chat-steady` on one v5e chip (PR 23's
    first traced chip run, trimmed to the device's op line and the
    benchmark's spans): eight decode steps."""
    loaded = trace.load(RECORDED)
    assert sorted(loaded["devices"]) == [0]
    assert len(loaded["devices"][0]) == 6547
    assert {name for name, _, _ in loaded["spans"]} == {
        "bench:window", "bench:engine_step", "bench:decode_step"}
    got = trace.reduce(loaded)
    assert got["window_s"] == pytest.approx(0.25)
    assert got["busy_s"] == pytest.approx(0.180362829)
    assert got["busy_s_by_device"] == {"0": pytest.approx(0.180362829)}
    assert got["collective_s"] == 0.0
    assert got["spans"]["decode_step"]["count"] == 8
    assert got["spans"]["decode_step"]["device_busy_s"] == \
        pytest.approx(0.16494775)
    # The weights' conversion to bf16 tops the list; the scanned layer
    # stack (a `while`) is busy time but not an operation of its own.
    top = got["breakdown"]["device_ops"]
    assert top[0] == ["convert.9 bf16[16,2048,2,8192]",
                      pytest.approx(0.04349224)]
    assert len(top) == 10
    assert not any(name.startswith("while") for name in got["op_s"])
    gaps = dict(got["breakdown"]["idle_gaps"])
    assert gaps["engine_step"] == pytest.approx(0.069596114)
    assert sum(gaps.values()) == pytest.approx(0.25 - 0.180362829)
    # The same file through the harness's CPU-pinned child process.
    child = trace.reduce_in_subprocess(os.path.dirname(RECORDED))
    assert child is None        # no plugins/profile/<time>/ layout there


def test_reduce_the_recorded_trace_of_a_four_chip_train_step():
    """60 ms of `olmo-1b.train.fsdp4` on the four chips of one v5e host
    (PR 23, trimmed to the op lines, device 0's collective transfers and
    the benchmark's spans). On a mesh the gathers and scatters are
    asynchronous ring steps on the "Async XLA Ops" line."""
    loaded = trace.load(os.path.join(os.path.dirname(RECORDED),
                                     "train_four_chips.xplane.pb"))
    assert sorted(loaded["devices"]) == [0, 1, 2, 3]
    assert all(len(events) == 467 for events in loaded["devices"].values())
    assert len(loaded["transfers"][0]) == 43
    assert all(trace.COLLECTIVE.match(name)
               for name, _, _ in loaded["transfers"][0])
    got = trace.reduce(loaded)
    assert got["window_s"] == pytest.approx(0.06)
    assert got["busy_s"] == pytest.approx(0.0566385555)    # mean of four
    assert sorted(got["busy_s_by_device"]) == ["0", "1", "2", "3"]
    # A transfer is in flight for most of the window; compute hides all
    # but 43 microseconds of it.
    assert got["collective_s"] == pytest.approx(0.048301049)
    assert got["collective_exposed_s"] == pytest.approx(4.2697e-05)
    without_transfers = trace.reduce(dict(loaded, transfers={}))
    assert without_transfers["collective_s"] < 0.01
