"""LLM-serving performance guards (`llm_serve` bench scenario).

In-process (no cluster): the continuous-batching engine and the
static-batching baseline run the identical `InferenceEngine` loop over
the same deterministic TinyLM workload, so the vs-static ratio is a
scheduling-policy measurement with most box noise common-moded out.

Calibration (idle 2-CPU dev box, 2026-08, fresh): engine 2.4-2.7k
tok/s vs static 0.9-1.0k on the mixed workload (ratio 2.66-2.87 — the
structure guarantees it: static forms full-width batches but pays the
long pole at shrinking occupancy, 186 model calls where continuous
pays 55 for the same tokens), TTFT p50 25-33 ms, 2x-overload p99
32-63 ms with thousands of pre-queue sheds. Floors/ceilings follow the
repo's 75-80%-of-low-end rule, wide enough for harness contention:
the ratio floor (1.5) only trips if iteration-level scheduling stops
refilling slots; the p99 ceiling (1500 ms) only trips if overload work
starts queuing unboundedly instead of shedding.

The prefix-sharing workload (PR 13) runs warm (prefix_sharing on: the
shared 80-token system prompt prefills once, every later conversation
adopts its blocks) vs cold (sharing off) through the IDENTICAL loop —
another scheduling-policy-only ratio. Fresh measurements: warm/cold
tokens/s 5.5-7x and TTFT p50 ratio 5-6x (structural: cold pays the
80-token simulated prefill per admission, warm pays a 3-token tail),
prefix_hit_tokens ~1k with 2 COW copies from the truncated re-asks.
The 1.5x floor only trips if adoption stops skipping prefill compute.

The paged guards are structural, read off the mixed workload's own
engine: its decode steps went through `decode_paged` (steps > 0) and it
gathered no sequence's KV on the host (ZERO host gathers).

Runs in the serialized perf tail stage (conftest reorders perf-marked
tests last); fold-best over up to 3 rounds like the other guards.
"""

import pytest

from ray_tpu.perf import run_llm_serve_bench

pytestmark = [pytest.mark.perf]

FLOORS = {
    "llm_engine_tok_s": 800.0,
    "llm_engine_vs_static": 1.5,
    "llm_overload_shed": 1,       # 2x overload MUST shed, not queue
    "llm_overload_served": 50,    # ...while still serving real traffic
    "llm_prefix_warm_vs_cold": 1.5,       # shared prefill must pay off
    "llm_prefix_ttft_cold_over_warm": 1.2,  # ...and cut first-token lat
    "llm_prefix_hit_tokens": 1,   # sharing actually engaged
    "llm_paged_steps": 1,         # the steps ran through decode_paged
}
CEILINGS = {
    "llm_ttft_p50_ms": 300.0,
    "llm_overload_p99_ms": 1500.0,
    "llm_paged_host_gathers": 0,  # the engine gathers no KV on the host
}

ROUNDS = 3


def _violations(best):
    out = []
    for metric, floor in FLOORS.items():
        if best[metric] < floor:
            out.append(f"{metric}={best[metric]} < floor {floor}")
    for metric, ceil in CEILINGS.items():
        if best[metric] > ceil:
            out.append(f"{metric}={best[metric]} > ceiling {ceil}")
    return out


def test_llm_serve_perf_guards():
    best = {}
    bad = ["never ran"]
    for _ in range(ROUNDS):
        r = run_llm_serve_bench(scale=0.5)
        for m in FLOORS:
            best[m] = max(best.get(m, float("-inf")), r[m])
        for m in CEILINGS:
            best[m] = min(best.get(m, float("inf")), r[m])
        bad = _violations(best)
        if not bad:
            break
    assert not bad, (
        f"llm_serve guards violated: {bad}\n{best}\n"
        "reproduce with: python -m ray_tpu.perf --llm-serve")
