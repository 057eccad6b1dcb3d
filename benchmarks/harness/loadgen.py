"""One general traffic generator and the two loops that offer the load.

A traffic mix is a data file (`benchmarks/traffic/<mix>.json`); this file
turns it and `--seed` into requests, and drives them through any
`send(request) -> iterator of (token, server_stamp)`. The work is the
same for every seed: lengths and gaps are quantiles of the mix's
distributions (a fixed multiset) in an order fixed by the mix's own
`schedule_seed`; `--seed` draws the token ids (and, in the cell, the
weights). At four fifths of the knee the order alone moved the p90 of the
time to first token sixfold (PERF.md, PR 23), so it is part of the cell,
not of the seed. Never imports JAX.
"""

from __future__ import annotations

import math
import statistics
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, Iterator, List, Optional, Tuple

import numpy as np

_NORMAL = statistics.NormalDist()


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """`--seed` may be larger than 32 signed bits hold."""
    return np.random.default_rng([int(seed), stream])


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def length_multiset(spec: dict, n: int) -> np.ndarray:
    """`n` lengths at evenly spaced quantiles of the distribution,
    clipped to [min, max] and rounded to the grid `step`."""
    u = _quantiles(n)
    if spec["dist"] == "lognormal":
        z = np.array([_NORMAL.inv_cdf(float(x)) for x in u])
        raw = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        raw = spec["min"] + u * (spec["max"] - spec["min"])
    elif spec["dist"] == "fixed":
        raw = np.full(n, float(spec["value"]))
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    step = int(spec.get("step", 1))
    lengths = np.rint(raw / step).astype(np.int64) * step
    return np.clip(lengths, spec.get("min", 1), spec.get("max", 1 << 30))


def length_grid(spec: dict) -> List[int]:
    """Every length the mix can produce: what set-up must warm."""
    if spec["dist"] == "fixed":
        return [int(spec["value"])]
    step = int(spec.get("step", 1))
    lo = int(math.ceil(spec["min"] / step)) * step
    return list(range(lo, int(spec["max"]) + 1, step))


def arrival_gaps(kind: str, rate_per_s: float, n: int,
                 cv: float = 1.0) -> np.ndarray:
    """`n` gaps with mean 1/rate: quantiles of the exponential (Poisson
    arrivals) or of a gamma with coefficient of variation `cv`."""
    u = _quantiles(n)
    if kind == "poisson":
        gaps = -np.log1p(-u)
    elif kind == "uniform":
        gaps = np.ones(n)
    elif kind == "gamma":
        # Wilson-Hilferty: no scipy here. Shape k = 1/cv^2, mean 1.
        k = 1.0 / (cv * cv)
        z = np.array([_NORMAL.inv_cdf(float(x)) for x in u])
        gaps = np.maximum(
            0.0, (1 - 1 / (9 * k) + z * math.sqrt(1 / (9 * k))) ** 3)
    else:
        raise ValueError(f"unknown arrival process {kind!r}")
    return gaps * (n / rate_per_s) / gaps.sum()


@dataclass
class Request:
    index: int
    prompt: List[int]
    max_new_tokens: int
    due: float = 0.0          # seconds after the window opens (open loop)


def plan(traffic: dict, seed: int, seconds: float, vocab_size: int,
         rate_per_s: Optional[float] = None) -> List[Request]:
    """The requests of one run. Open loop: `rate * seconds` requests, all
    due inside the window. Closed loop: the mix's `requests`, reused in
    order by the clients for as long as the window lasts."""
    if traffic["kind"] == "serve_open":
        rate = traffic["rate_per_s"] if rate_per_s is None else rate_per_s
        n = max(1, int(round(rate * seconds)))
    else:
        rate, n = None, int(traffic["requests"])
    schedule = int(traffic.get("schedule_seed", 0))
    prompts = length_multiset(traffic["prompt_len"], n)
    outputs = length_multiset(traffic["output_len"], n)
    rng_for(schedule, 1).shuffle(prompts)
    rng_for(schedule, 2).shuffle(outputs)
    due = np.zeros(n)
    if rate is not None:
        gaps = arrival_gaps(traffic.get("arrivals", "poisson"), rate, n,
                            traffic.get("arrival_cv", 1.0))
        rng_for(schedule, 3).shuffle(gaps)
        due = np.cumsum(gaps) - gaps[0]     # the first is due at once
    tokens = rng_for(seed, 4)
    # Ids 0 (pad) and 1 (the engine's default eos) stay out of prompts.
    return [Request(i, tokens.integers(2, vocab_size, int(prompts[i]))
                    .tolist(), int(outputs[i]), float(due[i]))
            for i in range(n)]


@dataclass
class Outcome:
    request: Request
    due: float = 0.0                  # absolute, time.time() clock
    sent: float = 0.0
    token_times: List[float] = field(default_factory=list)
    server_stamps: List[float] = field(default_factory=list)
    tokens: List[int] = field(default_factory=list)
    error: Optional[str] = None

    @property
    def ok(self) -> bool:
        return (self.error is None
                and len(self.tokens) == self.request.max_new_tokens)


Send = Callable[[Request], Iterator[Tuple[int, float]]]


def _consume(send: Send, outcome: Outcome) -> None:
    try:
        outcome.sent = time.time()
        for token, stamp in send(outcome.request):
            outcome.token_times.append(time.time())
            outcome.server_stamps.append(stamp)
            outcome.tokens.append(token)
    except Exception as e:  # noqa: BLE001 — a failed request, counted
        outcome.error = f"{type(e).__name__}: {e}"


def run_open_loop(send: Send, requests: List[Request], drain_s: float,
                  on_close: Optional[Callable[[], None]] = None
                  ) -> Tuple[float, float, List[Outcome]]:
    """Send each request at its due time whether or not earlier ones have
    finished. Returns (window open, window close, outcomes); a stream
    still open `drain_s` after the last request was due has failed.
    `on_close` runs when the last request has been sent, before the
    drain: where a sweep reads the queue."""
    t_open = time.time()
    outcomes = [Outcome(r, due=t_open + r.due) for r in requests]
    threads = []
    for outcome in outcomes:
        wait = outcome.due - time.time()
        if wait > 0:
            time.sleep(wait)
        t = threading.Thread(target=_consume, args=(send, outcome),
                             daemon=True)
        t.start()
        threads.append(t)
    t_close = time.time()
    if on_close is not None:
        on_close()
    deadline = t_close + drain_s
    for t, outcome in zip(threads, outcomes):
        t.join(max(0.0, deadline - time.time()))
        if t.is_alive() and outcome.error is None:
            outcome.error = f"still streaming {drain_s:.0f} s after the " \
                            f"window closed"
    return t_open, t_close, outcomes


def run_closed_loop(send: Send, requests: List[Request], clients: int,
                    seconds: float, drain_s: float
                    ) -> Tuple[float, float, List[Outcome]]:
    """`clients` callers, each sending its next request when its last one
    completed, for `seconds`; requests in flight at the close run to
    their end (their tokens after the close are not counted)."""
    t_open = time.time()
    t_close = t_open + seconds
    outcomes: List[Outcome] = []
    current: List[Optional[Outcome]] = [None] * clients
    lock = threading.Lock()

    def client(me: int) -> None:
        while time.time() < t_close:
            with lock:
                request = requests[len(outcomes) % len(requests)]
                outcome = current[me] = Outcome(request, due=time.time())
                outcomes.append(outcome)
            _consume(send, outcome)
            if outcome.error is not None:
                return

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(clients)]
    for t in threads:
        t.start()
    deadline = t_close + drain_s
    for t, outcome in zip(threads, current):
        t.join(max(0.0, deadline - time.time()))
    with lock:
        for t, outcome in zip(threads, current):
            if t.is_alive() and outcome is not None \
                    and outcome.error is None:
                outcome.error = f"still streaming {drain_s:.0f} s after " \
                                f"the window closed"
        return t_open, t_close, list(outcomes)


def reachable_shapes(traffic: dict, block_size: int, max_batch: int
                     ) -> Dict[str, list]:
    """What the engine can meet under this mix: every prompt length on
    the grid, and every (batch bucket, block-table bucket) of the paged
    decode step between the shortest prompt and the longest context."""
    prompts = length_grid(traffic["prompt_len"])
    longest = max(prompts) + max(length_grid(traffic["output_len"]))

    def pow2(n: int) -> int:
        return 1 << max(0, (n - 1).bit_length())

    nb_lo = pow2(min(prompts) // block_size + 1)
    nb_hi = pow2((longest - 1) // block_size + 1)
    batches, b = [], 1
    while b <= pow2(max_batch):
        batches.append(b)
        b *= 2
    tables, nb = [], nb_lo
    while nb <= nb_hi:
        tables.append(nb)
        nb *= 2
    return {"prompt_lengths": prompts, "decode_batches": batches,
            "decode_tables": tables, "longest_context": longest}
