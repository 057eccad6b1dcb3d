"""Distributed tracing with cross-process context propagation.

Reference equivalent: `python/ray/util/tracing/tracing_helper.py:34` —
spans around task submit/execute with the trace context injected into the
task spec so a worker's span parents to its caller's, across processes.

Design: W3C `traceparent` strings (`00-<trace_id>-<span_id>-01`) ride the
typed TaskSpec/ActorTaskSpec `trace_ctx` field (core/wire.py). Spans
record into a per-process buffer that flushes to
`<session>/tracing/<pid>.jsonl`; `collect()` merges every process's file
and `to_chrome_trace()` renders the familiar chrome://tracing view.
The OpenTelemetry *API* (installed here without an SDK, matching the
reference's optional dependency) is interoperated with when present:
`span()` also enters an otel span so user-installed SDK exporters see
the same tree. Disabled (the default) the hot path costs one dict.get.
"""

from __future__ import annotations

import contextlib
import contextvars
import json
import os
import random
import secrets
import threading
import time
from typing import Any, Dict, List, Optional

_enabled = False
_dir: Optional[str] = None
_buf: List[dict] = []
_buf_lock = threading.Lock()
_FLUSH_AT = 256

# Production controls (reference: OpenTelemetry TraceIdRatioBased sampler
# + span limits): the sampling decision is made ONCE at the trace root and
# rides the W3C traceparent flags byte, so every process in the request
# path agrees; a per-trace span cap bounds recording for pathological
# fan-outs (a compiled-graph pipeline or a 1000-block dataset must not
# write unbounded spans for one request).
_sample_rate = 1.0
_span_cap: Optional[int] = None
_span_counts: dict = {}          # trace_id -> spans recorded here
_SPAN_COUNT_MAX_TRACES = 4096    # bound the counter table itself

_ctx: contextvars.ContextVar = contextvars.ContextVar(
    "ray_tpu_trace_ctx", default=None)   # (trace_id, span_id, sampled)


_flusher: Optional[threading.Thread] = None


def enable_tracing(out_dir: Optional[str] = None, *,
                   sample_rate: Optional[float] = None,
                   max_spans_per_trace: Optional[int] = None) -> None:
    """Turn span recording on (reference: `ray.init(_tracing_startup_hook)`
    / RAY_TRACING_ENABLED). Workers inherit via the runtime-env
    RAY_TPU_TRACE_DIR / RAY_TPU_TRACE_SAMPLE / RAY_TPU_TRACE_SPAN_CAP
    variables set by the driver.

    `sample_rate` (0..1) is the head-sampling probability applied at each
    trace ROOT: an unsampled trace still propagates context (so a sampled
    child never orphans) but records nothing anywhere. Defaults to 1.0
    (every request), which is for tests/debugging — production traffic
    should run well below (e.g. 0.01)."""
    global _enabled, _dir, _flusher, _sample_rate, _span_cap
    _enabled = True
    if sample_rate is None:
        sample_rate = float(os.environ.get("RAY_TPU_TRACE_SAMPLE", "1.0"))
    _sample_rate = min(1.0, max(0.0, sample_rate))
    os.environ["RAY_TPU_TRACE_SAMPLE"] = repr(_sample_rate)
    if max_spans_per_trace is None:
        cap = os.environ.get("RAY_TPU_TRACE_SPAN_CAP")
        max_spans_per_trace = int(cap) if cap else None
    _span_cap = max_spans_per_trace
    if _span_cap is not None:
        os.environ["RAY_TPU_TRACE_SPAN_CAP"] = str(_span_cap)
    if out_dir is None:
        out_dir = os.environ.get("RAY_TPU_TRACE_DIR") or os.path.join(
            os.environ.get("TMPDIR", "/tmp"), "ray_tpu_tracing")
    os.makedirs(out_dir, exist_ok=True)
    _dir = out_dir
    os.environ["RAY_TPU_TRACE_DIR"] = out_dir
    if _flusher is None or not _flusher.is_alive():
        # Spans must reach disk without waiting for _FLUSH_AT: a serve
        # replica records a handful of spans per request and another
        # process's collect() cannot flush this one's buffer. Daemon
        # timer + atexit cover both long-lived and exiting processes.
        import atexit

        atexit.register(flush)

        def _loop():
            while _enabled:
                time.sleep(0.5)
                try:
                    flush()
                except Exception:
                    pass

        _flusher = threading.Thread(target=_loop, daemon=True,
                                    name="trace-flush")
        _flusher.start()


def tracing_enabled() -> bool:
    return _enabled


def _maybe_autoenable() -> None:
    """Workers: a driver that enabled tracing propagates the dir via the
    env; first span use turns recording on."""
    if not _enabled and os.environ.get("RAY_TPU_TRACE_DIR"):
        enable_tracing(os.environ["RAY_TPU_TRACE_DIR"])


def current_traceparent() -> Optional[str]:
    """W3C traceparent for the ACTIVE span (None outside any span or
    with tracing off). The flags byte carries the sampling decision."""
    ctx = _ctx.get()
    if ctx is None:
        return None
    return f"00-{ctx[0]}-{ctx[1]}-{'01' if ctx[2] else '00'}"


def current_trace_id() -> Optional[str]:
    """Trace id of the ACTIVE span (None outside any span or with
    tracing off): what a component that keeps its own events, like the
    serve engine's flight spans, stores to join them to the request's
    trace."""
    ctx = _ctx.get()
    return ctx[0] if ctx is not None else None


def _parse_traceparent(tp: Optional[str]):
    if not tp:
        return None
    parts = tp.split("-")
    if len(parts) != 4 or len(parts[1]) != 32 or len(parts[2]) != 16:
        return None
    sampled = parts[3] != "00"
    return parts[1], parts[2], sampled


def _under_span_cap(trace_id: str) -> bool:
    if _span_cap is None:
        return True
    n = _span_counts.get(trace_id, 0)
    if n >= _span_cap:
        return False
    if len(_span_counts) >= _SPAN_COUNT_MAX_TRACES:
        _span_counts.clear()   # bounded memory beats exact caps
    _span_counts[trace_id] = n + 1
    return True


def _record(span: dict) -> None:
    with _buf_lock:
        if not _under_span_cap(span["trace_id"]):
            return
        _buf.append(span)
        if len(_buf) >= _FLUSH_AT:
            _flush_locked()


def _flush_locked() -> None:
    if not _dir or not _buf:
        return
    path = os.path.join(_dir, f"{os.getpid()}.jsonl")
    with open(path, "a") as f:
        for s in _buf:
            f.write(json.dumps(s) + "\n")
    _buf.clear()


def flush() -> None:
    with _buf_lock:
        _flush_locked()


@contextlib.contextmanager
def span(name: str, *, parent: Optional[str] = None,
         attributes: Optional[Dict[str, Any]] = None):
    """Record one span. `parent` is a traceparent string (defaults to the
    ambient span via the contextvar — same-process nesting is automatic;
    cross-process callers pass the propagated header)."""
    _maybe_autoenable()
    if not _enabled:
        yield None
        return
    parent_ctx = _parse_traceparent(parent) or _ctx.get()
    if parent_ctx:
        trace_id, sampled = parent_ctx[0], parent_ctx[2]
    else:
        # Trace root: the head-sampling decision, inherited by every
        # descendant span in every process via the traceparent flags.
        sampled = _sample_rate >= 1.0 or random.random() < _sample_rate
        trace_id = (secrets.token_hex(16) if sampled
                    else f"{random.getrandbits(128):032x}")
    if not sampled:
        # Unsampled spans record nothing anywhere; their ids only ever
        # appear as parent_ids of other never-recorded spans. A PRNG id
        # keeps this path free of the os.urandom syscall.
        span_id = f"{random.getrandbits(64):016x}"
        token = _ctx.set((trace_id, span_id, False))
        try:
            yield {"trace_id": trace_id, "span_id": span_id,
                   "sampled": False}
        finally:
            _ctx.reset(token)
        return
    span_id = secrets.token_hex(8)
    token = _ctx.set((trace_id, span_id, True))
    t0 = time.time()
    err: Optional[str] = None
    try:
        yield {"trace_id": trace_id, "span_id": span_id}
    except BaseException as e:
        err = f"{type(e).__name__}: {e}"
        raise
    finally:
        _ctx.reset(token)
        rec = {
            "name": name,
            "trace_id": trace_id,
            "span_id": span_id,
            "parent_id": parent_ctx[1] if parent_ctx else None,
            "start_us": int(t0 * 1e6),
            "dur_us": int((time.time() - t0) * 1e6),
            "pid": os.getpid(),
            "attributes": attributes or {},
        }
        if err:
            rec["error"] = err
        _record(rec)


def collect(out_dir: Optional[str] = None) -> List[dict]:
    """Merge every process's span file (driver-side)."""
    flush()
    d = out_dir or _dir or os.environ.get("RAY_TPU_TRACE_DIR")
    if not d or not os.path.isdir(d):
        return []
    spans: List[dict] = []
    for fname in sorted(os.listdir(d)):
        if not fname.endswith(".jsonl"):
            continue
        with open(os.path.join(d, fname)) as f:
            for line in f:
                line = line.strip()
                if line:
                    try:
                        spans.append(json.loads(line))
                    except json.JSONDecodeError:
                        pass  # torn concurrent write
    return spans


def to_chrome_trace(spans: List[dict],
                    filename: Optional[str] = None):
    """Chrome-trace JSON ("X" complete events keyed by trace) for
    chrome://tracing / Perfetto."""
    events = [{
        "name": s["name"], "ph": "X", "ts": s["start_us"],
        "dur": max(s["dur_us"], 1), "pid": s.get("pid", 0),
        "tid": int(s["span_id"][:6], 16),
        "args": {**s.get("attributes", {}),
                 "trace_id": s["trace_id"],
                 "parent_id": s.get("parent_id")},
    } for s in spans]
    if filename:
        with open(filename, "w") as f:
            json.dump(events, f)
    return events
