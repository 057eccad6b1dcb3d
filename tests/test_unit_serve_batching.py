"""@serve.batch queue + multiplex LRU concurrency semantics (unit tier).

Regression coverage for the two fan-out paths that were previously
untested:
- `_BatchQueue`: the flush timer must fire even when the first awaiter
  (the one whose submit armed the timer) is cancelled mid-wait, and an
  exception in the batched fn must reject EVERY waiter's future;
- multiplex `_ModelMultiplexWrapper`: concurrent `get_model` calls for
  the same cold model id share one load (single-flight), and evicting a
  model an in-flight request still uses defers the drop until that
  request drains (loan scope).
"""

import asyncio
import contextvars
import gc

import pytest

pytestmark = pytest.mark.unit


# ---------------------------------------------------------------------------
# @serve.batch _BatchQueue
# ---------------------------------------------------------------------------
def test_flush_timer_survives_first_awaiter_cancellation():
    """The first submit arms the timer; cancelling that caller must NOT
    strand the second caller — the batch still flushes on time."""
    from ray_tpu.serve import _BatchQueue

    calls = []

    async def batched(owner, items):
        calls.append(list(items))
        return [x * 2 for x in items]

    async def main():
        q = _BatchQueue(batched, max_batch_size=8, wait_timeout_s=0.05)
        first = asyncio.ensure_future(q.submit(None, 1))
        await asyncio.sleep(0.01)       # timer armed by `first`
        first.cancel()
        with pytest.raises(asyncio.CancelledError):
            await first
        # The second waiter relies entirely on the timer the cancelled
        # caller created.
        second = asyncio.ensure_future(q.submit(None, 2))
        out = await asyncio.wait_for(second, timeout=2.0)
        return out

    assert asyncio.run(main()) == 4
    # The cancelled caller's item still rode the batch (its future is
    # just never read) — fan-out discipline, no selective drops.
    assert calls and 1 in calls[0] and 2 in calls[-1]


def test_batched_fn_exception_rejects_all_waiters():
    from ray_tpu.serve import _BatchQueue

    async def batched(owner, items):
        raise ValueError("model exploded")

    async def main():
        q = _BatchQueue(batched, max_batch_size=4, wait_timeout_s=0.01)
        futs = [asyncio.ensure_future(q.submit(None, i)) for i in range(3)]
        results = await asyncio.gather(*futs, return_exceptions=True)
        return results

    results = asyncio.run(main())
    assert len(results) == 3
    for r in results:
        assert isinstance(r, ValueError) and "model exploded" in str(r)


def test_batch_result_length_mismatch_rejects_all_waiters():
    from ray_tpu.serve import _BatchQueue
    from ray_tpu.serve.exceptions import RayServeException

    async def batched(owner, items):
        return [1]     # wrong arity

    async def main():
        q = _BatchQueue(batched, max_batch_size=2, wait_timeout_s=0.01)
        futs = [asyncio.ensure_future(q.submit(None, i)) for i in range(2)]
        return await asyncio.gather(*futs, return_exceptions=True)

    results = asyncio.run(main())
    for r in results:
        assert isinstance(r, RayServeException)


def test_max_batch_size_flushes_immediately_and_timer_is_harmless():
    from ray_tpu.serve import _BatchQueue

    calls = []

    async def batched(owner, items):
        calls.append(len(items))
        return items

    async def main():
        q = _BatchQueue(batched, max_batch_size=2, wait_timeout_s=5.0)
        # Two submits hit max_batch_size: flush NOW, not after 5 s.
        a, b = await asyncio.wait_for(
            asyncio.gather(q.submit(None, "a"), q.submit(None, "b")),
            timeout=2.0)
        return a, b

    assert asyncio.run(main()) == ("a", "b")
    assert calls == [2]


# ---------------------------------------------------------------------------
# multiplex LRU
# ---------------------------------------------------------------------------
@pytest.fixture
def _TrackedModel():
    """A model class that counts its live instances; a class of its own
    for each test, so that one test's leak is not the next one's count."""
    class Tracked:
        alive = 0

        def __init__(self, model_id):
            self.model_id = model_id
            type(self).alive += 1

        def __del__(self):
            type(self).alive -= 1

    return Tracked


def test_multiplex_single_flight_concurrent_cold_load(_TrackedModel):
    from ray_tpu.serve.multiplex import _ModelMultiplexWrapper

    loads = []

    async def load(owner, model_id):
        loads.append(model_id)
        await asyncio.sleep(0.05)       # a slow, expensive load
        return _TrackedModel(model_id)

    async def main():
        w = _ModelMultiplexWrapper(load, None, max_models=2)
        a, b, c = await asyncio.gather(
            w.load("m1"), w.load("m1"), w.load("m1"))
        return w, a, b, c

    w, a, b, c = asyncio.run(main())
    assert loads == ["m1"], f"cold load ran {len(loads)} times"
    assert a is b is c
    assert w.model_ids == ["m1"]


def test_multiplex_eviction_defers_until_inflight_drains(_TrackedModel):
    from ray_tpu.serve.multiplex import (_ModelMultiplexWrapper,
                                         _begin_request_loans,
                                         _end_request_loans)

    async def load(owner, model_id):
        return _TrackedModel(model_id)

    async def main():
        w = _ModelMultiplexWrapper(load, None, max_models=1)
        # Request A borrows m1 inside a loan scope...
        token_a = _begin_request_loans()
        m1 = await w.load("m1")
        assert _TrackedModel.alive == 1
        # ...request B (its own scope, in its own context as a replica
        # runs each request: closing A's scope first then leaves no
        # loan list behind in this task's context, and with it no
        # reference to `w`) loads m2: m1 must be EVICTED from the LRU
        # but kept alive while A still runs it.
        ctx_b = contextvars.copy_context()
        token_b = ctx_b.run(_begin_request_loans)
        m2 = await asyncio.get_running_loop().create_task(
            w.load("m2"), context=ctx_b)
        assert w.model_ids == ["m2"]
        del m1
        gc.collect()
        assert _TrackedModel.alive == 2, \
            "evicted model dropped while request A was still using it"
        # A finishes: the deferred eviction now actually frees m1.
        _end_request_loans(token_a)
        gc.collect()
        assert _TrackedModel.alive == 1
        ctx_b.run(_end_request_loans, token_b)
        del m2
        return w

    w = asyncio.run(main())
    del w              # the wrapper's LRU held the last ref to m2
    gc.collect()
    assert _TrackedModel.alive == 0


def test_multiplex_eviction_immediate_without_loan_scope(_TrackedModel):
    """Direct calls with no request scope keep the old behavior:
    eviction frees the model right away."""
    from ray_tpu.serve.multiplex import _ModelMultiplexWrapper

    async def load(owner, model_id):
        return _TrackedModel(model_id)

    async def main():
        w = _ModelMultiplexWrapper(load, None, max_models=1)
        await w.load("m1")
        await w.load("m2")
        gc.collect()
        # m1 freed the moment m2 displaced it; only m2 remains (held
        # by the wrapper's LRU).
        assert _TrackedModel.alive == 1
        return w.model_ids

    ids = asyncio.run(main())
    assert ids == ["m2"]
    gc.collect()
    assert _TrackedModel.alive == 0  # wrapper gone: m2 freed too


def test_multiplex_load_failure_propagates_to_all_waiters():
    from ray_tpu.serve.multiplex import _ModelMultiplexWrapper

    async def load(owner, model_id):
        await asyncio.sleep(0.02)
        raise RuntimeError("no such adapter")

    async def main():
        w = _ModelMultiplexWrapper(load, None, max_models=2)
        return await asyncio.gather(w.load("bad"), w.load("bad"),
                                    return_exceptions=True)

    results = asyncio.run(main())
    assert all(isinstance(r, RuntimeError) for r in results)
