"""Serve: controller reconciliation, routing, autoscaling, rolling
updates, HTTP ingress.

Reference coverage class: `python/ray/serve/tests/test_standalone.py` +
`test_autoscaling_policy.py` + `test_proxy.py`. BASELINE north-star #5:
deploy a jitted model, scale replicas under load, rolling update without
dropped requests.
"""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

pytestmark = pytest.mark.cluster


@pytest.fixture(scope="module")
def ray_cluster():
    import ray_tpu

    ray_tpu.init(num_cpus=8, ignore_reinit_error=True)
    yield ray_tpu
    ray_tpu.shutdown()


@pytest.fixture()
def serve_instance(ray_cluster):
    from ray_tpu import serve

    yield serve
    serve.shutdown()


def test_deploy_jitted_model_and_http(serve_instance):
    """A deployment holding a jitted model answers over handle and HTTP
    with 2 replicas."""
    serve = serve_instance

    @serve.deployment(num_replicas=2)
    class Model:
        def __init__(self, scale):
            import jax
            import jax.numpy as jnp

            jax.config.update("jax_platforms", "cpu")
            self._fwd = jax.jit(lambda x: (x * scale).sum())
            self._jnp = jnp

        def __call__(self, req):
            x = self._jnp.asarray(
                [float(v) for v in req["x"]], self._jnp.float32)
            return {"y": float(self._fwd(x))}

    handle = serve.run(Model.bind(3.0), route_prefix="/model")
    out = handle.remote({"x": [1, 2, 3]}).result(timeout_s=60)
    assert out["y"] == pytest.approx(18.0)

    port = serve.start()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/model",
        data=json.dumps({"x": [2, 2]}).encode(),
        headers={"Content-Type": "application/json"})
    body = json.loads(urllib.request.urlopen(req, timeout=30).read())
    assert body["y"] == pytest.approx(12.0)

    st = serve.status()["Model"]
    assert len([r for r in st["replicas"]
                if r["state"] == "RUNNING"]) == 2


def test_requests_spread_across_replicas(serve_instance):
    serve = serve_instance

    @serve.deployment(num_replicas=2)
    class WhoAmI:
        def __call__(self, _):
            import os

            return os.getpid()

    handle = serve.run(WhoAmI.bind(), route_prefix="/who")
    # Wait until BOTH replicas are running (serve.run only waits for the
    # first) so the router's table has both before we measure spread.
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        st = serve.status()["WhoAmI"]
        if len([r for r in st["replicas"]
                if r["state"] == "RUNNING"]) == 2:
            break
        time.sleep(0.1)
    pids = {handle.remote(None).result(timeout_s=30) for _ in range(20)}
    assert len(pids) == 2


def test_autoscaling_scales_up_under_load(serve_instance):
    """Queue-length autoscaling grows replicas from 1 toward max under
    sustained concurrent load (reference: autoscaling_policy.py:12)."""
    serve = serve_instance

    @serve.deployment(
        max_ongoing_requests=4,
        autoscaling_config=serve.AutoscalingConfig(
            min_replicas=1, max_replicas=3,
            target_ongoing_requests=1.0, upscale_delay_s=0.2,
            downscale_delay_s=60.0))
    class Slow:
        def __call__(self, _):
            time.sleep(0.3)
            return "done"

    handle = serve.run(Slow.bind(), route_prefix="/slow")

    stop = threading.Event()
    errors = []

    def hammer():
        while not stop.is_set():
            try:
                handle.remote(None).result(timeout_s=60)
            except Exception as e:  # noqa: BLE001
                errors.append(e)
                return

    threads = [threading.Thread(target=hammer) for _ in range(6)]
    for t in threads:
        t.start()
    try:
        deadline = time.monotonic() + 30
        peak = 1
        while time.monotonic() < deadline:
            st = serve.status()["Slow"]
            running = [r for r in st["replicas"]
                       if r["state"] == "RUNNING"]
            peak = max(peak, len(running))
            if peak >= 2:
                break
            time.sleep(0.25)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30)
    assert not errors, errors[:1]
    assert peak >= 2, f"autoscaler never scaled up (peak={peak})"


def test_rolling_update_no_dropped_requests(serve_instance):
    """Redeploying a new version keeps serving: no request errors while
    old replicas drain and new ones take over; afterwards every response
    is from the new version."""
    serve = serve_instance

    @serve.deployment(num_replicas=2, version="v1")
    class Versioned:
        def __init__(self, tag):
            self.tag = tag

        def __call__(self, _):
            time.sleep(0.02)
            return self.tag

    handle = serve.run(Versioned.bind("v1"), route_prefix="/v")
    assert handle.remote(None).result(timeout_s=30) == "v1"

    stop = threading.Event()
    errors = []
    seen = []

    def hammer():
        while not stop.is_set():
            try:
                seen.append(handle.remote(None).result(timeout_s=60))
            except Exception as e:  # noqa: BLE001
                errors.append(e)
                return

    threads = [threading.Thread(target=hammer) for _ in range(4)]
    for t in threads:
        t.start()
    time.sleep(0.3)
    serve.run(Versioned.options(version="v2").bind("v2"),
              route_prefix="/v")
    # Wait until only-v2 responses remain.
    deadline = time.monotonic() + 60
    try:
        while time.monotonic() < deadline:
            n = len(seen)
            time.sleep(0.5)
            recent = seen[n:]
            if recent and all(tag == "v2" for tag in recent):
                break
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30)
    assert not errors, f"dropped requests during rolling update: " \
                       f"{errors[:1]}"
    assert "v2" in seen, "update never completed"
    tail = seen[-5:]
    assert all(tag == "v2" for tag in tail), tail


def test_batching_folds_concurrent_requests(serve_instance):
    """@serve.batch folds concurrent calls into one vectorized forward
    (the MXU lever; reference: serve/batching.py)."""
    serve = serve_instance

    @serve.deployment(max_ongoing_requests=16)
    class Batched:
        def __init__(self):
            self.batch_sizes = []

        @serve.batch(max_batch_size=8, batch_wait_timeout_s=0.05)
        async def handle(self, items):
            self.batch_sizes.append(len(items))
            return [x * 2 for x in items]

        async def __call__(self, x):
            return await self.handle(x)

        def sizes(self):
            return self.batch_sizes

    handle = serve.run(Batched.bind(), route_prefix="/batched")
    resps = [handle.remote(i) for i in range(8)]
    outs = [r.result(timeout_s=60) for r in resps]
    assert outs == [i * 2 for i in range(8)]
    sizes = handle.options(method_name="sizes").remote().result(
        timeout_s=30)
    assert max(sizes) > 1, f"no batching happened: {sizes}"


def test_model_composition_via_handles(serve_instance):
    """Deployments call other deployments through handles passed as init
    args (reference: serve model composition / deployment graphs)."""
    serve = serve_instance

    @serve.deployment
    class Preprocessor:
        def __call__(self, x):
            return [v * 2 for v in x]

    @serve.deployment
    class Model:
        def __call__(self, x):
            return sum(x)

    @serve.deployment
    class Pipeline:
        def __init__(self, pre_handle, model_handle):
            self.pre = pre_handle
            self.model = model_handle

        def __call__(self, req):
            halfway = self.pre.remote(req["x"]).result(timeout_s=30)
            return {"y": self.model.remote(halfway).result(timeout_s=30)}

    pre = serve.run(Preprocessor.bind(), route_prefix="/pre")
    model = serve.run(Model.bind(), route_prefix="/m2")
    pipeline = serve.run(Pipeline.bind(pre, model), route_prefix="/pipe")
    out = pipeline.remote({"x": [1, 2, 3]}).result(timeout_s=60)
    assert out == {"y": 12}


def test_delete_deployment(serve_instance):
    serve = serve_instance

    @serve.deployment
    class Tmp:
        def __call__(self, _):
            return 1

    handle = serve.run(Tmp.bind(), route_prefix="/tmp")
    assert handle.remote(None).result(timeout_s=30) == 1
    serve.delete("Tmp")
    assert "Tmp" not in serve.status()


@pytest.mark.parametrize("listed, waits", [
    ("deployment", True),        # a router: its deployment, up to date
    ("routes_stale", False),     # a proxy that has not seen the table
    ("routes_current", True),    # a proxy that has
])
def test_long_poll_blocks_until_a_listed_key_moves(serve_instance, listed,
                                                    waits):
    """A handle's router lists its deployment and not "__routes__". Told
    of the route table all the same, its long poll returned at once,
    every time: some 500 calls a second from every process that held a
    handle, half a core there and half a core in the controller."""
    import ray_tpu

    serve = serve_instance

    @serve.deployment
    class Poll:
        def __call__(self, _):
            return 1

    handle = serve.run(Poll.bind(), route_prefix="/poll")
    assert handle.remote(None).result(timeout_s=30) == 1
    controller = handle._controller
    routes = ray_tpu.get(controller.listen_for_change.remote(
        {"__routes__": -1}, timeout_s=5.0), timeout=20)["__routes__"]
    versions = {
        "deployment": {"Poll": handle._router._version},
        "routes_stale": {"__routes__": routes["version"] - 1},
        "routes_current": {"__routes__": routes["version"]},
    }[listed]
    t0 = time.monotonic()
    out = ray_tpu.get(controller.listen_for_change.remote(
        versions, timeout_s=0.6), timeout=20)
    took = time.monotonic() - t0
    if waits:
        assert out == {} and took >= 0.5, (out, took)
    else:
        assert out["__routes__"]["routes"] == routes["routes"]


def test_requests_ask_the_controller_only_when_the_poll_cannot_know(
        serve_instance, monkeypatch):
    """The router's long poll hears of every change of the table, so a
    request a second after the last one routes without a controller
    round trip of its own (it used to pay one); the first request and
    the one after `invalidate` still ask."""
    from ray_tpu.serve._private.router import Router

    serve = serve_instance
    applied = []
    apply = Router._apply
    monkeypatch.setattr(
        Router, "_apply",
        lambda self, table: (applied.append(table["version"]),
                             apply(self, table))[1])

    @serve.deployment
    class Quiet:
        def __call__(self, _):
            return 1

    handle = serve.run(Quiet.bind(), route_prefix="/quiet")
    assert handle.remote(None).result(timeout_s=30) == 1
    router = handle._router
    deadline = time.monotonic() + 10
    while not router._poll_live and time.monotonic() < deadline:
        time.sleep(0.05)
    assert router._poll_live
    seen = len(applied)
    assert seen >= 1
    for _ in range(2):
        time.sleep(router._refresh_interval_s + 0.1)
        assert handle.remote(None).result(timeout_s=30) == 1
    assert len(applied) == seen, applied
    router.invalidate()
    assert handle.remote(None).result(timeout_s=30) == 1
    assert len(applied) > seen and router._version == applied[-1]


def test_model_composition(serve_instance):
    """Deployment graph: ingress holds a handle to a child deployment
    (reference: serve deployment_graph_build + handle-injection); the
    child response is awaitable inside the async ingress."""
    import ray_tpu
    from ray_tpu import serve

    @serve.deployment
    class Doubler:
        def __call__(self, x):
            return x * 2

    @serve.deployment
    class Ingress:
        def __init__(self, doubler, bias):
            self.doubler = doubler
            self.bias = bias

        async def __call__(self, x):
            y = await self.doubler.remote(x)
            return y + self.bias

    handle = serve.run(Ingress.bind(Doubler.bind(), 3), name="comp",
                       route_prefix="/comp")
    assert handle.remote(5).result(timeout_s=60) == 13
    # The child is addressable on its own too.
    child = serve.get_deployment_handle("Doubler")
    assert child.remote(7).result(timeout_s=60) == 14
    # And the composed app serves over HTTP.
    import json
    import urllib.request

    port = serve.start()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/comp", data=json.dumps(4).encode(),
        headers={"content-type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as resp:
        assert json.loads(resp.read()) == 11
    serve.delete("Ingress")
    serve.delete("Doubler")


def test_compiled_deployment_chain(serve_instance):
    """A fixed two-deployment pipeline compiled onto pinned replicas
    answers through channels (no router hop), matches the handle path,
    and tears down cleanly."""
    serve = serve_instance
    import ray_tpu

    @serve.deployment
    class Doubler:
        def __call__(self, x):
            return x * 2

    @serve.deployment
    class Biaser:
        def __call__(self, x):
            return x + 3

    serve.run(Doubler.bind(), name="d", route_prefix="/double")
    serve.run(Biaser.bind(), name="b", route_prefix="/bias")

    compiled = serve.compile_deployment_chain(["Doubler", "Biaser"])
    try:
        assert ray_tpu.get(compiled.execute(5), timeout=60) == 13
        # Matches the routed handle path.
        d = serve.get_deployment_handle("Doubler")
        b = serve.get_deployment_handle("Biaser")
        assert b.remote(d.remote(5).result(timeout_s=60)) \
            .result(timeout_s=60) == 13
        # Pipelined: many requests through the persistent loops.
        refs = [compiled.execute(i) for i in range(20)]
        assert [ray_tpu.get(r, timeout=60) for r in refs] \
            == [i * 2 + 3 for i in range(20)]
    finally:
        compiled.teardown()
    # The routed path still works after teardown.
    d = serve.get_deployment_handle("Doubler")
    assert d.remote(4).result(timeout_s=60) == 8
    serve.delete("Doubler")
    serve.delete("Biaser")


def test_autoscaler_consumes_gauges():
    """The controller folds the data plane's own gauges
    (serve_replica_ongoing_requests + serve_deployment_queued_queries)
    into its scaling signal instead of polling replicas (unit test of
    the fold; the end-to-end behavior is test_autoscaling_scales_up...)."""
    from ray_tpu.serve._private.controller import (
        _deployment_load_from_samples)

    snaps = [
        {"name": "serve_replica_ongoing_requests", "type": "gauge",
         "samples": [
             {"tags": {"deployment": "M", "replica": "M#1"}, "value": 3},
             {"tags": {"deployment": "M", "replica": "M#dead"},
              "value": 9},            # not in the live set: ignored
             {"tags": {"deployment": "other", "replica": "o#1"},
              "value": 7},            # another deployment: ignored
         ]},
        {"name": "serve_deployment_queued_queries", "type": "gauge",
         "samples": [
             {"tags": {"deployment": "M"}, "value": 4},
             {"tags": {"deployment": "M"}, "value": 2},  # second router
             {"tags": {"deployment": "other"}, "value": 5},
         ]},
    ]
    per_replica, queued = _deployment_load_from_samples(
        snaps, "M", ["M#1", "M#2"])
    assert per_replica == {"M#1": 3}
    assert queued == 6
