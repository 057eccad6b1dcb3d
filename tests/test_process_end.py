"""The rule for ending a process that may hold chips, memory or a socket
(`ray_tpu/core/procs.py`): whoever ends it waits until it is gone, from
the worker up to `ray_tpu.shutdown()`, and each layer's deadline comes
from the layer below it. On a TPU host the process that breaks the rule
is not the one that finds out: the next one meets "Device or resource
busy" on a chip the last holder still owns.
"""

import asyncio
import contextlib
import logging
import signal
import subprocess
import sys
import time

import pytest

from ray_tpu.core import procs

IGNORES_SIGTERM = ("import signal, time; "
                   "signal.signal(signal.SIGTERM, signal.SIG_IGN); "
                   "print('ready', flush=True); time.sleep(120)")
# Takes SLOW_STOP_S over its SIGTERM, as a raylet that waits for a chip
# holder does, then leaves with 0.
SLOW_STOP_S = 3.5
SLOW_STOPPER = ("import signal, sys, time; "
                "signal.signal(signal.SIGTERM, lambda *a: "
                f"(time.sleep({SLOW_STOP_S}), sys.exit(0))); "
                "print('ready', flush=True); time.sleep(120)")


@contextlib.contextmanager
def time_limit(seconds):
    """The test's own limit: a rule about deadlines is not tested by
    hanging the suite."""
    def over(*_):
        raise TimeoutError(f"the test took more than {seconds} s")

    before = signal.signal(signal.SIGALRM, over)
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, before)


def _start(code, cls=subprocess.Popen):
    proc = cls([sys.executable, "-c", code], stdout=subprocess.PIPE)
    if "ready" in code:
        assert proc.stdout.readline().strip() == b"ready"
    return proc


class _Unkillable(subprocess.Popen):
    """A process SIGKILL does not take inside the wait: what a holder of
    four chips is to the kernel for 3-13 s."""

    def kill(self):
        pass


class _Quiet:
    def shutdown(self):
        pass

    async def stop(self):
        pass

    close = stop


def _raylet_over(workers):
    """A `Raylet` that was never started, as far as `stop` reads it."""
    from ray_tpu.core.raylet import Raylet, _Worker

    raylet = Raylet.__new__(Raylet)
    raylet._stopping = False
    raylet._tasks, raylet._monitors = [], {}
    raylet._workers = {str(i): _Worker(str(i), proc)
                       for i, proc in enumerate(workers)}
    raylet.store = raylet._rpc = raylet._gcs = _Quiet()
    return raylet


@pytest.mark.unit
def test_the_deadlines_are_derived_and_fire_in_order():
    """The worker's own backstop before its ender's SIGKILL; a raylet's
    ender gives it what it may need for its workers, and more."""
    assert 0 < procs.WORKER_EXIT_S < procs.GRACE_S
    assert procs.RAYLET_GRACE_S > procs.GRACE_S + procs.GONE_S
    # PR 46 measured 3-13 s for four chips of 11.46 GB each.
    assert procs.GONE_S >= 2 * 13


@pytest.mark.unit
def test_raylet_stop_returns_when_every_worker_has_been_reaped():
    stubborn, plain = _start(IGNORES_SIGTERM), _start("import time; "
                                                     "time.sleep(120)")
    raylet = _raylet_over([stubborn, plain])
    ticks = []

    async def scenario():
        async def serve():
            while True:
                ticks.append(time.monotonic())
                await asyncio.sleep(0.05)

        serving = asyncio.ensure_future(serve())
        began = time.monotonic()
        await raylet.stop()
        took = time.monotonic() - began
        serving.cancel()
        return took

    try:
        with time_limit(procs.GRACE_S + 10):
            took = asyncio.run(scenario())
        # No poll() here: stop itself has reaped them.
        assert plain.returncode == -signal.SIGTERM
        assert stubborn.returncode == -signal.SIGKILL
        assert procs.GRACE_S <= took < procs.GRACE_S + 1.0, took
        # The loop kept serving meanwhile: workers that shut down cleanly
        # still talk to their raylet.
        assert len(ticks) > procs.GRACE_S / 0.05 / 2, len(ticks)
    finally:
        for proc in (stubborn, plain):
            subprocess.Popen.kill(proc)
            proc.wait()


@pytest.mark.unit
def test_a_process_that_cannot_be_reaped_is_named_and_stop_returns(
        monkeypatch, caplog):
    monkeypatch.setattr(procs, "GRACE_S", 0.3)
    monkeypatch.setattr(procs, "GONE_S", 0.4)
    held = _start(IGNORES_SIGTERM, cls=_Unkillable)
    gone = _start("pass")
    gone.wait()
    try:
        with time_limit(10), caplog.at_level(logging.WARNING):
            began = time.monotonic()
            assert procs.end_processes([held, gone]) == [held]
            asyncio.run(_raylet_over([held, gone]).stop())
            took = time.monotonic() - began
        assert 2 * (0.3 + 0.4) <= took < 2 * (0.3 + 0.4) + 1.0, took
        named = [r.getMessage() for r in caplog.records
                 if f"pid {held.pid} " in r.getMessage()]
        assert len(named) == 2 and "time.sleep(120)" in named[0], named
        assert held.poll() is None
    finally:
        subprocess.Popen.kill(held)
        held.wait()


def _supervisor_stop(proc, tmp_path):
    from ray_tpu.core.node import NodeSupervisor

    node = NodeSupervisor(str(tmp_path))
    node.processes["raylet"] = proc
    node.stop()
    assert node.processes == {}


def _cluster_shutdown(proc, tmp_path):
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(initialize_head=False)
    cluster._extra_raylets.append(proc)
    cluster.shutdown()
    assert cluster._extra_raylets == []


@pytest.mark.unit
@pytest.mark.parametrize("stop", [_supervisor_stop, _cluster_shutdown])
def test_a_raylets_ender_outwaits_it_and_does_not_kill_it(stop, tmp_path):
    """The old enders gave it 3 s whatever it was waiting for, and their
    SIGKILL left its workers to nobody."""
    slow = _start(SLOW_STOPPER)
    try:
        with time_limit(SLOW_STOP_S + 10):
            began = time.monotonic()
            stop(slow, tmp_path)
            took = time.monotonic() - began
        assert slow.returncode == 0
        assert SLOW_STOP_S <= took < SLOW_STOP_S + 1.0, took
    finally:
        slow.kill()
        slow.wait()


@pytest.mark.cluster
@pytest.mark.parametrize("lived_s", [0.0, 3.0],
                         ids=["starting", "registered"])
def test_the_worker_of_a_killed_raylet_is_gone_within_5_s(
        lived_s, session_processes):
    """Also a worker still inside its start-up (the connect retries of
    `ClusterRuntime`) when the raylet dies: that one used to live 10 s
    more, and on a TPU host a worker holds its chips."""
    from ray_tpu.cluster_utils import Cluster

    def workers_of(address):
        return [pid for pid, cmd in session_processes()
                if cmd.endswith(f"worker_main --raylet {address}")]

    cluster = Cluster(initialize_head=True, head_node_args={"num_cpus": 1})
    try:
        with time_limit(60):
            victim = cluster.add_node(num_cpus=2)
            time.sleep(lived_s)
            assert len(workers_of(victim["address"])) == 2
            cluster.kill_node(victim)
            deadline = time.monotonic() + 5.0
            while (workers_of(victim["address"])
                   and time.monotonic() < deadline):
                time.sleep(0.05)
            assert workers_of(victim["address"]) == []
    finally:
        cluster.shutdown()
    # And the cluster's own end leaves nothing to wait for.
    assert session_processes() == []
