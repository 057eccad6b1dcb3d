"""Shared pytest fixtures.

Mirrors the reference's conftest strategy (`python/ray/tests/conftest.py`):
fixtures that boot a real runtime per test, plus the TPU-less trick from
SURVEY.md §4.2 — JAX pinned to CPU with 8 virtual devices so mesh/sharding
tests run anywhere (`xla_force_host_platform_device_count`).
"""

import os

# Must be set before jax is imported anywhere in the test process.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import signal  # noqa: E402
import time  # noqa: E402

import pytest  # noqa: E402

# Every process the runtime spawns runs `python -m <one of these>`. Matching
# the exact ("-m", module) argv pair keeps the reaper from ever touching an
# unrelated process whose command line merely *mentions* ray_tpu.
_RAY_SPAWNED_MODULES = {
    "ray_tpu.core.raylet",
    "ray_tpu.core.gcs.server",
    "ray_tpu.core.worker_main",
    "ray_tpu.dashboard",
    "ray_tpu.util.client.server",
}

# Daemons started by THIS pytest session inherit this marker; the reaper
# only touches processes carrying it, so a developer's live dev cluster on
# the same box is never killed by a test run.
_SESSION_MARKER = f"RAY_TPU_TEST_SESSION={os.getpid()}"
os.environ["RAY_TPU_TEST_SESSION"] = str(os.getpid())


def _ray_tpu_processes(any_session: bool = False):
    found = []
    for pid_dir in os.listdir("/proc"):
        if not pid_dir.isdigit():
            continue
        pid = int(pid_dir)
        if pid == os.getpid():
            continue
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                argv = [a.decode("utf-8", "replace")
                        for a in f.read().split(b"\0") if a]
        except OSError:
            continue
        hit = None
        for i, arg in enumerate(argv[:-1]):
            if arg == "-m" and argv[i + 1] in _RAY_SPAWNED_MODULES:
                hit = " ".join(argv[i:i + 4])
                break
        if hit is None:
            continue
        if not any_session:
            try:
                with open(f"/proc/{pid}/environ", "rb") as f:
                    env = f.read().decode("utf-8", "replace")
            except OSError:
                continue
            if _SESSION_MARKER not in env.split("\0"):
                continue
        found.append((pid, hit))
    return found


@pytest.fixture
def session_processes():
    """`session_processes()`: the runtime processes this session started
    that are alive at this moment, `[(pid, command line)]`. For tests of
    what a shutdown leaves behind, with no grace added."""
    return _ray_tpu_processes


@pytest.fixture(autouse=True, scope="module")
def _no_leaked_clusters(request):
    """Fail any module that leaks runtime processes (raylets, GCS, workers).

    Mirrors the hygiene the reference enforces via per-test cluster fixtures
    (python/ray/tests/conftest.py:410): every module must tear its cluster
    all the way down. Leaked processes are killed so they can't poison the
    rest of the suite, then the module is failed loudly.
    """
    yield
    # Give just-shut-down daemons a moment to exit before declaring a leak.
    leaked = _ray_tpu_processes()
    deadline = time.monotonic() + 5.0
    while leaked and time.monotonic() < deadline:
        time.sleep(0.25)
        leaked = _ray_tpu_processes()
    if leaked:
        for pid, _ in leaked:
            try:
                os.kill(pid, signal.SIGKILL)
            except OSError:
                pass
        pytest.fail(
            f"{request.module.__name__} leaked ray_tpu processes "
            f"(killed): {leaked}", pytrace=False)


@pytest.fixture(autouse=True)
def _perf_state_isolation(request):
    """Pristine process-global state around every perf-guard test.

    The perf guards run as a serialized tail stage (see
    `pytest_collection_modifyitems`) but share one pytest process with
    every module before them — and with each other. A `_system_config`
    override leaked into the process-global Config by an earlier
    cluster test (or an earlier guard's own boot), or attribution
    counters left hot by a prior guard, skew the next guard's floor
    measurement: the round-13 ring-floor flake was exactly this, a
    leftover inline/ring override changing which dispatch tier the
    "ring" burst actually measured. Bracket each perf-marked test
    with a shutdown + config reset (an empty `_values` dict IS the
    pristine state: reads fall through to declared defaults and env)
    + profiler reset, so each guard boots the cluster it thinks it's
    booting.
    """
    if request.node.get_closest_marker("perf") is None:
        yield
        return
    import ray_tpu
    from ray_tpu.core import attribution
    from ray_tpu.core.config import ray_config

    ray_tpu.shutdown()
    ray_config()._values.clear()
    attribution.reset()
    yield
    ray_tpu.shutdown()
    ray_config()._values.clear()
    attribution.reset()


@pytest.fixture(autouse=True, scope="session")
def _jax_on_cpu():
    """Pin the default device to CPU for the whole test session: the real
    TPU (when attached) computes matmuls in bf16 by default, which breaks
    exact-comparison tests. TPU-specific tests opt back in explicitly."""
    import jax

    jax.config.update("jax_default_device", jax.devices("cpu")[0])
    yield


@pytest.fixture
def recorder():
    """The flight recorder on, empty and large enough for a test's spans;
    left as it was found."""
    from ray_tpu.core import flight

    prev = flight.enabled
    flight.enable()
    flight.configure(1 << 16)
    flight.reset()
    yield flight
    if not prev:
        flight.disable()


@pytest.fixture
def ray_start_local():
    """Local-mode runtime (reference fixture analog: ray_start_regular)."""
    import ray_tpu

    ray_tpu.init(local_mode=True, ignore_reinit_error=True)
    yield ray_tpu
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_regular():
    """Single-node cluster runtime (head + raylet + workers as processes)."""
    import ray_tpu

    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    yield ray_tpu
    ray_tpu.shutdown()


@pytest.fixture
def cpu_mesh8():
    """An 8-device CPU mesh for sharding tests."""
    import jax

    devices = jax.devices("cpu")
    assert len(devices) >= 8, (
        "conftest must run before jax import; got %d devices" % len(devices))
    from jax.sharding import Mesh
    import numpy as np

    return Mesh(np.array(devices[:8]).reshape(2, 2, 2), ("dp", "sp", "tp"))


def pytest_collection_modifyitems(config, items):
    """Stage the suite: fast unit tier first, perf guards last.

    Unit-marked tests (in-process loopback fakes, no cluster) run FIRST
    — they fail in seconds when a core protocol breaks, before half an
    hour of integration tests boots a single raylet.

    Perf-guard tests run as a dedicated serialized TAIL stage. The
    round-5 verdict measured 143 actor-calls/s when the guard ran
    mid-suite next to cluster integration tests — a number that says
    nothing about the runtime and everything about box contention. The
    reference runs `ray_perf.py` as its own serialized release stage
    (release_tests.yaml); the equivalent here is collection ordering:
    every `perf`-marked test is moved to the very end of the run, after
    all other modules have torn their clusters down. For calibration
    numbers, run the stage alone: `pytest -m perf`.
    """
    unit_items, perf_items, rest = [], [], []
    for it in items:
        if it.get_closest_marker("unit"):      # unit wins a double mark
            unit_items.append(it)
        elif it.get_closest_marker("perf"):
            perf_items.append(it)
        else:
            rest.append(it)
    # HA consensus scenarios (`ha` mark) are the heaviest unit tests
    # (multi-replica elections under fault schedules): run them as the
    # TAIL of the unit lane so a broken core protocol still fails in the
    # first seconds of the run. The 1000-node election storm additionally
    # carries `slow` and only runs in the nightly `-m slow` tier.
    unit_items.sort(key=lambda it: bool(it.get_closest_marker("ha")))
    if unit_items or perf_items:
        items[:] = unit_items + rest + perf_items
