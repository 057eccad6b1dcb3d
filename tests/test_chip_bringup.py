"""What the chip bring-up rests on, as far as a CPU can check it.

The sandbox has libtpu but no chip, which is exactly the case that used to
go wrong quietly: a process that was granted chips and cannot open them
must fail, never compute on the CPU. The chip itself is checked by
`chip_smoke.py`; here its two phases run at toy widths with the expected
platform turned to `cpu`.
"""

import os
import subprocess
import sys
from concurrent.futures import ThreadPoolExecutor

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.unit
def test_compile_cache_is_placed_from_outside_or_next_to_the_package(
        monkeypatch):
    import jax

    from ray_tpu.core.jax_platform import use_compile_cache

    before = jax.config.jax_compilation_cache_dir
    least = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        # Set from outside: JAX reads it itself, no directory is set in
        # code. Either way every program is kept, the quick ones too
        # (the paged decode step's buckets compile in about a second).
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        assert use_compile_cache() == "/some/dir"
        assert jax.config.jax_compilation_cache_dir == before
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        placed = os.path.join(REPO, ".jax_cache")
        assert use_compile_cache() == placed
        assert jax.config.jax_compilation_cache_dir == placed
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          least)


@pytest.mark.unit
def test_unset_platform_follows_the_lease_not_the_host(monkeypatch):
    """`JaxConfig(platform=None)`: chips leased (the runtime sets
    `TPU_VISIBLE_CHIPS` for the lease) means `tpu`, none leased means
    `cpu` whatever device nodes or `JAX_PLATFORMS` the host shows. The toy
    train phase below takes the `cpu` branch for real."""
    from ray_tpu.train.backend import _leased_platform

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.delenv("TPU_VISIBLE_CHIPS", raising=False)
    assert _leased_platform() == "cpu"
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "0,1")
    assert _leased_platform() == "tpu"


@pytest.fixture
def one_fake_chip():
    import ray_tpu

    ray_tpu.init(num_cpus=8, resources={"TPU": 1.0},
                 ignore_reinit_error=True)
    yield ray_tpu
    ray_tpu.shutdown()


@pytest.mark.cluster
def test_chip_lease_opens_the_tpu_or_raises_and_hands_over_after_exit(
        one_fake_chip, session_processes):
    """A worker that was leased a chip is on the explicit `tpu` platform
    and, with no chip to open, raises: no CPU devices come back. And the
    raylet used to return a retiring worker's chips to the pool in the
    same call that sent it SIGTERM, so libtpu in the next holder could
    meet a chip the old process still owned. The session's end keeps
    the same rule towards whatever runs next on the machine: when
    `shutdown()` returns, nothing of the session is alive."""
    ray = one_fake_chip

    @ray.remote(resources={"TPU": 1.0})
    def open_chip():
        import jax

        pinned = jax.config.jax_platforms
        try:
            return os.getpid(), pinned, [d.platform for d in jax.devices()]
        except RuntimeError as e:
            return os.getpid(), pinned, str(e)

    @ray.remote(resources={"TPU": 1.0})
    def next_holder(previous_pid):
        try:
            os.kill(previous_pid, 0)
        except ProcessLookupError:
            return "gone"
        return "still alive"

    @ray.remote
    def no_chip():
        import jax

        return jax.config.jax_platforms, jax.devices()[0].platform

    unleased = no_chip.remote()
    first_pid, pinned, got = ray.get(open_chip.remote(), timeout=120)
    assert pinned == "tpu"
    assert isinstance(got, str) and "Unable to initialize backend" in got, got
    # Another function, so another lease: it waits for the only chip.
    assert ray.get(next_holder.remote(first_pid), timeout=60) == "gone"
    assert ray.get(unleased, timeout=60) == ("cpu", "cpu")
    assert session_processes() != []
    ray.shutdown()
    assert session_processes() == []  # at once: no moment of grace


@pytest.mark.cluster
def test_chip_smoke_phases_at_toy_widths_and_main_demands_a_tpu(
        one_fake_chip):
    import chip_smoke

    # No chip here: run as a script it says so, exits non-zero within a
    # minute and prints no result. (Started now, collected below.)
    script = subprocess.Popen([sys.executable, "chip_smoke.py"], cwd=REPO,
                              stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                              text=True)
    toy = dict(vocab_size=512, d_model=64, n_layers=2, n_heads=4, d_ff=128)
    # Side by side: no chip to take turns on here.
    with ThreadPoolExecutor(2) as pool:
        training = pool.submit(
            chip_smoke.train_phase, toy, expect_platform="cpu", chips=0,
            batch=8, seq=64, steps=2, timeout_s=300)
        serving = pool.submit(
            chip_smoke.serve_phase, toy, expect_platform="cpu", chips=0,
            prompt_lens=(12, 40), new_tokens=6, max_seq_len=128,
            timeout_s=300)
        trained, served = training.result(), serving.result()
    assert trained["device"]["platform"] == "cpu"
    assert trained["losses"][-1] < trained["losses"][0]
    assert served["paged_steps"] > 0 and served["jit_compiles"] > 0

    out, err = script.communicate(timeout=60)
    assert script.returncode != 0
    assert out == ""
    assert "no TPU chip on this host" in err
