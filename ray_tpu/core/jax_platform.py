"""Which JAX platform a process may open, and where it keeps compiled code.

A TPU chip belongs to one process at a time. Workers therefore start with
`JAX_PLATFORMS=cpu` (the raylet sets it when it spawns them), and only a
lease that grants chips moves a process onto the TPU
(`cluster_runtime._apply_visible_chips` -> `claim_chip_platform`). The
claim names the platform explicitly: with an explicit platform JAX raises
when the chip cannot be opened, where autodetection would drop the TPU
quietly and hand back CPU devices.

Reference equivalent: `python/ray/_private/accelerators/tpu.py:214` keeps
worker processes off accelerators they were not granted via visibility env
vars.
"""

from __future__ import annotations

import os

CHIP_PLATFORM = "tpu"
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def default_compile_cache_dir() -> str:
    """`<checkout>/.jax_cache`: next to the package, so every process of
    every run from this checkout shares one cache. The directory is part
    of the cache key, so it must not depend on the pid, the time or the
    session."""
    package = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(package), ".jax_cache")


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache somewhere stable and
    return the directory in use. When `JAX_COMPILATION_CACHE_DIR` is set,
    JAX already reads it and no directory is set in code.

    Every program is kept, however quickly it compiled: JAX's default
    leaves out what compiled in under a second, and the paged decode
    step's twenty shape buckets compile in 0.9-1.5 s each on the chip,
    so a replica's warm-up either found them or compiled them again
    (25 s) by the machine's mood."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    placed = os.environ.get(CACHE_ENV)
    if placed:
        return placed
    path = default_compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def claim_chip_platform() -> None:
    """Move this process onto the chips it was just leased. Backends built
    earlier (a chip-less task may have run JAX on the CPU here) are
    dropped, so the next JAX call opens the TPU or raises. The
    environment keeps `JAX_PLATFORMS=cpu`, so a subprocess of this worker
    does not fight it for the chip."""
    import jax
    import jax.extend.backend

    if jax.config.jax_platforms != CHIP_PLATFORM:  # else: same lease again
        jax.config.update("jax_platforms", CHIP_PLATFORM)
        jax.extend.backend.clear_backends()
    use_compile_cache()
