"""Process-wide JAX set-up shared by every entry point (CLI, driver hooks,
benchmark/example bootstraps, ``chip_smoke.py``, ``bench.py``, the test
harness), so the two recipes cannot drift:

- which platform a virtual-device request runs on, and
- where the persistent compilation cache lives.

Both must run before the first backend touch (``jax.devices()`` or a
computation); afterwards a platform update is a silent no-op.
"""

from __future__ import annotations

import os

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def select_cpu_if_requested() -> bool:
    """Pin the CPU platform iff ``XLA_FLAGS`` carries
    ``--xla_force_host_platform_device_count`` — the test / dev
    virtual-mesh recipe. Returns whether the pin was applied.

    The flag only multiplies *host* devices, so on a machine that holds an
    accelerator (where the image may export ``JAX_PLATFORMS=tpu,cpu``) it
    would otherwise be ignored and the run would land on the chip with one
    device. The flag is this project's explicit "run on the host CPU"
    request and wins over ``JAX_PLATFORMS``; a run meant for the chip does
    not set it. The pin goes to the environment (spawned workers inherit
    it) and to ``jax.config`` (jax read the environment at import)."""
    if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""
    ):
        return False
    import jax

    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")
    return True


def configure_compile_cache() -> str:
    """Place jax's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and
    no directory is set in code — whoever runs the program decides where
    the cache lives. Otherwise it is ``<checkout>/.jax_cache`` (git-ignored),
    resolved from this file: a fixed path, never a temp name, pid or
    time, because a cache that moves between runs never hits. The cache
    is only ever a cache — nothing reads it but jax.

    Either way the sub-second programs are cached too (jax's default
    skips anything that compiled in under 1 s): a ``Trainer`` start-up is
    hundreds of them — 266 programs, 30 s of a warm ``chip_smoke.py`` run
    on the v5e (PR 21) — and the test suite's cost is mostly such
    compiles."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    path = os.path.join(_REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
