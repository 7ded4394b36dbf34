"""Test harness: force an 8-device virtual CPU platform so psum/sharding
logic is exercised without a TPU pod (SURVEY.md §4's multi-device test
strategy). The XLA host-device flag must be set before the backend
initializes; ``select_cpu_if_requested`` then pins the CPU platform so the
suite also runs on a machine that holds an accelerator."""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

from mercury_tpu.platform import (  # noqa: E402
    configure_compile_cache,
    select_cpu_if_requested,
)

select_cpu_if_requested()

# Persistent compilation cache: the suite's cost is dominated by XLA CPU
# compiles of the fused train-step programs (ResNet-50, MobileNetV2, scanned
# chunks — 10+ minutes cold). Cached, a rerun skips recompilation entirely.
configure_compile_cache()

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from mercury_tpu.lint.racecheck import ThreadLeakGuard  # noqa: E402


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(autouse=True)
def _no_thread_leaks(request):
    """Tier-1-wide thread-leak guard (graftlint Layer C's runtime side):
    any test that starts a non-daemon thread must join it before
    returning — a leaked writer/prefetch/checkpoint thread wedges the
    whole pytest process at exit and poisons every later test's thread
    census. Opt out with ``@pytest.mark.thread_leak_ok`` (the slow
    distributed matrix parks helpers across tests by design)."""
    if request.node.get_closest_marker("thread_leak_ok") is not None:
        yield
        return
    guard = ThreadLeakGuard(grace_s=5.0)
    yield
    strays = guard.strays()
    if strays:
        names = ", ".join(sorted(t.name for t in strays))
        pytest.fail(
            f"test leaked non-daemon thread(s) still alive after the "
            f"5s grace join: {names} — close()/join() them, or mark "
            f"the test thread_leak_ok", pytrace=False)
