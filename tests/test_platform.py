"""``mercury_tpu.platform``: the two process-wide jax set-up recipes every
entry point shares — where the compile cache lives, and which platform a
virtual-device request runs on."""

import os

import jax
import pytest

from mercury_tpu import platform

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield before
    jax.config.update("jax_compilation_cache_dir", before)


class TestCompileCache:
    def test_variable_set_means_nothing_is_set_in_code(
            self, monkeypatch, restore_cache_dir):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/outside")
        assert platform.configure_compile_cache() == "/placed/outside"
        # jax reads the variable itself; the directory is not touched.
        assert jax.config.jax_compilation_cache_dir == restore_cache_dir
        # sub-second programs are cached wherever the cache lives
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0

    def test_unset_means_the_checkout(self, monkeypatch, restore_cache_dir):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        jax.config.update("jax_compilation_cache_dir", None)
        want = os.path.join(REPO, ".jax_cache")
        assert platform.configure_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want

    def test_one_copy_of_the_recipe(self):
        """Every entry point calls the helper; none sets the directory
        itself."""
        entry_points = ["bench.py", "chip_smoke.py", "mercury_tpu/cli.py",
                        "benchmarks/_bootstrap.py", "examples/_bootstrap.py",
                        "tests/conftest.py"]
        for rel in entry_points:
            with open(os.path.join(REPO, rel)) as f:
                src = f.read()
            assert "configure_compile_cache()" in src, rel
            assert "jax_compilation_cache_dir" not in src, rel


class TestSelectCpu:
    def test_no_flag_no_pin(self, monkeypatch):
        monkeypatch.setenv("XLA_FLAGS", "--xla_cpu_enable_fast_math=false")
        monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
        assert platform.select_cpu_if_requested() is False
        assert os.environ["JAX_PLATFORMS"] == "tpu,cpu"

    def test_virtual_device_flag_wins_over_the_image_default(
            self, monkeypatch):
        # The machine with the chip exports JAX_PLATFORMS=tpu,cpu; the
        # flag is the project's explicit "host CPU" request.
        monkeypatch.setenv(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
        monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
        assert platform.select_cpu_if_requested() is True
        assert os.environ["JAX_PLATFORMS"] == "cpu"
        assert jax.config.jax_platforms == "cpu"
