"""Profiler-trace smoke test: ``trace`` must actually drive
``jax.profiler`` and leave a trace artifact on disk."""

import os

import jax
import jax.numpy as jnp

from mercury_tpu.train.profile import trace


def test_trace_writes_profile_artifacts(tmp_path):
    log_dir = str(tmp_path / "trace")
    with trace(log_dir):
        jnp.asarray(jax.jit(lambda x: x * 2)(jnp.ones((8, 8)))).block_until_ready()
    found = [os.path.join(root, f)
             for root, _, files in os.walk(log_dir) for f in files]
    assert found, "trace() produced no profile artifacts"
