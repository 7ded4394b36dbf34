"""Profiler hook.

The reference times five named segments by hand with wall-clock pairs
(``pytorch_collab.py:129-178``): ``step_time`` (whole step), ``ff_time``
(train forward), ``bp_time`` (backward), ``is_time`` (importance scoring),
``sync_time`` (gradient allreduce), printed every 100 steps. Known
reference defect (not replicated): its ``is_time`` brackets a commented-out
line so the logged value is ~0 while the real scoring cost lands elsewhere
(``:139-142``, SURVEY.md §5).

A fused XLA step has no host-visible internal boundaries; its segments are
``jax.named_scope`` regions of the one compiled program (``train/step.py``:
``mercury_scoring`` and the scopes inside it, ``mercury_draw``,
``mercury_train`` with jax's own ``transpose(`` mark on the backward pass,
``mercury_grad_sync``, ``mercury_optimizer``), and one :func:`trace` capture
of the real step gives all five exactly — ``perfbench`` reads them as
``device_ms_per_step``, ``train_forward_share``, ``train_backward_share``,
``scoring_share`` and the ``mercury_grad_sync`` scope's share. With
``TrainConfig.trace`` on, the program's host spans lie in the same capture
(``obs/trace.py``).
"""

from __future__ import annotations

import contextlib

import jax


@contextlib.contextmanager
def trace(log_dir: str):
    """``jax.profiler`` trace context — kernel-level TPU traces viewable in
    TensorBoard/Perfetto; the TPU-native replacement for host
    ``time.time()`` bracketing."""
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
