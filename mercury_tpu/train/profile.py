"""Per-segment timing + profiler hooks.

Capability parity with the reference's manual wall-clock instrumentation
(``pytorch_collab.py:129-178``): the five named segments — ``step_time``
(whole step), ``ff_time`` (train forward), ``bp_time`` (backward),
``is_time`` (importance scoring), ``sync_time`` (gradient allreduce) —
printed every 100 steps. Known reference defect (not replicated): its
``is_time`` brackets a commented-out line so the logged value is ~0 while
the real scoring cost lands elsewhere (``:139-142``, SURVEY.md §5).

A fused XLA step has no host-visible internal boundaries, so segment
attribution here times **separately-jitted sub-programs** with
device fences — comparable numbers, honestly labeled as estimates. The
parts-vs-fused relationship is DATA, not an invariant: segment overlap
inside the fused program pushes the sum above the whole, while fused-only
work no segment isolates (augmentation, gathers, the draw) pushes it
below — the measured ratio per platform is recorded by
``benchmarks/profile_validation.py``.

For real kernel-level traces use :func:`trace` (``jax.profiler`` wrapper),
the TPU-native answer to the reference's ``time.time()`` pairs.
"""

from __future__ import annotations

import contextlib
import time
from typing import Callable, Dict

import jax
import jax.numpy as jnp
from jax import lax
from mercury_tpu.compat import shard_map
from jax.sharding import PartitionSpec as P

from mercury_tpu.sampling.importance import per_sample_loss, reweighted_loss


def _timeit(fn: Callable[[], jax.Array], iters: int) -> float:
    """Median-of-iters wall time of ``fn`` with device fences.

    The fence is a device→host fetch (``np.asarray``): the transfer cannot
    complete before the value exists, and it is the fence ``bench.py``
    times with, so segment and end-to-end numbers share one clock."""
    import numpy as np

    np.asarray(fn())  # compile / warm
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        np.asarray(fn())
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def timing_breakdown(trainer, iters: int = 10) -> Dict[str, float]:
    """Estimate the reference's five timing segments for ``trainer``'s
    config (seconds, median of ``iters``).

    Segments: ``is`` (scoring forward over the candidate pool), ``ff``
    (train forward on the selected batch), ``bp`` (forward+backward minus
    ``ff``), ``sync`` (gradient-pytree pmean over the mesh), ``step`` (the
    real fused step). Keys mirror ``pytorch_collab.py:170-178``.
    """
    cfg = trainer.config
    ds = trainer.dataset
    model = trainer.model
    mesh = trainer.mesh
    axis = cfg.mesh_axis
    params = trainer.state.params
    batch_stats = trainer.state.batch_stats

    pool = ds.gather_batch(jnp.arange(cfg.candidate_pool_size) % ds.n_train)
    batch = ds.gather_batch(jnp.arange(cfg.batch_size) % ds.n_train)

    def _fwd(images, labels):
        variables = {"params": params}
        if batch_stats:
            variables["batch_stats"] = batch_stats
            logits, _ = model.apply(variables, images, train=True,
                                    mutable=["batch_stats"])
        else:
            logits = model.apply(variables, images, train=True)
        return per_sample_loss(logits, labels)

    # BN may psum over the mesh axis — run segments under a trivial
    # shard_map so the axis is bound (replicated inputs, same math).
    # Each sub-program is wrapped ONCE: a fresh jit(shard_map(...)) per
    # timed call would retrace every iteration and the "segment time"
    # would measure tracing, not compute (the bug behind the round-4
    # ff>fused artifact rows).
    def _wrap(fn):
        return jax.jit(shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                                 check_vma=False))

    def score_fn(images, labels):
        return jnp.sum(_fwd(images, labels))

    def train_fwd_fn(images, labels):
        return jnp.sum(_fwd(images, labels))

    def fwd_bwd_fn(images, labels):
        def loss_fn(p):
            variables = {"params": p}
            if batch_stats:
                variables["batch_stats"] = batch_stats
                logits, _ = model.apply(variables, images, train=True,
                                        mutable=["batch_stats"])
            else:
                logits = model.apply(variables, images, train=True)
            losses = per_sample_loss(logits, labels)
            return reweighted_loss(losses, jnp.ones_like(losses))

        grads = jax.grad(loss_fn)(params)
        return jax.tree_util.tree_reduce(
            lambda a, b: a + jnp.sum(b), grads, jnp.zeros(())
        )

    def sync_fn():
        meaned = jax.tree_util.tree_map(lambda x: lax.pmean(x, axis), params)
        return jax.tree_util.tree_reduce(
            lambda a, b: a + jnp.sum(b), meaned, jnp.zeros(())
        )

    score_j = _wrap(score_fn)
    train_fwd_j = _wrap(train_fwd_fn)
    fwd_bwd_j = _wrap(fwd_bwd_fn)
    sync_j = _wrap(sync_fn)
    is_t = _timeit(lambda: score_j(pool.image, pool.label), iters)
    ff_t = _timeit(lambda: train_fwd_j(batch.image, batch.label), iters)
    fb_t = _timeit(lambda: fwd_bwd_j(batch.image, batch.label), iters)
    sync_t = _timeit(lambda: sync_j(), iters)

    def fused():
        state, metrics = trainer.train_step(
            trainer.state, trainer._step_x, trainer._step_y, ds.shard_indices
        )
        trainer.state = state
        return metrics["train/loss"]

    step_t = _timeit(fused, iters)

    return {
        "step_time": step_t,
        "ff_time": ff_t,
        "bp_time": max(fb_t - ff_t, 0.0),
        # Raw forward+backward median: bp_time is fb−ff clamped at 0, so
        # a contended host can zero it (two noisy medians); fb_time keeps
        # the degenerate case diagnosable in recorded artifacts.
        "fb_time": fb_t,
        "is_time": is_t,
        "sync_time": sync_t,
    }


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
