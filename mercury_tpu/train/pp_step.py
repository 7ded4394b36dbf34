"""The Mercury importance-sampled step on a PIPELINED model.

Completes the flagship-algorithm × parallelism matrix (dp: ``train/step.py``;
dp×sp: ``train/sp_step.py``; dp×tp: ``train/step.py`` partial-auto; pp:
here): the candidate pool is scored through the GPipe schedule
(:func:`mercury_tpu.parallel.pipeline.make_pp_apply`), the batch is drawn
by the same EMA-smoothed ``loss + α·EMA`` rule (``pytorch_collab.py:
89-117``), and the reweighted backward runs through the schedule's exact
AD reverse — the transformer stack's params live staged across the pipe
axis the whole time.

One data worker (the pipe mesh IS the machine here); sampler state mirrors
``MercuryState``'s per-worker slice. The transformer family has no
BatchNorm, so scoring and training forwards are the same pure function —
the reference's BN-churn quirk has nothing to mutate.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh

from mercury_tpu.config import TrainConfig
from mercury_tpu.data.pipeline import (
    ShardStream,
    init_shard_streams,
    next_pool,
)
from mercury_tpu.parallel.pipeline import make_pp_apply
from mercury_tpu.sampling.importance import (
    EMAState,
    init_ema,
    per_sample_loss,
    reweighted_loss,
    select_from_pool,
)


class PPMercuryState(NamedTuple):
    step: jax.Array
    stacked: dict          # block params, layer axis sharded P(pipe)
    rest: dict             # embed/pos/norm/head params, replicated
    opt_state: tuple       # optax state over (stacked, rest)
    ema: EMAState
    stream: ShardStream    # single worker's presample stream (no [W] axis)
    rng: jax.Array


def create_pp_state(
    rng: jax.Array, model, tx: optax.GradientTransformation,
    sample_batch: jax.Array, shard_len: int, mesh: Mesh, axis: str = "pipe",
) -> PPMercuryState:
    """Init params, stage the block stack over the pipe axis, and derive
    the optimizer state from the STAGED params (its moments inherit the
    placement)."""
    from mercury_tpu.parallel.pipeline import (
        shard_stacked_blocks,
        stack_block_params,
    )

    init_key, stream_key, step_key = jax.random.split(rng, 3)
    params = model.init(init_key, sample_batch, train=False)["params"]
    stacked, rest = stack_block_params(params, model.num_layers)
    stacked = shard_stacked_blocks(stacked, mesh, axis)
    streams = init_shard_streams(stream_key, 1, shard_len)
    return PPMercuryState(
        step=jnp.zeros((), jnp.int32),
        stacked=stacked,
        rest=rest,
        opt_state=tx.init((stacked, rest)),
        ema=init_ema(),
        stream=ShardStream(perm=streams.perm[0], cursor=streams.cursor[0]),
        rng=step_key,
    )


def make_pp_mercury_step(
    model,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    batch_size: int,
    presample_batches: int = 10,
    num_microbatches: int = 2,
    axis: str = "pipe",
    is_alpha: float = 0.5,
    ema_alpha: float = 0.9,
    moe_aux_weight: float = TrainConfig.moe_aux_weight,
    telemetry: bool = False,
    io_constraints: bool = True,
) -> Callable[..., Tuple[PPMercuryState, dict]]:
    """Build ``step(state, x_train, y_train) → (state, metrics)``.

    ``x_train`` is the worker's shard data (float, model-ready — sequences
    or images for a ``patch_size`` model), ``y_train`` its labels; the
    pool (``presample_batches × batch_size`` candidates) and the drawn
    train batch both flow through the pipelined forward, so both must be
    divisible by ``num_microbatches``.

    MoE models compose: the Switch router's load-balancing aux loss flows
    out of the staged scan (``make_pp_apply(with_aux=True)``) and enters
    the training objective as ``moe_aux_weight × aux`` — the same term the
    fused data-parallel step applies (``train/step.py``). The default IS
    ``TrainConfig.moe_aux_weight`` (one source of truth); a caller using a
    config with a non-default value must pass ``config.moe_aux_weight``
    explicitly — this factory takes keywords, not a ``TrainConfig``. The
    scoring pass discards the aux (scores are per-sample CE, matching
    ``pytorch_collab.py:102``).

    ``telemetry=True`` adds the fused dp step's sampler-health scalars
    (``sampler/ess``, ``sampler/clip_frac``, ``sampler/ema_drift``,
    ``train/grad_norm`` — see ``obs/diagnostics.py``); gated at trace
    time, so the default traces the original program.

    SHARDING CONTRACT (graftlint Layer 3): ``x_train``/``y_train`` are
    pinned replicated over the pipe mesh (``P()``) with
    ``with_sharding_constraint`` at the step boundary — every stage
    reads the worker's full shard (stage 0 injects microbatches, the
    last stage emits), so a pipe-sharded input would silently all-gather
    per tick. ``io_constraints=False`` drops the pins (and the plan's
    ``sharding_constraints`` budget with them).
    """
    pool_size = presample_batches * batch_size
    if pool_size % num_microbatches or batch_size % num_microbatches:
        raise ValueError(
            f"pool ({pool_size}) and batch ({batch_size}) must divide by "
            f"num_microbatches ({num_microbatches})"
        )
    moe = getattr(model, "moe_experts", None) is not None
    pp_fwd = make_pp_apply(model, mesh, num_microbatches, axis,
                           with_aux=moe)
    from jax.sharding import NamedSharding, PartitionSpec as P

    rep_ns = NamedSharding(mesh, P())

    def step(state: PPMercuryState, x_train, y_train):
        if io_constraints:
            # SHARDING CONTRACT (see docstring): the shard data stays
            # replicated over the pipe axis.
            x_train = jax.lax.with_sharding_constraint(x_train, rep_ns)
            y_train = jax.lax.with_sharding_constraint(y_train, rep_ns)
        k_stream, k_sel, k_next = jax.random.split(state.rng, 3)
        stream, slots = next_pool(state.stream, k_stream, pool_size)
        pool_x = x_train[slots]
        pool_y = y_train[slots]

        # Score the pool through the pipeline (one schedule pass). The
        # mercury_scoring scope anchors the jaxpr auditor's per-region
        # checks (lint/audit.py).
        with jax.named_scope("mercury_scoring"):
            pool_out = pp_fwd(state.stacked, state.rest, pool_x)
            pool_logits = pool_out[0] if moe else pool_out
            pool_losses = per_sample_loss(pool_logits, pool_y)
        sel = select_from_pool(
            k_sel, pool_losses, state.ema, batch_size,
            is_alpha=is_alpha, ema_alpha=ema_alpha,
        )

        def loss_fn(stacked, rest):
            out = pp_fwd(stacked, rest, pool_x[sel.selected])
            logits, aux = out if moe else (out, jnp.zeros((), jnp.float32))
            total = reweighted_loss(
                per_sample_loss(logits, pool_y[sel.selected]),
                sel.scaled_probs,
            )
            if moe:
                total = total + moe_aux_weight * aux
            return total, (logits, aux)

        (loss, (logits, moe_aux)), grads = jax.value_and_grad(
            loss_fn, argnums=(0, 1), has_aux=True
        )(state.stacked, state.rest)
        with jax.named_scope("mercury_optimizer"):
            updates, opt_state = tx.update(
                grads, state.opt_state, (state.stacked, state.rest)
            )
            stacked, rest = optax.apply_updates(
                (state.stacked, state.rest), updates
            )
        acc = jnp.mean(
            (jnp.argmax(logits, -1) == pool_y[sel.selected]).astype(
                jnp.float32
            )
        )
        new_state = PPMercuryState(
            step=state.step + 1, stacked=stacked, rest=rest,
            opt_state=opt_state, ema=sel.ema, stream=stream, rng=k_next,
        )
        metrics = {
            "train/loss": loss,
            "train/acc": acc,
            "train/pool_loss": sel.avg_pool_loss,
            "train/moe_aux": moe_aux,
        }
        if telemetry:
            from mercury_tpu.obs.diagnostics import (
                clip_fraction,
                ema_drift,
                ess_fraction,
                global_grad_norm,
            )

            metrics["sampler/ess"] = ess_fraction(sel.scaled_probs)
            metrics["sampler/clip_frac"] = clip_fraction(
                pool_losses, sel.ema.value, is_alpha
            )
            metrics["sampler/ema_drift"] = ema_drift(
                sel.avg_pool_loss, state.ema.value
            )
            metrics["train/grad_norm"] = global_grad_norm(grads)
        return new_state, metrics

    return jax.jit(step, donate_argnums=(0,))
