"""Long-context training step on a 2-D (data × sequence) mesh.

The data-parallel Mercury step (``train/step.py``) shards *workers*; this
step additionally shards the *sequence axis of each example* over a second
mesh axis, with every self-attention running as blockwise ring attention
(:mod:`mercury_tpu.parallel.sequence`). Context length then scales with the
``seq`` axis size — no device ever holds a full sequence or an ``[L, L]``
score matrix. The reference has no long-context machinery (SURVEY.md §5);
this is the beyond-parity extension that makes long sequences first-class.

Gradient-reduction subtlety (pinned by ``tests/test_sequence_parallel.py``):
under ``shard_map`` with replicated (``P()``) params, JAX's autodiff
automatically ``psum``s the parameter cotangents over **all** mesh axes.
Summing per-sequence-shard partials over ``seq`` is exactly the chain rule,
but over ``data`` it turns the desired mean-over-workers into a sum — so the
local loss is ``pmean``-ed over the data axis *inside* the differentiated
function, which pre-divides the cotangent and makes the automatic psum land
on the true global gradient. No hand-written gradient collective is needed.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from mercury_tpu.compat import axis_size, shard_map

from mercury_tpu.config import TrainConfig
from mercury_tpu.data.pipeline import (
    ShardStream,
    init_shard_streams,
    next_pool,
)
from mercury_tpu.sampling.importance import (
    EMAState,
    init_ema,
    per_sample_loss,
    reweighted_loss,
    select_from_pool,
)
from mercury_tpu.utils.tree import sum_sowed_losses


def make_dp_sp_train_step(
    model,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    data_axis: str = "data",
    seq_axis: str = "seq",
    moe_aux_weight: float = TrainConfig.moe_aux_weight,
) -> Callable[..., Tuple[dict, tuple, jax.Array]]:
    """Build a jitted train step over a 2-D ``(data, seq)`` mesh.

    ``model`` must be sequence-parallel-aware (``sp_axis=seq_axis`` — e.g.
    :class:`~mercury_tpu.models.TransformerClassifier`), so its attention
    rides the ring and its pooling completes over ``seq_axis`` internally.

    Returns ``step(params, opt_state, x, y) → (params, opt_state, loss)``
    with ``x: [B, T, F]`` sharded ``P(data, seq)``, ``y: [B]`` sharded
    ``P(data)``, params/opt state replicated.

    With ``model.sp_impl == "zigzag"`` the step permutes the token axis
    into :func:`~mercury_tpu.parallel.sequence.zigzag_order` inside the
    jitted program before sharding — the caller keeps feeding plain
    sequence-ordered batches, and the balanced causal ring does half the
    matmul FLOPs per hop. (Classification loss reads the pooled head, so
    no inverse permutation is needed on the way out.)
    """
    zigzag = getattr(model, "sp_impl", "ring") == "zigzag"

    def local_step(params, opt_state, x, y):
        def loss_fn(p):
            logits, state = model.apply(
                {"params": p}, x, train=True, mutable=["losses"]
            )
            # Any sowed MoE load-balancing losses join the objective. Each
            # seq shard sows a router aux from its local tokens — pmean it
            # over the seq axis so the loss stays replicated (and the
            # auto-psum of cotangents doesn't rescale the aux term).
            aux = lax.pmean(sum_sowed_losses(state), seq_axis)
            loss = jnp.mean(per_sample_loss(logits, y)) + moe_aux_weight * aux
            # pmean over data INSIDE the grad: see module docstring.
            return lax.pmean(loss, data_axis)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    sharded = shard_map(
        local_step,
        mesh=mesh,
        in_specs=(P(), P(), P(data_axis, seq_axis), P(data_axis)),
        out_specs=(P(), P(), P()),
    )
    if not zigzag:
        return jax.jit(sharded, donate_argnums=(0, 1))

    from mercury_tpu.parallel.sequence import zigzag_order

    w_seq = mesh.shape[seq_axis]

    def step(params, opt_state, x, y):
        perm = jnp.asarray(zigzag_order(x.shape[1], w_seq))
        return sharded(params, opt_state, x[:, perm], y)

    return jax.jit(step, donate_argnums=(0, 1))


class SpMercuryState(NamedTuple):
    """State for the dp×sp Mercury step: model/opt replicated, per-data-
    worker sampler state (the seq axis sees each data row replicated, so
    every seq rank of a worker draws identically)."""

    params: dict
    opt_state: tuple
    ema: EMAState          # [Wd]-stacked
    stream: ShardStream    # [Wd]-stacked
    rng: jax.Array         # [Wd] keys


def init_sp_mercury_state(
    rng: jax.Array, model, tx, sample_batch: jax.Array,
    n_data_workers: int, shard_len: int,
) -> SpMercuryState:
    from mercury_tpu.train.state import init_worker_sampler_state

    init_key, stream_key, worker_key = jax.random.split(rng, 3)
    # Init OUTSIDE the mesh: an sp_axis model would call lax.axis_size on
    # an unbound axis — the axis-free clone has identical param shapes.
    init_model = (model.clone(sp_axis=None)
                  if getattr(model, "sp_axis", None) is not None else model)
    params = init_model.init(init_key, sample_batch, train=False)["params"]
    ema, stream, rng_keys = init_worker_sampler_state(
        stream_key, worker_key, n_data_workers, shard_len
    )
    return SpMercuryState(
        params=params,
        opt_state=tx.init(params),
        ema=ema,
        stream=stream,
        rng=rng_keys,
    )


def make_dp_sp_mercury_step(
    model,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    batch_size: int,
    presample_batches: int = 10,
    is_alpha: float = 0.5,
    ema_alpha: float = 0.9,
    moe_aux_weight: float = TrainConfig.moe_aux_weight,
    data_axis: str = "data",
    seq_axis: str = "seq",
    telemetry: bool = False,
    io_constraints: bool = True,
) -> Callable[..., Tuple["SpMercuryState", dict]]:
    """The FULL Mercury IS algorithm on a 2-D ``data × seq`` mesh —
    completing the composition matrix's IS×SP cell (IS×TP and IS×PP
    exist in ``train/step.py`` / ``train/pp_step.py``).

    Per step, per data worker: stream a candidate pool from its shard,
    score it with one sequence-parallel inference forward (ring /
    Ulysses / zigzag attention per ``model.sp_impl``), EMA-smooth with
    the cross-worker psum (north-star statistic), draw the train batch,
    and run the reweighted backward through the same sequence-parallel
    program. Sampler state rides the data axis only: every seq rank of a
    worker holds identical (EMA, stream, RNG) rows and therefore draws
    identical batches — the selection is computed redundantly instead of
    communicated, which costs nothing (it is a few hundred scalars) and
    keeps the step free of host-side coordination, the same trick the
    fused dp step uses for its per-worker divergence.

    Memory scaling note: the SP win here is in ACTIVATIONS — attention
    runs blockwise over seq windows, so no rank materializes an [L, L]
    score matrix or full-sequence activations. The raw INPUT arrays are
    replicated (each device gathers its pool rows then slices its seq
    window), matching the dp step's ``data_placement="replicated"``
    contract — input-side scaling would come from sharding x_train over
    seq, a data-placement change orthogonal to this step.

    With ``model.moe_experts`` set, the router's sowed load-balancing
    aux (collected via ``mutable=["losses"]``) joins the training
    objective scaled by ``moe_aux_weight``; the scoring forward discards
    it (selection is by per-sample loss, as in the dp step).

    Returns ``step(state, x_train, y_train) → (state, metrics)`` with
    ``x_train: [N, T, F]`` / ``y_train: [N]`` replicated (each device
    slices its own seq window; zigzag models get the token permutation
    applied inside the jitted program, like
    :func:`make_dp_sp_train_step`). ``T`` must divide by the seq axis
    size.

    ``telemetry=True`` adds the fused dp step's sampler-health scalars
    (``sampler/ess``, ``sampler/clip_frac``, ``sampler/ema_drift``,
    ``train/grad_norm`` — see ``obs/diagnostics.py``) to the metrics
    dict; gated at trace time, so the default traces the original
    program.

    SHARDING CONTRACT (graftlint Layer 3): ``x_train``/``y_train`` are
    pinned replicated (``P()``) with ``with_sharding_constraint`` at the
    step boundary — the replicated-input contract above made explicit,
    so a sharded caller array reshards once, visibly, instead of GSPMD
    re-laying-out the interior. ``io_constraints=False`` drops the pins
    (and the plan's ``sharding_constraints`` budget with them).
    """
    pool_size = presample_batches * batch_size
    w_seq = mesh.shape[seq_axis]
    zigzag = getattr(model, "sp_impl", "ring") == "zigzag"
    moe = getattr(model, "moe_experts", None) is not None

    def local_step(state: SpMercuryState, x_train, y_train):
        si = lax.axis_index(seq_axis)
        t = x_train.shape[1]
        if t % w_seq != 0:
            # Silent truncation here would quietly train on different
            # math than the unsharded run.
            raise ValueError(
                f"sequence length {t} must divide by the {seq_axis!r} "
                f"axis size {w_seq}"
            )
        t_loc = t // w_seq
        rng = state.rng[0]
        k_stream, k_sel, k_next = jax.random.split(rng, 3)
        stream = ShardStream(perm=state.stream.perm[0],
                             cursor=state.stream.cursor[0])
        ema = EMAState(value=state.ema.value[0], count=state.ema.count[0])

        stream, slots = next_pool(stream, k_stream, pool_size)
        # This device's sequence window of each pooled sample.
        pool_x = lax.dynamic_slice_in_dim(
            x_train[slots], si * t_loc, t_loc, axis=1
        )
        pool_y = y_train[slots]

        def fwd(p, xb):
            logits, mut = model.apply(
                {"params": p}, xb, train=True, mutable=["losses"]
            )
            # Router aux (MoE): per-seq-shard token statistic, pmeaned
            # over seq so the loss stays replicated (0.0 for dense).
            aux = lax.pmean(sum_sowed_losses(mut), seq_axis)
            return logits, aux

        # mercury_scoring / mercury_grad_sync scopes anchor the jaxpr
        # auditor's per-region collective budgets (lint/audit.py).
        with jax.named_scope("mercury_scoring"):
            pool_logits, _ = fwd(state.params, pool_x)  # scoring: aux unused
            pool_losses = per_sample_loss(pool_logits, pool_y)
        sel = select_from_pool(
            k_sel, pool_losses, ema, batch_size,
            is_alpha=is_alpha, ema_alpha=ema_alpha, axis_name=data_axis,
        )
        batch_x = pool_x[sel.selected]
        batch_y = pool_y[sel.selected]

        def loss_fn(p):
            logits, aux = fwd(p, batch_x)
            losses = per_sample_loss(logits, batch_y)
            total = reweighted_loss(losses, sel.scaled_probs)
            if moe:
                total = total + moe_aux_weight * aux
            return total

        loss, grads = jax.value_and_grad(loss_fn)(state.params)
        # Explicit gradient collectives (this shard_map runs with vma
        # checking off — the PRNG-driven sampler state defeats the
        # replication inference, so nothing is automatic here). With vma
        # off, the in-model sequence pmean transposes as a plain psum,
        # inflating EVERY rank-local cotangent by W_seq (pre-pmean params
        # via the doubled pooled cotangent, post-pmean head params via
        # their redundant full partials) — so one uniform normalization
        # lands everything: psum over both axes divided by W_data·W_seq
        # (the data division is the grad MEAN over workers, ≡ the fused
        # dp step's allreduce_mean_tree). Pinned against the unsharded
        # step by TestDpSpMercuryStep.
        with jax.named_scope("mercury_grad_sync"):
            grads = jax.tree.map(
                lambda g: lax.psum(g, (data_axis, seq_axis))
                / (axis_size(data_axis) * axis_size(seq_axis)),
                grads,
            )
        loss = lax.pmean(loss, data_axis)
        with jax.named_scope("mercury_optimizer"):
            updates, opt_state = tx.update(
                grads, state.opt_state, state.params)
            params = optax.apply_updates(state.params, updates)
        new_state = SpMercuryState(
            params=params,
            opt_state=opt_state,
            ema=EMAState(value=sel.ema.value[None],
                         count=sel.ema.count[None]),
            stream=ShardStream(perm=stream.perm[None],
                               cursor=stream.cursor[None]),
            rng=k_next[None],
        )
        metrics = {
            "train/loss": loss,
            # Already the psum-reduced global mean (select_from_pool ran
            # with axis_name=data_axis) — no extra collective needed.
            "train/pool_loss": sel.avg_pool_loss,
        }
        if telemetry:
            from mercury_tpu.obs.diagnostics import (
                clip_fraction,
                ema_drift,
                ess_fraction,
                global_grad_norm,
            )

            metrics["sampler/ess"] = lax.pmean(
                ess_fraction(sel.scaled_probs), data_axis
            )
            metrics["sampler/clip_frac"] = lax.pmean(
                clip_fraction(pool_losses, sel.ema.value, is_alpha),
                data_axis,
            )
            metrics["sampler/ema_drift"] = ema_drift(
                sel.avg_pool_loss, ema.value
            )
            # grads are already the global mean (psum/W above) —
            # replicated, so the norm needs no further collective.
            metrics["train/grad_norm"] = global_grad_norm(grads)
        return new_state, metrics

    state_specs = SpMercuryState(
        params=P(), opt_state=P(),
        ema=EMAState(value=P(data_axis), count=P(data_axis)),
        stream=ShardStream(perm=P(data_axis), cursor=P(data_axis)),
        rng=P(data_axis),
    )
    sharded = shard_map(
        local_step,
        mesh=mesh,
        in_specs=(state_specs, P(), P()),
        out_specs=(state_specs, P()),
        check_vma=False,
    )
    if io_constraints:
        from jax.sharding import NamedSharding

        # SHARDING CONTRACT (see docstring): pin the replicated-input
        # contract at the boundary, outside the shard_map.
        rep_ns = NamedSharding(mesh, P())
        constrained_inner = sharded

        def sharded(state, x_train, y_train):
            x_train = jax.lax.with_sharding_constraint(x_train, rep_ns)
            y_train = jax.lax.with_sharding_constraint(y_train, rep_ns)
            return constrained_inner(state, x_train, y_train)

    if not zigzag:
        return jax.jit(sharded, donate_argnums=(0,))

    from mercury_tpu.parallel.sequence import zigzag_order

    def step(state, x_train, y_train):
        perm = jnp.asarray(zigzag_order(x_train.shape[1], w_seq))
        return sharded(state, x_train[:, perm], y_train)

    return jax.jit(step, donate_argnums=(0,))
