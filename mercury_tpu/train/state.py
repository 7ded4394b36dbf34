"""Training state: params, BN stats, optimizer state, and the Mercury
sampler state (EMA + per-worker presampling streams + RNG).

The reference scatters this state across a ``Trainer`` object's attributes
(``pytorch_collab.py:38-54`` — net/optimizer/loaders/``next_batch_iter``/
EMA meter). Here it is one pytree, so the whole training step is a pure
function ``state → state`` and the entire thing checkpoints/resumes
deterministically (including sampler RNG — SURVEY.md §5's checkpoint gap).
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import flax.struct
import jax
import jax.numpy as jnp
import optax

from mercury_tpu.data.pipeline import ShardStream
from mercury_tpu.sampling.groupwise import GroupwiseState, init_groupwise
from mercury_tpu.sampling.importance import EMAState, init_ema
from mercury_tpu.sampling.scoretable import init_score_table


class CachedPool(NamedTuple):
    """A scored candidate pool reused across steps (score-refresh cadence,
    ``config.score_refresh_every > 1``).

    Refreshed every K-th step: the freshly streamed pool's shard slots and
    the normalized importance distribution computed from its scores
    (``update_samples``'s score→normalize, ``pytorch_collab.py:108-112``).
    Intermediate steps redraw from ``probs`` (fresh multinomial draws ≡
    ``:114``) and re-gather/re-augment by slot — the scoring forward, the
    dominant per-step IS cost, runs once per K steps."""

    slots: jax.Array      # [P] int32 — pool positions into the worker shard
    probs: jax.Array      # [P] float32 — normalized sampling distribution
    pool_loss: jax.Array  # [] float32 — pool-loss metric from the refresh


class PendingBatch(NamedTuple):
    """The next step's pre-selected train batch (pipelined scoring).

    Carries the exact augmented/normalized images that were scored — the
    reference also trains on the very tensors ``update_samples`` scored
    (``pytorch_collab.py:116,132``), not a re-load by index."""

    images: jax.Array        # [B, H, W, C] float32 — augmented + normalized
                             # ([B, T] int32 rows of a token dataset)
    labels: jax.Array        # [B] int32 ([B, T] per-token labels)
    scaled_probs: jax.Array  # [B] float32 — p_i·N for the unbiased reweight


class PendingSelection(NamedTuple):
    """Ring of in-flight sample selections (``data_placement=
    "host_stream"``): the step at t consumes ``slots[0]`` (its rows arrive
    pre-gathered from the host via ``data/stream.py``) and pushes the
    selection it just drew for step t+depth onto the back. The RNG
    lookahead makes the draws key-for-key identical to the device-resident
    path: ``rng`` is the worker RNG advanced ``depth`` steps ahead, so the
    slot draw for step t+d uses exactly the key the replicated step would
    split at t+d. Carried as raw uint32 key data rather than a typed key
    array (the checkpointed schema; ``jax.random.wrap_key_data`` to use)."""

    slots: jax.Array         # [depth, S] int32 — shard-local slot ids per step
    scaled_probs: jax.Array  # [depth, B] float32 — p_i·L at draw time
                             # (scoretable; ones for uniform/pool)
    rng: jax.Array           # [2] uint32 — raw key data of rng_{t+depth}


@flax.struct.dataclass
class MercuryState:
    step: jax.Array                 # [] int32 — global step counter
    params: Any                     # model params (replicated over mesh)
    batch_stats: Any                # BN running stats (replicated)
    opt_state: Any                  # optax state (replicated; under ZeRO-1
                                    # [W, ceil(P/W)]-chunked, sharded P(data))
    ema: EMAState                   # [W]-stacked per-worker EMA of mean pool loss
    stream: ShardStream             # [W]-stacked per-worker presample streams
    rng: jax.Array                  # [W, key] per-worker PRNG keys
    groupwise: Any = None           # [W]-stacked GroupwiseState (sampler="groupwise")
    pending: Any = None             # [W]-stacked PendingBatch (pipelined_scoring)
    cached_pool: Any = None         # [W]-stacked CachedPool (score_refresh_every>1)
    scoretable: Any = None          # [W]-stacked ScoreTableState (sampler="scoretable")
    pending_sel: Any = None         # [W]-stacked PendingSelection (host_stream)
    sel_counts: Any = None          # [W, L] int32 selection-count ledger
                                    # (scoretable + telemetry): draws of
                                    # each shard slot consumed by training
                                    # so far (obs/sampler_health.py)


#: Declared elastic policy per ``MercuryState`` field — the state-plane
#: contract checked by graftlint Layer E (``lint/state.py``). A PURE
#: literal (the linter parses it with ``ast.literal_eval``); every
#: dataclass field above MUST have an entry here (GLE01) and every
#: policy must have a matching carry site in ``train/elastic.py`` /
#: ``train/trainer.py`` (GLE02). The vocabulary:
#:
#: - ``replicate``      — restored exactly as saved; identical on every
#:                        worker, so (W, L) changes don't touch it.
#: - ``reshard-exact``  — re-partitioned across the new mesh with every
#:                        per-element value preserved bit-exactly
#:                        (ZeRO chunks, per-sample scoretable rows).
#: - ``re-aggregate``   — reduced to a global quantity and re-spread;
#:                        the global reduction (sum / weighted mean) is
#:                        invariant across the reshard.
#: - ``re-seed``        — deliberately NOT carried by copy: derived from
#:                        the new template's keys via ``fold_in`` so no
#:                        two workers ever share a key (GLE05 rejects a
#:                        plain copy).
#: - ``cursor-fraction``— positional state carried as an epoch fraction
#:                        and re-scaled to the new shard length.
#: - ``drop-on-shrink`` — transient pipeline state that is deliberately
#:                        re-initialized from the new template (and,
#:                        where needed, re-primed by the Trainer).
ELASTIC_POLICIES = {
    "step": "replicate",
    "params": "replicate",
    "batch_stats": "replicate",
    "opt_state": "reshard-exact",
    "ema": "re-aggregate",
    "stream": "cursor-fraction",
    "rng": "re-seed",
    "groupwise": "drop-on-shrink",
    "pending": "drop-on-shrink",
    "cached_pool": "drop-on-shrink",
    "scoretable": "reshard-exact",
    "pending_sel": "drop-on-shrink",
    "sel_counts": "re-aggregate",
}


def init_worker_sampler_state(
    stream_key: jax.Array, worker_key: jax.Array,
    n_workers: int, shard_len: int,
):
    """Per-worker sampler state, ``[W]``-stacked: bootstrap EMA, shuffled
    shard streams, independent PRNG keys. One definition shared by the
    fused dp step's :func:`create_state` and the dp×sp Mercury step's
    init (``train/sp_step.py``) so seeding/bootstrap semantics cannot
    drift between them. Returns ``(ema, stream, rng)``."""
    from mercury_tpu.data.pipeline import init_shard_streams

    ema0 = init_ema()
    ema = EMAState(
        value=jnp.zeros((n_workers,), jnp.float32) + ema0.value,
        count=jnp.zeros((n_workers,), jnp.int32) + ema0.count,
    )
    stream = init_shard_streams(stream_key, n_workers, shard_len)
    rng = jax.random.split(worker_key, n_workers)
    return ema, stream, rng


def create_state(
    rng: jax.Array,
    model,
    tx: optax.GradientTransformation,
    sample_batch: jax.Array,
    n_workers: int,
    shard_len: int,
    with_groupwise: bool = False,
    pending_batch_size: int = 0,
    pending_sample_shape: Optional[tuple] = None,
    pending_label_shape: tuple = (),
    zero_sharding: bool = False,
    init_opt: bool = True,
    cached_pool_size: int = 0,
    with_scoretable: bool = False,
    stream_depth: int = 0,
    stream_emit_size: int = 0,
    stream_batch_size: int = 0,
    with_sel_counts: bool = False,
) -> MercuryState:
    """Initialize model/optimizer/sampler state.

    Initial cross-worker parameter sync (``Trainer.average_model``,
    ``pytorch_collab.py:84-87``) is implicit: params are created once and
    placed replicated — every device starts from identical weights.
    """
    from mercury_tpu.data.pipeline import init_shard_streams

    init_key, stream_key, worker_key = jax.random.split(rng, 3)
    variables = model.init(init_key, sample_batch, train=False)
    params = variables["params"]
    batch_stats = variables.get("batch_stats", {})
    if zero_sharding:
        # ZeRO-1: the optimizer runs on this worker's 1/W chunk of the
        # flattened parameter vector, so its state is chunk-shaped,
        # [W]-stacked here (sharded P(axis) by the step's specs).
        from mercury_tpu.utils.tree import tree_flatten_to_vector, zero_chunk_size

        pvec, _ = tree_flatten_to_vector(params)
        chunk = zero_chunk_size(pvec.size, n_workers)
        chunk_state = tx.init(jnp.zeros((chunk,), pvec.dtype))
        opt_state = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(
                jnp.asarray(x), (n_workers,) + jnp.shape(x)
            ),
            chunk_state,
        )
    elif init_opt:
        opt_state = tx.init(params)
    else:
        # Caller re-derives the optimizer state from re-placed params
        # (e.g. tensor-parallel layout) — don't allocate a replicated
        # moment tree just to discard it.
        opt_state = None
    ema, stream, worker_keys = init_worker_sampler_state(
        stream_key, worker_key, n_workers, shard_len
    )
    groupwise = None
    if with_groupwise:
        g0 = init_groupwise(shard_len)
        groupwise = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x, (n_workers,) + x.shape), g0
        )
    pending = None
    if pending_batch_size:
        # Placeholder only — step 0 primes it in-graph (the analogue of the
        # reference's epoch-prologue update_samples call, pytorch_collab:125).
        # The stored samples are POST-augmentation, whose shape can differ
        # from the raw dataset's (the IID pipeline crops to 32) — lax.cond
        # requires the placeholder to match exactly.
        shape = (tuple(pending_sample_shape) if pending_sample_shape is not None
                 else tuple(sample_batch.shape[1:]))
        pending = PendingBatch(
            # rows of token ids stay the integers they are
            images=jnp.zeros(
                (n_workers, pending_batch_size) + shape,
                sample_batch.dtype
                if jnp.issubdtype(sample_batch.dtype, jnp.integer)
                else jnp.float32),
            labels=jnp.zeros((n_workers, pending_batch_size)
                             + tuple(pending_label_shape), jnp.int32),
            scaled_probs=jnp.ones((n_workers, pending_batch_size), jnp.float32),
        )
    cached_pool = None
    if cached_pool_size:
        # Placeholder only — step 0's refresh branch fires (step % K == 0)
        # and overwrites it before any draw happens; uniform probs keep the
        # placeholder a valid distribution regardless.
        cached_pool = CachedPool(
            slots=jnp.zeros((n_workers, cached_pool_size), jnp.int32),
            probs=jnp.full((n_workers, cached_pool_size),
                           1.0 / cached_pool_size, jnp.float32),
            pool_loss=jnp.zeros((n_workers,), jnp.float32),
        )
    pending_sel = None
    if stream_depth:
        # Placeholder only — the jitted prime program (step.py
        # make_host_stream_prime) overwrites it with depth uniform
        # cold-start draws (and the advanced lookahead RNG) before the
        # first step runs; the Trainer feeds the host pipeline from the
        # prime's emitted indices.
        pending_sel = PendingSelection(
            slots=jnp.zeros((n_workers, stream_depth, stream_emit_size),
                            jnp.int32),
            scaled_probs=jnp.ones((n_workers, stream_depth,
                                   stream_batch_size), jnp.float32),
            rng=jnp.zeros((n_workers, 2), jnp.uint32),
        )
    scoretable = None
    if with_scoretable:
        # Uniform initial scores over every shard slot — step 0 draws
        # uniformly (the table IS the distribution, no priming branch
        # needed) and the first refresh windows sharpen it in place.
        t0 = init_score_table(shard_len)
        scoretable = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x, (n_workers,) + x.shape), t0
        )
    sel_counts = None
    if with_sel_counts:
        # Selection-count ledger (obs/sampler_health.py): zeros until the
        # first trained batch scatter-adds its slots. Rides alongside the
        # scoretable (same [W, L] geometry) but is a MercuryState field of
        # its own so the ScoreTableState constructors in the step and the
        # elastic carry stay untouched.
        sel_counts = jnp.zeros((n_workers, shard_len), jnp.int32)
    return MercuryState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        batch_stats=batch_stats,
        opt_state=opt_state,
        ema=ema,
        stream=stream,
        rng=worker_keys,
        groupwise=groupwise,
        pending=pending,
        cached_pool=cached_pool,
        scoretable=scoretable,
        pending_sel=pending_sel,
        sel_counts=sel_counts,
    )


def make_optimizer(
    name: str,
    lr: float,
    total_steps: int,
    weight_decay: float = 0.0,
    grad_accum_steps: int = 1,
    warmup_steps: int = 0,
) -> optax.GradientTransformation:
    """Adam + cosine decay — the reference's recipe: ``optim.Adam`` at
    ``0.001×world_size`` (``pytorch_collab.py:262,28``) under
    ``CosineAnnealingLR`` over the full run (``:62``). The reference steps
    its scheduler per epoch; here the schedule is per-step (smooth cosine to
    the same endpoint). ``sgd`` is provided as the uniform-baseline control.

    ``grad_accum_steps=A > 1`` wraps the optimizer in ``optax.MultiSteps``:
    each train step contributes its (mean) gradient to an accumulator and
    the parameter update applies every A-th step — an effective batch of
    ``A × batch_size`` per worker without the activation memory. The
    cosine schedule then decays over actual updates (``total_steps / A``).

    ``warmup_steps > 0`` runs a linear 0→peak warmup, then the cosine
    decays over the *remaining* steps so the schedule still ends with the
    run (counted in steps; divided by A like the decay horizon). Must be
    smaller than ``total_steps``.
    """
    if grad_accum_steps < 1:
        raise ValueError(f"grad_accum_steps must be >= 1, got {grad_accum_steps}")
    updates = max(-(-total_steps // grad_accum_steps), 1)
    if warmup_steps > 0:
        w_updates = max(-(-warmup_steps // grad_accum_steps), 1)
        # Compare post-division (update-count) values: with accumulation,
        # ceil(warmup/A) can collide with ceil(total/A) even when
        # warmup_steps < total_steps, which would leave optax a zero-length
        # cosine segment.
        if w_updates >= updates:
            raise ValueError(
                f"warmup_steps ({warmup_steps}) must leave decay room after "
                f"accumulation: warmup updates ({w_updates}) >= total "
                f"updates ({updates})"
            )
        # optax's decay_steps INCLUDES the warmup segment, so this is
        # warmup then cosine over the remaining (updates - w) updates.
        schedule = optax.warmup_cosine_decay_schedule(
            init_value=0.0, peak_value=lr, warmup_steps=w_updates,
            decay_steps=updates,
        )
    else:
        schedule = optax.cosine_decay_schedule(lr, decay_steps=updates)
    if name == "adam":
        opt = optax.adam(schedule)
    elif name == "adamw":
        opt = optax.adamw(schedule, weight_decay=weight_decay)
    elif name == "sgd":
        opt = optax.sgd(schedule, momentum=0.9)
    else:
        raise ValueError(f"unknown optimizer {name!r}")
    if weight_decay and name == "adam":
        opt = optax.chain(optax.add_decayed_weights(weight_decay), opt)
    if grad_accum_steps > 1:
        opt = optax.MultiSteps(opt, every_k_schedule=grad_accum_steps)
    return opt
