"""The sampler ladder: one function per sampler kind, one signature.

``sample(ctx, state, rows, keys, stream, ema) -> Drawn``: from this
worker's unstacked carry (presample ``stream``, ``ema``, the kind's own
optional field of ``state``), the step's eight keys and a row source, to
the train batch ``(images, labels, scaled_probs)`` with the pool loss, the
telemetry scalars and the carry out. The scoretable's post-update write-back
(:func:`rescore_trained`, :func:`commit_table`) is the one hook that needs
the train logits. The row source is all that differs between placements: a
gather from the resident set (:class:`ResidentRows`) or the slab the host
delivered (:class:`StreamedRows`). ``RESIDENT`` and ``STREAMED`` are the
ladder as tables: a kind is one row.
"""

from __future__ import annotations

import collections
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from mercury_tpu.data.pipeline import ShardStream, next_pool
from mercury_tpu.obs.diagnostics import (
    clip_fraction,
    ema_drift,
    table_age_summary,
)
from mercury_tpu.obs.sampler_health import (
    SCORE_HIST_HI,
    SCORE_HIST_LO,
    log_bin_histogram,
)
from mercury_tpu.sampling.importance import (
    EMAState,
    draw_with_replacement,
    ema_update,
    importance_probs,
    pool_mean,
)
from mercury_tpu.sampling.scoretable import (
    ScoreTableState,
    advance_cursor,
    decay_scores,
    refresh_window,
    scatter_mean,
    table_draw_inverse_cdf,
    table_probs,
    table_refresh_draw,
)
from mercury_tpu.train.stages import (
    StepContext,
    drawn_rows,
    ingest,
    pool_loss_metric,
    score_rows,
    select,
)
from mercury_tpu.train.state import CachedPool, MercuryState, PendingBatch


def unstack(tree):
    """This worker's row of a ``[W]``-stacked subtree (inside shard_map
    the leading axis is the device's single worker)."""
    return jax.tree_util.tree_map(lambda x: x[0], tree)


def restack(tree):
    return jax.tree_util.tree_map(lambda x: x[None], tree)


#: The step's 8-way split of ``state.rng``, by position.
Keys = collections.namedtuple(
    "Keys", "stream aug sel aug2 boot_stream boot_aug boot_sel next")

#: What the scoretable's write-back scatters into: the table as the step
#: found it, its scores after decay and refresh, the slots being trained.
TableDraw = collections.namedtuple("TableDraw", "table scores slots")


class Drawn(NamedTuple):
    images: jax.Array
    labels: jax.Array
    scaled_probs: jax.Array       # N·p of each drawn row (ones: uniform)
    avg_pool_loss: jax.Array
    # () with telemetry off, else (clip_frac, drift): each kind's own
    # measurement (obs/diagnostics.py); the uniform baseline keeps zeros
    # (nothing is scored, nothing can clip or drift).
    tel: Tuple
    stream: ShardStream
    ema: EMAState
    own: Optional[Dict[str, Any]] = None   # state field -> new unstacked value
    table: Optional[TableDraw] = None
    ages: Tuple = ()              # scoretable, sync: (min, mean, max) age


# --------------------------------------------------------------------------
# row sources
# --------------------------------------------------------------------------

class ResidentRows:
    """The train set on the device. ``sharded``: ``x``/``y`` arrive as
    ``[1, L, ...]``, this worker's shard rows, gathers are shard-local;
    ``replicated``: the whole set, indexed through ``shard_indices``."""

    def __init__(self, x_train, y_train, shard_indices, sharded: bool):
        self._sharded = sharded
        if sharded:
            self._x, self._y = x_train[0], y_train[0]
        else:
            self._x, self._y, self._idx = x_train, y_train, shard_indices

    def next_pool(self, stream, key, n):
        """Shuffled wrapping presample stream (≡ Trainer.get_next over the
        presampling loader, :74-82)."""
        return next_pool(stream, key, n)

    def gather(self, slots):
        if self._sharded:
            return self._x[slots], self._y[slots]
        gidx = self._idx[0][slots]
        return self._x[gidx], self._y[gidx]


class StreamedRows:
    """The ``[S, ...]`` slab the host pipeline pre-gathered for THIS step:
    the rows of the ``PendingSelection`` ring's front, drawn ``depth``
    steps ago by the lookahead with this step's own stream key — so the
    pool whose turn it is exists already and the stream does not move."""

    def __init__(self, xs, y_train, shard_indices, ring):
        self.xs, self.ring = xs, ring
        self.front = ring.slots[0]
        self._y, self._idx = y_train, shard_indices

    def next_pool(self, stream, key, n):
        return stream, self.front

    def labels(self, slots):
        return self._y[self._idx[0][slots]]

    def gather(self, slots):
        """``slots`` is the front, or the leading part of it."""
        labels, n = self.labels(slots), slots.shape[0]
        return (self.xs if n == self.xs.shape[0] else self.xs[:n]), labels


# --------------------------------------------------------------------------
# shared pieces
# --------------------------------------------------------------------------

def score_slots(ctx: StepContext, state, rows, slots, ka, reuse_images=True):
    """Gather → augment → inference-mode scoring forward — the prologue of
    every scoring sampler, under the ``mercury_scoring`` named scope the
    jaxpr auditor (``lint/audit.py``) keys per-region checks on (e.g.
    bf16-scoring dot dtypes). ``reuse_images`` forwards to ``score_rows``
    (False at scorer-only sites: bf16 ingest under scoring_dtype)."""
    with jax.named_scope("mercury_scoring"):
        with jax.named_scope("mercury_pool_ingest"):
            raw, labs = rows.gather(slots)
        imgs, pool_logits, scores = score_rows(
            ctx, state, raw, labs, ka, reuse_images=reuse_images
        )
        return imgs, labs, pool_logits, scores


def _zeros():
    return jnp.zeros((), jnp.float32)


def _tel_zeros(mode) -> Tuple:
    return (_zeros(), _zeros()) if mode.telemetry else ()


def _scoring_tel(mode, scores, ema, score_avg, ema_prev) -> Tuple:
    """Clip/drift of a pool scored this step."""
    if not mode.telemetry:
        return ()
    return (clip_fraction(scores, ema.value, mode.is_alpha),
            ema_drift(score_avg, ema_prev))


def _score_and_select(ctx, state, rows, stream, ema, k_stream, k_aug, k_sel):
    """Next pool → ONE batched inference forward over it (≡ the no_grad loop,
    :95-106; batch statistics, running-stat updates discarded) → EMA update
    and draw. The pool and pipelined kinds keep their own tails apart: the
    pool-loss metric and the drawn-row gather trade places between them, an
    op order that shows under ``importance_score="grad_norm"``."""
    stream, slots = rows.next_pool(stream, k_stream, ctx.mode.pool_size)
    images, labels, pool_logits, pool_losses = score_slots(
        ctx, state, rows, slots, k_aug)
    ema_prev = ema.value
    selected, scaled, ema, score_avg = select(ctx, k_sel, pool_losses, ema)
    return (stream, ema, (images, labels, pool_logits, pool_losses),
            (selected, scaled, score_avg, ema_prev))


def _decayed(mode, table, ema):
    """Every entry one step older: shrunk toward the EMA mean."""
    return decay_scores(
        table.scores.astype(jnp.float32), ema.value, mode.table_decay)


def _refresh_window_scores(ctx, state, rows, slots, ema, k_aug):
    """Scoretable, sync refresh: score the round-robin window (one small
    scorer-only forward) and move the EMA to its mean."""
    _, r_labels, r_logits, r_scores = score_slots(
        ctx, state, rows, slots, k_aug, reuse_images=False)
    score_avg = pool_mean(r_scores, ctx.mode.stat_axis)
    ema_prev = ema.value
    ema = ema_update(ema, score_avg, ctx.mode.ema_alpha)
    return r_labels, r_logits, r_scores, score_avg, ema_prev, ema


# --------------------------------------------------------------------------
# the ladder
# --------------------------------------------------------------------------

def sample_uniform(ctx, state, rows, keys, stream, ema) -> Drawn:
    """Uniform baseline: consume the freshly streamed batch directly — a
    shuffled without-replacement epoch pass, i.e. shuffled-loader SGD — with
    unit IS weights so loss/(N·p) = loss (pool_size == batch_size here)."""
    mode = ctx.mode
    stream, slots = rows.next_pool(stream, keys.stream, mode.pool_size)
    raw, labels = rows.gather(slots)
    images = ingest(ctx, keys.aug, raw)[:mode.batch_size]
    labels = labels[:mode.batch_size]
    scaled_probs = jnp.ones((mode.batch_size,), jnp.float32)
    return Drawn(images, labels, scaled_probs, _zeros(), _tel_zeros(mode),
                 stream, ema)


def sample_pool(ctx, state, rows, keys, stream, ema) -> Drawn:
    """Score a fresh candidate pool, draw the batch from it, train on the
    drawn rows of the scored (already augmented) pool."""
    stream, ema, (images, labels, pool_logits, pool_losses), (
        selected, scaled_probs, score_avg, ema_prev) = _score_and_select(
            ctx, state, rows, stream, ema, keys.stream, keys.aug, keys.sel)
    avg_pool_loss = pool_loss_metric(ctx, pool_logits, labels, score_avg)
    sel_images, sel_labels = drawn_rows(selected, images, labels)
    tel = _scoring_tel(ctx.mode, pool_losses, ema, score_avg, ema_prev)
    return Drawn(sel_images, sel_labels, scaled_probs, avg_pool_loss, tel,
                 stream, ema)


def sample_pipelined(ctx, state, rows, keys, stream, ema) -> Drawn:
    """Pipelined scoring: train on the batch selected last step, score the
    NEXT pool with the same (pre-update) params — the two chains are
    independent, so XLA overlaps the scoring forward with the gradient
    collective. Reference dataflow: update_samples for t+1 runs before
    optimizer.step (pytorch_collab.py:158-164)."""
    mode = ctx.mode

    def score_next(stream, ema, ks, ka, ksel):
        stream, ema, (images, labels, pool_logits, pool_losses), (
            selected, scaled, avg, ema_prev) = _score_and_select(
                ctx, state, rows, stream, ema, ks, ka, ksel)
        sel_imgs, sel_labs = drawn_rows(selected, images, labels)
        pend = PendingBatch(
            images=sel_imgs, labels=sel_labs, scaled_probs=scaled)
        # Clip/drift of the pool scored THIS step (trained next step).
        tel = _scoring_tel(mode, pool_losses, ema, avg, ema_prev)
        return stream, ema, pend, pool_loss_metric(
            ctx, pool_logits, labels, avg), tel

    stored = unstack(state.pending)

    # Step 0 primes the pending batch in-graph (≡ the epoch-prologue
    # update_samples call, pytorch_collab.py:125).
    def boot(args):
        s, e = args
        return score_next(s, e, keys.boot_stream, keys.boot_aug,
                          keys.boot_sel)

    def keep(args):
        s, e = args
        return s, e, stored, _zeros(), _tel_zeros(mode)

    stream, ema, current, _, _ = lax.cond(
        state.step == 0, boot, keep, (stream, ema)
    )
    stream, ema, new_pending, avg_pool_loss, tel = score_next(
        stream, ema, keys.stream, keys.aug, keys.sel
    )
    return Drawn(current.images, current.labels, current.scaled_probs,
                 avg_pool_loss, tel, stream, ema,
                 own={"pending": new_pending})


def sample_cadence(ctx, state, rows, keys, stream, ema) -> Drawn:
    """Score-refresh cadence: every K-th step stream + score a fresh pool
    and cache its normalized importance distribution; the K-1 steps in
    between redraw from the cache (fresh multinomial draws ≡
    pytorch_collab.py:114, fresh augmentation) and skip the scoring
    forward — the dominant IS cost amortizes by K. The 1/(N·p) reweight
    uses the cached probs the batch was drawn from: still unbiased."""
    mode = ctx.mode
    cached = unstack(state.cached_pool)

    def refresh(args):
        stream, ema, _, _ = args
        stream, slots = rows.next_pool(stream, keys.stream, mode.pool_size)
        _, labs, pool_logits, pool_losses = score_slots(
            ctx, state, rows, slots, keys.aug, reuse_images=False
        )
        avg = pool_mean(pool_losses, mode.stat_axis)
        ema_prev = ema.value
        ema = ema_update(ema, avg, mode.ema_alpha)
        probs = importance_probs(pool_losses, ema.value, mode.is_alpha)
        pool = CachedPool(
            slots=slots.astype(jnp.int32),
            probs=probs,
            pool_loss=pool_loss_metric(ctx, pool_logits, labs, avg),
        )
        return stream, ema, pool, _scoring_tel(
            mode, pool_losses, ema, avg, ema_prev)

    def reuse(args):
        return args

    # clip/drift read 0 on cache-hit steps: nothing was scored.
    stream, ema, cached, tel = lax.cond(
        state.step % mode.cadence == 0, refresh, reuse,
        (stream, ema, cached, _tel_zeros(mode)),
    )
    selected = draw_with_replacement(keys.sel, cached.probs, mode.batch_size)
    scaled_probs = cached.probs[selected] * mode.pool_size
    sel_raw, sel_labels = rows.gather(cached.slots[selected])
    sel_images = ingest(ctx, keys.aug2, sel_raw)
    return Drawn(sel_images, sel_labels, scaled_probs, cached.pool_loss,
                 tel, stream, ema, own={"cached_pool": cached})


def sample_groupwise(ctx, state, rows, keys, stream, ema) -> Drawn:
    """Sliding-window refresh over the shard (util.py:114-138): score the
    next ``pool_size`` slots in order, wrapping, persist the scores into
    the shard-wide importance array, draw from it with the +mean shift
    (util.py:133-153). The scoring pass is scorer-only: drawn slots are
    re-gathered and re-augmented (the reference re-loads by index via
    get_slice, util.py:123)."""
    from mercury_tpu.sampling.groupwise import (
        draw as gw_draw,
        update_importance,
        window_indices,
    )

    mode = ctx.mode
    groupwise = unstack(state.groupwise)
    slots = window_indices(groupwise, mode.pool_size)
    _, labels, pool_logits, pool_losses = score_slots(
        ctx, state, rows, slots, keys.aug, reuse_images=False
    )
    groupwise = update_importance(groupwise, slots, pool_losses)
    sel_slots, scaled_probs = gw_draw(groupwise, keys.sel, mode.batch_size)
    sel_raw, sel_labels = rows.gather(sel_slots)
    sel_images = ingest(ctx, keys.aug2, sel_raw)
    score_avg = pool_mean(pool_losses, mode.stat_axis)
    ema_prev = ema.value
    ema = ema_update(ema, score_avg, mode.ema_alpha)
    avg_pool_loss = pool_loss_metric(ctx, pool_logits, labels, score_avg)
    tel = _scoring_tel(mode, pool_losses, ema, score_avg, ema_prev)
    return Drawn(sel_images, sel_labels, scaled_probs, avg_pool_loss, tel,
                 stream, ema, own={"groupwise": groupwise})


def sample_scoretable(ctx, state, rows, keys, stream, ema) -> Drawn:
    """Score-table sampler: a device-resident [L] float32 score over THIS
    worker's whole shard. Each step (a) refreshes only ``refresh_size`` entries
    — a round-robin window, every slot rescored within ceil(L/R) steps — via
    one small scoring forward, (b) age-decays the rest toward the EMA mean (an
    entry untouched for k steps has shrunk by decay^k toward the pool-typical
    score), and (c) draws the train batch from the FULL shard's distribution in
    one fused normalize→CDF→draw. Scoring FLOPs per step drop from pool_size to
    refresh_size while the draw sees every sample.

    ``scoretable_async``: no refresh window, no scoring forward, no
    ``mercury_scoring`` scope — the scorer fleet refreshed the table between
    dispatches; in-graph it is decay → normalize → draw, and the post-train
    write-back moves the EMA."""
    mode = ctx.mode
    table = unstack(state.scoretable)
    tel, ages = _tel_zeros(mode), ()
    if mode.async_refresh:
        new_scores = _decayed(mode, table, ema)
    else:
        refresh_slots = refresh_window(table, mode.refresh_size)
        r_labels, r_logits, r_scores, score_avg, ema_prev, ema = (
            _refresh_window_scores(
                ctx, state, rows, refresh_slots, ema, keys.aug))
        if mode.use_pallas:
            # Decay and refresh scatter are the jax-native ops of
            # table_refresh_draw; the kernel owns normalize → CDF → draw
            # over the whole table.
            new_scores = scatter_mean(
                _decayed(mode, table, ema), refresh_slots, r_scores)
    if mode.use_pallas:
        from mercury_tpu.ops import score_and_draw_pallas

        _, selected, scaled_probs = score_and_draw_pallas(
            keys.sel, new_scores, ema.value, mode.batch_size, mode.is_alpha)
    elif mode.async_refresh:
        probs = table_probs(new_scores, ema.value, mode.is_alpha)
        # Inverse-CDF, not categorical: a [B, L] Gumbel field is B·L threefry
        # draws — at shard scale that alone would cost more than the scoring
        # forward we just removed (measured ~5 ms at L≈3k on CPU).
        selected = table_draw_inverse_cdf(keys.sel, probs, mode.batch_size)
        scaled_probs = probs[selected] * new_scores.shape[0]
    else:
        new_scores, _, selected, scaled_probs = table_refresh_draw(
            keys.sel, table.scores, refresh_slots, r_scores,
            ema.value, mode.batch_size,
            alpha=mode.is_alpha, decay=mode.table_decay,
        )
    # No refresh forward → no pool-loss measurement on an async step.
    avg_pool_loss = (_zeros() if mode.async_refresh else pool_loss_metric(
        ctx, r_logits, r_labels, score_avg))
    sel_raw, sel_labels = rows.gather(selected)
    sel_images = ingest(ctx, keys.aug2, sel_raw)
    if mode.telemetry:
        # Clip over the FULL refreshed (async: decayed) table — the
        # distribution the draw actually normalizes.
        tel = (clip_fraction(new_scores, ema.value, mode.is_alpha), tel[1])
        if not mode.async_refresh:
            tel, ages = _window_tel(mode, table, tel, score_avg, ema_prev)
    return Drawn(sel_images, sel_labels, scaled_probs, avg_pool_loss, tel,
                 stream, ema, table=TableDraw(table, new_scores, selected),
                 ages=ages)


def _window_tel(mode, table, tel, score_avg, ema_prev):
    """Drift of the refreshed window and the cursor's staleness
    (pre-advance: this window is age 0). Under async the fleet owns the
    sweep, so ages live host-side (sampler/score_staleness_* via
    ScorerFleet.stats) and drift moves to the post-train EMA update."""
    drift = ema_drift(score_avg, ema_prev)
    ages = table_age_summary(
        table.cursor, table.scores.shape[0], mode.refresh_size)
    return (tel[0], drift), ages


def streamed_scoretable(ctx, state, rows, keys, stream, ema) -> Drawn:
    """The scoretable under ``host_stream``. Apart from
    :func:`sample_scoretable` because there is no draw here: the batch is
    the ring front, drawn ``depth`` steps ago from the table as it was
    then (its carried draw-time ``scaled_probs`` keep the reweighting
    unbiased); what is left is ``table_refresh_draw``'s decay →
    refresh-scatter without its draw half. Streamed layout: rows 0:R are
    the step's refresh window (round-robin, drawn without the table),
    rows R: the train rows; async streams the train rows only."""
    mode = ctx.mode
    table = unstack(state.scoretable)
    tel, ages = _tel_zeros(mode), ()
    if mode.async_refresh:
        train_slots = rows.front
        refreshed = _decayed(mode, table, ema)
        avg_pool_loss = _zeros()
    else:
        refresh_slots = rows.front[:mode.refresh_size]
        train_slots = rows.front[mode.refresh_size:]
        r_labels, r_logits, r_scores, score_avg, ema_prev, ema = (
            _refresh_window_scores(
                ctx, state, rows, refresh_slots, ema, keys.aug))
        refreshed = scatter_mean(
            _decayed(mode, table, ema), refresh_slots, r_scores)
    sel_labels = rows.labels(train_slots)
    sel_images = ingest(
        ctx, keys.aug2,
        rows.xs if mode.async_refresh else rows.xs[mode.refresh_size:])
    scaled_probs = rows.ring.scaled_probs[0]
    if not mode.async_refresh:
        avg_pool_loss = pool_loss_metric(ctx, r_logits, r_labels, score_avg)
        if mode.telemetry:
            tel, ages = _window_tel(mode, table, tel, score_avg, ema_prev)
    return Drawn(sel_images, sel_labels, scaled_probs, avg_pool_loss, tel,
                 stream, ema, table=TableDraw(table, refreshed, train_slots),
                 ages=ages)


RESIDENT = {
    "uniform": sample_uniform,
    "pool": sample_pool,
    "pipelined": sample_pipelined,
    "cadence": sample_cadence,
    "groupwise": sample_groupwise,
    "scoretable": sample_scoretable,
    "scoretable_async": sample_scoretable,
}

#: host_stream runs the kinds whose draw can be made ahead (StepMode
#: refuses the others); uniform and pool are the resident functions on
#: the streamed row source.
STREAMED = {
    "uniform": sample_uniform,
    "pool": sample_pool,
    "scoretable": streamed_scoretable,
    "scoretable_async": streamed_scoretable,
}


# --------------------------------------------------------------------------
# the scoretable's write-back: the hook that needs the train logits
# --------------------------------------------------------------------------

def rescore_trained(ctx: StepContext, drawn: Drawn, logits, ema, tel):
    """Free write-back: the train forward's logits re-score the
    just-trained slots for zero extra FLOPs (they fall out of the backward
    pass anyway); with-replacement duplicates average. Returns the table's
    scores after it, the EMA and the telemetry pair."""
    mode = ctx.mode
    train_scores = ctx.rows.score(
        logits.astype(jnp.float32), drawn.labels)
    if mode.async_refresh:
        # With no refresh forward, the EMA mean (decay target, smoothing
        # anchor) comes from the trained batch, reweighted back to the
        # uniform mean: E[score_i/(L·p_i)] = mean_L(score), the identity
        # the loss reweighting rests on — the SHARD-typical score, not the
        # importance-tilted batch mean.
        score_avg = pool_mean(
            train_scores / drawn.scaled_probs, mode.stat_axis)
        ema_prev = ema.value
        ema = ema_update(ema, score_avg, mode.ema_alpha)
        if mode.telemetry:
            tel = (tel[0], ema_drift(score_avg, ema_prev))
    scores = scatter_mean(drawn.table.scores, drawn.table.slots, train_scores)
    return scores, ema, tel


def commit_table(mode, state: MercuryState, drawn: Drawn, scores):
    """The step's new ``scoretable`` and ``sel_counts`` fields, stacked."""
    table = drawn.table.table
    new_table = ScoreTableState(
        scores=scores,
        # Async: the fleet owns the round-robin sweep — the in-graph
        # cursor stays put.
        cursor=(table.cursor if mode.async_refresh
                else advance_cursor(table, mode.refresh_size)),
    )
    sel_counts = state.sel_counts
    scoretable = restack(new_table)
    if mode.use_ledger:
        # Selection-count ledger, counted at TRAIN time: every draw once
        # (duplicates once per occurrence), a host stream's in-flight ring
        # not yet (tests/test_sampler_health.py replays the ring).
        sel_counts = (
            state.sel_counts[0].at[drawn.table.slots].add(1)
        )[None]
    return scoretable, sel_counts


def table_histogram(mode, scores):
    """Global (psum'd) histogram of the post-write-back table — the
    distribution the NEXT draw normalizes. Per-bin scalars: the async
    writer means any vector."""
    return lax.psum(
        log_bin_histogram(scores, SCORE_HIST_LO, SCORE_HIST_HI), mode.axis)
