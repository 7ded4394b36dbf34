"""Persistent per-shard score table with amortized incremental refresh.

The ``sampler="scoretable"`` mode: each worker carries a device-resident
``[L]`` float32 score over its ENTIRE shard (every slot of the cyclically
tiled ``shard_indices`` row), and each step

1. re-scores only a small round-robin window of ``refresh_size`` slots
   (one small scoring forward — the amortization: scoring FLOPs drop from
   ``pool_size`` per step to ``refresh_size``),
2. age-decays every table entry toward the EMA mean
   (``score ← μ + γ·(score − μ)``, :func:`decay_scores`) so stale entries
   drift back to the average instead of pinning old extremes — never-
   refreshed samples stay drawable and never starve,
3. draws the train batch from the WHOLE shard's distribution
   (``p ∝ max(score + α·EMA, ε)`` over all ``L`` slots — a strictly larger
   candidate set than the 320-sample pool), and
4. after the train forward, writes the just-trained batch's fresh scores
   back into the table for free (:func:`scatter_mean` — those scores fall
   out of the training forward's logits).

The lineage is the distributed score-table design of Alain et al.,
*Variance Reduction in SGD by Distributed Importance Sampling*
(arXiv:1511.06481), and the staleness-decay is the history-smoothing trick
of Katharopoulos & Fleuret (arXiv:1803.00942). Relative to the in-repo
``groupwise`` sampler (which also persists scores shard-wide) the
differences are: draws come from the FULL table rather than the newest
refresh generation only, entries decay toward the EMA instead of aging
silently, and the refresh window is decoupled from the draw (64 scored vs
320, yet every slot drawable every step).

Unbiasedness: the ``1/(L·p)`` reweight uses the probabilities the batch
was ACTUALLY drawn with, so ``E[loss_i/(L·p_i)] = mean_L(loss)`` exactly,
for any table contents — staleness shifts variance, never the mean
(verified in ``tests/test_scoretable.py``).

Everything here is the pure jax-native formulation; on the Pallas path
(``use_pallas``) step 3's normalize → CDF → draw over the whole table is
``ops.mercury_kernels.score_and_draw_pallas``, fed the table steps 1-2
produce here.

Observability: under ``telemetry=True`` the step emits the post-refresh
table's log-binned histogram (``sampler_dist/score_hist/*``) and
scatter-adds every trained slot into the ``MercuryState.sel_counts``
selection-count ledger; ``obs/sampler_health.py`` owns the histogram /
ledger derivations (coverage, Gini, inclusion-bias audit against
:func:`table_probs` — its numpy mirror ``table_probs_np`` lives there).
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from mercury_tpu.sampling.importance import importance_probs


class ScoreTableState(NamedTuple):
    """Per-worker persistent score memory (``[W]``-stacked in
    ``MercuryState.scoretable``)."""

    scores: jax.Array  # [L] float32 — last known (decayed) per-slot score
    cursor: jax.Array  # [] int32 — round-robin refresh window start


def init_score_table(n_slots: int) -> ScoreTableState:
    """Uniform initial scores (like the groupwise sampler's importance
    init): before any refresh every slot is equally drawable."""
    return ScoreTableState(
        scores=jnp.ones((n_slots,), jnp.float32),
        cursor=jnp.zeros((), jnp.int32),
    )


def refresh_period(n_slots: int, refresh_size: int) -> int:
    """``ceil(L/R)`` — steps for the round-robin window to sweep the whole
    shard, i.e. the guaranteed staleness bound: no entry's cursor-age ever
    exceeds ``refresh_period - 1`` sweeps. The telemetry age summary
    (``obs.diagnostics.table_age_summary``) reports live ages against this
    bound."""
    return -(-n_slots // refresh_size)


def refresh_window(state: ScoreTableState, refresh_size: int) -> jax.Array:
    """Shard slots of the next refresh window, wrapping modularly.

    Modular windows (the groupwise idiom) rather than the shuffled
    ``ShardStream``: the stream skips its tail at reshuffle, while
    ``(cursor + arange(R)) % L`` visits EVERY slot exactly once per
    ``ceil(L/R)`` windows — bounded staleness for the whole shard."""
    n = state.scores.shape[0]
    return (state.cursor + jnp.arange(refresh_size)) % n


def advance_cursor(state: ScoreTableState, refresh_size: int) -> jax.Array:
    n = state.scores.shape[0]
    return (state.cursor + refresh_size) % n


def decay_scores(scores: jax.Array, target: jax.Array,
                 decay: float) -> jax.Array:
    """Age-decay every entry toward ``target`` (the EMA mean):
    ``score ← target + γ·(score − target)``.

    An entry refreshed ``a`` steps ago has been pulled ``γ^a`` of the way
    to the mean — with refresh disabled the table converges geometrically
    to a constant, i.e. the draw converges to uniform (tested)."""
    return target + (scores - target) * decay


def scatter_mean(scores: jax.Array, slots: jax.Array,
                 values: jax.Array) -> jax.Array:
    """Write ``values`` into ``scores`` at ``slots``; duplicate slots
    (with-replacement draws hit the same slot twice) receive the MEAN of
    their values, untouched slots keep their current score. Shared by the
    Pallas and jax-native step paths so the post-train write-back cannot
    drift between them."""
    sums = jnp.zeros_like(scores).at[slots].add(values.astype(jnp.float32))
    counts = jnp.zeros_like(scores).at[slots].add(1.0)
    return jnp.where(counts > 0, sums / jnp.maximum(counts, 1.0), scores)


def stale_weighted(values: jax.Array, ema_value: jax.Array,
                   age_weight: jax.Array) -> jax.Array:
    """Staleness-discount a refreshed chunk's scores toward the EMA mean:
    ``w·value + (1−w)·μ`` with ``w = γ^age``.

    This is :func:`decay_scores` applied ``age`` times to the fresh value
    — a chunk scored ``age`` steps ago enters the table carrying exactly
    the value it would have had had it been applied at age 0 and decayed
    in-graph since, so the async fleet's host-side refresh composes with
    the step's decay instead of fighting it. Written in the convex form
    (not ``μ + w·(v − μ)``) so that ``age_weight == 1.0`` is BIT-exact
    identity (``v·1.0 + μ·0.0 == v`` in IEEE-754), which is what lets
    ``tests/test_async_refresh.py`` pin the async apply bit-identical to
    the in-graph refresh at age 0."""
    return values * age_weight + ema_value * (1.0 - age_weight)


def apply_async_chunk(scores: jax.Array, slots: jax.Array,
                      values: jax.Array, ema_value: jax.Array,
                      age_weight: jax.Array) -> jax.Array:
    """Scatter one async scorer-fleet chunk into the table:
    staleness-weight the fresh ``values`` (:func:`stale_weighted`), then
    write them through the SAME :func:`scatter_mean` the in-graph refresh
    uses — the only difference between an async chunk at age 0 and the
    in-graph refresh is who computed the scores."""
    return scatter_mean(
        scores, slots, stale_weighted(values, ema_value, age_weight))


def table_probs(scores: jax.Array, ema_value: jax.Array,
                alpha: float = 0.5) -> jax.Array:
    """Staleness-aware smoothing + normalization over the full table:
    ``p ∝ max(score + α·EMA, ε)`` — the same smoothing the pool sampler
    applies (``importance_probs``), over ``L`` slots instead of the
    pool."""
    return importance_probs(scores, ema_value, alpha)


def table_draw_inverse_cdf(key: jax.Array, probs: jax.Array,
                           batch_size: int) -> jax.Array:
    """Draw ``batch_size`` slots with replacement by inverse-CDF on
    ``batch_size`` uniforms — the Pallas kernel's draw strategy.

    ``jax.random.categorical`` materializes a ``[B, L]`` Gumbel field
    (``B·L`` threefry draws — ~5 ms at L≈3k on CPU, the entire async
    step-time budget); inverse-CDF is ``O(L)`` cumsum + ``B`` uniforms +
    a binary search, so the async step's draw costs like the uniform
    sampler's. ``P(sel=i) = probs[i]/Σprobs`` exactly, so the
    ``1/(L·p)`` reweight stays unbiased. Used by ``refresh_mode="async"``
    only: the sync path keeps its committed categorical trajectory."""
    cdf = jnp.cumsum(probs)
    u = jax.random.uniform(key, (batch_size,)) * cdf[-1]
    sel = jnp.searchsorted(cdf, u)
    return jnp.clip(sel, 0, probs.shape[0] - 1).astype(jnp.int32)


def table_refresh_draw(
    key: jax.Array,
    scores: jax.Array,
    refresh_slots: jax.Array,
    refresh_scores: jax.Array,
    ema_value: jax.Array,
    batch_size: int,
    alpha: float = 0.5,
    decay: float = 0.98,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Jax-native fused-step reference: decay → scatter-refresh →
    smooth/normalize → draw ``batch_size`` with replacement → ``p·L``.

    Returns ``(new_scores [L], probs [L], selected [B] int32,
    scaled_probs [B])``. The Pallas path keeps this decay and scatter
    and hands normalize → draw to ``score_and_draw_pallas``;
    ``tests/test_scoretable.py`` pins the two together."""
    decayed = decay_scores(scores.astype(jnp.float32), ema_value, decay)
    refreshed = scatter_mean(decayed, refresh_slots, refresh_scores)
    probs = table_probs(refreshed, ema_value, alpha)
    n = scores.shape[0]
    selected = jax.random.categorical(
        key, jnp.log(jnp.maximum(probs, 1e-30)), shape=(batch_size,)
    ).astype(jnp.int32)
    scaled = probs[selected] * n
    return refreshed, probs, selected, scaled
