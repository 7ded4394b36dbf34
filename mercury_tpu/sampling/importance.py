"""The Mercury importance-sampling core, as pure jittable functions.

Capability parity with ``Trainer.update_samples`` (``pytorch_collab.py:
89-117``) and the unbiased reweighting at ``:137``:

1. run inference-only forward passes over a candidate pool of presampled
   data and take the **per-sample** cross-entropy (``:101-102``);
2. update an EMA of the mean presampling loss (``:110`` via
   ``util.py:200-217``);
3. smooth: ``score_i = loss_i + α·EMA`` (``:111`` — the additive term keeps
   easy samples drawable);
4. normalize scores to a distribution ``p_i`` (``:112``);
5. draw the train batch **with replacement** from ``p`` (``:114``,
   ``torch.multinomial(..., replacement=True)``);
6. return ``p_i·N`` for the drawn samples (``:116``) so the training loss
   ``mean(loss_i / (N·p_i))`` (``:137``) is an unbiased estimator of the
   uniform-sampling expected loss.

Design deltas from the reference (deliberate, TPU-first):
- the whole candidate pool is scored in **one batched forward** instead of a
  10-iteration Python loop — and the reference's wasted per-iteration
  ``cat``/EMA/``multinomial`` work (``:108-114``, SURVEY.md §2.1) is hoisted
  so sampling happens exactly once;
- sampling uses ``jax.random.categorical`` over log-scores — i.i.d. draws ≡
  multinomial with replacement — keyed by a threaded PRNG key, so runs are
  deterministic and resumable;
- an optional ``axis_name`` psums (sum_loss, count) across data-parallel
  workers before the EMA update, giving a **globally consistent EMA** — the
  cross-worker importance-statistic exchange the reference lacks
  (BASELINE.json north-star; SURVEY.md §2.5).
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from mercury_tpu.ops import head_nll_pallas, head_nll_takes


# Numerical floor applied to smoothed scores before normalization
# (guards the all-zero pool). Shared with the telemetry clip-rate
# diagnostic (obs/diagnostics.py) so "clipped" means exactly "floored
# here" — the two cannot drift apart.
SCORE_FLOOR = 1e-12


class EMAState(NamedTuple):
    """In-graph EMA with first-update bootstrap (``util.py:200-217``)."""

    value: jax.Array  # [] float32 — current EMA
    count: jax.Array  # [] int32 — number of updates (0 → bootstrap next)


def init_ema() -> EMAState:
    return EMAState(value=jnp.zeros((), jnp.float32), count=jnp.zeros((), jnp.int32))


def ema_update(state: EMAState, value: jax.Array, alpha: float = 0.9) -> EMAState:
    """``ema ← α·ema + (1-α)·value`` with bootstrap on first update
    (``util.py:207-213``)."""
    value = value.astype(jnp.float32)
    new = jnp.where(state.count == 0, value, alpha * state.value + (1.0 - alpha) * value)
    return EMAState(value=new, count=state.count + 1)


def per_sample_loss(
    logits: jax.Array, labels: jax.Array, label_smoothing: float = 0.0
) -> jax.Array:
    """Per-sample cross-entropy, ``reduction='none'``
    (``pytorch_collab.py:102,133``)."""
    logits = logits.astype(jnp.float32)
    log_probs = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(log_probs, labels[:, None], axis=-1)[:, 0]
    if label_smoothing > 0.0:
        smooth = -jnp.mean(log_probs, axis=-1)
        nll = (1.0 - label_smoothing) * nll + label_smoothing * smooth
    return nll


def _token_rows_plain(hidden: jax.Array, head: jax.Array, labels: jax.Array
                      ) -> Tuple[jax.Array, jax.Array]:
    """``(nll [T], hit [T])`` of one sequence from its whole ``[T, V]``
    float32 logits: one product, then ``logsumexp``, the label's logit and
    ``argmax`` read them back."""
    logits = jnp.dot(hidden, head, preferred_element_type=jnp.float32)
    nll = (jax.nn.logsumexp(logits, axis=-1)
           - jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0])
    return nll, (jnp.argmax(logits, axis=-1) == labels).astype(jnp.float32)


@jax.custom_vjp
def _token_rows_by_blocks(hidden: jax.Array, head: jax.Array,
                          labels: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """:func:`_token_rows_plain`'s two outputs, by the way the pass needs
    them (one algorithm, as ``models/resnet.py::_closing_unit``). Nothing
    differentiates it (the scoring pass, ``evaluate()``): one kernel over
    blocks of the vocabulary (``ops.head_nll_pallas``), which holds a tile
    of the logits in VMEM and writes two numbers a token. Under
    ``jax.grad`` the backward needs the whole logits anyway, so the
    ``custom_vjp`` rule is the vjp of the plain form and the
    differentiated pass is what it was."""
    return head_nll_pallas(hidden, head, labels)


_token_rows_by_blocks.defvjp(
    lambda *args: jax.vjp(_token_rows_plain, *args),
    lambda vjp, cotangents: vjp(cotangents))


def head_takes_kernel(hidden: jax.Array, use_kernel: bool) -> bool:
    """Whether :func:`sequence_loss` gives rows of ``hidden`` (``[..., T,
    D]``) to the kernel where nothing differentiates it: asked for
    (``StepMode.use_pallas``) and of a shape it takes
    (``ops.head_nll_takes``). Else the plain form, everywhere."""
    return use_kernel and head_nll_takes(*hidden.shape[-2:],
                                         hidden.dtype.itemsize)


def sequence_loss(hidden: jax.Array, head: jax.Array, labels: jax.Array,
                  use_kernel: bool = False) -> jax.Array:
    """The next-token loss of ONE sequence and its share of positions
    predicted right, ``[2]`` float32: ``hidden [T, D]`` the final hidden
    states, ``head [D, V]`` the output projection, ``labels [T]`` the ids
    that follow. The loss is the mean over the ``T`` positions of the token
    negative log-likelihood over the ``V`` rows, from float32 logits.

    What runs under ``mercury_lm_head``: the plain form
    (:func:`_token_rows_plain`: the ``[T, V]`` logits written once and read
    back three times), or with ``use_kernel``, at shapes the kernel takes
    (:func:`head_takes_kernel`), :func:`_token_rows_by_blocks`: the kernel
    where nothing differentiates the pass, the plain form and its transpose
    under ``jax.grad``."""
    with jax.named_scope("mercury_lm_head"):
        rows = (_token_rows_by_blocks
                if head_takes_kernel(hidden, use_kernel)
                else _token_rows_plain)
        nll, hit = rows(hidden, head, labels)
        return jnp.stack([jnp.mean(nll), jnp.mean(hit)])


def sequence_rows(outputs, labels: jax.Array,
                  use_kernel: bool = False) -> jax.Array:
    """Rows of per-token labels reduced to the unit that is scored and
    drawn, a sequence: ``outputs`` is what a model of per-token logits
    returns, ``(hidden [n, T, D], head [D, V])`` (``models/decoder.py``),
    ``labels [n, T]``; ``[n, 2]`` float32, a row's :func:`sequence_loss`
    and hit share. The head's product and the token loss run a row at a
    time (``lax.map``), each under ``jax.checkpoint``: in the train pass no
    more than one row's ``[T, V]`` logits ever exist, forward or backward;
    with ``use_kernel`` (:func:`sequence_loss`) the passes that nothing
    differentiates hold none at all."""
    hidden, head = outputs
    row_loss = jax.checkpoint(sequence_loss, static_argnums=(3,))
    with jax.named_scope("mercury_rows"):
        return jax.lax.map(
            lambda row: row_loss(row[0], head, row[1], use_kernel),
            (hidden, labels))


def token_logits(outputs) -> jax.Array:
    """The whole ``[n, T, V]`` float32 logits of ``outputs`` (as
    :func:`sequence_rows` takes them): for the few rows ``predict`` is
    given."""
    hidden, head = outputs
    with jax.named_scope("mercury_lm_head"):
        return jnp.dot(hidden, head, preferred_element_type=jnp.float32)


def per_sample_grad_norm_bound(
    logits: jax.Array, labels: jax.Array, label_smoothing: float = 0.0
) -> jax.Array:
    """Per-sample gradient-norm importance score: ``||softmax(z_i) −
    target(y_i)||₂``.

    This is the exact L2 norm of the (optionally label-smoothed)
    cross-entropy gradient w.r.t. the logits — the target matching the
    training objective: ``(1−ls)·onehot + ls/K`` — which upper-bounds (up
    to the network's Lipschitz factor) the full per-sample
    parameter-gradient norm: the variance-optimal importance score of
    Katharopoulos & Fleuret, *"Not All Samples Are Created Equal: Deep
    Learning with Importance Sampling"* (arXiv:1803.00942; retrieved in
    PAPERS.md). Computable from the scoring forward's logits at no extra
    cost, in place of the loss score the reference uses
    (``pytorch_collab.py:102``) — select with
    ``config.importance_score="grad_norm"``. The downstream IS math
    (smoothing, normalization, ``1/(N·p)`` reweighting) is score-agnostic,
    so the estimator stays unbiased for any score.
    """
    logits = logits.astype(jnp.float32)
    k = logits.shape[-1]
    p = jax.nn.softmax(logits, axis=-1)
    target = jax.nn.one_hot(labels, k, dtype=jnp.float32)
    if label_smoothing > 0.0:
        target = (1.0 - label_smoothing) * target + label_smoothing / k
    return jnp.linalg.norm(p - target, axis=-1)


def smoothed_scores(
    losses: jax.Array, ema_value: jax.Array, alpha: float = 0.5
) -> jax.Array:
    """The additive smoothing ``score_i = loss_i + α·EMA``
    (``pytorch_collab.py:111``) — the pre-normalization scores every
    sampler draws from. Factored out so the telemetry clip-rate
    diagnostic measures exactly the quantity ``importance_probs``
    floors."""
    return losses.astype(jnp.float32) + alpha * ema_value


def importance_probs(
    losses: jax.Array, ema_value: jax.Array, alpha: float = 0.5
) -> jax.Array:
    """Scores → normalized sampling distribution over the candidate pool.

    ``score_i = loss_i + α·EMA`` (``pytorch_collab.py:111``) then
    ``p = score / Σ score`` (``:112``). Losses are ≥0 so scores are ≥0;
    the ``SCORE_FLOOR`` guards the all-zero edge case.
    """
    scores = jnp.maximum(smoothed_scores(losses, ema_value, alpha),
                         SCORE_FLOOR)
    return scores / jnp.sum(scores)


def draw_with_replacement(
    key: jax.Array, probs: jax.Array, num_draws: int
) -> jax.Array:
    """``torch.multinomial(probs, n, replacement=True)``
    (``pytorch_collab.py:114``) ≡ ``num_draws`` i.i.d. categorical draws."""
    return jax.random.categorical(key, jnp.log(probs), shape=(num_draws,))


def reweighted_loss(
    losses: jax.Array, scaled_probs: jax.Array
) -> jax.Array:
    """Unbiased IS estimator ``mean(loss_i / (N·p_i))``
    (``pytorch_collab.py:116,137`` — ``scaled_probs = p_i·N``)."""
    return jnp.mean(losses / scaled_probs)


def pool_mean(pool_losses: jax.Array, axis_name: Optional[str] = None) -> jax.Array:
    """Mean presampling loss; with ``axis_name``, the **global** mean —
    psum of (sum, count) over the data axis (the north-star cross-worker
    importance-statistic exchange, SURVEY.md §2.5)."""
    pool_losses = pool_losses.astype(jnp.float32)
    n = pool_losses.shape[0]
    if axis_name is not None:
        total = jax.lax.psum(jnp.sum(pool_losses), axis_name)
        count = jax.lax.psum(jnp.asarray(n, jnp.float32), axis_name)
        return total / count
    return jnp.mean(pool_losses)


class SelectionResult(NamedTuple):
    ema: EMAState
    selected: jax.Array       # [batch] int32 — positions into the candidate pool
    scaled_probs: jax.Array   # [batch] float32 — p_i·N for the drawn samples
    avg_pool_loss: jax.Array  # [] float32 — mean presampling loss (returned at :117)


def select_from_pool(
    key: jax.Array,
    pool_losses: jax.Array,
    ema: EMAState,
    batch_size: int,
    is_alpha: float = 0.5,
    ema_alpha: float = 0.9,
    axis_name: Optional[str] = None,
) -> SelectionResult:
    """Full selection step given per-candidate losses — the pure core of
    ``update_samples`` (``pytorch_collab.py:108-117``), scoring hoisted out
    of the loop.

    With ``axis_name`` set (inside ``shard_map``), the EMA input is the
    **global** mean pool loss — psum of (sum, count) over the data axis —
    so every worker smooths against the same statistic while keeping its own
    local candidate distribution (the north-star extension).
    """
    pool_losses = pool_losses.astype(jnp.float32)
    n = pool_losses.shape[0]
    mean_loss = pool_mean(pool_losses, axis_name)
    new_ema = ema_update(ema, mean_loss, ema_alpha)
    probs = importance_probs(pool_losses, new_ema.value, is_alpha)
    selected = draw_with_replacement(key, probs, batch_size)
    scaled = probs[selected] * n  # p_i·N (pytorch_collab.py:116)
    return SelectionResult(
        ema=new_ema,
        selected=selected.astype(jnp.int32),
        scaled_probs=scaled,
        avg_pool_loss=mean_loss,
    )


def uniform_selection(
    key: jax.Array, pool_size: int, batch_size: int
) -> Tuple[jax.Array, jax.Array]:
    """Uniform-sampling control arm (the baseline Mercury is compared
    against, BASELINE.md config #1): uniform draws with unit weights —
    ``loss/(N·p) = loss`` when ``p = 1/N``."""
    selected = jax.random.randint(key, (batch_size,), 0, pool_size)
    return selected.astype(jnp.int32), jnp.ones((batch_size,), jnp.float32)
