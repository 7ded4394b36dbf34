"""The four stages of the Mercury step: ingest, score, select, update.

Each stage is a function of its arguments — a :class:`StepContext` (mode,
models, normalisation constants, per-row loss and score) and the arrays it
works on — so a sampler (``train/samplers.py``) composes them without closing
over a ``TrainConfig``. The named scopes opened here (``mercury_pool_ingest``
/ ``mercury_score_forward`` / ``mercury_score_loss`` inside the caller's
``mercury_scoring``; ``mercury_draw``; ``mercury_augmentation`` /
``mercury_input_fuse``; ``mercury_train``, ``mercury_grad_sync``,
``mercury_optimizer``, ``mercury_variance_probe``) are what the per-layer
metrics, ``lint/audit.py`` and ``obs/profile_parse.py`` key on. A model of
per-token logits opens its own inside the forward scopes
(``models/decoder.py``: ``mercury_attention``, ``mercury_moe`` with
``mercury_moe_route``, ``mercury_lm_head``).

**The loss seam** (:func:`row_loss_and_score`, chosen once from the mode) is
what the stages read a forward's outputs through: ``reduce`` what the model
returned to what is carried of it, then ``loss``, ``score`` and ``hits`` a
row. One class label a row: the logits are carried whole. Rows of per-token
labels (``StepMode.token_rows``): the model returns hidden states and its
head, and ``reduce`` is ``sampling.importance.sequence_rows`` (head and token
loss a row at a time): ``[n, 2]``, a row's loss, which is its score too, and
its hit share. Under ``StepMode.use_pallas``, at shapes the kernel takes, a
pass that nothing differentiates (the scoring pass, ``evaluate()``) takes
loss and hits from blocks of the vocabulary in one kernel
(``ops.head_nll_pallas``) and writes no logits; the train pass runs the plain
product and its transpose, one row's logits at a time (the seam's
``custom_vjp``). How many rows of a step took the kernel is counted as the
step is traced (``trace_facts["head_kernel_rows"]``), as is how many
operands of the decoder's attention took the one-pass form
(``trace_facts["rope_kernel_sites"]``).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax

from mercury_tpu.compat import axis_size
from mercury_tpu.data.pipeline import (
    augment_batch,
    augment_normalize,
    normalize_images,
)
from mercury_tpu.models.moe import MOE_LOAD
from mercury_tpu.models.resnet import MOMENT_UNITS
from mercury_tpu.obs.diagnostics import global_grad_norm
from mercury_tpu.parallel import collectives as coll
from mercury_tpu.sampling.importance import (
    ema_update,
    head_takes_kernel,
    per_sample_grad_norm_bound,
    per_sample_loss,
    pool_mean,
    reweighted_loss,
    select_from_pool,
    sequence_rows,
)
from mercury_tpu.train.mode import StepMode
from mercury_tpu.utils.quantize import sparsity, stochastic_quantize
from mercury_tpu.utils.tree import (
    pad_to_chunks,
    sum_sowed_losses,
    tree_flatten_to_vector,
)

RowFn = Callable[[jax.Array, jax.Array], jax.Array]


class RowFns(NamedTuple):
    """The loss seam: how the stages read a forward's outputs, by row."""

    reduce: Callable[[Any, Optional[jax.Array]], jax.Array]
    #                               (model outputs, labels) -> what is carried
    loss: RowFn                   # (carried, labels) -> [n] training loss
    score: RowFn                  # (carried, labels) -> [n] importance score
    hits: RowFn                   # (carried, labels) -> [n] predicted right


class StepContext(NamedTuple):
    """Everything a stage may read besides its array arguments."""

    mode: StepMode
    model: Any
    scoring_model: Any            # lower-precision scorer, or None
    tx: optax.GradientTransformation
    mean: np.ndarray
    std: np.ndarray
    image_shape: Optional[Tuple[int, int, int]]   # flat uint8 rows' (H, W, C)
    rows: RowFns                  # the loss seam (row_loss_and_score)
    param_specs: Any              # per-leaf specs of pinned params, or None
    trace_facts: Optional[Dict[str, int]]


def row_fns(token_rows: bool = False, use_pallas: bool = False,
            label_smoothing: float = 0.0,
            importance_score: str = "loss") -> RowFns:
    """The loss seam of rows of per-token labels (``token_rows``: loss =
    score = the mean over a sequence's positions of the token negative
    log-likelihood, hits the share of positions predicted right; under
    ``use_pallas`` by the kernel over vocabulary blocks where nothing
    differentiates the pass) or of one class label a row (cross-entropy, by
    the Pallas kernel under ``use_pallas``; scored by the loss or by the
    gradient-norm bound)."""
    if token_rows:
        def column(i):
            return lambda carried, labels: carried[:, i]

        return RowFns(functools.partial(sequence_rows, use_kernel=use_pallas),
                      column(0), column(0), column(1))

    if use_pallas:
        from mercury_tpu.ops import per_sample_nll_pallas as loss
    else:
        def loss(logits, labels):
            return per_sample_loss(logits, labels, label_smoothing)

    if importance_score == "grad_norm":
        def score(logits, labels):
            return per_sample_grad_norm_bound(
                logits, labels, label_smoothing)
    else:
        score = loss

    def hits(logits, labels):
        return (jnp.argmax(logits, -1) == labels).astype(jnp.float32)

    return RowFns(lambda outputs, labels: outputs, loss, score, hits)


def row_loss_and_score(mode: StepMode) -> RowFns:
    """The loss seam, built once from the mode and handed to the score and
    update stages. Training losses always use ``loss`` — the IS reweighting
    is score-agnostic, so any scorer stays unbiased."""
    return row_fns(mode.token_rows, mode.use_pallas, mode.label_smoothing,
                   mode.importance_score)


def pool_loss_metric(ctx: StepContext, pool_logits, labels, score_avg):
    """Keep the ``train/pool_loss`` metric a true mean CE even when the
    SCORES are gradient norms (the EMA still smooths the score
    statistic — that's the selection math); comparing pool-loss curves
    across score modes must compare the same quantity."""
    if ctx.mode.importance_score == "grad_norm":
        return pool_mean(ctx.rows.loss(pool_logits, labels),
                         ctx.mode.stat_axis)
    return score_avg


def _note_moment_units(ctx: StepContext, model_state) -> None:
    """A forward that nothing differentiates ran: its closing units
    (each sowed a 1) took their statistic from input moments."""
    if ctx.trace_facts is not None:
        ctx.trace_facts["bn_moment_units"] = len(
            jax.tree_util.tree_leaves(model_state.get(MOMENT_UNITS, {})))


def _note_token_kernels(ctx: StepContext, outputs) -> None:
    """A forward that nothing differentiates is reduced through the loss
    seam (token rows have one such pass a step, the pool's scoring): its
    rows' heads run in the kernel over vocabulary blocks
    (``head_kernel_rows``) or, where that is not asked for or refuses the
    shape, in the plain form (``head_plain_rows``); and the model that ran
    it makes so many operands of its attention in one pass
    (``rope_kernel_sites``) and so many by the plain forms
    (``rope_plain_sites``): ``CausalDecoder.operand_sites``."""
    if ctx.trace_facts is not None and ctx.mode.token_rows:
        hidden = outputs[0]
        kernel = head_takes_kernel(hidden, ctx.mode.use_pallas)
        ctx.trace_facts["head_kernel_rows"] = hidden.shape[0] * kernel
        ctx.trace_facts["head_plain_rows"] = hidden.shape[0] * (not kernel)
        (ctx.trace_facts["rope_kernel_sites"],
         ctx.trace_facts["rope_plain_sites"]) = ctx.model.operand_sites()


def _apply(ctx: StepContext, module, params, batch_stats, images,
           moment_units: bool, labels=None):
    """Train-mode ``module.apply`` with the collections it may write:
    ``(logits, written)``, the logits as the loss seam carries them
    (``ctx.rows.reduce``). ``moment_units``: a forward that nothing
    differentiates lets the closing units take their batch statistic from
    input moments (``models/resnet.py::_closing_unit``)."""
    variables, mutable = {"params": params}, ["losses", MOE_LOAD]
    if batch_stats:
        variables["batch_stats"] = batch_stats
        mutable.append("batch_stats")
    if moment_units:
        mutable.append(MOMENT_UNITS)
    outputs, written = module.apply(variables, images, train=True,
                                    mutable=mutable)
    if moment_units:
        _note_token_kernels(ctx, outputs)
    return ctx.rows.reduce(outputs, labels), written


def moe_load(model_state) -> Dict[str, jax.Array]:
    """What a model of routed experts sowed of its routing
    (``models/decoder.py``): ``{"held_pair_share": ...,
    "load_max_over_mean": ..., "bounded_share": ...}``, with
    ``"bias_moved_share"`` where its router has a selection bias; empty for
    every other model."""
    return {name: value[-1] for name, value in
            model_state.get(MOE_LOAD, {}).items()}


def apply_train(ctx: StepContext, params, batch_stats, images,
                keep_stats: bool, labels=None):
    """Train-mode forward. ``keep_stats=False`` (the scoring pass) uses
    batch statistics for normalization but discards the running-stat
    update — the clean version of the reference's quirk where
    ``update_samples``'s no_grad forwards still mutate BN running means
    (``pytorch_collab.py:101`` runs the net in train mode).

    Returns ``(logits, new_stats, aux, load)`` where ``aux`` is the sum of
    any sowed ``"losses"`` collection entries (the MoE router's
    load-balancing loss; 0.0 for models that sow nothing) and ``load``
    is :func:`moe_load`'s dict."""
    logits, new_model_state = _apply(
        ctx, ctx.model, params, batch_stats, images,
        moment_units=not keep_stats, labels=labels)
    if not keep_stats:
        _note_moment_units(ctx, new_model_state)
    aux = sum_sowed_losses(new_model_state)
    keep = batch_stats and keep_stats
    return (logits, new_model_state["batch_stats"] if keep else batch_stats,
            aux, moe_load(new_model_state))


def augment(mode: StepMode, key, images):
    # mercury_augmentation anchors the ops' op_name metadata for device-time
    # attribution (obs/profile_parse.py); named scopes live in source_info
    # only, so the pretty-printed jaxpr (Layer-2 digests) is unchanged.
    if mode.augmentation == "noniid":
        with jax.named_scope("mercury_augmentation"):
            return augment_batch(key, images, use_cutout=mode.cutout)
    if mode.augmentation == "iid":
        from mercury_tpu.data.transforms import augment_batch_iid

        with jax.named_scope("mercury_augmentation"):
            return augment_batch_iid(key, images)
    if mode.augmentation != "none":
        raise ValueError(f"unknown augmentation {mode.augmentation!r}")
    return images


def ingest(ctx: StepContext, key, raw, out_dtype=None):
    """Raw rows → augmented normalized images: THE ingest boundary of every
    sampler. Which ingest runs is read off the rows (``StepMode.ingest_path``):
    uint8 image rows under the noniid crop/flip take one dense pass over the
    raw bytes (``data.pipeline.augment_normalize``: crop and flip as exact
    selection, normalize last) under ``mercury_augmentation`` —
    ``mercury_input_fuse`` with ``fused_input``; float inputs, ``iid`` and
    cutout keep the ``normalize_images`` + :func:`augment` chain; rows of
    integer ids (a token dataset) are the model's inputs as they are. Both consume
    ``key`` identically and agree bit for bit at f32 (tests/test_ops.py).
    ``out_dtype`` (the bf16 scoring ingest) is the LAST op on both paths."""
    mode = ctx.mode
    if mode.ingest_path(raw.dtype) == "tokens":
        return raw
    if mode.fused_input and raw.dtype != jnp.uint8:
        raise ValueError(
            "fused_input ingests raw uint8 rows (the chain owns "
            f"the /255 dequant); got {raw.dtype}"
        )
    if mode.ingest_path(raw.dtype) == "select":
        with jax.named_scope("mercury_input_fuse" if mode.fused_input
                             else "mercury_augmentation"):
            return augment_normalize(
                key, raw, ctx.mean, ctx.std, image_shape=ctx.image_shape,
                out_dtype=(jnp.float32 if out_dtype is None
                           else out_dtype),
            )
    imgs = augment(mode, key, normalize_images(raw, ctx.mean, ctx.std))
    if out_dtype is not None:
        imgs = imgs.astype(out_dtype)
    return imgs


@jax.named_scope("mercury_draw")
def select(ctx: StepContext, k_sel, pool_losses, ema):
    """EMA update + score→normalize→draw, returning
    ``(selected, scaled_probs, new_ema, avg_pool_loss)`` — shared by the
    inline and pipelined paths (Pallas or jax-native). With
    ``drawn_rows`` it is the ``mercury_draw`` scope: the pool sampler's
    draw, beside ``mercury_scoring`` and not inside it."""
    mode = ctx.mode
    if mode.use_pallas:
        from mercury_tpu.ops import score_and_draw_pallas

        avg = pool_mean(pool_losses, mode.stat_axis)
        new_ema = ema_update(ema, avg, mode.ema_alpha)
        _, selected, scaled = score_and_draw_pallas(
            k_sel, pool_losses, new_ema.value, mode.batch_size,
            mode.is_alpha,
        )
        return selected, scaled, new_ema, avg
    sel = select_from_pool(
        k_sel, pool_losses, ema, mode.batch_size,
        is_alpha=mode.is_alpha, ema_alpha=mode.ema_alpha,
        axis_name=mode.stat_axis,
    )
    return sel.selected, sel.scaled_probs, sel.ema, sel.avg_pool_loss


@jax.named_scope("mercury_draw")
def drawn_rows(selected, images, labels):
    """The drawn rows of the scored pool. Images are gathered as
    ``[n, H, W*C]`` rows — the dense form the ingest's selection
    leaves them in — so the row gather reads the pool as it was
    written instead of a relayout with the channels minor."""
    if images.ndim == 4:
        n, h, w, c = images.shape
        drawn = images.reshape(n, h, w * c)[selected]
        return drawn.reshape(-1, h, w, c), labels[selected]
    return images[selected], labels[selected]


def score_rows(ctx: StepContext, state, raw, labs, ka, reuse_images=True):
    """Augment → inference-mode scoring forward over already-gathered rows —
    the pool-scoring core under :func:`score_slots` (a host stream's rows
    arrive pre-gathered from the host pipeline). Callers wrap the call in the
    ``mercury_scoring`` named scope the jaxpr auditor anchors on (one scope per
    call site — nesting would rename the anchor); the three scopes opened here
    split it by layer, for the device trace: ``mercury_pool_ingest`` (with the
    caller's gather), ``mercury_score_forward``, ``mercury_score_loss``.
    ``reuse_images=False`` marks scorer-only sites (the returned images are
    discarded, e.g. scoretable refresh windows): with
    ``scoring_dtype="bfloat16"`` those ingest straight to bf16 — uint8 → bf16
    score, no f32 activation round trip. Returns ``(imgs, pool_logits,
    scores)``."""
    scoring_model, scoring_bf16 = ctx.scoring_model, ctx.mode.scoring_bf16
    scorer_only = not reuse_images and scoring_bf16
    with jax.named_scope("mercury_pool_ingest"):
        imgs = ingest(
            ctx, ka, raw, out_dtype=jnp.bfloat16 if scorer_only else None
        )
    if scoring_model is None:
        with jax.named_scope("mercury_score_forward"):
            pool_logits, _, _, _ = apply_train(
                ctx, state.params, state.batch_stats, imgs, False, labs
            )
    else:
        # Same params, lower-precision compute (scoring_dtype) — scores only
        # rank candidates, and the reweight divides by the realized probs, so
        # this stays unbiased. The forward's input is pre-cast to the scoring
        # dtype (a no-op when the ingest already emitted bf16) so the
        # activations never materialize at f32; the returned imgs keep the
        # training precision when the caller reuses them.
        with jax.named_scope("mercury_score_forward"):
            s_in = imgs.astype(jnp.bfloat16) if scoring_bf16 else imgs
            pool_logits, model_state = _apply(
                ctx, scoring_model, state.params, state.batch_stats, s_in,
                True, labs)
            pool_logits = pool_logits.astype(jnp.float32)
        _note_moment_units(ctx, model_state)
    with jax.named_scope("mercury_score_loss"):
        scores = ctx.rows.score(pool_logits, labs)
    return imgs, pool_logits, scores


def probe_var_ratio(ctx: StepContext, state, sel_images, sel_labels,
                    scaled_probs):
    """Grad-variance probe (``sampler_dist/var_ratio``, the 1803.00942 gate
    signal, observe-only): every ``probe_every``-th step, ONE extra
    scoring-model pass over the just-trained microbatch yields per-example
    grad-norm bounds ``g_i``; with the batch drawn from ``p`` and
    ``scaled_probs_i = N·p_i``, ``pool_mean((g/(N·p))²)`` estimates the IS
    gradient estimator's second moment and ``pool_mean(g²/(N·p))`` the uniform
    one (same unbiased reweighting as the loss). Their ratio follows
    ``benchmarks/grad_variance.py``'s convention: < 1 ⇔ IS is winning. Uses
    PRE-update params (``state`` is the input state) — the distribution the
    draw actually came from. Off-cadence steps return the -1.0 sentinel every
    consumer ignores."""
    mode = ctx.mode
    scoring_model, scoring_bf16 = ctx.scoring_model, mode.scoring_bf16

    def run(_):
        with jax.named_scope("mercury_variance_probe"):
            if scoring_model is None:
                logits, _, _, _ = apply_train(
                    ctx, state.params, state.batch_stats, sel_images, False
                )
            else:
                s_in = (sel_images.astype(jnp.bfloat16)
                        if scoring_bf16 else sel_images)
                logits, _ = _apply(ctx, scoring_model, state.params,
                                   state.batch_stats, s_in, False)
            g = per_sample_grad_norm_bound(
                logits.astype(jnp.float32), sel_labels,
                mode.label_smoothing,
            )
        sp = jnp.maximum(scaled_probs.astype(jnp.float32), 1e-30)
        # Pool the moments across workers BEFORE the ratio (a pmean
        # of per-worker ratios is not the global ratio);
        # obs/sampler_health.variance_probe_ratio is the single-host
        # reference the tests cross-validate against.
        m_is = pool_mean(jnp.square(g / sp), mode.stat_axis)
        m_unif = pool_mean(jnp.square(g) / sp, mode.stat_axis)
        return m_is / jnp.maximum(m_unif, 1e-30)

    # Cadence on the POST-increment step: metric records carry state.step + 1,
    # so this makes the probe land on the records whose step is a multiple of
    # probe_every — aligning with log_every (set probe_every to a multiple of
    # it), instead of emitting the sentinel one record off forever.
    return lax.cond(
        (state.step + 1) % mode.probe_every == 0, run,
        lambda _: jnp.full((), -1.0, jnp.float32), operand=None,
    )


def train_update(ctx: StepContext, state, rng, sel_images, sel_labels,
                 scaled_probs):
    """The update stage, shared verbatim by both drivers: reweighted fwd/bwd,
    optional gradient compression, the gradient collective (plain allreduce or
    ZeRO-1 reduce-scatter/all-gather, int8 wire variants), optimizer apply, and
    the BN-stat sync. Returns a dict with the new model/optimizer state, the
    train logits (the scoretable write-back re-scores them for free), and the
    replicated loss/acc reductions."""
    mode = ctx.mode
    axis = mode.axis
    int8_allreduce = mode.compression == "int8"
    # fold_in (not a 9-way split) so the eight existing streams — and
    # every recorded seeded trajectory — are unchanged by the
    # compression feature's existence.
    k_quant = jax.random.fold_in(rng, 0x71)  # graftlint: disable=GL101 -- deliberate sentinel stream: fold_in(rng, 0x71) is disjoint from the 8-way split, preserving recorded trajectories

    # --- train forward/backward with the unbiased IS reweighting
    # mean(loss_i/(N·p_i)) (:132-148) --------------------------------
    def loss_fn(params):
        logits, new_bs, aux, load = apply_train(
            ctx, params, state.batch_stats, sel_images, True, sel_labels
        )
        losses = ctx.rows.loss(logits, sel_labels)
        total = reweighted_loss(losses, scaled_probs)
        if mode.moe_aux_weight is not None:
            # Switch load-balancing term (sowed by the MoE blocks).
            total = total + mode.moe_aux_weight * aux
        return total, (logits, new_bs, aux, load)

    # One scope for both halves: jax marks the backward's ops itself
    # (``transpose(jvp(...))`` in the op's path), which is what the
    # device trace splits forward from backward by.
    with jax.named_scope("mercury_train"):
        (loss, (logits, new_batch_stats, moe_aux, load)), grads = (
            jax.value_and_grad(loss_fn, has_aux=True)(state.params))

    # --- optional quantization: each worker stochastically quantizes its
    # local gradient (independent keys); the worker mean stays unbiased — the
    # reference's dead-code experiment, live (util.py:65-70; "sparse rate",
    # pytorch_collab.py:184). Estimator semantics only: the psum below still
    # moves dense tensors (see TrainConfig.grad_compression).
    sparse_rate = jnp.ones((), jnp.float32)
    if mode.compression == "stochastic":
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        qkeys = jax.random.split(k_quant, len(leaves))
        leaves = [stochastic_quantize(k, g) for k, g in zip(qkeys, leaves)]
        grads = jax.tree_util.tree_unflatten(treedef, leaves)
        total = float(sum(g.size for g in leaves))
        sparse_rate = sum(sparsity(g) * (g.size / total) for g in leaves)

    loss_mean = lax.pmean(loss, axis)
    correct = lax.psum(
        jnp.sum(ctx.rows.hits(logits, sel_labels)), axis
    )
    count = lax.psum(jnp.asarray(mode.batch_size, jnp.float32), axis)

    # mercury_grad_sync anchors the jaxpr auditor's per-region collective
    # budgets (lint/audit.py), mercury_optimizer the profile's attribution
    # of the update (obs/profile_parse.py); both digest-invisible. With
    # grad_compression="int8" BOTH wire phases move int8 payloads
    # (per-chunk scales, stochastic rounding — unbiased), 4× fewer bytes
    # each (parallel/collectives.py), keyed by the sentinel stream 0x72.
    grad_norm = None
    if mode.zero:
        # --- ZeRO-1: reduce-scatter the flattened gradient (each worker
        # receives the mean of its 1/W chunk — reduce-scatter +
        # all-gather IS the ring allreduce, util.py:280-324, so the
        # collective volume matches average_gradients :236-249), update
        # only that chunk's optimizer state, all-gather the updates.
        w = axis_size(axis)
        opt_chunk = jax.tree_util.tree_map(lambda x: x[0], state.opt_state)
        gvec, unravel = tree_flatten_to_vector(grads)
        if int8_allreduce:
            kz = jax.random.fold_in(rng, 0x72)  # graftlint: disable=GL101 -- deliberate sentinel stream 0x72 for int8 grad compression, disjoint from the 8-way split and 0x71
            kz1, kz2 = jax.random.split(kz)
            with jax.named_scope("mercury_grad_sync"):
                gchunk = coll.compressed_psum_scatter_mean(
                    pad_to_chunks(gvec, w), axis, kz1
                )
        else:
            with jax.named_scope("mercury_grad_sync"):
                gchunk = lax.psum_scatter(pad_to_chunks(gvec, w), axis) / w
        if mode.telemetry:
            # The chunks partition the full mean-gradient vector (the
            # pad is zeros), so psum of the per-chunk square-sums is the
            # exact global norm² — one scalar on the wire.
            grad_norm = jnp.sqrt(lax.psum(
                jnp.sum(jnp.square(gchunk.astype(jnp.float32))), axis
            ))
        pvec, _ = tree_flatten_to_vector(state.params)
        pchunk = pad_to_chunks(pvec, w)[lax.axis_index(axis)]
        with jax.named_scope("mercury_optimizer"):
            updates_chunk, new_opt_chunk = ctx.tx.update(
                gchunk, opt_chunk, pchunk)
        with jax.named_scope("mercury_grad_sync"):
            if int8_allreduce:
                uvec = coll.compressed_all_gather(updates_chunk, axis, kz2)
            else:
                uvec = lax.all_gather(updates_chunk, axis, tiled=True)
            uvec = uvec[: gvec.size]
        with jax.named_scope("mercury_optimizer"):
            new_params = optax.apply_updates(state.params, unravel(uvec))
        new_opt_state = jax.tree_util.tree_map(
            lambda x: x[None], new_opt_chunk
        )
    else:
        # --- gradient allreduce (≡ average_gradients, :236-249) in-graph
        with jax.named_scope("mercury_grad_sync"):
            if not int8_allreduce:
                grads = coll.allreduce_mean_tree(grads, axis)
            elif mode.tp_active:
                # Per-leaf, shape-preserving compression: the wire
                # chunking avoids the dims TP/FSDP shard, so the grads
                # stay sharded through both phases.
                grads = coll.compressed_pmean_tree_sharded(
                    grads, axis, axis_size(axis),
                    # graftlint: disable=GL101 -- same deliberate 0x72 sentinel stream as the ZeRO branch (mutually exclusive at trace time)
                    jax.random.fold_in(rng, 0x72),
                    specs=ctx.param_specs,
                )
            else:
                grads = coll.compressed_allreduce_mean_tree(
                    grads, axis, axis_size(axis),
                    # graftlint: disable=GL101 -- same deliberate 0x72 sentinel stream as the ZeRO branch (mutually exclusive at trace time)
                    jax.random.fold_in(rng, 0x72),
                )
        if mode.telemetry:
            # Post-allreduce: already the worker-mean gradient, so the
            # norm is identical on every worker (replicated output).
            grad_norm = global_grad_norm(grads)
        with jax.named_scope("mercury_optimizer"):
            updates, new_opt_state = ctx.tx.update(
                grads, state.opt_state, state.params
            )
            new_params = optax.apply_updates(state.params, updates)

    # Keep replicated BN stats replicated: under synced BN they already
    # agree; under local BN we average the running stats across workers
    # (normalization still used local batch stats this step).
    if new_batch_stats:
        new_batch_stats = coll.allreduce_mean_tree(new_batch_stats, axis)

    return dict(
        loss_mean=loss_mean, acc=correct / count, logits=logits,
        moe_aux=moe_aux, moe_load=load, sparse_rate=sparse_rate,
        grad_norm=grad_norm,
        new_params=new_params, new_batch_stats=new_batch_stats,
        new_opt_state=new_opt_state,
    )
