"""The fused SPMD Mercury train step.

One jitted ``shard_map`` program per step does everything the reference's hot
loop does across Python/gloo boundaries (``pytorch_collab.py:119-199`` —
SURVEY.md §3.2): pull presample candidates, score them (10 inference
forwards in the reference — here **one batched forward** over the whole
pool), EMA-smooth, draw the train batch with replacement, compute the
unbiased reweighted loss, backprop, allreduce gradients, and apply the
optimizer — with the collectives (gradient pmean ≡ ``average_gradients``
``:236-249``, importance-stat psum = north-star extension) fused in-graph by
XLA. The compute/communication overlap the reference only gestures at in
commented-out thread code (``:154-156``) falls out for free: XLA schedules
the ICI collectives asynchronously against independent compute.

Per-worker divergence (the whole point of Mercury on non-IID data: each
worker scores its *own* Dirichlet shard) lives on the mesh's data axis:
shard index rows, presample streams, EMAs, and RNG keys are ``[W]``-stacked
and sharded; params/optimizer state are replicated.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from mercury_tpu.config import TrainConfig
from mercury_tpu.data.pipeline import (
    ShardStream,
    augment_batch,
    augment_normalize,
    next_pool,
    normalize_images,
)
from mercury_tpu.models.resnet import MOMENT_UNITS
from mercury_tpu.obs.diagnostics import (
    clip_fraction,
    ema_drift,
    ess_fraction,
    global_grad_norm,
    table_age_summary,
)
from mercury_tpu.obs.sampler_health import (
    SCORE_HIST_HI,
    SCORE_HIST_LO,
    WEIGHT_HIST_HI,
    WEIGHT_HIST_LO,
    hist_keys,
    log_bin_histogram,
)
from mercury_tpu.parallel.collectives import allreduce_mean_tree
from mercury_tpu.sampling.importance import (
    EMAState,
    draw_with_replacement,
    ema_update,
    importance_probs,
    per_sample_grad_norm_bound,
    per_sample_loss,
    pool_mean,
    reweighted_loss,
    select_from_pool,
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
from mercury_tpu.train.state import (
    CachedPool,
    MercuryState,
    PendingBatch,
    PendingSelection,
)

from mercury_tpu.compat import axis_size, shard_map


def _state_specs(
    axis: str, has_groupwise: bool = False, has_pending: bool = False,
    zero_sharding: bool = False, has_cached_pool: bool = False,
    has_scoretable: bool = False, has_pending_sel: bool = False,
    has_sel_counts: bool = False,
) -> MercuryState:
    """PartitionSpec pytree-prefix for :class:`MercuryState`: model state
    replicated, per-worker sampler state sharded along the data axis;
    optimizer state sharded too under ZeRO-1 (each worker owns its chunk's
    moments)."""
    return MercuryState(
        step=P(),
        params=P(),
        batch_stats=P(),
        opt_state=P(axis) if zero_sharding else P(),
        ema=EMAState(value=P(axis), count=P(axis)),
        stream=ShardStream(perm=P(axis), cursor=P(axis)),
        rng=P(axis),
        groupwise=P(axis) if has_groupwise else None,
        pending=P(axis) if has_pending else None,
        cached_pool=P(axis) if has_cached_pool else None,
        scoretable=P(axis) if has_scoretable else None,
        pending_sel=P(axis) if has_pending_sel else None,
        sel_counts=P(axis) if has_sel_counts else None,
    )


def mercury_state_out_shardings(
    mesh: Mesh, axis: str, params_sh, opt_sh,
    has_groupwise: bool = False, has_pending: bool = False,
    has_cached_pool: bool = False, has_scoretable: bool = False,
    has_pending_sel: bool = False, has_sel_counts: bool = False,
) -> Tuple[MercuryState, Any]:
    """Output shardings pinning the post-step state layout under partial-
    auto meshes (dp×tp): without this, GSPMD is free to re-replicate the
    tensor-parallel params on every step's output, silently discarding the
    TP memory/compute split. ``params_sh``/``opt_sh`` are the committed
    input sharding trees; everything else follows :func:`_state_specs`."""
    from jax.sharding import NamedSharding

    def n(spec):
        return NamedSharding(mesh, spec)

    state_sh = MercuryState(
        step=n(P()),
        params=params_sh,
        batch_stats=n(P()),
        opt_state=opt_sh,
        ema=EMAState(value=n(P(axis)), count=n(P(axis))),
        stream=ShardStream(perm=n(P(axis)), cursor=n(P(axis))),
        rng=n(P(axis)),
        groupwise=n(P(axis)) if has_groupwise else None,
        pending=n(P(axis)) if has_pending else None,
        cached_pool=n(P(axis)) if has_cached_pool else None,
        scoretable=n(P(axis)) if has_scoretable else None,
        pending_sel=n(P(axis)) if has_pending_sel else None,
        sel_counts=n(P(axis)) if has_sel_counts else None,
    )
    return state_sh, n(P())


def ingest_path(config: TrainConfig, dtype) -> str:
    """Which ingest :func:`make_train_step` builds for rows of ``dtype``:
    ``"select"`` — uint8 image rows under the noniid crop/flip, one dense
    pass over the raw bytes (``data.pipeline.select_crop_flip``) — or
    ``"chain"`` — ``normalize_images`` then the augmentation, for float
    inputs, ``augmentation="iid"``/``"none"`` and cutout. Decided at
    trace time from what the step sees; no config field picks it."""
    select = (jnp.dtype(dtype) == jnp.uint8
              and config.augmentation == "noniid" and not config.cutout)
    return "select" if select else "chain"


def make_train_step(
    model,
    tx: optax.GradientTransformation,
    config: TrainConfig,
    mesh: Mesh,
    mean: np.ndarray,
    std: np.ndarray,
    scan_steps: int = 1,
    state_out_shardings=None,
    scoring_model=None,
    io_constraints: bool = True,
    image_shape: Optional[Tuple[int, int, int]] = None,
    trace_facts: Optional[Dict[str, int]] = None,
) -> Callable[..., Tuple[MercuryState, Dict[str, jax.Array]]]:
    """Build the jitted train step.

    Returns ``step_fn(state, x_train, y_train, shard_indices) →
    (new_state, metrics)`` where ``x_train``/``y_train`` are the full
    device-resident train arrays (replicated) and ``shard_indices`` is the
    ``[W, L]`` per-worker index matrix (sharded over the data axis).

    uint8 image rows may arrive flat — ``x_train`` as ``[N, H*W*C]`` (or
    ``[W, L, H*W*C]`` sharded, ``[W, S, H*W*C]`` streamed) with
    ``image_shape=(H, W, C)`` — which is what ``Trainer`` hands the step
    on the selection ingest (:func:`ingest_path`): the pool's gather is
    then a dense row gather and the resident set is never relaid out.

    With ``scan_steps > 1`` the returned function advances ``scan_steps``
    steps per call — the step body wrapped in ``lax.scan`` inside the same
    ``shard_map`` program, so one host dispatch covers the whole chunk and
    each metric comes back as a ``[scan_steps]`` array.

    ``scoring_model`` (optional) is a second module with identical params
    structure but a different compute dtype (``config.scoring_dtype``);
    when given, the candidate-scoring forward runs through it instead of
    ``model`` — the IS reweight divides by the realized probabilities, so
    a lower-precision scorer reranks candidates without biasing the loss.

    ``trace_facts`` (optional) is filled as the step is traced with what
    only the trace knows: ``bn_moment_units``, how many conv+BN units of
    the scoring forward take their batch statistic from their input's
    moments (``models/resnet.py::_closing_unit``; 0 where the model has
    none). ``Trainer`` reports it as the instant ``trainer/bn_moment_units``.

    SHARDING CONTRACT (enforced by graftlint Layer 3, ``lint/
    sharding.py`` — see docs/LINT.md): the step's inputs are pinned with
    ``with_sharding_constraint`` before they enter the shard_map —
    ``x_train``/``y_train`` to the data spec (``P(axis)`` when
    ``data_placement`` shards them, else replicated ``P()``) and
    ``shard_indices`` to ``P(axis)`` — so a caller handing in foreign
    layouts pays one visible reshard here instead of GSPMD quietly
    rewriting layouts inside the step. ``io_constraints=False`` drops
    the pins (the per-plan ``sharding_constraints`` budget in
    ``lint/shard_budgets.json`` then fails — that is the point).
    """
    axis = config.mesh_axis
    use_is = config.use_importance_sampling
    pool_size = config.candidate_pool_size if use_is else config.batch_size
    batch_size = config.batch_size
    stat_axis = axis if (use_is and config.sync_importance_stats) else None
    # In-graph telemetry is gated at TRACE time: with telemetry=False every
    # diagnostic below is simply never traced, so the compiled program is
    # identical to the seed step (no reliance on XLA DCE — verified by
    # benchmarks/telemetry_overhead.py comparing jaxprs).
    telemetry = bool(config.telemetry)

    # Mesh axes beyond the data axis (e.g. the "model" axis of a dp×tp
    # mesh) are left to GSPMD: the step is manual-SPMD over `axis` only,
    # and XLA partitions the forwards/backwards over the auto axes per the
    # params' committed shardings (transformer_tp_shardings). This is how
    # the flagship IS algorithm composes with tensor parallelism — the
    # scoring forward, draw, reweighted backward, and stat psum all run
    # TP-sharded without any change to the body below.
    auto_axes = [a for a in mesh.axis_names if a != axis]
    tp_active = any(mesh.shape[a] > 1 for a in auto_axes)
    if tp_active and config.zero_sharding:
        raise ValueError(
            "zero_sharding flattens params to a vector, which would force "
            "an all-gather of the sharded params; use fsdp_parallel or "
            "plain allreduce when a second mesh axis shards the params"
        )
    # int8 wire compression composes with TP/FSDP via the per-leaf path:
    # the flattened collective would force an all-gather of the sharded
    # leaves, so under an active auto axis each leaf is compressed in its
    # natural shape, wire-chunked along a dim the auto axes don't claim
    # (parallel/collectives.py compressed_pmean_tree_sharded — closes the
    # round-3 int8×TP rejection).
    sharded_param_specs = None
    if state_out_shardings is not None:
        sharded_param_specs = jax.tree_util.tree_map(
            lambda s: s.spec, state_out_shardings[0].params
        )

    use_pallas = config.use_pallas
    if use_pallas is None:  # auto: Mosaic kernels on real TPU only
        from mercury_tpu.ops import on_tpu

        use_pallas = on_tpu()
    if use_pallas and config.label_smoothing != 0.0:
        raise ValueError("use_pallas requires label_smoothing == 0")
    if config.sampler not in ("pool", "groupwise", "scoretable"):
        raise ValueError(f"unknown sampler {config.sampler!r}")
    if config.grad_compression not in ("none", "stochastic", "int8"):
        raise ValueError(f"unknown grad_compression {config.grad_compression!r}")
    compress_grads = config.grad_compression == "stochastic"
    int8_allreduce = config.grad_compression == "int8"
    if tp_active and int8_allreduce and sharded_param_specs is None:
        raise ValueError(
            "grad_compression='int8' under an active auto mesh axis needs "
            "state_out_shardings (per-leaf PartitionSpecs): without them "
            "the wire chunker picks the largest dim, which may be the "
            "GSPMD-sharded one — silently forcing the all-gather the "
            "per-leaf path exists to avoid; pass state_out_shardings "
            "(Trainer does) or drop grad_compression"
        )
    use_groupwise = use_is and config.sampler == "groupwise"
    use_scoretable = use_is and config.sampler == "scoretable"
    pipelined = use_is and config.pipelined_scoring
    zero = config.zero_sharding
    if pipelined and config.sampler != "pool":
        # Measured justification for this cut (round-3 ladder,
        # BASELINE.md): pipelined overlap recovered ~2% on chip even for
        # the pool sampler — the scoring cost is FLOPs, not exposed
        # latency — so a groupwise/scoretable pipeline's ceiling is the
        # same ~2%, and those samplers already shrink the scoring cost.
        raise ValueError(
            "pipelined_scoring requires sampler='pool', got "
            f"{config.sampler!r}"
        )
    cadence = int(config.score_refresh_every)
    if cadence < 1:
        raise ValueError(
            f"score_refresh_every must be >= 1, got {cadence}"
        )
    use_cadence = use_is and cadence > 1
    if use_cadence and config.sampler != "pool":
        raise ValueError(
            "score_refresh_every > 1 requires sampler='pool' (the "
            f"{config.sampler!r} sampler already persists scores across "
            "steps)"
        )
    if use_cadence and pipelined:
        raise ValueError(
            "score_refresh_every > 1 does not compose with "
            "pipelined_scoring: cadence already removes the per-step "
            "scoring forward the pipeline overlaps"
        )
    refresh_size = int(config.refresh_size)
    if use_scoretable:
        if refresh_size < 1:
            raise ValueError(
                f"refresh_size must be >= 1, got {refresh_size}"
            )
        if not 0.0 <= config.table_decay <= 1.0:
            raise ValueError(
                f"table_decay must be in [0, 1], got {config.table_decay}"
            )
    if config.scoring_dtype is not None and not use_is:
        raise ValueError(
            "scoring_dtype only affects the candidate-scoring forward; "
            "set use_importance_sampling=True (or drop scoring_dtype)"
        )
    if config.refresh_mode not in ("sync", "async"):
        raise ValueError(f"unknown refresh_mode {config.refresh_mode!r}")
    # Async refresh: the round-robin scoring forward moves OFF the step and
    # onto the host scorer fleet (sampling/scorer_fleet.py) — the traced
    # branches below simply omit it, so the compiled hot program carries
    # zero scoring FLOPs/collectives (the graftlint `async` plan budgets
    # pin this down).
    async_refresh = use_scoretable and config.refresh_mode == "async"
    if config.refresh_mode == "async" and not use_scoretable:
        raise ValueError(
            "refresh_mode='async' requires sampler='scoretable' with "
            "use_importance_sampling=True (the scorer fleet refreshes the "
            "persistent score table; the pool/groupwise samplers have no "
            f"table to stream into) — got sampler={config.sampler!r}, "
            f"use_importance_sampling={use_is}"
        )
    if async_refresh:
        if int(config.scorer_workers) < 1:
            raise ValueError(
                f"scorer_workers must be >= 1, got {config.scorer_workers}"
            )
        if int(config.snapshot_every) < 1:
            raise ValueError(
                f"snapshot_every must be >= 1, got {config.snapshot_every}"
            )
        if float(config.scorer_throttle_s) < 0:
            raise ValueError(
                "scorer_throttle_s must be >= 0, got "
                f"{config.scorer_throttle_s}"
            )
    if config.scorer_backend not in ("host", "device"):
        raise ValueError(
            "scorer_backend must be 'host' or 'device', got "
            f"{config.scorer_backend!r}"
        )
    if not async_refresh:
        # Backend/tenancy knobs only mean something under the async
        # scorer — a silently-ignored scorer_backend='device' on a sync
        # run would read as the device scorer being in play.
        if config.scorer_backend != "host":
            raise ValueError(
                "scorer_backend='device' requires refresh_mode='async' "
                "with sampler='scoretable' (the device scorer program "
                "feeds the async chunk queue; the sync path scores "
                "in-graph) — got refresh_mode="
                f"{config.refresh_mode!r}, sampler={config.sampler!r}"
            )
        if int(config.scorer_tenants) != 1:
            raise ValueError(
                "scorer_tenants requires refresh_mode='async' with "
                "sampler='scoretable' (tenancy is a property of the "
                f"scorer service) — got scorer_tenants="
                f"{config.scorer_tenants}"
            )

    if config.importance_score not in ("loss", "grad_norm"):
        raise ValueError(
            f"unknown importance_score {config.importance_score!r}"
        )
    # Selection-count ledger (obs/sampler_health.py): rides alongside the
    # scoretable, trace-gated with the rest of the telemetry — with
    # telemetry=False the state carries no ledger and the program is the
    # seed's, byte-identical (Layer-2/3 digest-enforced).
    use_ledger = use_scoretable and telemetry
    probe_every = int(config.variance_probe_every)
    if probe_every < 0:
        raise ValueError(
            f"variance_probe_every must be >= 0, got {probe_every}"
        )
    # Grad-variance probe (sampler_dist/var_ratio): one extra
    # scoring-model pass over the trained microbatch every probe_every
    # steps. Trace-gated like the ledger; meaningless without IS weights.
    use_probe = telemetry and probe_every > 0 and use_is
    if use_probe and scan_steps > 1:
        raise ValueError(
            "variance_probe_every > 0 requires scan_steps == 1: scanned "
            "chunks mean their metrics, which would blend the probe's "
            "-1.0 off-step sentinel into the ratio"
        )
    if config.data_placement not in ("replicated", "sharded", "host_stream"):
        raise ValueError(
            f"unknown data_placement {config.data_placement!r}"
        )
    # "sharded": x_train/y_train arrive as [W, L, ...] per-worker shard
    # rows sharded P(axis) — each device holds only its own worker's
    # samples, and gathers are shard-local (slots index the row directly).
    data_sharded = config.data_placement == "sharded"
    # "host_stream": the pixel arrays never enter the graph. The step's
    # second input is the [W, S, ...] uint8 rows the host pipeline
    # pre-gathered for THIS step (selected `prefetch_depth` steps ago by
    # the step itself), and the step emits the NEXT selection's global
    # indices as a third, non-donated output (out_specs P(axis)) for the
    # host to gather while the intervening steps run. See hs_body below
    # and data/stream.py.
    host_stream = config.data_placement == "host_stream"
    depth = int(config.prefetch_depth)
    if host_stream:
        if depth < 1:
            raise ValueError(f"prefetch_depth must be >= 1, got {depth}")
        if pipelined:
            raise ValueError(
                "host_stream already pipelines selection (the lookahead "
                "draw); pipelined_scoring does not compose with it"
            )
        if use_cadence:
            raise ValueError(
                "host_stream requires score_refresh_every == 1: the "
                "cached-pool cadence redraws from slots whose rows were "
                "never streamed"
            )
        if use_groupwise:
            raise ValueError(
                "host_stream supports sampler='pool'|'scoretable' (and "
                "the uniform baseline); the groupwise window draw depends "
                "on post-update scores and cannot be drawn ahead"
            )
        if scan_steps > 1:
            raise ValueError(
                "host_stream requires scan_steps == 1: each step consumes "
                "one host-prefetched batch and emits the next indices — a "
                "scanned chunk would need the streamed batches mid-graph"
            )
        if auto_axes:
            raise ValueError(
                "host_stream requires a data-only mesh (no tensor/fsdp "
                "axis); drop tensor_parallel/fsdp_parallel"
            )
    fused_input = bool(config.fused_input)
    if fused_input:
        if config.augmentation != "noniid":
            raise ValueError(
                "fused_input fuses the noniid crop/flip augmentation into "
                "the uint8 ingest chain (data.pipeline.augment_normalize); "
                f"set augmentation='noniid' (got {config.augmentation!r})"
            )
        if config.cutout:
            raise ValueError(
                "fused_input does not fuse cutout; set cutout=False"
            )
    # scoring_dtype="bfloat16" end-to-end: scorer-only ingest sites (rows
    # whose images are never reused for training) emit bf16 directly —
    # with fused_input the kernel's final cast, so the scoring forward is
    # bf16 from uint8 to score with no f32 activation round trip.
    scoring_bf16 = config.scoring_dtype == "bfloat16"
    # Streamed rows per worker per step: the candidate pool for the pool
    # sampler (selection happens in-step on the streamed rows), the
    # refresh window + the pre-drawn train batch for the scoretable one —
    # train rows only under async refresh (the fleet scores its own
    # windows host-side, so no refresh rows ever cross the stream).
    emit_size = (batch_size if async_refresh
                 else (refresh_size + batch_size) if use_scoretable
                 else pool_size)

    def _loss_per_sample(logits, labels):
        if use_pallas:
            from mercury_tpu.ops import per_sample_nll_pallas

            return per_sample_nll_pallas(logits, labels)
        return per_sample_loss(logits, labels, config.label_smoothing)

    def _score_per_sample(logits, labels):
        """Candidate scorer: what the pool forward's logits become scores
        by. Training losses always use ``_loss_per_sample`` — the IS
        reweighting is score-agnostic, so any scorer stays unbiased."""
        if config.importance_score == "grad_norm":
            return per_sample_grad_norm_bound(
                logits, labels, config.label_smoothing
            )
        return _loss_per_sample(logits, labels)

    def _pool_loss_metric(pool_logits, labels, score_avg):
        """Keep the ``train/pool_loss`` metric a true mean CE even when the
        SCORES are gradient norms (the EMA still smooths the score
        statistic — that's the selection math); comparing pool-loss curves
        across score modes must compare the same quantity."""
        if config.importance_score == "grad_norm":
            return pool_mean(_loss_per_sample(pool_logits, labels), stat_axis)
        return score_avg

    def _note_moment_units(model_state):
        """A forward that nothing differentiates ran: its closing units
        (each sowed a 1) took their statistic from input moments."""
        if trace_facts is not None:
            trace_facts["bn_moment_units"] = len(
                jax.tree_util.tree_leaves(model_state.get(MOMENT_UNITS, {})))

    def _apply_train(params, batch_stats, images, keep_stats: bool):
        """Train-mode forward. ``keep_stats=False`` (the scoring pass) uses
        batch statistics for normalization but discards the running-stat
        update — the clean version of the reference's quirk where
        ``update_samples``'s no_grad forwards still mutate BN running means
        (``pytorch_collab.py:101`` runs the net in train mode).

        Returns ``(logits, new_stats, aux)`` where ``aux`` is the sum of
        any sowed ``"losses"`` collection entries (the MoE router's
        load-balancing loss; 0.0 for models that sow nothing)."""
        variables = {"params": params}
        mutable = ["losses"]
        if batch_stats:
            variables["batch_stats"] = batch_stats
            mutable = ["batch_stats", "losses"]
        if not keep_stats:
            mutable.append(MOMENT_UNITS)
        logits, new_model_state = model.apply(
            variables, images, train=True, mutable=mutable
        )
        if not keep_stats:
            _note_moment_units(new_model_state)
        from mercury_tpu.utils.tree import sum_sowed_losses

        aux = sum_sowed_losses(new_model_state)
        if batch_stats and keep_stats:
            new_stats = new_model_state["batch_stats"]
        else:
            new_stats = batch_stats
        return logits, new_stats, aux

    def _augment(key, images):
        # mercury_augmentation anchors the augmentation ops' op_name
        # metadata for offline device-time attribution
        # (obs/profile_parse.py). Named scopes live in source_info only —
        # the pretty-printed jaxpr (and so Layer-2 digests) is unchanged.
        if config.augmentation == "noniid":
            with jax.named_scope("mercury_augmentation"):
                return augment_batch(key, images, use_cutout=config.cutout)
        if config.augmentation == "iid":
            from mercury_tpu.data.transforms import augment_batch_iid

            with jax.named_scope("mercury_augmentation"):
                return augment_batch_iid(key, images)
        if config.augmentation != "none":
            raise ValueError(f"unknown augmentation {config.augmentation!r}")
        return images

    def _ingest(key, raw, out_dtype=None):
        """Raw rows → augmented normalized images: THE ingest boundary —
        every sampler path funnels its pixel rows through here. Which
        ingest runs is read off the rows (:func:`ingest_path`): uint8
        image rows under the noniid crop/flip take one dense pass over the
        raw bytes (``data.pipeline.augment_normalize``: crop and flip as
        exact selection, normalize last); float inputs, ``iid`` and cutout
        keep the ``normalize_images`` + ``_augment`` chain. Both consume
        ``key`` identically and agree bit for bit at f32 (test-enforced,
        tests/test_ops.py). The selection's ops sit under
        ``mercury_augmentation``, or ``mercury_input_fuse`` with
        ``config.fused_input`` — the same pass under the scope the
        profile attribution and the jaxpr auditor key on.
        ``out_dtype`` (the bf16 scoring ingest) is applied as the LAST op
        on both paths."""
        if fused_input and raw.dtype != jnp.uint8:
            raise ValueError(
                "fused_input ingests raw uint8 rows (the chain owns "
                f"the /255 dequant); got {raw.dtype}"
            )
        if ingest_path(config, raw.dtype) == "select":
            with jax.named_scope("mercury_input_fuse" if fused_input
                                 else "mercury_augmentation"):
                return augment_normalize(
                    key, raw, mean, std, image_shape=image_shape,
                    out_dtype=(jnp.float32 if out_dtype is None
                               else out_dtype),
                )
        imgs = _augment(key, normalize_images(raw, mean, std))
        if out_dtype is not None:
            imgs = imgs.astype(out_dtype)
        return imgs

    @jax.named_scope("mercury_draw")
    def _select(k_sel, pool_losses, ema):
        """EMA update + score→normalize→draw, returning
        ``(selected, scaled_probs, new_ema, avg_pool_loss)`` — shared by the
        inline and pipelined paths (Pallas or jax-native). With
        ``_drawn_rows`` it is the ``mercury_draw`` scope: the pool sampler's
        draw, beside ``mercury_scoring`` and not inside it."""
        if use_pallas:
            from mercury_tpu.ops import score_and_draw_pallas

            avg = pool_mean(pool_losses, stat_axis)
            new_ema = ema_update(ema, avg, config.ema_alpha)
            _, selected, scaled = score_and_draw_pallas(
                k_sel, pool_losses, new_ema.value, batch_size, config.is_alpha
            )
            return selected, scaled, new_ema, avg
        sel = select_from_pool(
            k_sel, pool_losses, ema, batch_size,
            is_alpha=config.is_alpha, ema_alpha=config.ema_alpha,
            axis_name=stat_axis,
        )
        return sel.selected, sel.scaled_probs, sel.ema, sel.avg_pool_loss

    @jax.named_scope("mercury_draw")
    def _drawn_rows(selected, images, labels):
        """The drawn rows of the scored pool. Images are gathered as
        ``[n, H, W*C]`` rows — the dense form the ingest's selection
        leaves them in — so the row gather reads the pool as it was
        written instead of a relayout with the channels minor."""
        if images.ndim == 4:
            n, h, w, c = images.shape
            drawn = images.reshape(n, h, w * c)[selected]
            return drawn.reshape(-1, h, w, c), labels[selected]
        return images[selected], labels[selected]

    def score_rows(state, raw, labs, ka, reuse_images=True):
        """Augment → inference-mode scoring forward over already-gathered
        rows — the pool-scoring core shared by the device-resident
        ``score_slots`` prologue and the host-stream body (whose rows
        arrive pre-gathered from the host pipeline). Callers wrap the
        call in the ``mercury_scoring`` named scope the jaxpr auditor
        anchors on (one scope per call site — nesting would rename the
        anchor); the three scopes opened here split it by layer, for the
        device trace: ``mercury_pool_ingest`` (with the caller's gather),
        ``mercury_score_forward``, ``mercury_score_loss``.
        ``reuse_images=False`` marks scorer-only sites (the
        returned images are discarded, e.g. scoretable refresh windows):
        with ``scoring_dtype="bfloat16"`` those ingest straight to bf16 —
        uint8 → bf16 score, no f32 activation round trip. Returns
        ``(imgs, pool_logits, scores)``."""
        scorer_only = not reuse_images and scoring_bf16
        with jax.named_scope("mercury_pool_ingest"):
            imgs = _ingest(
                ka, raw, out_dtype=jnp.bfloat16 if scorer_only else None
            )
        if scoring_model is None:
            with jax.named_scope("mercury_score_forward"):
                pool_logits, _, _ = _apply_train(
                    state.params, state.batch_stats, imgs, False
                )
        else:
            # Same params, lower-precision compute (scoring_dtype) —
            # scores only rank candidates, and the reweight divides by
            # the realized probs, so this stays unbiased. The forward's
            # input is pre-cast to the scoring dtype (a no-op when the
            # ingest already emitted bf16) so the activations never
            # materialize at f32; the returned imgs keep the training
            # precision when the caller reuses them.
            variables = {"params": state.params}
            mutable = ["losses", MOMENT_UNITS]
            if state.batch_stats:
                variables["batch_stats"] = state.batch_stats
                mutable.append("batch_stats")
            with jax.named_scope("mercury_score_forward"):
                s_in = imgs.astype(jnp.bfloat16) if scoring_bf16 else imgs
                pool_logits, model_state = scoring_model.apply(
                    variables, s_in, train=True, mutable=mutable
                )
                pool_logits = pool_logits.astype(jnp.float32)
            _note_moment_units(model_state)
        with jax.named_scope("mercury_score_loss"):
            scores = _score_per_sample(pool_logits, labs)
        return imgs, pool_logits, scores

    def probe_var_ratio(state, sel_images, sel_labels, scaled_probs):
        """Grad-variance probe (``sampler_dist/var_ratio``, the
        1803.00942 gate signal, observe-only): every ``probe_every``-th
        step, ONE extra scoring-model pass over the just-trained
        microbatch yields per-example grad-norm bounds ``g_i``; with the
        batch drawn from ``p`` and ``scaled_probs_i = N·p_i``,
        ``pool_mean((g/(N·p))²)`` estimates the IS gradient estimator's
        second moment and ``pool_mean(g²/(N·p))`` the uniform one (same
        unbiased reweighting as the loss). Their ratio follows
        ``benchmarks/grad_variance.py``'s convention: < 1 ⇔ IS is
        winning. Uses PRE-update params (``state`` is the input state) —
        the distribution the draw actually came from. Off-cadence steps
        return the -1.0 sentinel every consumer ignores."""

        def run(_):
            with jax.named_scope("mercury_variance_probe"):
                if scoring_model is None:
                    logits, _, _ = _apply_train(
                        state.params, state.batch_stats, sel_images, False
                    )
                else:
                    s_in = (sel_images.astype(jnp.bfloat16)
                            if scoring_bf16 else sel_images)
                    variables = {"params": state.params}
                    mutable = ["losses"]
                    if state.batch_stats:
                        variables["batch_stats"] = state.batch_stats
                        mutable = ["batch_stats", "losses"]
                    logits, _ = scoring_model.apply(
                        variables, s_in, train=True, mutable=mutable
                    )
                g = per_sample_grad_norm_bound(
                    logits.astype(jnp.float32), sel_labels,
                    config.label_smoothing,
                )
            sp = jnp.maximum(scaled_probs.astype(jnp.float32), 1e-30)
            # Pool the moments across workers BEFORE the ratio (a pmean
            # of per-worker ratios is not the global ratio);
            # obs/sampler_health.variance_probe_ratio is the single-host
            # reference the tests cross-validate against.
            m_is = pool_mean(jnp.square(g / sp), stat_axis)
            m_unif = pool_mean(jnp.square(g) / sp, stat_axis)
            return m_is / jnp.maximum(m_unif, 1e-30)

        # Cadence on the POST-increment step: metric records carry
        # state.step + 1, so this makes the probe land on the records
        # whose step is a multiple of probe_every — aligning with
        # log_every (set probe_every to a multiple of it), instead of
        # emitting the sentinel one record off forever.
        return lax.cond(
            (state.step + 1) % probe_every == 0, run,
            lambda _: jnp.full((), -1.0, jnp.float32), operand=None,
        )

    def train_update(state, rng, sel_images, sel_labels, scaled_probs):
        """The train back-end — the second half of the fused step, split
        from the per-sampler selection front-ends so the host-stream body
        (which consumes a batch selected ``prefetch_depth`` steps ago)
        shares it verbatim with the device-resident paths: reweighted
        fwd/bwd, optional gradient compression, the gradient collective
        (plain allreduce or ZeRO-1 reduce-scatter/all-gather, int8 wire
        variants), optimizer apply, and the BN-stat sync. Returns a dict
        with the new model/optimizer state, the train logits (the
        scoretable write-back re-scores them for free), and the
        replicated loss/acc reductions."""
        # fold_in (not a 9-way split) so the eight existing streams — and
        # every recorded seeded trajectory — are unchanged by the
        # compression feature's existence.
        k_quant = jax.random.fold_in(rng, 0x71)  # graftlint: disable=GL101 -- deliberate sentinel stream: fold_in(rng, 0x71) is disjoint from the 8-way split, preserving recorded trajectories

        # --- train forward/backward with the unbiased IS reweighting
        # mean(loss_i/(N·p_i)) (:132-148) --------------------------------
        def loss_fn(params):
            logits, new_bs, aux = _apply_train(
                params, state.batch_stats, sel_images, True
            )
            losses = _loss_per_sample(logits, sel_labels)
            total = reweighted_loss(losses, scaled_probs)
            if config.moe_experts is not None:
                # Switch load-balancing term (sowed by the MoE blocks).
                total = total + config.moe_aux_weight * aux
            return total, (logits, new_bs, aux)

        # One scope for both halves: jax marks the backward's ops itself
        # (``transpose(jvp(...))`` in the op's path), which is what the
        # device trace splits forward from backward by.
        with jax.named_scope("mercury_train"):
            (loss, (logits, new_batch_stats, moe_aux)), grads = (
                jax.value_and_grad(loss_fn, has_aux=True)(state.params))

        # --- optional quantization: each worker stochastically quantizes
        # its local gradient (independent keys); the mean across workers
        # stays unbiased — the live version of the reference's dead-code
        # experiment (util.py:65-70; "sparse rate", pytorch_collab.py:184).
        # Estimator semantics only: the psum below still moves dense
        # tensors (see TrainConfig.grad_compression).
        sparse_rate = jnp.ones((), jnp.float32)
        if compress_grads:
            from mercury_tpu.utils.quantize import sparsity, stochastic_quantize

            leaves, treedef = jax.tree_util.tree_flatten(grads)
            qkeys = jax.random.split(k_quant, len(leaves))
            leaves = [stochastic_quantize(k, g) for k, g in zip(qkeys, leaves)]
            grads = jax.tree_util.tree_unflatten(treedef, leaves)
            total = float(sum(g.size for g in leaves))
            sparse_rate = sum(sparsity(g) * (g.size / total) for g in leaves)

        loss_mean = lax.pmean(loss, axis)
        correct = lax.psum(
            jnp.sum((jnp.argmax(logits, -1) == sel_labels).astype(jnp.float32)), axis
        )
        count = lax.psum(jnp.asarray(batch_size, jnp.float32), axis)

        grad_norm = None
        if zero:
            # --- ZeRO-1: reduce-scatter the flattened gradient (each worker
            # receives the mean of its 1/W chunk — reduce-scatter +
            # all-gather IS the ring allreduce, util.py:280-324, so the
            # collective volume matches average_gradients :236-249), update
            # only that chunk's optimizer state, all-gather the updates.
            # With grad_compression="int8", BOTH wire phases move int8
            # payloads (per-chunk scales, stochastic rounding — unbiased):
            # the gradient reduce-scatter and the update all-gather, 4×
            # fewer bytes each (parallel/collectives.py).
            from mercury_tpu.utils.tree import (
                pad_to_chunks,
                tree_flatten_to_vector,
            )

            w = axis_size(axis)
            opt_chunk = jax.tree_util.tree_map(lambda x: x[0], state.opt_state)
            gvec, unravel = tree_flatten_to_vector(grads)
            if int8_allreduce:
                from mercury_tpu.parallel.collectives import (
                    compressed_all_gather,
                    compressed_psum_scatter_mean,
                )

                kz = jax.random.fold_in(rng, 0x72)  # graftlint: disable=GL101 -- deliberate sentinel stream 0x72 for int8 grad compression, disjoint from the 8-way split and 0x71
                kz1, kz2 = jax.random.split(kz)
                # mercury_grad_sync scopes anchor the jaxpr auditor's
                # per-region collective budgets (lint/audit.py).
                with jax.named_scope("mercury_grad_sync"):
                    gchunk = compressed_psum_scatter_mean(
                        pad_to_chunks(gvec, w), axis, kz1
                    )
            else:
                with jax.named_scope("mercury_grad_sync"):
                    gchunk = (
                        lax.psum_scatter(pad_to_chunks(gvec, w), axis) / w
                    )
            if telemetry:
                # The chunks partition the full mean-gradient vector (the
                # pad is zeros), so psum of the per-chunk square-sums is the
                # exact global norm² — one scalar on the wire.
                grad_norm = jnp.sqrt(lax.psum(
                    jnp.sum(jnp.square(gchunk.astype(jnp.float32))), axis
                ))
            pvec, _ = tree_flatten_to_vector(state.params)
            pchunk = pad_to_chunks(pvec, w)[lax.axis_index(axis)]
            # mercury_optimizer: profiler-attribution anchor for the
            # optimizer update (obs/profile_parse.py); digest-invisible.
            with jax.named_scope("mercury_optimizer"):
                updates_chunk, new_opt_chunk = tx.update(
                    gchunk, opt_chunk, pchunk)
            if int8_allreduce:
                with jax.named_scope("mercury_grad_sync"):
                    uvec = compressed_all_gather(updates_chunk, axis, kz2)[
                        : gvec.size
                    ]
            else:
                with jax.named_scope("mercury_grad_sync"):
                    uvec = lax.all_gather(
                        updates_chunk, axis, tiled=True
                    )[: gvec.size]
            with jax.named_scope("mercury_optimizer"):
                new_params = optax.apply_updates(state.params,
                                                 unravel(uvec))
            new_opt_state = jax.tree_util.tree_map(
                lambda x: x[None], new_opt_chunk
            )
        else:
            # --- gradient allreduce (≡ average_gradients, :236-249) in-graph
            if int8_allreduce:
                # int8 on the wire, both phases (collectives.py); unbiased.
                if tp_active:
                    # Per-leaf, shape-preserving compression: the wire
                    # chunking avoids the dims TP/FSDP shard, so the
                    # grads stay sharded through both phases.
                    from mercury_tpu.parallel.collectives import (
                        compressed_pmean_tree_sharded,
                    )

                    with jax.named_scope("mercury_grad_sync"):
                        grads = compressed_pmean_tree_sharded(
                            grads, axis, axis_size(axis),
                            # graftlint: disable=GL101 -- same deliberate 0x72 sentinel stream as the ZeRO branch (mutually exclusive at trace time)
                            jax.random.fold_in(rng, 0x72),
                            specs=sharded_param_specs,
                        )
                else:
                    from mercury_tpu.parallel.collectives import (
                        compressed_allreduce_mean_tree,
                    )

                    with jax.named_scope("mercury_grad_sync"):
                        grads = compressed_allreduce_mean_tree(
                            grads, axis, axis_size(axis),
                            # graftlint: disable=GL101 -- same deliberate 0x72 sentinel stream as the ZeRO branch (mutually exclusive at trace time)
                            jax.random.fold_in(rng, 0x72),
                        )
            else:
                with jax.named_scope("mercury_grad_sync"):
                    grads = allreduce_mean_tree(grads, axis)
            if telemetry:
                # Post-allreduce: already the worker-mean gradient, so the
                # norm is identical on every worker (replicated output).
                grad_norm = global_grad_norm(grads)
            with jax.named_scope("mercury_optimizer"):
                updates, new_opt_state = tx.update(
                    grads, state.opt_state, state.params
                )
                new_params = optax.apply_updates(state.params, updates)

        # Keep replicated BN stats replicated: under synced BN they already
        # agree; under local BN we average the running stats across workers
        # (normalization still used local batch stats this step).
        if new_batch_stats:
            new_batch_stats = allreduce_mean_tree(new_batch_stats, axis)

        return dict(
            loss_mean=loss_mean, acc=correct / count, logits=logits,
            moe_aux=moe_aux, sparse_rate=sparse_rate, grad_norm=grad_norm,
            new_params=new_params, new_batch_stats=new_batch_stats,
            new_opt_state=new_opt_state,
        )

    def body(state: MercuryState, x_train, y_train, shard_indices):
        # Leading axis inside shard_map is this device's single worker row.
        if data_sharded:
            x_loc, y_loc = x_train[0], y_train[0]

            def gather_train(slots):
                return x_loc[slots], y_loc[slots]
        else:
            def gather_train(slots):
                gidx = shard_indices[0][slots]
                return x_train[gidx], y_train[gidx]

        rng = state.rng[0]
        (k_stream, k_aug, k_sel, k_aug2, k_boot_stream, k_boot_aug,
         k_boot_sel, k_next) = jax.random.split(rng, 8)

        groupwise = None
        new_pending = None
        stream = ShardStream(perm=state.stream.perm[0], cursor=state.stream.cursor[0])
        ema = EMAState(value=state.ema.value[0], count=state.ema.count[0])

        # Per-path sampler-health scalars (obs/diagnostics.py). Each branch
        # overwrites these with its own measurement; the uniform baseline
        # keeps the zeros (nothing is scored, nothing can clip or drift).
        if telemetry:
            clip_frac = jnp.zeros((), jnp.float32)
            drift = jnp.zeros((), jnp.float32)

        def score_slots(slots, ka, reuse_images=True):
            """Gather → augment → inference-mode scoring forward — the
            pool-scoring prologue shared by the inline, pipelined,
            cadence, and groupwise IS paths (one definition so a change
            to scoring cannot drift between them). The whole prologue
            runs under the ``mercury_scoring`` named scope — the jaxpr
            auditor (``mercury_tpu/lint/audit.py``) keys per-region
            checks (e.g. bf16-scoring dot dtypes) on this anchor.
            ``reuse_images`` forwards to ``score_rows`` (False at
            scorer-only sites: bf16 ingest under scoring_dtype)."""
            with jax.named_scope("mercury_scoring"):
                with jax.named_scope("mercury_pool_ingest"):
                    raw, labs = gather_train(slots)
                imgs, pool_logits, scores = score_rows(
                    state, raw, labs, ka, reuse_images=reuse_images
                )
                return imgs, labs, pool_logits, scores

        if pipelined:
            # --- pipelined scoring: train on the batch selected last step,
            # score the NEXT pool with the same (pre-update) params — the
            # two chains are independent, so XLA overlaps the scoring
            # forward with the gradient collective. Reference dataflow:
            # update_samples for t+1 runs before optimizer.step
            # (pytorch_collab.py:158-164). --------------------------------
            def score_next(stream, ema, ks, ka, ksel):
                stream, slots = next_pool(stream, ks, pool_size)
                imgs, labs, pool_logits, pool_losses = score_slots(slots, ka)
                ema_prev = ema.value
                selected, scaled, ema, avg = _select(ksel, pool_losses, ema)
                sel_imgs, sel_labs = _drawn_rows(selected, imgs, labs)
                pend = PendingBatch(
                    images=sel_imgs, labels=sel_labs, scaled_probs=scaled,
                )
                tel = ()
                if telemetry:
                    # Clip/drift of the pool scored THIS step (the one
                    # trained next step) — the pipeline's live scoring work.
                    tel = (
                        clip_fraction(pool_losses, ema.value, config.is_alpha),
                        ema_drift(avg, ema_prev),
                    )
                return stream, ema, pend, _pool_loss_metric(
                    pool_logits, labs, avg
                ), tel

            stored = jax.tree_util.tree_map(lambda x: x[0], state.pending)

            # Step 0 primes the pending batch in-graph (≡ the epoch-prologue
            # update_samples call, pytorch_collab.py:125).
            def boot(args):
                s, e = args
                return score_next(s, e, k_boot_stream, k_boot_aug, k_boot_sel)

            def keep(args):
                s, e = args
                tel = ()
                if telemetry:
                    tel = (jnp.zeros((), jnp.float32),
                           jnp.zeros((), jnp.float32))
                return s, e, stored, jnp.zeros((), jnp.float32), tel

            stream, ema, current, _, _ = lax.cond(
                state.step == 0, boot, keep, (stream, ema)
            )
            sel_images, sel_labels = current.images, current.labels
            scaled_probs = current.scaled_probs
            stream, ema, new_pending, avg_pool_loss, tel = score_next(
                stream, ema, k_stream, k_aug, k_sel
            )
            if telemetry:
                clip_frac, drift = tel
        elif use_cadence:
            # --- score-refresh cadence: every K-th step stream + score a
            # fresh pool and cache its normalized importance distribution;
            # the K-1 steps in between redraw from the cache (fresh
            # multinomial draws ≡ pytorch_collab.py:114, fresh
            # augmentation) and skip the scoring forward entirely — the
            # dominant per-step IS cost amortizes by K. The 1/(N·p)
            # reweight uses the cached probs the batch was actually drawn
            # from, so the estimator stays unbiased for those scores. ----
            cached = jax.tree_util.tree_map(lambda x: x[0], state.cached_pool)
            # Telemetry carry through the cond: the refresh branch measures,
            # the reuse branch returns these zeros — clip/drift read 0 on
            # cache-hit steps (no scoring happened, nothing to measure).
            tel0 = ()
            if telemetry:
                tel0 = (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32))

            def refresh(args):
                stream, ema, _, _ = args
                stream, slots = next_pool(stream, k_stream, pool_size)
                _, labs, pool_logits, pool_losses = score_slots(
                    slots, k_aug, reuse_images=False
                )
                avg = pool_mean(pool_losses, stat_axis)
                ema_prev = ema.value
                ema = ema_update(ema, avg, config.ema_alpha)
                probs = importance_probs(
                    pool_losses, ema.value, config.is_alpha
                )
                pool = CachedPool(
                    slots=slots.astype(jnp.int32),
                    probs=probs,
                    pool_loss=_pool_loss_metric(pool_logits, labs, avg),
                )
                tel = ()
                if telemetry:
                    tel = (
                        clip_fraction(pool_losses, ema.value, config.is_alpha),
                        ema_drift(avg, ema_prev),
                    )
                return stream, ema, pool, tel

            def reuse(args):
                return args

            stream, ema, cached, tel = lax.cond(
                state.step % cadence == 0, refresh, reuse,
                (stream, ema, cached, tel0),
            )
            if telemetry:
                clip_frac, drift = tel
            selected = draw_with_replacement(k_sel, cached.probs, batch_size)
            scaled_probs = cached.probs[selected] * pool_size
            sel_raw, sel_labels = gather_train(cached.slots[selected])
            sel_images = _ingest(k_aug2, sel_raw)
            avg_pool_loss = cached.pool_loss
            new_cached = cached
        elif use_scoretable:
            # --- score-table sampler: a device-resident [L] float32 score
            # over THIS worker's whole shard. Each step (a) refreshes only
            # `refresh_size` entries — a round-robin window, so every slot
            # is rescored within ceil(L/R) steps — via one small scoring
            # forward, (b) age-decays the rest toward the EMA mean
            # (staleness-aware smoothing: an entry untouched for k steps
            # has shrunk by decay^k toward the pool-typical score), and
            # (c) draws the train batch from the FULL shard's distribution
            # in one fused normalize→CDF→draw kernel. Scoring FLOPs per
            # step drop from pool_size to refresh_size while the draw sees
            # every sample — vs. the pool sampler's fresh-320 window.
            table = jax.tree_util.tree_map(lambda x: x[0], state.scoretable)
            if async_refresh:
                # --- refresh_mode="async": no refresh window, no scoring
                # forward, no mercury_scoring scope — the scorer fleet
                # refreshed the table between dispatches. The in-graph work
                # is decay → normalize → draw only; the post-train
                # write-back below still re-scores the trained batch for
                # free (those logits exist either way).
                new_scores = decay_scores(
                    table.scores.astype(jnp.float32), ema.value,
                    config.table_decay,
                )
                if use_pallas:
                    from mercury_tpu.ops import score_and_draw_pallas

                    _, selected, scaled_probs = score_and_draw_pallas(
                        k_sel, new_scores, ema.value, batch_size,
                        config.is_alpha,
                    )
                else:
                    probs = table_probs(
                        new_scores, ema.value, config.is_alpha
                    )
                    # Inverse-CDF, not categorical: a [B, L] Gumbel field
                    # is B·L threefry draws — at shard scale that alone
                    # would cost more than the scoring forward we just
                    # removed (measured ~5 ms at L≈3k on CPU).
                    selected = table_draw_inverse_cdf(
                        k_sel, probs, batch_size
                    )
                    scaled_probs = probs[selected] * new_scores.shape[0]
                # No refresh forward → no pool-loss measurement this step;
                # the EMA update moves post-train (see the write-back).
                avg_pool_loss = jnp.zeros((), jnp.float32)
            else:
                refresh_slots = refresh_window(table, refresh_size)
                _, r_labels, r_logits, r_scores = score_slots(
                    refresh_slots, k_aug, reuse_images=False
                )
                score_avg = pool_mean(r_scores, stat_axis)
                ema_prev = ema.value
                ema = ema_update(ema, score_avg, config.ema_alpha)
                if use_pallas:
                    from mercury_tpu.ops import score_and_draw_pallas

                    # Decay and refresh scatter are the jax-native ops of
                    # table_refresh_draw; the kernel owns normalize → CDF
                    # → draw over the whole table.
                    new_scores = scatter_mean(
                        decay_scores(table.scores.astype(jnp.float32),
                                     ema.value, config.table_decay),
                        refresh_slots, r_scores,
                    )
                    _, selected, scaled_probs = score_and_draw_pallas(
                        k_sel, new_scores, ema.value, batch_size,
                        config.is_alpha,
                    )
                else:
                    new_scores, _, selected, scaled_probs = (
                        table_refresh_draw(
                            k_sel, table.scores, refresh_slots, r_scores,
                            ema.value, batch_size,
                            alpha=config.is_alpha, decay=config.table_decay,
                        )
                    )
                avg_pool_loss = _pool_loss_metric(
                    r_logits, r_labels, score_avg
                )
            sel_raw, sel_labels = gather_train(selected)
            sel_images = _ingest(k_aug2, sel_raw)
            table_scores_predraw = new_scores
            table_selected = selected
            if telemetry:
                # Clip over the FULL refreshed (async: decayed) table — the
                # distribution the draw actually normalizes.
                clip_frac = clip_fraction(
                    new_scores, ema.value, config.is_alpha
                )
                if not async_refresh:
                    # Cursor staleness from the round-robin window
                    # (pre-advance: this window is age 0); under async the
                    # fleet owns the sweep, so ages live host-side
                    # (sampler/score_staleness_* via ScorerFleet.stats) and
                    # drift moves to the post-train EMA update below.
                    drift = ema_drift(score_avg, ema_prev)
                    age_min, age_mean, age_max = table_age_summary(
                        table.cursor, table.scores.shape[0], refresh_size
                    )
        else:
            if use_groupwise:
                # Sliding-window refresh over the shard (util.py:114-138):
                # the next `pool_size` slots in order, wrapping — no shuffle.
                from mercury_tpu.sampling.groupwise import (
                    draw as gw_draw,
                    update_importance,
                    window_indices,
                )

                groupwise = jax.tree_util.tree_map(lambda x: x[0], state.groupwise)
                slots = window_indices(groupwise, pool_size)
            else:
                # Shuffled wrapping presample stream (≡ Trainer.get_next over
                # the presampling loader, :74-82).
                stream, slots = next_pool(stream, k_stream, pool_size)

            if use_is:
                # --- importance scoring: ONE batched inference forward over
                # the pool (≡ the 10-iteration no_grad loop, :95-106),
                # batch-stat normalization, running-stat updates discarded --
                # Groupwise discards the scored images (drawn slots are
                # re-gathered below), so its scoring pass is scorer-only.
                images, labels, pool_logits, pool_losses = score_slots(
                    slots, k_aug, reuse_images=not use_groupwise
                )
                if use_groupwise:
                    # Persist scores into the shard-wide importance array,
                    # tag the new generation, draw from it with the +mean
                    # shift (util.py:133-153). Drawn slots are re-gathered
                    # and re-augmented (the sampler re-loads by index, as
                    # the reference's does via get_slice, util.py:123).
                    groupwise = update_importance(groupwise, slots, pool_losses)
                    sel_slots, scaled_probs = gw_draw(groupwise, k_sel, batch_size)
                    sel_raw, sel_labels = gather_train(sel_slots)
                    sel_images = _ingest(k_aug2, sel_raw)
                    score_avg = pool_mean(pool_losses, stat_axis)
                    ema_prev = ema.value
                    ema = ema_update(ema, score_avg, config.ema_alpha)
                    avg_pool_loss = _pool_loss_metric(
                        pool_logits, labels, score_avg
                    )
                else:
                    ema_prev = ema.value
                    selected, scaled_probs, ema, score_avg = _select(
                        k_sel, pool_losses, ema
                    )
                    avg_pool_loss = _pool_loss_metric(
                        pool_logits, labels, score_avg
                    )
                    sel_images, sel_labels = _drawn_rows(
                        selected, images, labels
                    )
                if telemetry:
                    clip_frac = clip_fraction(
                        pool_losses, ema.value, config.is_alpha
                    )
                    drift = ema_drift(score_avg, ema_prev)
            else:
                # Uniform baseline: consume the freshly streamed batch
                # directly — the stream is a shuffled without-replacement
                # epoch pass, i.e. standard shuffled-loader SGD — with unit
                # IS weights so loss/(N·p) = loss. (pool_size == batch_size
                # here, so no scoring forward and no wasted gather.)
                raw, sel_labels = gather_train(slots)
                sel_images = _ingest(k_aug, raw)[:batch_size]
                sel_labels = sel_labels[:batch_size]
                scaled_probs = jnp.ones((batch_size,), jnp.float32)
                avg_pool_loss = jnp.zeros((), jnp.float32)

        upd = train_update(state, rng, sel_images, sel_labels, scaled_probs)
        logits = upd["logits"]
        if telemetry:
            grad_norm = upd["grad_norm"]
        if use_probe:
            var_ratio = probe_var_ratio(
                state, sel_images, sel_labels, scaled_probs
            )

        new_scoretable = state.scoretable
        new_sel_counts = state.sel_counts
        if use_scoretable:
            # Free write-back: the train forward's logits re-score the
            # just-trained slots for zero extra FLOPs (they fall out of the
            # backward pass anyway); with-replacement duplicates average.
            train_scores = _score_per_sample(
                logits.astype(jnp.float32), sel_labels
            )
            if async_refresh:
                # With no refresh forward, the EMA mean (decay target and
                # smoothing anchor) comes from the trained batch itself,
                # reweighted back to the uniform-mean estimate:
                # E[score_i/(L·p_i)] = mean_L(score) — the same unbiased
                # identity the loss reweighting rests on — so the EMA
                # tracks the SHARD-typical score, not the importance-tilted
                # batch mean, at zero extra FLOPs.
                score_avg = pool_mean(train_scores / scaled_probs, stat_axis)
                ema_prev = ema.value
                ema = ema_update(ema, score_avg, config.ema_alpha)
                if telemetry:
                    drift = ema_drift(score_avg, ema_prev)
            new_table = ScoreTableState(
                scores=scatter_mean(
                    table_scores_predraw, table_selected, train_scores
                ),
                # Async: the fleet owns the round-robin sweep — the
                # in-graph cursor stays put.
                cursor=(table.cursor if async_refresh
                        else advance_cursor(table, refresh_size)),
            )
            new_scoretable = jax.tree_util.tree_map(
                lambda x: x[None], new_table
            )
            if use_ledger:
                # Selection-count ledger: the drawn batch IS the trained
                # batch on this path, so counting at train time counts
                # every draw exactly once (with-replacement duplicates
                # add once per occurrence).
                new_sel_counts = (
                    state.sel_counts[0].at[table_selected].add(1)
                )[None]
            if telemetry:
                # Global (psum'd) histogram of the post-refresh table —
                # the distribution the NEXT draw normalizes. Per-bin
                # scalars: the async writer means any vector.
                score_hist = lax.psum(
                    log_bin_histogram(
                        new_table.scores, SCORE_HIST_LO, SCORE_HIST_HI
                    ),
                    axis,
                )

        new_state = MercuryState(
            step=state.step + 1,
            params=upd["new_params"],
            batch_stats=upd["new_batch_stats"],
            opt_state=upd["new_opt_state"],
            ema=EMAState(value=ema.value[None], count=ema.count[None]),
            stream=ShardStream(perm=stream.perm[None], cursor=stream.cursor[None]),
            rng=k_next[None],
            groupwise=(
                jax.tree_util.tree_map(lambda x: x[None], groupwise)
                if use_groupwise else state.groupwise
            ),
            pending=(
                jax.tree_util.tree_map(lambda x: x[None], new_pending)
                if pipelined else state.pending
            ),
            cached_pool=(
                jax.tree_util.tree_map(lambda x: x[None], new_cached)
                if use_cadence else state.cached_pool
            ),
            scoretable=new_scoretable,
            pending_sel=state.pending_sel,
            sel_counts=new_sel_counts,
        )
        metrics = {
            "train/loss": upd["loss_mean"],
            "train/acc": upd["acc"],
            "train/pool_loss": lax.pmean(avg_pool_loss, axis),
            "train/sparse_rate": lax.pmean(upd["sparse_rate"], axis),
            "train/moe_aux": lax.pmean(upd["moe_aux"], axis),
        }
        if telemetry:
            metrics["sampler/ess"] = lax.pmean(
                ess_fraction(scaled_probs), axis
            )
            metrics["sampler/clip_frac"] = lax.pmean(clip_frac, axis)
            metrics["sampler/ema_drift"] = lax.pmean(drift, axis)
            metrics["train/grad_norm"] = grad_norm
            if use_scoretable and not async_refresh:
                # Cursor-derived, identical on every worker (the cursors
                # advance in lockstep from the same init). Async has no
                # in-graph cursor motion — staleness is tracked host-side
                # (sampler/score_staleness_*).
                metrics["sampler/table_age_min"] = age_min
                metrics["sampler/table_age_mean"] = age_mean
                metrics["sampler/table_age_max"] = age_max
            if use_is:
                # Per-batch IS-weight histogram (scaled_probs = N·p, the
                # reweight's divisor), psum'd global.
                w_hist = lax.psum(
                    log_bin_histogram(
                        scaled_probs, WEIGHT_HIST_LO, WEIGHT_HIST_HI
                    ),
                    axis,
                )
                for i, k in enumerate(hist_keys("w_hist")):
                    metrics[k] = w_hist[i]
            if use_scoretable:
                for i, k in enumerate(hist_keys("score_hist")):
                    metrics[k] = score_hist[i]
            if use_probe:
                metrics["sampler_dist/var_ratio"] = lax.pmean(
                    var_ratio, axis
                )
        return new_state, metrics

    def hs_body(state: MercuryState, x_stream, y_train, shard_indices):
        """Host-stream step: train on the batch whose indices were drawn
        ``prefetch_depth`` steps ago (the front of the ``PendingSelection``
        ring — its pixel rows arrive pre-gathered in ``x_stream``), then
        draw the selection for step t+depth and emit its GLOBAL indices as
        a third, non-donated output for the host prefetch pipeline. The
        lookahead draw for step u consumes the same key positions of
        rng_u's 8-way split that the device-resident body would consume AT
        step u (``sel_ks[0]``/``sel_ks[2]``), carried in ``psel.rng`` — so
        uniform and pool selections (param-independent draws) are
        bit-identical to ``replicated``, while the scoretable draw sees a
        depth-step-stale table (the ``pipelined_scoring`` trade, one step
        deeper); the carried draw-time ``scaled_probs`` keep the IS
        reweighting unbiased either way."""
        # x_stream: [1, S, ...] — this worker's pre-gathered rows for the
        # ring front (scoretable: refresh window rows ‖ train rows).
        xs = x_stream[0]
        rng = state.rng[0]
        (k_stream, k_aug, k_sel, k_aug2, k_boot_stream, k_boot_aug,
         k_boot_sel, k_next) = jax.random.split(rng, 8)

        stream = ShardStream(perm=state.stream.perm[0],
                             cursor=state.stream.cursor[0])
        ema = EMAState(value=state.ema.value[0], count=state.ema.count[0])
        psel = jax.tree_util.tree_map(lambda x: x[0], state.pending_sel)
        # rng_{t+depth}'s split — the lookahead draw's key material.
        sel_ks = jax.random.split(jax.random.wrap_key_data(psel.rng), 8)
        front = psel.slots[0]

        if telemetry:
            clip_frac = jnp.zeros((), jnp.float32)
            drift = jnp.zeros((), jnp.float32)

        if use_scoretable:
            table = jax.tree_util.tree_map(lambda x: x[0], state.scoretable)
            if async_refresh:
                # Async: the stream carries ONLY the train rows (the fleet
                # owns the refresh sweep host-side — no refresh rows ever
                # cross the pipeline, no in-graph scoring forward). The
                # table still age-decays; the EMA update moves post-train.
                train_slots = front
                refreshed = decay_scores(
                    table.scores.astype(jnp.float32), ema.value,
                    config.table_decay,
                )
                sel_labels = y_train[shard_indices[0][train_slots]]
                sel_images = _ingest(k_aug2, xs)
                scaled_probs = psel.scaled_probs[0]
                avg_pool_loss = jnp.zeros((), jnp.float32)
            else:
                # Streamed layout: rows 0:R are the step-t refresh window
                # (deterministic round-robin — drawn without the table),
                # rows R: are the train rows selected depth steps ago.
                refresh_slots = front[:refresh_size]
                train_slots = front[refresh_size:]
                with jax.named_scope("mercury_scoring"):
                    r_labels = y_train[shard_indices[0][refresh_slots]]
                    _, r_logits, r_scores = score_rows(
                        state, xs[:refresh_size], r_labels, k_aug,
                        reuse_images=False,
                    )
                score_avg = pool_mean(r_scores, stat_axis)
                ema_prev = ema.value
                ema = ema_update(ema, score_avg, config.ema_alpha)
                # Same decay → refresh-scatter as table_refresh_draw; the
                # draw half ran depth steps ago, so only the table update
                # remains.
                refreshed = scatter_mean(
                    decay_scores(
                        table.scores.astype(jnp.float32), ema.value,
                        config.table_decay,
                    ),
                    refresh_slots, r_scores,
                )
                sel_labels = y_train[shard_indices[0][train_slots]]
                sel_images = _ingest(k_aug2, xs[refresh_size:])
                scaled_probs = psel.scaled_probs[0]
                avg_pool_loss = _pool_loss_metric(
                    r_logits, r_labels, score_avg
                )
                if telemetry:
                    drift = ema_drift(score_avg, ema_prev)
                    age_min, age_mean, age_max = table_age_summary(
                        table.cursor, table.scores.shape[0], refresh_size
                    )
        elif use_is:
            # Pool sampler: the streamed rows ARE the candidate pool drawn
            # depth steps ago with rng_t's stream key; scoring + selection
            # happen in-step with rng_t's k_aug/k_sel — bit-identical to
            # the device-resident inline path.
            labs = y_train[shard_indices[0][front]]
            with jax.named_scope("mercury_scoring"):
                imgs, pool_logits, pool_losses = score_rows(
                    state, xs, labs, k_aug
                )
            ema_prev = ema.value
            selected, scaled_probs, ema, score_avg = _select(
                k_sel, pool_losses, ema
            )
            avg_pool_loss = _pool_loss_metric(pool_logits, labs, score_avg)
            sel_images, sel_labels = _drawn_rows(selected, imgs, labs)
            if telemetry:
                clip_frac = clip_fraction(
                    pool_losses, ema.value, config.is_alpha
                )
                drift = ema_drift(score_avg, ema_prev)
        else:
            # Uniform baseline (pool_size == batch_size): consume the
            # streamed rows directly, unit IS weights.
            sel_labels = y_train[shard_indices[0][front]][:batch_size]
            sel_images = _ingest(k_aug, xs)[:batch_size]
            scaled_probs = jnp.ones((batch_size,), jnp.float32)
            avg_pool_loss = jnp.zeros((), jnp.float32)

        upd = train_update(state, rng, sel_images, sel_labels, scaled_probs)
        logits = upd["logits"]
        if telemetry:
            grad_norm = upd["grad_norm"]
        if use_probe:
            var_ratio = probe_var_ratio(
                state, sel_images, sel_labels, scaled_probs
            )

        # --- lookahead draw for step t+depth -----------------------------
        next_scaled = jnp.ones((batch_size,), jnp.float32)
        new_scoretable = state.scoretable
        new_sel_counts = state.sel_counts
        if use_scoretable:
            # Write-back first (train logits re-score the trained slots),
            # then draw from the freshest table this host can have.
            train_scores = _score_per_sample(
                logits.astype(jnp.float32), sel_labels
            )
            if async_refresh:
                # Post-train EMA from the reweighted trained batch — the
                # same unbiased mean_L estimate as the device-resident
                # async body (see there) — BEFORE the lookahead normalize
                # so the next draw smooths against the freshest mean.
                score_avg = pool_mean(train_scores / scaled_probs, stat_axis)
                ema_prev = ema.value
                ema = ema_update(ema, score_avg, config.ema_alpha)
                if telemetry:
                    drift = ema_drift(score_avg, ema_prev)
            table_after = scatter_mean(refreshed, train_slots, train_scores)
            n_slots = table_after.shape[0]
            probs_next = table_probs(table_after, ema.value, config.is_alpha)
            if async_refresh:
                # Inverse-CDF draw, matching the device-resident async
                # body: categorical's [B, L] Gumbel field would put the
                # removed scoring forward's cost right back on the step.
                next_sel = table_draw_inverse_cdf(
                    sel_ks[2], probs_next, batch_size
                )
            else:
                next_sel = draw_with_replacement(
                    sel_ks[2], probs_next, batch_size
                ).astype(jnp.int32)
            next_scaled = probs_next[next_sel] * n_slots
            if async_refresh:
                # No window rows in the stream — the lookahead emits the
                # train draw only, and the cursor stays put (the fleet
                # owns the sweep).
                next_slots = next_sel
            else:
                # The refresh window for step t+depth is
                # cursor-deterministic: depth more R-sized round-robin
                # advances from here.
                next_window = (
                    (table.cursor + depth * refresh_size
                     + jnp.arange(refresh_size)) % n_slots
                ).astype(jnp.int32)
                next_slots = jnp.concatenate([next_window, next_sel])
            new_table = ScoreTableState(
                scores=table_after,
                cursor=(table.cursor if async_refresh
                        else advance_cursor(table, refresh_size)),
            )
            new_scoretable = jax.tree_util.tree_map(
                lambda x: x[None], new_table
            )
            if use_ledger:
                # Ledger counts at TRAIN time (the ring front consumed
                # this step), not at draw time — so the counts equal the
                # examples actually trained on and the in-flight ring is
                # not yet counted. tests/test_sampler_health.py pins this
                # against a host-side ring replay.
                new_sel_counts = (
                    state.sel_counts[0].at[train_slots].add(1)
                )[None]
            if telemetry:
                # Clip over the table the NEXT draw normalizes (the
                # freshest distribution this step produced).
                clip_frac = clip_fraction(
                    table_after, ema.value, config.is_alpha
                )
                score_hist = lax.psum(
                    log_bin_histogram(
                        table_after, SCORE_HIST_LO, SCORE_HIST_HI
                    ),
                    axis,
                )
        else:
            # Uniform/pool: the draw is param-independent, so running it
            # depth steps early with rng_{t+depth}'s stream key reproduces
            # the device-resident sequence exactly.
            stream, next_slots = next_pool(stream, sel_ks[0], emit_size)
            next_slots = next_slots.astype(jnp.int32)

        new_psel = PendingSelection(
            slots=jnp.concatenate([psel.slots[1:], next_slots[None]], 0),
            scaled_probs=jnp.concatenate(
                [psel.scaled_probs[1:], next_scaled[None]], 0
            ),
            rng=jax.random.key_data(sel_ks[7]),
        )
        # Global ids for the host gather — the pipeline's only view of the
        # draw (slots are shard-local; the host indexes the global array).
        next_gidx = shard_indices[0][next_slots][None]

        new_state = MercuryState(
            step=state.step + 1,
            params=upd["new_params"],
            batch_stats=upd["new_batch_stats"],
            opt_state=upd["new_opt_state"],
            ema=EMAState(value=ema.value[None], count=ema.count[None]),
            stream=ShardStream(perm=stream.perm[None],
                               cursor=stream.cursor[None]),
            rng=k_next[None],
            groupwise=state.groupwise,
            pending=state.pending,
            cached_pool=state.cached_pool,
            scoretable=new_scoretable,
            pending_sel=jax.tree_util.tree_map(
                lambda x: x[None], new_psel
            ),
            sel_counts=new_sel_counts,
        )
        metrics = {
            "train/loss": upd["loss_mean"],
            "train/acc": upd["acc"],
            "train/pool_loss": lax.pmean(avg_pool_loss, axis),
            "train/sparse_rate": lax.pmean(upd["sparse_rate"], axis),
            "train/moe_aux": lax.pmean(upd["moe_aux"], axis),
        }
        if telemetry:
            metrics["sampler/ess"] = lax.pmean(
                ess_fraction(scaled_probs), axis
            )
            metrics["sampler/clip_frac"] = lax.pmean(clip_frac, axis)
            metrics["sampler/ema_drift"] = lax.pmean(drift, axis)
            metrics["train/grad_norm"] = grad_norm
            if use_scoretable and not async_refresh:
                metrics["sampler/table_age_min"] = age_min
                metrics["sampler/table_age_mean"] = age_mean
                metrics["sampler/table_age_max"] = age_max
            if use_is:
                w_hist = lax.psum(
                    log_bin_histogram(
                        scaled_probs, WEIGHT_HIST_LO, WEIGHT_HIST_HI
                    ),
                    axis,
                )
                for i, k in enumerate(hist_keys("w_hist")):
                    metrics[k] = w_hist[i]
            if use_scoretable:
                for i, k in enumerate(hist_keys("score_hist")):
                    metrics[k] = score_hist[i]
            if use_probe:
                metrics["sampler_dist/var_ratio"] = lax.pmean(
                    var_ratio, axis
                )
        return new_state, metrics, next_gidx

    if host_stream:
        fn = hs_body
    elif scan_steps > 1:
        def chunk(state, x_train, y_train, shard_indices):
            def scan_body(s, _):
                return body(s, x_train, y_train, shard_indices)

            return lax.scan(scan_body, state, None, length=scan_steps)

        fn = chunk
    else:
        fn = body

    specs = _state_specs(axis, has_groupwise=use_groupwise,
                         has_pending=pipelined, zero_sharding=zero,
                         has_cached_pool=use_cadence,
                         has_scoretable=use_scoretable,
                         has_pending_sel=host_stream,
                         has_sel_counts=use_ledger)
    smap_kw = {}
    if auto_axes:
        # Manual over the data axis only; GSPMD handles the rest.
        smap_kw["axis_names"] = frozenset({axis})
    # host_stream: x is the per-worker streamed rows ([W, S, ...] — sharded
    # like the indices that drew them) while y stays the replicated label
    # table the in-graph gathers index; the third output is the next
    # selection's global indices, one row per worker.
    x_spec = P(axis) if (data_sharded or host_stream) else P()
    y_spec = P(axis) if data_sharded else P()
    out_specs_t = (specs, P(), P(axis)) if host_stream else (specs, P())
    sharded = shard_map(
        fn,
        mesh=mesh,
        in_specs=(specs, x_spec, y_spec, P(axis)),
        out_specs=out_specs_t,
        check_vma=False,
        **smap_kw,
    )
    if io_constraints:
        from jax.sharding import NamedSharding

        # SHARDING CONTRACT (see docstring): pin the data inputs' layouts
        # at the step boundary, outside the shard_map, so any caller-side
        # layout drift surfaces as one explicit reshard here — not as
        # GSPMD rewrites inside the program. Layer 3 budgets these
        # constraint ops per plan (lint/shard_budgets.json).
        x_ns = NamedSharding(mesh, x_spec)
        y_ns = NamedSharding(mesh, y_spec)
        idx_ns = NamedSharding(mesh, P(axis))
        constrained_inner = sharded

        def sharded(state, x_train, y_train, shard_indices):
            x_train = jax.lax.with_sharding_constraint(x_train, x_ns)
            y_train = jax.lax.with_sharding_constraint(y_train, y_ns)
            shard_indices = jax.lax.with_sharding_constraint(
                shard_indices, idx_ns)
            return constrained_inner(state, x_train, y_train,
                                     shard_indices)

    jit_kw = {}
    if state_out_shardings is not None:
        jit_kw["out_shardings"] = state_out_shardings
    # host_stream also donates the streamed slab (arg 1): the rows are
    # consumed by this step only (trainer pops, dispatches, drops — see
    # Trainer._host_stream_step), and without the donation the slab stays
    # live across the whole step, blocking the H2D-for-t+1 / compute
    # overlap the lookahead exists to buy. The non-donated next_gidx
    # output never aliases it (int32 [W, S] vs uint8 rows), so the
    # PendingSelection outputs no longer pin the buffer. Layer-3's
    # memory_analysis() ratchet + the Layer-2 donation-consistency check
    # (lint/audit.py) pin this down per plan.
    donated = (0, 1) if host_stream else (0,)
    return jax.jit(sharded, donate_argnums=donated, **jit_kw)


def make_host_stream_prime(config: TrainConfig, mesh: Mesh):
    """Cold-start primer for ``data_placement="host_stream"``: one jitted
    shard_map that draws the first ``prefetch_depth`` selections UNIFORMLY
    (the reference's cold start — the table/scores don't exist yet),
    advancing the per-worker rng/stream chains exactly as ``hs_body``'s
    lookahead would have, and fills the ``PendingSelection`` ring.

    Returns ``prime(state, shard_indices) -> (state, gidx)`` with ``gidx``
    ``[depth, W, S]`` int32 global indices — one prefetch push per ring
    slot. For uniform/pool samplers the primed draws are the exact draws
    ``replicated`` would make at steps 0..depth-1 (``next_pool`` with each
    step's stream key), so trajectories match from step 0; the scoretable
    sampler primes with uniform-with-replacement draws plus the
    deterministic round-robin refresh windows (unit ``scaled_probs`` keep
    step 0..depth-1 unbiased)."""
    axis = config.mesh_axis
    depth = int(config.prefetch_depth)
    use_is = bool(config.use_importance_sampling)
    use_scoretable = use_is and config.sampler == "scoretable"
    batch_size = int(config.batch_size)
    pool_size = int(config.candidate_pool_size) if use_is else int(
        config.batch_size)
    refresh_size = int(config.refresh_size)
    async_refresh = use_scoretable and config.refresh_mode == "async"
    emit_size = (batch_size if async_refresh
                 else (refresh_size + batch_size) if use_scoretable
                 else pool_size)
    # Same gate as make_train_step: the ledger exists iff the step carries
    # it — the prime passes it through untouched, but the spec prefix must
    # cover the field.
    use_ledger = use_scoretable and bool(config.telemetry)

    def prime(state: MercuryState, shard_indices):
        stream = ShardStream(perm=state.stream.perm[0],
                             cursor=state.stream.cursor[0])
        sel_rng = state.rng[0]
        slots_steps = []
        for i in range(depth):
            ks = jax.random.split(sel_rng, 8)
            if use_scoretable:
                table = jax.tree_util.tree_map(
                    lambda x: x[0], state.scoretable
                )
                n = table.scores.shape[0]
                # Uniform-with-replacement through the SAME draw kernel the
                # steady state uses, on the flat distribution — consumes
                # k_sel exactly as hs_body's lookahead will.
                flat = jnp.full((n,), 1.0 / n, jnp.float32)
                if async_refresh:
                    drawn = table_draw_inverse_cdf(ks[2], flat, batch_size)
                else:
                    drawn = draw_with_replacement(
                        ks[2], flat, batch_size
                    ).astype(jnp.int32)
                if async_refresh:
                    # Async streams train rows only (the fleet owns the
                    # refresh sweep) — no window rows to prime.
                    slots_i = drawn
                else:
                    window = (
                        (table.cursor + i * refresh_size
                         + jnp.arange(refresh_size)) % n
                    ).astype(jnp.int32)
                    slots_i = jnp.concatenate([window, drawn])
            else:
                stream, slots_i = next_pool(stream, ks[0], emit_size)
                slots_i = slots_i.astype(jnp.int32)
            slots_steps.append(slots_i)
            sel_rng = ks[7]
        slots = jnp.stack(slots_steps)                 # [depth, S]
        gidx = shard_indices[0][slots]                 # [depth, S] global
        psel = PendingSelection(
            slots=slots[None],
            scaled_probs=jnp.ones((1, depth, batch_size), jnp.float32),
            rng=jax.random.key_data(sel_rng)[None],
        )
        new_state = state.replace(
            stream=ShardStream(perm=stream.perm[None],
                               cursor=stream.cursor[None]),
            pending_sel=psel,
        )
        # [depth, 1, S]: stacked pushes, worker row sharded P(axis).
        return new_state, gidx[:, None]

    specs = _state_specs(
        axis, zero_sharding=config.zero_sharding,
        has_scoretable=use_scoretable, has_pending_sel=True,
        has_sel_counts=use_ledger,
    )
    sharded = shard_map(
        prime,
        mesh=mesh,
        in_specs=(specs, P(axis)),
        out_specs=(specs, P(None, axis)),
        check_vma=False,
    )
    return jax.jit(sharded)


def make_eval_step(model) -> Callable[..., Tuple[jax.Array, jax.Array, jax.Array]]:
    """Jitted eval on one fixed-size batch with a validity mask.

    ≡ the inner loop of ``Trainer.evaluate`` (``pytorch_collab.py:201-234``):
    inference-mode forward (BN running averages — the ``eval()`` flip at
    ``:207``), summed loss/correct counts. Returns
    ``(loss_sum, correct, n)`` for meter accumulation.
    """

    def eval_fn(params, batch_stats, images, labels, valid_n):
        variables = {"params": params}
        if batch_stats:
            variables["batch_stats"] = batch_stats
        logits = model.apply(variables, images, train=False)
        losses = per_sample_loss(logits, labels)
        mask = (jnp.arange(images.shape[0]) < valid_n).astype(jnp.float32)
        loss_sum = jnp.sum(losses * mask)
        correct = jnp.sum((jnp.argmax(logits, -1) == labels).astype(jnp.float32) * mask)
        return loss_sum, correct, jnp.sum(mask)

    return jax.jit(eval_fn)


def make_per_class_epoch(
    model, mean: np.ndarray, std: np.ndarray, num_classes: int,
    eval_augmentation: str = "none",
    mesh: Optional[Mesh] = None, axis: str = "data",
) -> Callable[..., Tuple[jax.Array, jax.Array]]:
    """One-dispatch per-class (hits, totals) over pre-batched eval arrays —
    same scan/sharding structure as :func:`make_eval_epoch`, with a
    scatter-add per batch instead of scalar sums. Returns int32 ``[C]``
    pairs for host-side division."""
    from mercury_tpu.data.pipeline import normalize_images

    def per_class_epoch(params, batch_stats, images_b, labels_b, valid_b):
        variables = {"params": params}
        if batch_stats:
            variables["batch_stats"] = batch_stats

        def body(carry, batch):
            imgs_u8, labels, mask = batch
            imgs = normalize_images(imgs_u8, mean, std)
            if eval_augmentation == "iid":
                from mercury_tpu.data.transforms import eval_transform_iid

                imgs = eval_transform_iid(jax.random.key(0), imgs)
            logits = model.apply(variables, imgs, train=False)
            maski = mask.astype(jnp.int32)
            hit = (jnp.argmax(logits, -1) == labels).astype(jnp.int32) * maski
            hits, totals = carry
            return (hits.at[labels].add(hit),
                    totals.at[labels].add(maski)), None

        init = (jnp.zeros((num_classes,), jnp.int32),
                jnp.zeros((num_classes,), jnp.int32))
        (hits, totals), _ = jax.lax.scan(
            body, init, (images_b, labels_b, valid_b)
        )
        return hits, totals

    if mesh is None:
        return jax.jit(per_class_epoch)
    from jax.sharding import NamedSharding

    from mercury_tpu.parallel.mesh import replicated_sharding

    rep = replicated_sharding(mesh)
    batched = NamedSharding(mesh, P(None, axis))
    return jax.jit(
        per_class_epoch,
        in_shardings=(rep, rep, batched, batched, batched),
        out_shardings=(rep, rep),
    )


def make_eval_epoch(
    model, mean: np.ndarray, std: np.ndarray, eval_augmentation: str = "none",
    mesh: Optional[Mesh] = None, axis: str = "data",
) -> Callable[..., Tuple[jax.Array, jax.Array, jax.Array]]:
    """One-dispatch full-split eval: ``lax.scan`` over pre-batched uint8
    arrays, normalize + forward + masked reduce in-graph.

    The reference's ``evaluate`` walks a DataLoader batch-by-batch from the
    host (``pytorch_collab.py:201-234``); a whole split here is a single
    device call — ~24 host dispatches become 1, which matters wherever a
    dispatch costs a visible fraction of a small batch's compute.

    With ``mesh``, each scanned batch's sample dimension is sharded over
    the mesh's data axis (``in_shardings`` only — GSPMD partitions the
    forward and inserts the reduction collectives), so eval uses every
    device instead of leaving W−1 idle.

    ``eval_augmentation="iid"`` applies the reference IID path's *test*
    transform — resize(33) → random crop(32) (``exp_dataset.py:63-68``; yes,
    the reference random-crops at eval) — with a fixed key per batch so
    eval stays deterministic. The live non-IID path normalizes only
    (``cifar10/data_loader.py:92-96``).
    """
    from mercury_tpu.data.pipeline import normalize_images

    def eval_epoch(params, batch_stats, images_b, labels_b, valid_b):
        # images_b: [nb, B, H, W, C] uint8; labels_b: [nb, B]; valid_b: [nb, B]
        variables = {"params": params}
        if batch_stats:
            variables["batch_stats"] = batch_stats

        def body(carry, batch):
            imgs_u8, labels, mask = batch
            imgs = normalize_images(imgs_u8, mean, std)
            if eval_augmentation == "iid":
                from mercury_tpu.data.transforms import eval_transform_iid

                # Deterministic: key derived from the batch's first label
                # sum is overkill — a fixed key is what "same transform
                # every eval" means here.
                imgs = eval_transform_iid(jax.random.key(0), imgs)
            logits = model.apply(variables, imgs, train=False)
            losses = per_sample_loss(logits, labels)
            maskf = mask.astype(jnp.float32)
            hit = (jnp.argmax(logits, -1) == labels).astype(jnp.float32)
            loss_sum, correct, count = carry
            return (
                loss_sum + jnp.sum(losses * maskf),
                correct + jnp.sum(hit * maskf),
                count + jnp.sum(maskf),
            ), None

        init = (jnp.zeros(()), jnp.zeros(()), jnp.zeros(()))
        (loss_sum, correct, count), _ = jax.lax.scan(
            body, init, (images_b, labels_b, valid_b)
        )
        return loss_sum, correct, count

    if mesh is None:
        return jax.jit(eval_epoch)
    from jax.sharding import NamedSharding

    from mercury_tpu.parallel.mesh import replicated_sharding

    rep = replicated_sharding(mesh)
    batched = NamedSharding(mesh, P(None, axis))  # [nb, B, ...]: shard B
    return jax.jit(
        eval_epoch,
        in_shardings=(rep, rep, batched, batched, batched),
        out_shardings=(rep, rep, rep),
    )
