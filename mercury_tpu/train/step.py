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

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from mercury_tpu.compat import shard_map
from mercury_tpu.config import TrainConfig
from mercury_tpu.data.pipeline import ShardStream, next_pool, normalize_images
from mercury_tpu.obs.diagnostics import clip_fraction, ess_fraction
from mercury_tpu.obs.sampler_health import (
    WEIGHT_HIST_HI,
    WEIGHT_HIST_LO,
    hist_keys,
    log_bin_histogram,
)
from mercury_tpu.sampling.importance import (
    EMAState,
    draw_with_replacement,
)
from mercury_tpu.sampling.scoretable import (
    table_draw_inverse_cdf,
    table_probs,
)
from mercury_tpu.train.mode import StepMode
from mercury_tpu.train.samplers import (
    RESIDENT,
    STREAMED,
    Drawn,
    Keys,
    ResidentRows,
    StreamedRows,
    commit_table,
    rescore_trained,
    restack,
    table_histogram,
    unstack,
)
from mercury_tpu.train.stages import (
    StepContext,
    probe_var_ratio,
    row_fns,
    row_loss_and_score,
    train_update,
)
from mercury_tpu.train.state import MercuryState, PendingSelection


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
    mesh: Mesh, axis: str, params_sh, opt_sh, **fields: bool,
) -> Tuple[MercuryState, Any]:
    """Output shardings pinning the post-step state layout under partial-
    auto meshes (dp×tp): without this, GSPMD is free to re-replicate the
    tensor-parallel params on every step's output, discarding the TP split.
    ``params_sh``/``opt_sh`` are the committed input sharding trees; the rest
    follows :func:`_state_specs`'s ``has_*`` (``StepMode.state_fields``)."""
    from jax.sharding import NamedSharding

    state_sh = jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec), _state_specs(axis, **fields),
        is_leaf=lambda x: isinstance(x, P))
    return (state_sh.replace(params=params_sh, opt_state=opt_sh),
            NamedSharding(mesh, P()))


def _unstack_carry(state: MercuryState):
    """The drivers' shared prologue: this worker's rng with its 8-way
    split (same positions on every path, so seeded trajectories agree
    across placements), its presample stream and its EMA."""
    rng = state.rng[0]
    keys = Keys(*jax.random.split(rng, 8))
    return rng, keys, unstack(state.stream), unstack(state.ema)


def _train_and_probe(ctx: StepContext, state, rng, drawn: Drawn):
    batch = (drawn.images, drawn.labels, drawn.scaled_probs)
    upd = train_update(ctx, state, rng, *batch)
    return upd, (probe_var_ratio(ctx, state, *batch)
                 if ctx.mode.use_probe else None)


def _finish(ctx: StepContext, state, upd, drawn: Drawn, stream, ema, k_next,
            tel, own, scoretable, sel_counts, score_hist, var_ratio):
    """The drivers' shared epilogue: the step's ``MercuryState`` and metrics.
    ``own`` maps an optional field to its new unstacked value, the others
    pass through (``scoretable``/``sel_counts`` arrive stacked: made there)."""
    mode, axis = ctx.mode, ctx.mode.axis

    def field(name):
        return restack(own[name]) if name in own else getattr(state, name)

    new_state = MercuryState(
        step=state.step + 1,
        params=upd["new_params"],
        batch_stats=upd["new_batch_stats"],
        opt_state=upd["new_opt_state"],
        ema=restack(ema),
        stream=restack(stream),
        rng=k_next[None],
        groupwise=field("groupwise"),
        pending=field("pending"),
        cached_pool=field("cached_pool"),
        scoretable=scoretable,
        pending_sel=field("pending_sel"),
        sel_counts=sel_counts,
    )
    metrics = {
        "train/loss": upd["loss_mean"],
        "train/acc": upd["acc"],
        "train/pool_loss": lax.pmean(drawn.avg_pool_loss, axis),
        "train/sparse_rate": lax.pmean(upd["sparse_rate"], axis),
        "train/moe_aux": lax.pmean(upd["moe_aux"], axis),
    }
    # Routing of the routed experts in the train pass (models/decoder.py
    # sows it; no other model does).
    for name, value in upd["moe_load"].items():
        metrics[f"moe/{name}"] = lax.pmean(value, axis)
    if mode.telemetry:
        clip_frac, drift = tel
        metrics["sampler/ess"] = lax.pmean(
            ess_fraction(drawn.scaled_probs), axis
        )
        metrics["sampler/clip_frac"] = lax.pmean(clip_frac, axis)
        metrics["sampler/ema_drift"] = lax.pmean(drift, axis)
        metrics["train/grad_norm"] = upd["grad_norm"]
        if drawn.ages:
            # Cursor-derived, identical on every worker (the cursors advance in
            # lockstep from the same init). Async has no in-graph cursor motion
            # — staleness is tracked host-side (sampler/score_staleness_*).
            (metrics["sampler/table_age_min"],
             metrics["sampler/table_age_mean"],
             metrics["sampler/table_age_max"]) = drawn.ages
        if mode.use_is:
            # Per-batch IS-weight histogram (scaled_probs = N·p, the
            # reweight's divisor), psum'd global.
            w_hist = lax.psum(
                log_bin_histogram(
                    drawn.scaled_probs, WEIGHT_HIST_LO, WEIGHT_HIST_HI
                ),
                axis,
            )
            for i, k in enumerate(hist_keys("w_hist")):
                metrics[k] = w_hist[i]
        if mode.use_scoretable:
            for i, k in enumerate(hist_keys("score_hist")):
                metrics[k] = score_hist[i]
        if mode.use_probe:
            metrics["sampler_dist/var_ratio"] = lax.pmean(var_ratio, axis)
    return new_state, metrics


def resident_step(ctx: StepContext, state: MercuryState, x_train, y_train,
                  shard_indices):
    """One step on a device-resident train set (``replicated`` /
    ``sharded``): sample → update → (scoretable) write-back."""
    mode = ctx.mode
    rows = ResidentRows(x_train, y_train, shard_indices, mode.data_sharded)
    rng, keys, stream, ema = _unstack_carry(state)
    drawn = RESIDENT[mode.sampler](ctx, state, rows, keys, stream, ema)
    upd, var_ratio = _train_and_probe(ctx, state, rng, drawn)
    ema, tel = drawn.ema, drawn.tel
    scoretable, sel_counts, hist = state.scoretable, state.sel_counts, None
    if mode.use_scoretable:
        scores, ema, tel = rescore_trained(ctx, drawn, upd["logits"], ema, tel)
        scoretable, sel_counts = commit_table(mode, state, drawn, scores)
        if mode.telemetry:
            hist = table_histogram(mode, scores)
    return _finish(ctx, state, upd, drawn, drawn.stream, ema, keys.next, tel,
                   drawn.own or {}, scoretable, sel_counts, hist,
                   var_ratio)


def _table_draw(mode: StepMode, key, probs):
    """A host stream's ``batch_size`` slots from the table's distribution:
    the prime's and the lookahead's alike. Inverse-CDF under async, as the
    resident sampler: a [B, L] Gumbel field costs what the forward did."""
    if mode.async_refresh:
        return table_draw_inverse_cdf(key, probs, mode.batch_size)
    return draw_with_replacement(key, probs, mode.batch_size).astype(
        jnp.int32)


def _window_ahead(mode: StepMode, table, advances: int):
    """The refresh window ``advances`` steps on: cursor-deterministic, that
    many R-sized round-robin advances (async streams none: the fleet's)."""
    return ((table.cursor + advances * mode.refresh_size
             + jnp.arange(mode.refresh_size)) % table.scores.shape[0]
            ).astype(jnp.int32)


def streamed_step(ctx: StepContext, state: MercuryState, x_stream, y_train,
                  shard_indices):
    """Host-stream step: train on the batch whose indices were drawn
    ``prefetch_depth`` steps ago (the ``PendingSelection`` ring's front —
    its rows arrive pre-gathered in ``x_stream``, ``[1, S, ...]``), then
    draw the selection for step t+depth and emit its GLOBAL indices as a
    third, non-donated output for the host pipeline. The lookahead draw
    for step u consumes the key positions of rng_u's 8-way split that the
    resident driver consumes AT step u (``sel_ks[0]``/``sel_ks[2]``,
    carried in ``psel.rng``) — so uniform and pool selections are
    bit-identical to ``replicated``; the scoretable draw sees a
    depth-step-stale table, and the carried draw-time ``scaled_probs``
    keep the reweighting unbiased. This driver's own: the lookahead draw,
    the ring, ``next_gidx``."""
    mode = ctx.mode
    xs = x_stream[0]
    rng, keys, stream, ema = _unstack_carry(state)
    psel = unstack(state.pending_sel)
    # rng_{t+depth}'s split — the lookahead draw's key material.
    sel_ks = jax.random.split(jax.random.wrap_key_data(psel.rng), 8)
    rows = StreamedRows(xs, y_train, shard_indices, psel)
    drawn = STREAMED[mode.sampler](ctx, state, rows, keys, stream, ema)
    upd, var_ratio = _train_and_probe(ctx, state, rng, drawn)
    stream, ema, tel = drawn.stream, drawn.ema, drawn.tel

    # --- lookahead draw for step t+depth ---------------------------------
    next_scaled = jnp.ones((mode.batch_size,), jnp.float32)
    scoretable, sel_counts, hist = state.scoretable, state.sel_counts, None
    if mode.use_scoretable:
        # Write-back first (train logits re-score the trained slots; async:
        # the post-train EMA BEFORE the lookahead normalize, so the next
        # draw smooths against the freshest mean), then draw from the
        # freshest table this host can have.
        table_after, ema, tel = rescore_trained(
            ctx, drawn, upd["logits"], ema, tel)
        n_slots = table_after.shape[0]
        probs_next = table_probs(table_after, ema.value, mode.is_alpha)
        next_slots = next_sel = _table_draw(mode, sel_ks[2], probs_next)
        next_scaled = probs_next[next_sel] * n_slots
        if not mode.async_refresh:
            next_slots = jnp.concatenate([_window_ahead(
                mode, drawn.table.table, mode.depth), next_sel])
        scoretable, sel_counts = commit_table(mode, state, drawn, table_after)
        if mode.telemetry:
            # Clip over the table the NEXT draw normalizes (the freshest
            # distribution this step produced).
            tel = (clip_fraction(table_after, ema.value, mode.is_alpha),
                   tel[1])
            hist = table_histogram(mode, table_after)
    else:
        # Uniform/pool: the draw is param-independent, so running it depth
        # steps early with rng_{t+depth}'s stream key reproduces the
        # resident sequence exactly.
        stream, next_slots = next_pool(stream, sel_ks[0], mode.emit_size)
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
    new_state, metrics = _finish(
        ctx, state, upd, drawn, stream, ema, keys.next, tel,
        {"pending_sel": new_psel}, scoretable, sel_counts, hist,
        var_ratio)
    return new_state, metrics, next_gidx


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
    """Build the jitted train step: mode → stages → driver → ``shard_map``
    → ``jit`` (``train/mode.py``, ``stages.py``, ``samplers.py``; the
    drivers are :func:`resident_step` and :func:`streamed_step`).

    Returns ``step_fn(state, x_train, y_train, shard_indices) →
    (new_state, metrics)``: ``x_train``/``y_train`` the device-resident
    train arrays, ``shard_indices`` the ``[W, L]`` per-worker index matrix
    (sharded over the data axis). uint8 image rows may arrive flat —
    ``[N, H*W*C]`` (``[W, L, H*W*C]`` sharded, ``[W, S, H*W*C]`` streamed)
    with ``image_shape=(H, W, C)`` — as ``Trainer`` hands them on the
    selection ingest (``StepMode.ingest_path``): the pool's gather is then
    a dense row gather and the resident set is never relaid out.

    ``scan_steps > 1``: the driver wrapped in ``lax.scan`` inside the same
    ``shard_map`` program — one host dispatch covers the chunk, each metric
    comes back as a ``[scan_steps]`` array. ``scoring_model`` (optional):
    a second module over the same params at ``config.scoring_dtype`` for
    the candidate-scoring forward — the reweight divides by the realized
    probabilities, so a lower-precision scorer does not bias the loss.

    ``trace_facts`` (optional) is filled as the step is traced with what
    only the trace knows: ``bn_moment_units``, how many conv+BN units of
    the scoring forward take their batch statistic from input moments
    (``models/resnet.py::_closing_unit``), and for rows of per-token labels
    ``head_kernel_rows`` / ``head_plain_rows``, how many rows of the
    scoring pass take loss and hits from the kernel over vocabulary blocks
    and how many from whole logits (``sampling/importance.py::
    sequence_loss``), and ``rope_kernel_sites`` / ``rope_plain_sites``, how
    many operands of a decoder's attention one forward makes from their
    products in one pass and how many by the plain forms
    (``models/decoder.py::CausalDecoder.operand_sites``); ``Trainer``
    reports them as the instants ``trainer/bn_moment_units``,
    ``trainer/head_kernel_rows`` and ``trainer/rope_kernel_sites``.

    SHARDING CONTRACT (graftlint Layer 3, ``lint/sharding.py``,
    docs/LINT.md): the inputs are pinned with ``with_sharding_constraint``
    before they enter the shard_map — ``x_train``/``y_train`` to the data
    spec, ``shard_indices`` to ``P(axis)`` — so a caller handing in foreign
    layouts pays one visible reshard here instead of GSPMD rewriting
    layouts inside the step. ``io_constraints=False`` drops the pins (the
    plan's ``sharding_constraints`` budget then fails — that is the point).
    """
    mode = StepMode.from_config(
        config, scan_steps=scan_steps, mesh_axes=dict(mesh.shape),
        param_specs_pinned=state_out_shardings is not None)
    axis = mode.axis
    # The update stage's per-leaf int8 compression reads the pinned specs.
    param_specs = None
    if state_out_shardings is not None:
        param_specs = jax.tree_util.tree_map(
            lambda s: s.spec, state_out_shardings[0].params
        )
    ctx = StepContext(
        mode=mode, model=model, scoring_model=scoring_model, tx=tx,
        mean=mean, std=std, image_shape=image_shape,
        rows=row_loss_and_score(mode),
        param_specs=param_specs, trace_facts=trace_facts,
    )

    if mode.host_stream:
        fn = functools.partial(streamed_step, ctx)
    elif mode.scan_steps > 1:
        def fn(state, x_train, y_train, shard_indices):
            def scan_body(s, _):
                return resident_step(ctx, s, x_train, y_train, shard_indices)

            return lax.scan(scan_body, state, None, length=mode.scan_steps)
    else:
        fn = functools.partial(resident_step, ctx)

    specs = _state_specs(axis, zero_sharding=mode.zero, **mode.state_fields())
    # Manual over the data axis only; GSPMD handles the auto axes.
    smap_kw = {"axis_names": frozenset({axis})} if mode.auto_axes else {}
    # host_stream: x is the per-worker streamed rows ([W, S, ...]), y the
    # replicated label table the in-graph gathers index; the third output
    # is the next selection's global indices, one row per worker.
    x_spec = P(axis) if mode.placement != "replicated" else P()
    y_spec = P(axis) if mode.data_sharded else P()
    out_specs_t = ((specs, P(), P(axis)) if mode.host_stream
                   else (specs, P()))
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

        # SHARDING CONTRACT (see docstring): pinned outside the shard_map.
        constrained_inner = sharded

        def pin(array, spec):
            return jax.lax.with_sharding_constraint(
                array, NamedSharding(mesh, spec))

        def sharded(state, x_train, y_train, shard_indices):
            return constrained_inner(
                state, pin(x_train, x_spec), pin(y_train, y_spec),
                pin(shard_indices, P(axis)))

    jit_kw = ({} if state_out_shardings is None
              else {"out_shardings": state_out_shardings})
    # host_stream also donates the streamed slab (arg 1): this step alone
    # consumes the rows (Trainer._host_stream_step pops, dispatches,
    # drops), and a slab live across the step would block the H2D-for-t+1
    # / compute overlap the lookahead buys. The non-donated next_gidx
    # output never aliases it. lint/audit.py's donation-consistency check
    # and Layer 3's memory ratchet pin this per plan.
    donated = (0, 1) if mode.host_stream else (0,)
    return jax.jit(sharded, donate_argnums=donated, **jit_kw)


def make_host_stream_prime(config: TrainConfig, mesh: Mesh):
    """Cold-start primer for ``data_placement="host_stream"``: one jitted
    shard_map that draws the first ``prefetch_depth`` selections UNIFORMLY
    (the reference's cold start — no scores exist yet), advancing the
    per-worker rng/stream chains exactly as :func:`streamed_step`'s
    lookahead would have, and fills the ``PendingSelection`` ring.

    Returns ``prime(state, shard_indices) -> (state, gidx)``, ``gidx``
    ``[depth, W, S]`` int32 global indices — one prefetch push per ring
    slot. Uniform/pool: the exact draws ``replicated`` makes at steps
    0..depth-1, so trajectories match from step 0; scoretable:
    uniform-with-replacement draws plus the round-robin refresh windows
    (unit ``scaled_probs`` keep steps 0..depth-1 unbiased)."""
    mode = StepMode.from_config(config, mesh_axes=dict(mesh.shape))
    axis, depth = mode.axis, mode.depth

    def prime(state: MercuryState, shard_indices):
        stream = unstack(state.stream)
        sel_rng = state.rng[0]
        slots_steps = []
        for i in range(depth):
            ks = jax.random.split(sel_rng, 8)
            if mode.use_scoretable:
                table = unstack(state.scoretable)
                n = table.scores.shape[0]
                # Uniform-with-replacement through the SAME draw the steady
                # state uses, on the flat distribution — consumes k_sel
                # exactly as the lookahead will.
                flat = jnp.full((n,), 1.0 / n, jnp.float32)
                slots_i = _table_draw(mode, ks[2], flat)
                if not mode.async_refresh:
                    slots_i = jnp.concatenate(
                        [_window_ahead(mode, table, i), slots_i])
            else:
                stream, slots_i = next_pool(stream, ks[0], mode.emit_size)
                slots_i = slots_i.astype(jnp.int32)
            slots_steps.append(slots_i)
            sel_rng = ks[7]
        slots = jnp.stack(slots_steps)                 # [depth, S]
        gidx = shard_indices[0][slots]                 # [depth, S] global
        psel = PendingSelection(
            slots=slots[None],
            scaled_probs=jnp.ones((1, depth, mode.batch_size), jnp.float32),
            rng=jax.random.key_data(sel_rng)[None],
        )
        new_state = state.replace(stream=restack(stream), pending_sel=psel)
        # [depth, 1, S]: stacked pushes, worker row sharded P(axis).
        return new_state, gidx[:, None]

    specs = _state_specs(axis, zero_sharding=mode.zero, **mode.state_fields())
    sharded = shard_map(
        prime,
        mesh=mesh,
        in_specs=(specs, P(axis)),
        out_specs=(specs, P(None, axis)),
        check_vma=False,
    )
    return jax.jit(sharded)


def _make_epoch_scan(
    name: str, model, mean, std, eval_augmentation, mesh, axis,
    init: Callable[[], Tuple], accumulate: Callable[..., Tuple],
    token_rows: bool = False,
):
    """One-dispatch pass over a pre-batched split: ``lax.scan`` over
    ``[nb, B, ...]`` uint8 arrays — normalize, (``"iid"``: the reference's
    test transform, fixed key) inference-mode forward,
    ``accumulate(carry, logits, labels, mask)`` — jitted as ``name``. With
    ``mesh``, each scanned batch's sample dimension is sharded over the
    data axis (``in_shardings`` only — GSPMD partitions the forward and
    inserts the reduction collectives), so eval uses every device instead
    of leaving W−1 idle."""

    def epoch(params, batch_stats, images_b, labels_b, valid_b):
        variables = {"params": params}
        if batch_stats:
            variables["batch_stats"] = batch_stats

        def body(carry, batch):
            imgs_u8, labels, mask = batch
            if token_rows:
                # rows of token ids are the model's inputs as they are
                outputs = model.apply(variables, imgs_u8, train=False)
                return accumulate(carry, outputs, labels, mask), None
            imgs = normalize_images(imgs_u8, mean, std)
            if eval_augmentation == "iid":
                from mercury_tpu.data.transforms import eval_transform_iid

                # A fixed key is what "same transform every eval" means.
                imgs = eval_transform_iid(jax.random.key(0), imgs)
            logits = model.apply(variables, imgs, train=False)
            return accumulate(carry, logits, labels, mask), None

        out, _ = jax.lax.scan(body, init(), (images_b, labels_b, valid_b))
        return out

    epoch.__name__ = epoch.__qualname__ = name
    if mesh is None:
        return jax.jit(epoch)
    from jax.sharding import NamedSharding

    from mercury_tpu.parallel.mesh import replicated_sharding

    rep = replicated_sharding(mesh)
    batched = NamedSharding(mesh, P(None, axis))  # [nb, B, ...]: shard B
    return jax.jit(
        epoch,
        in_shardings=(rep, rep, batched, batched, batched),
        out_shardings=tuple(rep for _ in init()),
    )


def make_per_class_epoch(
    model, mean: np.ndarray, std: np.ndarray, num_classes: int,
    eval_augmentation: str = "none",
    mesh: Optional[Mesh] = None, axis: str = "data",
) -> Callable[..., Tuple[jax.Array, jax.Array]]:
    """One-dispatch per-class (hits, totals) over pre-batched eval arrays —
    :func:`make_eval_epoch`'s scan with a scatter-add per batch instead of
    scalar sums. Returns int32 ``[C]`` pairs for host-side division."""

    def init():
        return (jnp.zeros((num_classes,), jnp.int32),
                jnp.zeros((num_classes,), jnp.int32))

    def accumulate(carry, logits, labels, mask):
        maski = mask.astype(jnp.int32)
        hit = (jnp.argmax(logits, -1) == labels).astype(jnp.int32) * maski
        hits, totals = carry
        return hits.at[labels].add(hit), totals.at[labels].add(maski)

    return _make_epoch_scan("per_class_epoch", model, mean, std,
                            eval_augmentation, mesh, axis, init, accumulate)


def make_eval_epoch(
    model, mean: np.ndarray, std: np.ndarray, eval_augmentation: str = "none",
    mesh: Optional[Mesh] = None, axis: str = "data",
    token_rows: bool = False, use_pallas: bool = False,
) -> Callable[..., Tuple[jax.Array, jax.Array, jax.Array]]:
    """One-dispatch full-split eval → ``(loss_sum, correct, count)``: the
    reference's ``evaluate`` walks a DataLoader batch-by-batch from the host
    (``pytorch_collab.py:201-234``), ~24 dispatches; here it is one.
    ``eval_augmentation="iid"`` applies the reference IID path's *test*
    transform — resize(33) → random crop(32) (``exp_dataset.py:63-68``; yes,
    it random-crops at eval) — with a fixed key so eval stays deterministic;
    the live non-IID path normalizes only (``cifar10/data_loader.py:92-96``).
    ``token_rows``: rows of token ids with per-token labels, read through
    the step's own loss seam (``stages.row_fns``): the loss of a row is the
    mean over its positions, its hit the share predicted right; nothing
    differentiates the pass, so under ``use_pallas`` both come from the
    kernel over vocabulary blocks, at shapes it takes.
    """
    rows = row_fns(token_rows, use_pallas and token_rows)

    def init():
        return (jnp.zeros(()), jnp.zeros(()), jnp.zeros(()))

    def accumulate(carry, outputs, labels, mask):
        logits = rows.reduce(outputs, labels)
        losses = rows.loss(logits, labels)
        maskf = mask.astype(jnp.float32)
        hit = rows.hits(logits, labels)
        loss_sum, correct, count = carry
        return (
            loss_sum + jnp.sum(losses * maskf),
            correct + jnp.sum(hit * maskf),
            count + jnp.sum(maskf),
        )

    return _make_epoch_scan("eval_epoch", model, mean, std,
                            eval_augmentation, mesh, axis, init, accumulate,
                            token_rows)
