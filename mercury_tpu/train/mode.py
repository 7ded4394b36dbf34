"""The step's mode: which program a ``TrainConfig`` asks for, decided once.

``StepMode.from_config`` is the one place that reads the sampler, placement
and gate fields of a :class:`~mercury_tpu.config.TrainConfig` (which
validates nothing itself), refuses the combinations that do not compose, and
returns a frozen value holding everything the traced step reads: the sampler
kind as one closed set, the placement, the sizes, the trace-time gates and
the hyperparameters the stages bake in. ``make_train_step``,
``make_host_stream_prime``, the state's spec tree and ``Trainer`` read it
and derive nothing themselves. No jax in this module.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Mapping, Optional, Tuple

import numpy as np

from mercury_tpu.config import TrainConfig
from mercury_tpu.data.tokens import TOKEN_DATASETS

#: The sampler ladder, one closed set. ``uniform``: the baseline; ``pool``
#: scores a fresh candidate pool every step; ``pipelined`` trains on the batch
#: the previous step drew; ``cadence`` rescores every K-th step and redraws
#: from the cache between; ``groupwise`` / ``scoretable`` persist scores over
#: the shard; ``scoretable_async`` leaves the refresh to the scorer fleet.
SAMPLERS = ("uniform", "pool", "pipelined", "cadence", "groupwise",
            "scoretable", "scoretable_async")
PLACEMENTS = ("replicated", "sharded", "host_stream")
#: TrainConfig fields the stages bake into the program, copied as they are.
_BAKED = ("is_alpha", "ema_alpha", "table_decay", "label_smoothing",
          "importance_score", "augmentation", "cutout", "fused_input")


@dataclasses.dataclass(frozen=True)
class StepMode:
    sampler: str                  # one of SAMPLERS
    placement: str                # one of PLACEMENTS
    axis: str                     # the mesh's data axis (manual SPMD)
    auto_axes: Tuple[str, ...]    # the mesh's other axes, left to GSPMD
    tp_active: bool               # one of them is larger than 1
    scan_steps: int
    # --- sizes (rows per worker per step)
    batch_size: int
    pool_size: int                # candidates scored (= batch when uniform)
    refresh_size: int             # scoretable: window rescored each step
    emit_size: int                # host_stream: rows the stream carries
    depth: int                    # host_stream: lookahead (0 elsewhere)
    cadence: int                  # score_refresh_every
    probe_every: int              # variance_probe_every
    # --- trace-time gates
    telemetry: bool
    zero: bool                    # ZeRO-1 optimizer sharding
    compression: str              # "none" | "stochastic" | "int8"
    use_pallas: bool
    sync_stats: bool              # importance statistics psum'd over axis
    # --- what the stages bake into the program
    is_alpha: float
    ema_alpha: float
    table_decay: float
    label_smoothing: float
    importance_score: str         # "loss" | "grad_norm"
    augmentation: str
    cutout: bool
    fused_input: bool
    scoring_bf16: bool
    moe_aux_weight: Optional[float]   # None: the model sows no MoE loss
    token_rows: bool              # rows of token ids, per-token labels

    # ------------------------------------------------------------ the kind
    @property
    def use_is(self) -> bool:
        return self.sampler != "uniform"

    @property
    def use_scoretable(self) -> bool:
        return self.sampler in ("scoretable", "scoretable_async")

    @property
    def async_refresh(self) -> bool:
        return self.sampler == "scoretable_async"

    @property
    def host_stream(self) -> bool:
        return self.placement == "host_stream"

    @property
    def data_sharded(self) -> bool:
        return self.placement == "sharded"

    @property
    def stat_axis(self) -> Optional[str]:
        return self.axis if self.sync_stats else None

    # ----------------------------------------------------- telemetry gates
    @property
    def use_ledger(self) -> bool:
        """Selection-count ledger (obs/sampler_health.py): rides with the
        scoretable, trace-gated like all telemetry — without it the state
        carries no ledger and the program is the seed's (digest-enforced)."""
        return self.use_scoretable and self.telemetry

    @property
    def use_probe(self) -> bool:
        """Grad-variance probe (sampler_dist/var_ratio): one extra scoring
        pass over the trained batch every probe_every steps; meaningless
        without IS weights."""
        return self.telemetry and self.probe_every > 0 and self.use_is

    # ------------------------------------------------- the optional fields
    def state_fields(self) -> Dict[str, bool]:
        """Which optional ``MercuryState`` fields this mode's state
        carries — the keywords of ``_state_specs`` /
        ``mercury_state_out_shardings``."""
        return dict(
            has_groupwise=self.sampler == "groupwise",
            has_pending=self.sampler == "pipelined",
            has_cached_pool=self.sampler == "cadence",
            has_scoretable=self.use_scoretable,
            has_pending_sel=self.host_stream,
            has_sel_counts=self.use_ledger,
        )

    def create_state_fields(self) -> Dict[str, Any]:
        """The same gates as ``create_state``'s keywords (sizes where it
        wants a size, 0 for "absent")."""
        has = self.state_fields()
        return dict(
            with_groupwise=has["has_groupwise"],
            pending_batch_size=self.batch_size if has["has_pending"] else 0,
            cached_pool_size=self.pool_size if has["has_cached_pool"] else 0,
            with_scoretable=has["has_scoretable"],
            with_sel_counts=has["has_sel_counts"],
            stream_depth=self.depth,
            stream_emit_size=self.emit_size,
            stream_batch_size=self.batch_size,
            zero_sharding=self.zero,
        )

    def ingest_path(self, dtype) -> str:
        """Which ingest the step builds for rows of ``dtype``: ``"select"``
        — uint8 rows under the noniid crop/flip, one dense pass over the
        raw bytes (``data.pipeline.select_crop_flip``) — or ``"chain"`` —
        ``normalize_images`` then the augmentation (float inputs, ``iid``,
        ``none``, cutout) — or ``"tokens"``: rows of integer ids (a token
        dataset), which are the model's inputs as they are: no statistic
        to normalise by, and ``Trainer`` has held ``augmentation`` to
        ``"none"``. Read off what the step sees; no field picks it."""
        if np.issubdtype(np.dtype(dtype), np.signedinteger):
            return "tokens"
        select = (np.dtype(dtype) == np.uint8
                  and self.augmentation == "noniid" and not self.cutout)
        return "select" if select else "chain"

    # ------------------------------------------------------- the one ladder
    @classmethod
    def from_config(
        cls,
        config: TrainConfig,
        scan_steps: int = 1,
        mesh_axes: Optional[Mapping[str, int]] = None,
        param_specs_pinned: bool = False,
    ) -> "StepMode":
        """Derive and validate. ``mesh_axes`` is the mesh's ``{axis name:
        size}`` (default: the data axis alone); ``param_specs_pinned`` says
        the caller pins per-leaf parameter layouts on the step's outputs
        (``state_out_shardings``), which int8 compression needs under an
        active second axis."""
        axis = config.mesh_axis
        use_is = config.use_importance_sampling
        pool_size = config.candidate_pool_size if use_is else config.batch_size
        batch_size = config.batch_size
        # In-graph telemetry is gated at TRACE time: with telemetry=False
        # no diagnostic is traced and the program is the seed step's (no
        # reliance on XLA DCE — benchmarks/telemetry_overhead.py compares
        # the jaxprs).
        telemetry = bool(config.telemetry)

        # Mesh axes beyond the data axis (a dp×tp mesh's "model" axis) are
        # left to GSPMD: the step is manual-SPMD over `axis` only, and XLA
        # partitions every stage over the auto axes per the params'
        # committed shardings (transformer_tp_shardings).
        mesh_axes = {axis: 1} if mesh_axes is None else mesh_axes
        auto_axes = tuple(a for a in mesh_axes if a != axis)
        tp_active = any(mesh_axes[a] > 1 for a in auto_axes)
        if tp_active and config.zero_sharding:
            raise ValueError(
                "zero_sharding flattens params to a vector, which would force "
                "an all-gather of the sharded params; use fsdp_parallel or "
                "plain allreduce when a second mesh axis shards the params"
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
            raise ValueError(
                f"unknown grad_compression {config.grad_compression!r}")
        # int8 under an active auto axis compresses each leaf in its
        # natural shape, wire-chunked along a dim the auto axes don't claim
        # (parallel/collectives.py compressed_pmean_tree_sharded).
        if (tp_active and config.grad_compression == "int8"
                and not param_specs_pinned):
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
        if pipelined and config.sampler != "pool":
            # The overlap recovered ~2% on chip for the pool sampler
            # (BASELINE.md): scoring costs FLOPs, not exposed latency, and
            # the other samplers already shrink it.
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
        # Async refresh: the round-robin scoring forward moves OFF the step
        # onto the host scorer fleet (sampling/scorer_fleet.py); the hot
        # program carries zero scoring FLOPs/collectives (graftlint's
        # `async` plan budgets pin this down).
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
            # scorer; ignoring them silently would mislead.
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
        # A token dataset's rows carry per-token labels: the loss seam
        # reduces each to its mean token loss (stages.row_loss_and_score).
        token_rows = config.dataset in TOKEN_DATASETS
        if token_rows and (
                config.sampler != "pool" or config.label_smoothing
                or config.importance_score != "loss"
                or config.data_placement == "host_stream"
                or (telemetry and config.variance_probe_every)):
            raise ValueError(
                "rows of per-token labels train under sampler='pool' "
                "(uniform, pool, pipelined or cadence) scored by their "
                "loss, device-resident, without label smoothing and "
                "without the variance probe: the other paths read one "
                "class label or whole logits a row")
        probe_every = int(config.variance_probe_every)
        if probe_every < 0:
            raise ValueError(
                f"variance_probe_every must be >= 0, got {probe_every}"
            )
        if telemetry and probe_every > 0 and use_is and scan_steps > 1:
            raise ValueError(
                "variance_probe_every > 0 requires scan_steps == 1: scanned "
                "chunks mean their metrics, which would blend the probe's "
                "-1.0 off-step sentinel into the ratio"
            )
        if config.data_placement not in PLACEMENTS:
            raise ValueError(
                f"unknown data_placement {config.data_placement!r}"
            )
        # "sharded": x_train/y_train arrive as [W, L, ...] rows sharded
        # P(axis) — each device holds its own worker's samples, gathers are
        # shard-local. "host_stream": pixels never enter the graph; the
        # step's second input is the [W, S, ...] uint8 rows the host
        # pre-gathered for THIS step, and the step emits the NEXT
        # selection's global indices as a third, non-donated output
        # (step.py::streamed_step, data/stream.py).
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
        if config.fused_input:
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

        sampler = ("uniform" if not use_is
                   else "pipelined" if pipelined
                   else "cadence" if use_cadence
                   else "groupwise" if use_groupwise
                   else "scoretable_async" if async_refresh
                   else "scoretable" if use_scoretable
                   else "pool")
        # THE emit_size rule — streamed rows per worker per step: the
        # candidate pool (pool sampler: selection happens in-step), the
        # refresh window + the pre-drawn batch (scoretable), the batch
        # alone under async refresh (the fleet scores its own windows).
        emit_size = (batch_size if async_refresh
                     else (refresh_size + batch_size) if use_scoretable
                     else pool_size)
        return cls(
            sampler=sampler,
            placement=config.data_placement,
            axis=axis,
            auto_axes=auto_axes,
            tp_active=tp_active,
            scan_steps=int(scan_steps),
            batch_size=int(batch_size),
            pool_size=int(pool_size),
            refresh_size=refresh_size,
            emit_size=int(emit_size),
            depth=depth if host_stream else 0,
            cadence=cadence,
            probe_every=probe_every,
            telemetry=telemetry,
            zero=bool(config.zero_sharding),
            compression=config.grad_compression,
            use_pallas=bool(use_pallas),
            sync_stats=bool(use_is and config.sync_importance_stats),
            **{f: getattr(config, f) for f in _BAKED},
            # bf16 scoring end to end: scorer-only ingest sites (rows never
            # reused for training) emit bf16 directly.
            scoring_bf16=config.scoring_dtype == "bfloat16",
            moe_aux_weight=(config.moe_aux_weight
                            if config.moe_experts is not None else None),
            token_rows=token_rows,
        )
