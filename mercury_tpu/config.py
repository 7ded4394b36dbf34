"""Structured run configuration.

Replaces the reference's module-level config globals
(``pytorch_collab.py:21-33`` — alpha, seed, world_size, model name, noniid
flag, epochs, linearly-scaled lr, log-dir naming) with a frozen dataclass
that can be serialized into run names and checkpoints.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    """All knobs for a Mercury-style training run.

    Defaults mirror the reference's pinned parameters (see BASELINE.md):
    ResNet-18 on CIFAR-10, 4 workers, batch 32, Adam at 0.001×world_size with
    cosine decay over 100 epochs, Dirichlet(0.5) non-IID partition, a
    320-candidate importance pool per step drawn down to 32.
    """

    # Model / data ----------------------------------------------------------
    model: str = "resnet18"          # key into mercury_tpu.models.create_model
    dataset: str = "cifar10"         # "cifar10" | "cifar100" | "synthetic"
    num_classes: Optional[int] = None  # None → derived from dataset; set → validated
    image_size: int = 32             # ingest resize for dataset="imagefolder";
                                     # array datasets carry their own shapes
    # Token models and datasets (models/decoder.py, data/tokens.py). The
    # model's name selects the published widths; what a chip holds of it is
    # the cut: (layers kept, first expert held, experts held) or, with a
    # share of the attention heads too, (layers, first expert, experts,
    # first head, heads); None = whole.
    # The vocabulary rows held are ``num_classes`` (None: the dataset's own
    # slice); ``seq_len`` is the length of the token dataset's sequences.
    model_cut: Optional[Tuple[int, ...]] = None
    seq_len: int = 8192

    # Parallelism -----------------------------------------------------------
    world_size: int = 4              # number of data-parallel workers (mesh size)
    mesh_axis: str = "data"          # name of the data-parallel mesh axis
    # Parallelism-plan selection. "" (default): manual — the knobs below
    # are taken exactly as set. "auto": the auto-planner
    # (plan/auto.py::resolve_plan_config) scores the graftlint plan
    # matrix from the committed cost goldens (Layer P FLOP/byte
    # attribution, memory_analysis() footprints, analytic collective
    # latency) at trainer construction and overwrites the plan-defining
    # knobs (zero_sharding, data_placement, refresh_mode, scorer_backend,
    # fused_input, scoring_dtype, …) with the ranked winner's; the scored
    # table is journaled as plan/selected, and restore_elastic re-plans
    # on a (W, L) change (elastic/replan). A concrete plan name
    # ("dp", "zero", "hs", "async", …) forces that plan's knob set while
    # still recording where it ranked. DESIGN.md §16.
    plan: str = ""
    # auto-planner: per-device memory budget in bytes. Candidates whose
    # committed memory_analysis() peak (W-scaled for sharded plans)
    # exceeds it are HARD-excluded from the feasible set (their rejection
    # carries rule="memory_budget"). 0 = unbounded.
    plan_memory_budget_bytes: int = 0
    # Tensor parallelism WITHIN each data-parallel worker: a second mesh
    # axis of this size carries the Megatron column/row split of every
    # transformer block (parallel/tensor.py). The Mercury IS step runs
    # manual-SPMD over the data axis and leaves the model axis to GSPMD,
    # so scoring forward, draw, reweighted backward, and the stat psum all
    # execute TP-sharded. Requires the transformer family
    # (model="transformer" | "vit") and
    # num_heads % tensor_parallel == 0; total devices =
    # world_size × tensor_parallel.
    tensor_parallel: int = 1
    model_axis: str = "model"        # name of the tensor-parallel mesh axis
    # FSDP (ZeRO-3 analogue) WITHIN each data-parallel worker: a second
    # mesh axis of this size over which every large parameter leaf is
    # sharded along its largest divisible dimension
    # (parallel/fsdp.py:fsdp_shardings); optimizer moments inherit the
    # layout (ZeRO-2 for free). The Mercury IS step runs manual-SPMD over
    # the data axis and leaves this axis to GSPMD — XLA inserts the
    # per-layer weight all-gathers and gradient reduce-scatters — so the
    # scoring forward, draw, reweighted backward, and stat psum all
    # execute with params fully sharded. Works for ANY model family
    # (unlike tensor_parallel's Megatron layout). Total devices =
    # world_size × fsdp_parallel. Mutually exclusive with
    # tensor_parallel > 1 and zero_sharding.
    fsdp_parallel: int = 1
    fsdp_axis: str = "fsdp"          # name of the FSDP mesh axis
    # Train-data placement. "replicated" (default): the full train arrays
    # are device-resident and every worker gathers its shard rows by
    # global index — fine for CIFAR, a dead end past it. "sharded": each
    # worker's shard rows are MATERIALIZED as a [W, L, ...] array sharded
    # P(data) — per-device train-data memory is 1/W of the shard matrix,
    # and in multi-controller runs each host transfers only its own
    # workers' rows (the load_partition_data_distributed_cifar10 pattern,
    # cifar10/data_loader.py:214-245). Train-split eval gathers from the
    # host copy. "host_stream": pixels stay HOST-resident (numpy / memmap)
    # and only each step's rows cross PCIe — the step emits the NEXT
    # selection's global indices as an extra output (a lookahead draw,
    # mirroring pipelined_scoring's carried-PendingBatch design) and a
    # background thread gathers those rows into pre-allocated staging
    # buffers and commits them with the step's batch sharding while the
    # current steps execute (data/stream.py), so H2D fully overlaps
    # compute. Device train-data memory drops from the full dataset to
    # prefetch_depth batches (+ the [L] score table for the scoretable
    # sampler — the only piece importance sampling needs on-device).
    # Multi-controller capable: each process runs its own prefetch
    # pipeline over its local workers' rows (see stream_shard_mode) and
    # device_puts only to its addressable shards — zero cross-host pixel
    # traffic. Requires sampler="pool"|"scoretable", scan_steps=1, no
    # pipelined_scoring / score-refresh cadence.
    data_placement: str = "replicated"
    # host_stream: how many batches the prefetch pipeline keeps in flight
    # (the lookahead distance of the in-graph index draw). The first
    # prefetch_depth batches are drawn uniformly (cold start). 2 =
    # classic double buffering.
    prefetch_depth: int = 2
    # host_stream: worker threads for the host-side row gather / image
    # decode (data/stream.py sources). 0 = gather inline on the single
    # prefetch thread.
    decode_workers: int = 0
    # host_stream, multi-controller: which rows of the [W, S] index slab
    # each process's prefetch pipeline gathers and transfers.
    # - "auto": "local" when process_count > 1, "replicated" otherwise
    #   (the single-process fast path is untouched);
    # - "local": each process gathers ONLY its own workers' rows
    #   (host_worker_slice) and device_puts them to its addressable
    #   shards — the global streamed batch is assembled from per-host
    #   slabs with zero cross-host pixel traffic. Forceable in a
    #   single-process run to exercise the per-host assembly path
    #   (that is how tier-1 covers it on CPU);
    # - "replicated": the legacy single-pipeline full-slab path.
    #   Rejected when process_count > 1: a process can only read its
    #   addressable rows of the in-flight index output, so a full-slab
    #   gather would need a collective from the prefetch thread.
    stream_shard_mode: str = "auto"
    # host_stream: carry the stream cursor + PendingSelection ring +
    # scoretable through checkpoints (they are MercuryState fields, so
    # same-world restores always resume exactly). Under restore_elastic
    # this toggle gates the mid-epoch carry: True reshards the score
    # table by new worker ownership and carries the epoch-fraction
    # cursor; False restarts sampler state fresh at the restored step.
    stream_checkpoint_cursor: bool = True

    # Optimization ----------------------------------------------------------
    batch_size: int = 32             # per-worker train batch (exp_dataset.py:11,24)
    base_lr: float = 0.001           # scaled by world_size (pytorch_collab.py:28)
    optimizer: str = "adam"          # the reference uses Adam (pytorch_collab.py:262)
    num_epochs: int = 100
    steps_per_epoch: Optional[int] = None  # None → derived from dataset size
    step_budget: float = 1e7         # stop when step×world_size exceeds this (pytorch_collab.py:71)
    weight_decay: float = 0.0
    label_smoothing: float = 0.0
    # Linear LR warmup from 0 to the peak over this many steps (microsteps
    # when grad_accum_steps > 1), then cosine decay over the REMAINING
    # steps (the schedule ends with the run). Must be < total steps.
    # 0 = reference behavior (cosine from step 0, pytorch_collab.py:62).
    warmup_steps: int = 0
    # Gradient accumulation: each step contributes its gradient to an
    # accumulator (optax.MultiSteps) and the parameter update applies every
    # A-th step — effective batch A×batch_size per worker without the
    # activation memory. steps/log/eval cadences still count microsteps.
    grad_accum_steps: int = 1
    # ZeRO-1: shard the optimizer state over the data axis. Gradients are
    # reduce-scattered (each worker owns 1/W of the flattened parameter
    # vector), the optimizer updates only that chunk, and the updates are
    # all-gathered back onto the replicated params — optimizer memory and
    # update compute drop by W with the same collective volume as a plain
    # allreduce (reduce-scatter + all-gather IS the ring allreduce).
    zero_sharding: bool = False

    # Importance sampling ---------------------------------------------------
    use_importance_sampling: bool = True
    # "pool": score a fresh candidate pool each step and draw from it
    #   (the live Trainer.update_samples path, pytorch_collab.py:89-117);
    # "groupwise": persistent per-sample importance over the whole shard
    #   with sliding-window refresh + draws from the newest group
    #   (Groupwise_Sampler, util.py:94-160 — library-only in the reference,
    #   a first-class strategy here);
    # "scoretable": persistent [L] score table over the whole shard with
    #   amortized incremental refresh (sampling/scoretable.py): each step
    #   draws the train batch from the ENTIRE shard's distribution but
    #   re-scores only refresh_size round-robin candidates (plus the
    #   just-trained batch, whose scores fall out of the training forward
    #   for free) — scoring FLOPs drop from candidate_pool_size to
    #   refresh_size per step with no cadence staleness cliff: every
    #   entry age-decays toward the EMA mean (table_decay) so stale
    #   extremes fade and nothing starves.
    sampler: str = "pool"
    presample_batches: int = 10      # candidate pool = 10×batch (pytorch_collab.py:95)
    is_alpha: float = 0.5            # score = loss + alpha·EMA (pytorch_collab.py:111)
    ema_alpha: float = 0.9           # EMA smoothing factor (util.py:202)
    # What the candidate scorer computes from the pool logits:
    # - "loss": per-sample CE (the reference's score, pytorch_collab.py:102)
    # - "grad_norm": ||softmax − onehot||₂ — the exact CE-gradient norm
    #   w.r.t. the logits, the variance-optimal upper-bound score of
    #   Katharopoulos & Fleuret (arXiv:1803.00942). Same cost; the
    #   reweighting stays unbiased for any score.
    importance_score: str = "loss"
    sync_importance_stats: bool = True  # north-star: psum (sum_loss, count) across workers
    # Score-refresh cadence (pool sampler only): score a fresh candidate
    # pool every K-th step and CACHE the resulting sampling distribution;
    # the K-1 steps in between redraw their train batch from the cached
    # pool (fresh multinomial draws + fresh augmentation, same probs).
    # Scoring is the dominant IS cost (a pool/batch-sized extra forward
    # per step — the reference pays it every step, pytorch_collab.py:95),
    # so cadence K amortizes that cost by K at the price of K-step-stale
    # scores. The 1/(N·p) reweighting still matches the distribution the
    # batch was ACTUALLY drawn from, so the estimator stays unbiased for
    # the cached scores' selection. 1 = reference behavior (fresh pool
    # every step). Measured guidance (BASELINE.md): where IS is benefit-
    # neutral (CNN/image regime) K=8 prices it at 0.79x uniform; in the
    # win regime (heavy-tailed gradient norms, e.g. transformers past the
    # easy bulk) stale scores give the step advantage back — keep K=1.
    score_refresh_every: int = 1
    # Scoretable sampler: how many shard slots the per-step round-robin
    # refresh re-scores (the amortized scoring forward's batch). Full-shard
    # staleness bound: every slot is re-scored at least once per
    # ceil(L / refresh_size) steps. 64 ≈ 5× fewer scoring FLOPs than the
    # reference's 320-candidate pool at the default geometry.
    refresh_size: int = 64
    # Scoretable sampler: per-step geometric decay of every table entry
    # toward the EMA mean score (score ← μ + γ·(score − μ)). Entries
    # refreshed a steps ago carry weight γ^a on their stale deviation —
    # 0.98 halves a stale extreme in ~34 steps, about one full refresh
    # cycle at L≈2200/refresh 64. 1.0 disables the decay (scores persist
    # until re-scored, the groupwise behavior).
    table_decay: float = 0.98
    # Scoretable sampler: where the round-robin refresh forward runs.
    # - "sync": in-graph, inside the fused step (the default — refresh_size
    #   scoring FLOPs per step on the critical path);
    # - "async": on a background scorer fleet (sampling/scorer_fleet.py) —
    #   host threads re-score round-robin chunks against a periodically-
    #   snapshotted copy of the params and stream (slots, scores) chunks
    #   into the device table between steps, staleness-weighted by
    #   table_decay**age. The fused step's refresh branch compiles away:
    #   zero scoring FLOPs/collectives in the hot program (the graftlint
    #   `async` plan budgets enforce this), at the price of score ages
    #   measured in steps. Requires sampler="scoretable"; single-controller
    #   (one-process) runs only — the fleet snapshots params and scores
    #   against one process's table copy, with no cross-process
    #   consistency protocol for the streamed (slots, scores) chunks.
    refresh_mode: str = "sync"
    # Async refresh only: background scoring threads. One is enough on the
    # CPU smoke; more overlap more scoring forwards with the hot loop when
    # host cores are spare.
    scorer_workers: int = 1
    # Async refresh only: snapshot the live params for the fleet every
    # K steps. Smaller = fresher scores, more device copies; the staleness
    # telemetry (sampler/score_staleness_*) shows where the knob sits.
    snapshot_every: int = 16
    # Async refresh only: minimum idle time (seconds) a scorer worker
    # inserts between chunks. 0.0 = score continuously (max freshness —
    # right when host cores/devices are spare). On core-constrained hosts
    # (the CPU smoke runs on one core) a continuously-scoring fleet steals
    # the compute the step needs; a throttle trades refresh rate for step
    # time, and the table's age-decay absorbs the extra staleness.
    scorer_throttle_s: float = 0.0
    # Async refresh only: WHERE the scoring program runs.
    # - "host": the PR-8 fleet — vmapped scoring forwards jitted onto the
    #   default placement, driven by host threads (scorer_throttle_s
    #   paces the duty cycle).
    # - "device": the scoring forward is its own pjit program compiled
    #   onto a dedicated mesh slice (parallel/mesh.py
    #   reserve_scorer_slice: spare devices when any exist, else a second
    #   program on the training mesh's devices — the CPU two-program
    #   degradation). Params reach the slice by snapshot RPC
    #   (device_put), and scoring is snapshot-paced: each params push
    #   triggers at most a queue's worth of chunk scorings, so the duty
    #   cycle is bounded by snapshot_every and scorer_throttle_s is
    #   meaningless (validated to 0). The chunk protocol — (slots,
    #   scores, snapshot_step) over the bounded queue — is unchanged, so
    #   apply_async_chunk and the staleness weighting are reused
    #   verbatim and the applies are bit-identical to the host backend
    #   at equal snapshot age (test-enforced).
    scorer_backend: str = "host"
    # Scorer service tenancy: >1 runs the ScorerService front
    # (sampling/scorer_service.py) with per-tenant bounded queues and
    # weighted-fair chunk scheduling. Tenant 0 feeds THIS trainer's
    # table; extra tenants model co-hosted scoring consumers and are
    # drained/discarded by the trainer after accounting (their telemetry
    # streams under scorer/*/t{i}). 1..4.
    scorer_tenants: int = 1
    # Comma-separated per-tenant drain weights ("2,1": tenant 0 gets 2/3
    # of scored chunks). "" = equal weights. len must equal
    # scorer_tenants; entries > 0.
    scorer_tenant_weights: str = ""
    # Scorer-service SLO: max tolerated score staleness (steps between a
    # tenant's latest delivered chunk's snapshot and the current step)
    # before the supervisor walks the ladder one level (async → sync →
    # frozen → uniform). 0 disables. Arm at a few multiples of
    # snapshot_every: staleness persistently above that means the
    # service has wedged or starved.
    slo_score_staleness_max: int = 0
    # Scorer-service SLO: queue-depth high-water. A tenant's ready queue
    # sitting at or above this depth when the supervisor ticks means the
    # consumer stopped draining (backpressure breach) — same ladder
    # walk. 0 disables.
    scorer_queue_highwater: int = 0
    # Optional dtype override for the SCORING forward only (scores only
    # rank, so bf16 scoring is safe even when training compute is f32) —
    # e.g. "bfloat16" halves the refresh forward's bandwidth. None = score
    # with compute_dtype (the training model).
    scoring_dtype: Optional[str] = None
    # Pipelined scoring (pool sampler only): step t trains on the batch
    # selected at step t-1 and scores the NEXT pool with the same params —
    # the train fwd/bwd and the scoring forward become independent, so XLA
    # overlaps the scoring with the gradient collective. This is the proper
    # realization of the reference's commented-out background-thread
    # allreduce overlap (pytorch_collab.py:154-156) and matches its
    # dataflow: update_samples for step t+1 runs before optimizer.step()
    # (:158-164), i.e. selection uses pre-update params.
    pipelined_scoring: bool = False

    # Augmentation ----------------------------------------------------------
    # "noniid": pad-4 random crop + hflip (the live hetero pipeline,
    #   cifar10/data_loader.py:83-96);
    # "iid": resize(35)→crop(32)→hflip→random affine (exp_dataset.py:25-32);
    # "none": normalize only.
    augmentation: str = "noniid"
    cutout: bool = False             # Cutout(16) — defined-but-unused in the
                                     # reference (data_loader.py:57-76); opt-in here

    # Non-IID partition -----------------------------------------------------
    noniid: bool = True
    dirichlet_alpha: float = 0.5     # pytorch_collab.py:21
    min_shard_size: int = 10         # retry floor (cifar10/data_loader.py:145)

    # BatchNorm strategy: "local" lets per-worker stats drift (reference
    # behavior — gloo workers never sync BN); "sync" psums batch stats.
    batch_norm: str = "sync"

    # Gradient compression:
    # - "stochastic": the unbiased sign·max·Bernoulli quantizer the
    #   reference left as dead code (`quantize_tensor`, util.py:65-70;
    #   "sparse rate" logging at pytorch_collab.py:184-185), applied
    #   per-worker BEFORE the psum. Estimator semantics only — the psum
    #   still moves dense f32 (XLA collectives don't exploit value
    #   sparsity).
    # - "int8": a genuinely bandwidth-compressed allreduce — both wire
    #   phases (all-to-all reduce-scatter + all-gather) move int8 payloads
    #   with per-chunk scales and stochastic rounding (unbiased), 4× fewer
    #   bytes than the f32 psum (parallel/collectives.py
    #   `compressed_allreduce_mean`). Composes with zero_sharding: the
    #   ZeRO gradient reduce-scatter and update all-gather both run int8
    #   on the wire.
    grad_compression: str = "none"

    # Bookkeeping -----------------------------------------------------------
    seed: int = 102                  # pytorch_collab.py:22
    eval_every: int = 200            # steps (pytorch_collab.py:181)
    log_every: int = 100             # steps (pytorch_collab.py:170)
    # In-graph telemetry (obs/diagnostics.py): sampler-health scalars —
    # ESS of the importance weights, score-clip fraction, EMA drift,
    # global grad norm, and (scoretable sampler) table staleness — emitted
    # from inside the fused step as extra metric outputs. Gated at TRACE
    # time: with telemetry=False none of these ops exist in the compiled
    # program (the jaxpr is identical to the seed step; verified by
    # benchmarks/telemetry_overhead.py).
    telemetry: bool = True
    # In-graph grad-variance probe (obs/sampler_health.py): every K-th
    # step run ONE extra scoring-model microbatch pass over the trained
    # batch and emit sampler_dist/var_ratio — the estimated IS-vs-uniform
    # gradient second-moment ratio (the 1803.00942 gate signal; < 1 means
    # importance sampling is beating uniform). Observe-only. Requires
    # telemetry=True and scan_steps == 1; set K to a multiple of
    # log_every so the probe lands on logged records (non-probe steps
    # carry the -1.0 sentinel, which every consumer ignores). 0 disables
    # — and the probe is trace-time-gated, so the compiled program is
    # untouched when off.
    variance_probe_every: int = 0
    # Stdout heartbeat cadence (steps) for the async metric writer's
    # rate-limited one-line progress print; 0 disables the heartbeat.
    # Independent of log_every: metrics stream to JSONL/TensorBoard every
    # log_every steps, the terminal line appears every heartbeat_every.
    heartbeat_every: int = 100
    # Host-side step-timeline tracing (obs/trace.py): record named spans
    # around the trainer hot loop and the prefetch pipeline into a
    # bounded ring; on close() the trace exports as Chrome-trace JSON
    # (perfetto-loadable) next to the metrics. Host-only — the traced
    # device program is identical either way; disabled call sites cost
    # one shared no-op context manager (~100 ns, measured by
    # benchmarks/telemetry_overhead.py).
    trace: bool = False
    # Span-ring capacity: the trace keeps the LAST trace_capacity spans
    # (bounded memory for arbitrarily long runs); the same ring feeds
    # the flight recorder's post-mortem span window.
    trace_capacity: int = 4096
    # Anomaly engine + flight recorder (obs/anomaly.py): evaluate health
    # triggers continuously (non-finite loss/grad-norm, slow-step, ESS
    # collapse, input-stall breach, MFU floor) and dump a self-contained
    # flight_record_*.json on trigger. Value checks run on the metric
    # writer's drain thread (log cadence, zero training-thread cost);
    # the slow-step check is ~1 µs of host float math per step. Dumps
    # land in anomaly_dir (default: log_dir); with neither set, triggers
    # are detected and counted (anomaly/triggers) but nothing is
    # written.
    anomaly_detection: bool = True
    anomaly_window: int = 64         # metric records kept in the ring
    # slow_step trigger: step time > factor × rolling-median step time
    # (armed after 16 samples so compiles don't false-positive); 0
    # disables.
    anomaly_slow_step_factor: float = 3.0
    anomaly_cooldown_steps: int = 200  # min steps between flight dumps
    # On trigger, arm jax.profiler for the next M steps (kernel-level
    # trace into {anomaly_dir|log_dir}/profile). 0 disables.
    anomaly_profile_steps: int = 0
    anomaly_dir: Optional[str] = None  # flight-record dir; None → log_dir
    # Fault injection for tests/CI ONLY: at the first log tick at or
    # after this step, poison the HOST metric record's train/loss with
    # NaN (the traced program is untouched) so the non_finite trigger
    # path can be exercised end-to-end. 0 disables.
    anomaly_inject_nan_step: int = 0
    # --- SLOs: declarative health floors, evaluated continuously by the
    # anomaly engine.
    # MFU floor (fraction of peak). Checked only when the device peak is
    # known AND cost analysis produced FLOPs (never on CPU hosts). The
    # committed TPU headline is 0.0185; 0.01 trips on a >~2x regression.
    slo_mfu_floor: float = 0.01
    # ESS floor for sampler/ess (0..1; 0 disables): below it the IS
    # weight distribution has collapsed onto a few samples.
    slo_ess_floor: float = 0.0
    # host_stream: max input-attributable stall fraction of wall time
    # per log interval (benchmarks budget is 0.10 steady-state; 0.25
    # flags a sustained 2.5x breach). 0 disables.
    slo_stall_frac_max: float = 0.25
    # Selection-collapse ceiling on sampler_dist/gini (the selection
    # -count ledger's Gini, 0 = uniform coverage, →1 = all draws on a
    # vanishing slice): above it the `selection_collapse` trigger fires
    # the flight recorder with the live histograms attached. 0 disables.
    # Note a healthy importance sampler is deliberately non-uniform —
    # arm this well above the run's steady-state Gini.
    slo_selection_gini_max: float = 0.0
    # Per-class starvation floor: a class whose share of draws falls
    # below this fraction of its share of the data counts as starved
    # (sampler_dist/class_starved), and any starved class fires the
    # `class_starvation` trigger. Also the monitor's starvation
    # definition when triggers are disarmed. 0 disables the trigger
    # (the monitor then uses its 0.2 default for the metric).
    slo_class_starvation_share: float = 0.0
    # `is_losing` patience: consecutive LOGGED probe records with
    # sampler_dist/var_ratio >= 1 (IS not beating uniform) before the
    # trigger fires. Needs variance_probe_every > 0 to mean anything.
    # 0 disables.
    slo_var_ratio_patience: int = 0
    # --- cross-host telemetry (obs/aggregate.py): merge per-host metric
    # shards into host/{min,max,spread}/* + host/straggler_ratio on
    # host 0's records. "auto" → "files" when process_count > 1, off
    # otherwise. "files" tails the metrics.h{p}.jsonl shards on the
    # writer's drain thread (needs a log_dir shared across hosts);
    # "allgather" runs a small dedicated jitted gather on the log
    # cadence instead (no shared filesystem needed — the fused step is
    # never touched, so Layer-2/3 digests are identical either way).
    crosshost_telemetry: str = "auto"   # auto | off | files | allgather
    # Rolling per-host step-time window behind host/straggler_ratio.
    crosshost_window: int = 8
    # straggler trigger: max/median per-host step time above this factor
    # fires the flight recorder (multi-process only; 0 disables).
    anomaly_straggler_factor: float = 2.0
    # Control-plane event journal (obs/events.py): append-only
    # events.h{p}.jsonl of every supervisor/scorer/fault/elastic/
    # checkpoint/anomaly decision with causal parent_id links, flushed
    # on the metric writer's drain thread. Host-side only — the traced
    # program is byte-identical either way. Needs log_dir; on-by-default
    # because emission is a buffered dict append (~µs, measured by
    # benchmarks/telemetry_overhead.py's journal arm).
    event_journal: bool = True
    # Live scrape plane (obs/serve.py): localhost HTTP endpoint with
    # /healthz (liveness + ladder level), /statusz (manifest, ladder,
    # tenant queues, event tail) and /metricsz (OpenMetrics text from
    # the latest host record). 0 (default) disables — no thread, no
    # socket; > 0 binds that port on host 0 only. Port 0 cannot request
    # an ephemeral port from the config (use StatusServer directly in
    # tests for that).
    serve_port: int = 0
    log_dir: Optional[str] = None
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 1000     # steps; 0 disables
    # Write cadence checkpoints on a background thread (the device→host
    # fetch stays synchronous; serialization/IO overlap training).
    # Single-process only; multi-controller saves stay synchronous.
    async_checkpoint: bool = False
    # Keep the newest N checkpoint generations, pruning older ones after
    # each successful save; 0 keeps everything (seed behavior).
    checkpoint_keep: int = 3
    # Retry transient checkpoint-write OSErrors this many times with
    # exponential backoff before surfacing the failure; every failed
    # attempt counts into checkpoint/write_failures.
    checkpoint_write_retries: int = 2
    checkpoint_retry_backoff_s: float = 0.25
    # Write a sha256 manifest sidecar (whole-file + per-leaf digests)
    # next to each cadence checkpoint, and verify it on restore; a
    # checkpoint failing verification falls back to the next-older one
    # exactly like a torn file. Forces the msgpack backend for cadence
    # saves (the manifest describes those bytes).
    checkpoint_manifest: bool = True
    checkpoint_verify: bool = True

    # Fault injection + supervision -----------------------------------------
    # Deterministic fault schedule (mercury_tpu/faults.py grammar), e.g.
    # "scorer_die@step=40;ckpt_io_error@step=100,every=50". "" disables —
    # the hook sites are plain attribute checks and the traced program is
    # byte-identical (Layer-2/3 digest-enforced).
    fault_spec: str = ""
    # Host supervisor (runtime/supervisor.py): watch worker liveness on
    # the fit loop's cadence, restart dead scorer fleets / prefetch
    # pipelines with exponential backoff under a restart budget, and on
    # exhaustion walk the degradation ladder async → sync → frozen →
    # uniform instead of crashing the run.
    supervise: bool = False
    # Restarts allowed per supervised unit before it is declared
    # exhausted (budget resets when the ladder fully recovers to async).
    supervisor_restart_budget: int = 3
    supervisor_backoff_s: float = 0.5   # base of the exponential backoff
    # Probe cadence (steps) for climbing back up the degradation ladder;
    # 0 disables probing (a degraded run stays degraded).
    supervisor_probe_every: int = 200
    # Optional wall-clock liveness poll thread (seconds between polls);
    # 0 = step-cadence checks only (no extra thread — the tier-1
    # default, and sufficient while the trainer thread is healthy).
    supervisor_poll_s: float = 0.0
    # Degraded level 1 ("sync"): trainer-thread score refresh every K
    # steps (the async fleet is dead; K amortizes the on-thread forward).
    supervisor_sync_every: int = 16
    # Restore the latest checkpoint in checkpoint_dir (if any) at Trainer
    # construction — crash/preemption recovery without a separate restore
    # call. The sampler state is in the checkpoint, so the resumed
    # importance-sampling trajectory is bit-deterministic.
    auto_resume: bool = False
    data_dir: Optional[str] = None   # where CIFAR binaries live; None → search

    # Mixture-of-experts (transformer family only): number of Switch
    # experts per block's MLP; None = dense MLP. The router's
    # load-balancing aux loss enters the training objective scaled by
    # moe_aux_weight (Switch paper's α).
    moe_experts: Optional[int] = None
    moe_aux_weight: float = 0.01

    # Activation rematerialization (transformer family only): recompute
    # block activations in the backward pass (jax.checkpoint) — ~1 extra
    # forward of FLOPs for O(layers) less activation memory.
    remat: bool = False

    # Precision -------------------------------------------------------------
    compute_dtype: str = "bfloat16"  # MXU-friendly activations/matmuls
    param_dtype: str = "float32"

    # Kernels ---------------------------------------------------------------
    # None → auto (Pallas kernels on TPU, jax-native elsewhere);
    # True/False force. Pallas path requires label_smoothing == 0.
    use_pallas: Optional[bool] = None
    # uint8 image rows under augmentation="noniid" without cutout are
    # ingested by data.pipeline.augment_normalize on every path — one
    # dense pass over the raw bytes, crop/flip as exact selection, then
    # normalize (StepMode.ingest_path picks it from what the step
    # sees; PERF.md section 6, PR 26) — bit-identical at f32 to the
    # normalize_images + augment_batch chain (test-enforced). This flag
    # no longer changes the ingest: it names the scope the pass runs
    # under (mercury_input_fuse instead of mercury_augmentation, for the
    # profile bucket and the lint plan that key on it) and keeps its
    # validation: requires uint8 image data, augmentation="noniid",
    # cutout=False.
    fused_input: bool = False

    # Dispatch --------------------------------------------------------------
    # Train steps fused into ONE device dispatch via lax.scan. The reference
    # pays a host round-trip per step (DataLoader pull + gloo sync,
    # pytorch_collab.py:119-199); with a device-resident dataset the whole
    # K-step chunk runs as a single XLA program — it pays off where
    # per-dispatch host cost rivals step compute (small models).
    scan_steps: int = 1

    @property
    def lr(self) -> float:
        """Linear-scaling rule: base_lr × world_size (pytorch_collab.py:28)."""
        return self.base_lr * self.world_size

    @property
    def candidate_pool_size(self) -> int:
        """Per-step importance candidate count (10×32=320 in the reference)."""
        return self.presample_batches * self.batch_size

    def run_name(self) -> str:
        """Config-encoding run name (mirrors the log-dir naming scheme at
        ``pytorch_collab.py:33``)."""
        iid = "noniid" if self.noniid else "iid"
        isp = "is" if self.use_importance_sampling else "uniform"
        return (
            f"{self.model}_{self.dataset}_{isp}_{iid}_w{self.world_size}"
            f"_b{self.batch_size}_lr{self.lr:g}_seed{self.seed}"
        )

    def replace(self, **kw) -> "TrainConfig":
        return dataclasses.replace(self, **kw)
