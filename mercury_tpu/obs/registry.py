"""Central metric-key registry: every tag the training path may emit.

One flat ``key → one-line meaning`` dict, stdlib-only (graftlint's
metric-key layer AST-parses this file without importing jax — keep it a
pure literal plus trivial helpers). The registry is the contract between
the emitters (``train/step.py``, ``train/trainer.py``, ``data/stream.py``,
``sampling/scorer_fleet.py``, ``obs/*``) and the consumers (sinks, dashboards, the anomaly engine,
``docs/API.md``'s glossary): a key that is not here is a lint error, so a
renamed or fat-fingered metric fails CI instead of silently forking the
stream (``python -m mercury_tpu.lint --layer metrics``).
"""

from __future__ import annotations

from typing import Dict

#: Metric tags proper — ``prefix/name``, one row per scalar in the
#: stream. Grouped families (``sampler/table_age_{min,mean,max}``) are
#: spelled out: the registry is exact-match, expansion lives in docs.
METRIC_KEYS: Dict[str, str] = {
    # train/* — the step's own scalars
    "train/loss": "selected-batch reweighted loss (chunk mean under scan)",
    "train/acc": "selected-batch accuracy",
    "train/pool_loss": "mean score over the candidate pool",
    "train/sparse_rate": "gradient-compression sparsity (0 when off)",
    "train/moe_aux": "MoE load-balancing aux loss (0 when off)",
    "train/grad_norm": "global L2 norm of the post-allreduce gradient",
    # moe/* — routing of the last layer of routed experts, train pass
    # (a causal decoder, models/decoder.py; no other model emits them)
    "moe/held_pair_share":
        "share of the (token, expert) pairs that fell on experts held here",
    "moe/load_max_over_mean":
        "pairs of the busiest held expert over the mean of the held ones",
    "moe/bounded_share":
        "share of the train pass's routed layers (all of them) whose held "
        "pairs fit the bound on the sorted rows (1 where no bound is traced)",
    "moe/bias_moved_share":
        "share of the (token, choice) pairs that the router's selection bias "
        "chose and the unbiased scores would not (sigmoid routers only)",
    "train/eval_loss": "train-split eval loss (inference mode)",
    "train/eval_acc": "train-split eval accuracy (inference mode)",
    # test/* — eval pass over the held-out split
    "test/eval_loss": "test-split eval loss (inference mode)",
    "test/eval_acc": "test-split eval accuracy (inference mode)",
    # sampler/* — importance-sampling health (telemetry=True only)
    "sampler/ess": "normalized effective sample size of the IS weights",
    "sampler/clip_frac": "fraction of candidate scores at/below the floor",
    "sampler/ema_drift": "fresh score mean minus pre-update EMA",
    "sampler/table_age_min": "scoretable: youngest entry age (sweeps)",
    "sampler/table_age_mean": "scoretable: mean entry age (sweeps)",
    "sampler/table_age_max": "scoretable: oldest entry age (sweeps)",
    "sampler/score_staleness_mean":
        "async refresh: mean applied-chunk age (steps) since last tick",
    "sampler/score_staleness_max":
        "async refresh: oldest applied-chunk age (steps) since last tick",
    "sampler/refresh_lag_chunks":
        "async refresh: scored chunks queued but not yet applied",
    "sampler/chunks_rejected":
        "cumulative non-finite score chunks rejected by the apply guard",
    "sampler/is_active":
        "1 while importance sampling drives the draw; 0 once degraded "
        "to uniform (supervisor ladder level 3)",
    # sampler_dist/* — distribution-level sampler health
    # (obs/sampler_health.py). The in-graph half (histogram bins,
    # var_ratio) exists only under telemetry=True with the scoretable
    # sampler; the host-side half (coverage, gini, class spread, bias
    # audit) is derived from the selection-count ledger at the log gate
    # by SamplerHealthMonitor (single-controller runs).
    "sampler_dist/var_ratio":
        "grad-variance probe: IS/uniform grad-norm second-moment ratio "
        "(>= 1 means IS is losing; -1 on off-cadence steps)",
    "sampler_dist/frac_never_selected":
        "fraction of the dataset never drawn for training so far",
    "sampler_dist/gini":
        "Gini coefficient of per-sample selection counts (0 uniform)",
    "sampler_dist/class_share_min":
        "smallest per-class selection share over data share",
    "sampler_dist/class_share_max":
        "largest per-class selection share over data share",
    "sampler_dist/class_starved":
        "classes whose selection/data share ratio is below the floor",
    "sampler_dist/bias_chi2":
        "chi-square-per-slot drift of observed draws vs table probs",
    "sampler_dist/bias_ok":
        "1 while the inclusion-bias audit is within threshold, else 0",
    # score-table histogram, 16 log-spaced bins over [1e-6, 1e2);
    # under/overflow clamps into the end bins (counts total the table)
    "sampler_dist/score_hist/b00": "score-table histogram bin 0 count",
    "sampler_dist/score_hist/b01": "score-table histogram bin 1 count",
    "sampler_dist/score_hist/b02": "score-table histogram bin 2 count",
    "sampler_dist/score_hist/b03": "score-table histogram bin 3 count",
    "sampler_dist/score_hist/b04": "score-table histogram bin 4 count",
    "sampler_dist/score_hist/b05": "score-table histogram bin 5 count",
    "sampler_dist/score_hist/b06": "score-table histogram bin 6 count",
    "sampler_dist/score_hist/b07": "score-table histogram bin 7 count",
    "sampler_dist/score_hist/b08": "score-table histogram bin 8 count",
    "sampler_dist/score_hist/b09": "score-table histogram bin 9 count",
    "sampler_dist/score_hist/b10": "score-table histogram bin 10 count",
    "sampler_dist/score_hist/b11": "score-table histogram bin 11 count",
    "sampler_dist/score_hist/b12": "score-table histogram bin 12 count",
    "sampler_dist/score_hist/b13": "score-table histogram bin 13 count",
    "sampler_dist/score_hist/b14": "score-table histogram bin 14 count",
    "sampler_dist/score_hist/b15": "score-table histogram bin 15 count",
    # per-batch IS-weight (scaled_probs) histogram, 16 log-spaced bins
    # over [1e-4, 1e4); 1.0 is the uniform weight
    "sampler_dist/w_hist/b00": "IS-weight histogram bin 0 count",
    "sampler_dist/w_hist/b01": "IS-weight histogram bin 1 count",
    "sampler_dist/w_hist/b02": "IS-weight histogram bin 2 count",
    "sampler_dist/w_hist/b03": "IS-weight histogram bin 3 count",
    "sampler_dist/w_hist/b04": "IS-weight histogram bin 4 count",
    "sampler_dist/w_hist/b05": "IS-weight histogram bin 5 count",
    "sampler_dist/w_hist/b06": "IS-weight histogram bin 6 count",
    "sampler_dist/w_hist/b07": "IS-weight histogram bin 7 count",
    "sampler_dist/w_hist/b08": "IS-weight histogram bin 8 count",
    "sampler_dist/w_hist/b09": "IS-weight histogram bin 9 count",
    "sampler_dist/w_hist/b10": "IS-weight histogram bin 10 count",
    "sampler_dist/w_hist/b11": "IS-weight histogram bin 11 count",
    "sampler_dist/w_hist/b12": "IS-weight histogram bin 12 count",
    "sampler_dist/w_hist/b13": "IS-weight histogram bin 13 count",
    "sampler_dist/w_hist/b14": "IS-weight histogram bin 14 count",
    "sampler_dist/w_hist/b15": "IS-weight histogram bin 15 count",
    # perf/* — throughput accounting between log ticks
    "perf/steps_per_s": "steps per second since the previous log tick",
    "perf/examples_per_s": "examples per second since the previous log tick",
    "perf/flops_per_step": "XLA cost-analysis FLOPs of the fused step",
    "perf/mfu": "model FLOPs utilization against the device peak",
    # time/* — legacy aliases kept for dashboard continuity
    "time/step": "seconds per step (legacy alias)",
    "time/images_per_sec": "examples per second (legacy alias)",
    # data/* — host_stream input pipeline
    "data/stall_s": "input-attributable pop() wait since the last log tick",
    "data/queue_depth": "committed prefetch batches ready at log time",
    "data/h2d_bytes": "staged host-to-device bytes since the last log tick",
    # scorer/* — the async scorer fleet (sampling/scorer_fleet.py) and
    # the scorer service front (sampling/scorer_service.py). The
    # service emits the aggregates plus one stream per tenant t0..t3
    # (scorer_tenants is capped at 4 so the per-tenant keys stay an
    # exact-match enumeration).
    "scorer/throughput": "async refresh: rows scored per second by the fleet",
    "scorer/queue_depth":
        "scorer service: ready chunks queued across all tenants",
    "scorer/staleness":
        "scorer service: max tenant staleness, steps since the latest "
        "delivered chunk's snapshot",
    "scorer/slo_breaches":
        "scorer service: cumulative SLO breach events across tenants",
    "scorer/throughput/t0": "scorer service: tenant 0 rows per second",
    "scorer/throughput/t1": "scorer service: tenant 1 rows per second",
    "scorer/throughput/t2": "scorer service: tenant 2 rows per second",
    "scorer/throughput/t3": "scorer service: tenant 3 rows per second",
    "scorer/queue_depth/t0": "scorer service: tenant 0 ready-queue depth",
    "scorer/queue_depth/t1": "scorer service: tenant 1 ready-queue depth",
    "scorer/queue_depth/t2": "scorer service: tenant 2 ready-queue depth",
    "scorer/queue_depth/t3": "scorer service: tenant 3 ready-queue depth",
    "scorer/staleness/t0": "scorer service: tenant 0 staleness (steps)",
    "scorer/staleness/t1": "scorer service: tenant 1 staleness (steps)",
    "scorer/staleness/t2": "scorer service: tenant 2 staleness (steps)",
    "scorer/staleness/t3": "scorer service: tenant 3 staleness (steps)",
    "scorer/slo_breaches/t0": "scorer service: tenant 0 SLO breach events",
    "scorer/slo_breaches/t1": "scorer service: tenant 1 SLO breach events",
    "scorer/slo_breaches/t2": "scorer service: tenant 2 SLO breach events",
    "scorer/slo_breaches/t3": "scorer service: tenant 3 SLO breach events",
    # obs/* — the metric stream observing itself
    "obs/dropped": "cumulative records dropped by the bounded queue",
    # anomaly/* — flight-recorder health accounting
    "anomaly/triggers": "cumulative anomaly triggers fired this run",
    # host/* — cross-host aggregates merged onto host 0's records
    # (obs/aggregate.py; multi-process runs only)
    "host/reporting": "hosts whose telemetry shard has data this pass",
    "host/min/step_time_s": "fastest host's latest seconds per step",
    "host/max/step_time_s": "slowest host's latest seconds per step",
    "host/spread/step_time_s": "max-min cross-host seconds per step",
    "host/min/stall_s": "smallest per-host input stall this interval",
    "host/max/stall_s": "largest per-host input stall this interval",
    "host/spread/stall_s": "max-min cross-host input stall",
    "host/min/queue_depth": "shallowest per-host prefetch queue",
    "host/max/queue_depth": "deepest per-host prefetch queue",
    "host/spread/queue_depth": "max-min cross-host prefetch queue depth",
    "host/straggler_ratio": "max/median per-host step time (rolling)",
    # prof/* — offline device-time attribution folded back after an
    # anomaly-armed profiler capture (obs/profile_parse.py)
    "prof/scope_frac/mercury_scoring": "device-time share: scoring scope",
    "prof/scope_frac/mercury_grad_sync": "device-time share: grad sync",
    "prof/scope_frac/mercury_augmentation":
        "device-time share: augmentation scope",
    "prof/scope_frac/mercury_input_fuse":
        "device-time share: fused uint8 ingest kernel",
    "prof/scope_frac/mercury_optimizer": "device-time share: optimizer",
    "prof/scope_frac/unattributed":
        "device-time share outside every named scope",
    "prof/h2d_overlap_frac": "H2D copy time hidden under device compute",
    "prof/idle_frac": "device-lane idle gaps over the capture span",
    # threads/* — host thread-fleet liveness (obs/writer.py
    # host_thread_stats + per-queue depths merged at the log gate);
    # audited by graftlint Layer C against lint/thread_manifest.json
    "threads/alive": "live python threads in this process",
    "threads/daemon": "live daemon threads (the worker fleet)",
    "threads/queue_depth/metrics": "async metric records pending drain",
    "threads/queue_depth/prefetch": "committed prefetch batches pending",
    "threads/queue_depth/scorer": "scored chunks pending application",
    # lint/* — runtime retrace guard (lint/tracecheck.py), emitted at the
    # log gate only while Trainer.arm_retrace_guard() has a monitor armed
    "lint/retrace_events": "jaxpr traces observed since the last log tick",
    "lint/compile_count": "XLA backend compiles observed since the last tick",
    # fault/* — deterministic fault-injection plane (faults.py), emitted
    # at the log gate only when config.fault_spec is non-empty
    "fault/injected": "cumulative faults fired by the injection plane",
    "fault/armed": "fault schedule entries still pending (not yet fired)",
    # supervisor/* — host supervisor (runtime/supervisor.py), emitted at
    # the log gate only when config.supervise is on
    "supervisor/level":
        "degradation ladder level: 0 async, 1 sync, 2 frozen, 3 uniform",
    "supervisor/restarts": "cumulative successful unit restarts",
    "supervisor/degradations": "cumulative one-level ladder descents",
    "supervisor/recoveries": "cumulative one-level ladder ascents",
    "supervisor/units_down": "registered units currently failing liveness",
    "supervisor/slo_breaches":
        "cumulative registered-SLO breach events (rising edges)",
    "supervisor/slo_latched":
        "registered SLOs currently latched (breached and not released)",
    "supervisor/probe_pinned":
        "1 while a latched SLO pins the recovery probe, else 0",
    # checkpoint/* — durable checkpoint writer (train/checkpoint.py)
    "checkpoint/write_failures":
        "cumulative failed checkpoint write attempts (retries included)",
    # plan/* — auto-planner (plan/auto.py via train/trainer.py)
    "plan/candidates_considered":
        "plans the auto-planner enumerated for this run's decision",
    "plan/replan_count":
        "cumulative elastic re-plan evaluations since construction",
}

#: Control-plane event kinds (``obs/events.py`` journal rows). Same
#: contract as METRIC_KEYS: a PURE literal (graftlint Layer M parses it
#: with ``ast.literal_eval``), every kind emitted somewhere in the
#: package (GLM04 errors otherwise), every kind documented in the
#: docs/OBSERVABILITY.md kind catalog. ``subsystem/name`` shape; the
#: subsystem names the journal lane in the merged Perfetto timeline.
EVENT_KINDS: Dict[str, str] = {
    # supervisor/* — ladder + restart lifecycle (runtime/supervisor.py)
    "supervisor/slo_breach":
        "a registered SLO latched (rising edge); roots a breach episode",
    "supervisor/slo_release":
        "a latched SLO stopped breaching; parent = the breach event",
    "supervisor/degrade":
        "one-level ladder descent; parent = breach/exhaustion/probe event",
    "supervisor/recover":
        "one-level ladder ascent; parent = the successful probe",
    "supervisor/restart": "a dead host unit was restarted successfully",
    "supervisor/restart_failed": "a unit restart attempt raised",
    "supervisor/exhausted":
        "a unit ran out of restart budget; parent = the failed restart",
    "supervisor/probe_ok":
        "recovery probe succeeded; parent = the degrade it is probing",
    "supervisor/probe_failed":
        "recovery probe raised; parent = the degrade it is probing",
    # scorer/* — multi-tenant scorer service (sampling/scorer_service.py)
    "scorer/tenant_admitted": "a tenant queue was admitted at startup",
    "scorer/wedged": "a tenant was wedged by the scorer_wedge fault",
    "scorer/starved":
        "a tenant's staleness/queue SLO latched (starvation decision)",
    "scorer/snapshot": "a new params snapshot opened a scoring epoch",
    # fault/* — injection plane (faults.py); chaos runs self-describe
    "fault/fired": "a scheduled fault fired at its hook point",
    # elastic/* — (W, L) resharding (train/elastic.py)
    "elastic/reshard_begin": "elastic restore started; detail has old/new W,L",
    "elastic/reshard_end": "elastic restore finished; parent = reshard_begin",
    "elastic/replan":
        "auto-planner re-evaluated the plan after a (W, L) change; "
        "detail carries both scored tables",
    # plan/* — auto-planner decision (train/trainer.py)
    "plan/selected":
        "plan resolution at construction; detail carries the scored table",
    # checkpoint/* — durable generations (train/checkpoint.py)
    "checkpoint/written": "a checkpoint generation was written durably",
    "checkpoint/verified": "a generation passed manifest verification",
    "checkpoint/fallback":
        "restore rejected a generation and fell back to an older one",
    "checkpoint/schema_drift":
        "a restored manifest's state_schema_sha differs from HEAD's",
    # anomaly/* — flight recorder (obs/anomaly.py)
    "anomaly/triggered":
        "an anomaly trigger fired; detail carries the flight-record path",
}

#: Bookkeeping fields that ride along in every record but are not metric
#: tags (no ``prefix/`` namespace, never plotted as series of their own).
RECORD_FIELDS = ("step", "time", "epoch")


def is_registered(key: str) -> bool:
    """True when ``key`` is a known metric tag or bookkeeping field."""
    return key in METRIC_KEYS or key in RECORD_FIELDS
