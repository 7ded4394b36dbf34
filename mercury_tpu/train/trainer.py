"""Trainer — epoch orchestration, eval, logging, checkpoints.

Capability parity with the reference ``Trainer`` (``pytorch_collab.py:
36-250``) and the launch path ``my_run``/``init_processes``/``__main__``
(``:252-292``), collapsed into single-controller SPMD: no process forking,
no gloo world — one Python process drives a jitted ``shard_map`` step over
the device mesh.

Parity map:
- ``fit`` (``:56-72``): epoch loop, cosine schedule, step-budget break
  (``step×world_size > budget``, ``:71``); initial parameter sync
  (``average_model``, ``:84-87``) is implicit in replicated init.
- ``train`` (``:119-199``): the hot loop is one fused step
  (``mercury_tpu.train.step``); the global train loader's only live role —
  a step clock (``:127``, SURVEY.md §3.2) — becomes ``steps_per_epoch =
  n_train // batch_size``.
- ``evaluate`` (``:201-234``): full pass over train and test sets in
  inference mode, loss/accuracy meters.
- rank-0 printing/TensorBoard every 100/200 steps (``:170-195``) →
  non-blocking metric streaming (``obs/writer.py``: JSONL + TensorBoard +
  a rate-limited stdout heartbeat on ``heartbeat_every``), same tags; a
  run manifest and steps/s / examples/s / MFU accounting ride along
  (``obs/manifest.py``, ``obs/accounting.py``).
- wall-clock segment timing (``step/ff/is/bp/sync``, ``:129-168``): a fused
  XLA step has no host-visible segment boundaries — the trainer reports
  true ``step_time`` and throughput; the five segments are read off one
  profiler capture of the real step, by its named scopes (``perfbench``'s
  ``device_ms_per_step``, ``train_forward_share``, ``train_backward_share``,
  ``scoring_share`` and the ``mercury_grad_sync`` scope's share).
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from mercury_tpu.config import TrainConfig
from mercury_tpu.data import cifar
from mercury_tpu.data.partition import partition_data
from mercury_tpu.data.pipeline import ShardedDataset, eval_batches, make_sharded_dataset
from mercury_tpu.data.tokens import (
    DEFAULT_VOCAB,
    TOKEN_DATASETS,
    load_token_dataset,
)
from mercury_tpu.models import LM_WIDTHS, create_model
from mercury_tpu.obs.accounting import ThroughputMeter, analytic_flops_per_step
from mercury_tpu.obs.aggregate import (
    CrossHostGatherAggregator,
    HostShardAggregator,
    shard_filename,
)
from mercury_tpu.obs.anomaly import AnomalyEngine
from mercury_tpu.obs.manifest import build_run_manifest, write_run_manifest
from mercury_tpu.obs.sampler_health import SamplerHealthMonitor
from mercury_tpu.obs.trace import NULL_TRACER, SpanTracer
from mercury_tpu.obs.writer import (
    AsyncMetricWriter,
    HeartbeatShardSink,
    HeartbeatSink,
    JsonlSink,
    host_thread_stats,
    try_tensorboard_sink,
)
from mercury_tpu.parallel.mesh import make_mesh
from mercury_tpu.sampling.importance import token_logits
from mercury_tpu.train import checkpoint as ckpt
from mercury_tpu.train.mode import StepMode
from mercury_tpu.train.state import MercuryState, create_state, make_optimizer
from mercury_tpu.train.step import make_eval_epoch, make_train_step
from mercury_tpu.utils.logging import get_logger

_log = get_logger("mercury_tpu.train.trainer")


def build_dataset(config: TrainConfig, seed_offset: int = 0) -> ShardedDataset:
    """Load + partition per config (≡ ``__main__``'s parent-process dataset
    build, ``pytorch_collab.py:280-282`` → ``exp_dataset.py``)."""
    if config.dataset == "imagefolder":
        from mercury_tpu.data.imagefolder import load_imagefolder_dataset

        if not config.data_dir:
            raise ValueError("dataset='imagefolder' requires data_dir")
        train, test, info = load_imagefolder_dataset(
            config.data_dir, image_size=config.image_size,
            seed=config.seed + seed_offset,
        )
    elif config.dataset in TOKEN_DATASETS:
        train, test, info = load_token_dataset(
            config.dataset, config.num_classes or DEFAULT_VOCAB,
            config.seq_len, seed=config.seed + seed_offset,
        )
    else:
        train, test, info = cifar.load_dataset(
            config.dataset, data_dir=config.data_dir, seed=config.seed + seed_offset
        )
    # Dirichlet skew is over one class label a row; rows of per-token
    # labels are dealt out evenly.
    mode = ("hetero" if config.noniid
            and config.dataset not in TOKEN_DATASETS else "homo")
    shards = partition_data(
        train[1],
        config.world_size,
        mode=mode,
        alpha=config.dirichlet_alpha,
        seed=config.seed,
        min_size=config.min_shard_size,
    )
    return make_sharded_dataset(
        train, test, shards, info["mean"], info["std"], info["num_classes"],
        synthetic=info.get("synthetic", True),
        # host_stream: pixels stay host numpy arrays — the prefetch
        # pipeline streams selected rows; only labels go to device.
        device_resident=config.data_placement not in ("sharded",
                                                      "host_stream"),
    )


class Trainer:
    def __init__(
        self,
        config: TrainConfig,
        dataset: Optional[ShardedDataset] = None,
        mesh=None,
    ) -> None:
        # --- auto-planner (plan/auto.py): resolve config.plan to concrete
        # knob overrides BEFORE anything reads the config — the dataset
        # build keys off data_placement and the whole constructor below
        # keys off the resolved parallelism knobs. The decision (scored
        # table included) is journaled as plan/selected once the journal
        # exists, and restore_elastic re-runs the planner on a (W, L)
        # change (elastic/replan). DESIGN.md §16.
        self._plan_decision = None
        self._replan_count = 0
        if getattr(config, "plan", ""):
            from mercury_tpu.plan.auto import resolve_plan_config

            config, self._plan_decision = resolve_plan_config(
                config,
                device_kind=jax.devices()[0].device_kind,
                process_count=jax.process_count(),
            )
            _log.info(
                "auto-planner: plan=%r resolved to %s "
                "(%d candidates, %d feasible)",
                self._plan_decision and config.plan,
                self._plan_decision.selected,
                len(self._plan_decision.candidates),
                len(self._plan_decision.feasible),
            )
        self.config = config
        if config.serve_port < 0 or config.serve_port > 65535:
            raise ValueError(
                f"serve_port must be 0 (off) or a valid TCP port, "
                f"got {config.serve_port}"
            )
        self.dataset = dataset if dataset is not None else build_dataset(config)
        tp = config.tensor_parallel
        fs = config.fsdp_parallel
        if tp > 1 and fs > 1:
            raise ValueError(
                "tensor_parallel and fsdp_parallel are mutually exclusive "
                "(both claim the second mesh axis); pick one"
            )
        if mesh is not None:
            self.mesh = mesh
        elif tp > 1:
            from mercury_tpu.parallel.mesh import make_tp_mesh

            self.mesh = make_tp_mesh(config.world_size, tp,
                                     config.mesh_axis, config.model_axis)
        elif fs > 1:
            from mercury_tpu.parallel.mesh import make_tp_mesh

            self.mesh = make_tp_mesh(config.world_size, fs,
                                     config.mesh_axis, config.fsdp_axis)
        else:
            self.mesh = make_mesh(config.world_size, config.mesh_axis)
        if self.mesh.shape[config.mesh_axis] != config.world_size:
            raise ValueError(
                f"mesh axis size {self.mesh.shape[config.mesh_axis]} != "
                f"world_size {config.world_size}"
            )
        if tp > 1:
            if config.model not in ("transformer", "vit"):
                raise ValueError(
                    "tensor_parallel requires the transformer family "
                    f"(model='transformer'|'vit'), got {config.model!r}"
                )
            if config.model_axis not in self.mesh.axis_names or (
                self.mesh.shape[config.model_axis] != tp
            ):
                raise ValueError(
                    f"mesh must carry a {config.model_axis!r} axis of size "
                    f"{tp}; mesh axes: {dict(self.mesh.shape)}"
                )
        if fs > 1 and (
            config.fsdp_axis not in self.mesh.axis_names
            or self.mesh.shape[config.fsdp_axis] != fs
        ):
            raise ValueError(
                f"mesh must carry a {config.fsdp_axis!r} axis of size "
                f"{fs}; mesh axes: {dict(self.mesh.shape)}"
            )

        if (
            config.num_classes is not None
            and config.num_classes != self.dataset.num_classes
        ):
            raise ValueError(
                f"config.num_classes={config.num_classes} but dataset "
                f"{config.dataset!r} has {self.dataset.num_classes} classes"
            )

        bn_axis = config.mesh_axis if config.batch_norm == "sync" else None
        # Rows of token ids with per-token labels ([N, T]): the step's
        # mode refuses what cannot take such rows (StepMode.token_rows).
        self._token_rows = config.dataset in TOKEN_DATASETS
        model_kw = {}
        if config.model in LM_WIDTHS:
            if not self._token_rows:
                raise ValueError(
                    f"model={config.model!r} reads rows of token ids; "
                    f"dataset {config.dataset!r} has none "
                    f"(token datasets: {TOKEN_DATASETS})")
            from mercury_tpu.ops import on_tpu

            # the attention kernel under the step's own switch
            # (StepMode.use_pallas: None is "on the TPU")
            model_kw.update(
                cut=config.model_cut,
                use_pallas=(on_tpu() if config.use_pallas is None
                            else bool(config.use_pallas)))
        elif self._token_rows or config.model_cut is not None:
            raise ValueError(
                f"dataset {config.dataset!r} / model_cut="
                f"{config.model_cut} need a model of per-token logits "
                f"({sorted(LM_WIDTHS)}), got model={config.model!r}")
        if config.moe_experts is not None:
            if config.model not in ("transformer", "vit"):
                raise ValueError(
                    "moe_experts requires the transformer family "
                    f"(model='transformer'|'vit'), got {config.model!r}"
                )
            model_kw["moe_experts"] = config.moe_experts
        if config.remat:
            if config.model not in ("transformer", "vit"):
                raise ValueError(
                    "remat requires the transformer family "
                    f"(model='transformer'|'vit'), got {config.model!r}"
                )
            model_kw["remat"] = True
        self.model = create_model(
            config.model,
            num_classes=self.dataset.num_classes,
            compute_dtype=config.compute_dtype,
            param_dtype=config.param_dtype,
            bn_axis_name=bn_axis,
            **model_kw,
        )
        # Optional low-precision scorer: same architecture (params are
        # shared — flax modules are layout, not weights), different compute
        # dtype for the candidate-scoring forward only.
        self.scoring_model = None
        if config.scoring_dtype is not None:
            self.scoring_model = create_model(
                config.model,
                num_classes=self.dataset.num_classes,
                compute_dtype=config.scoring_dtype,
                param_dtype=config.param_dtype,
                bn_axis_name=bn_axis,
                **model_kw,
            )

        n_train = self.dataset.n_train
        self.steps_per_epoch = config.steps_per_epoch or max(n_train // config.batch_size, 1)
        total_steps = self.steps_per_epoch * config.num_epochs
        self.tx = make_optimizer(
            config.optimizer, config.lr, total_steps, config.weight_decay,
            grad_accum_steps=config.grad_accum_steps,
            warmup_steps=config.warmup_steps,
        )

        # Model-init sample and pending-batch shapes come from the dataset
        # itself: [H, W, C] for images, [T, F] for sequences (the BiLSTM
        # speech path — beyond the reference, which never trains MyLSTM).
        sample_shape = tuple(int(s) for s in self.dataset.x_train.shape[1:])
        label_shape = tuple(int(s) for s in self.dataset.y_train.shape[1:])
        is_image = len(sample_shape) == 3
        if not is_image and config.augmentation != "none":
            raise ValueError(
                f"augmentation={config.augmentation!r} needs image data; "
                f"dataset {config.dataset!r} has sample shape {sample_shape} — "
                "set augmentation='none'"
            )
        # (parameters do not depend on a token row's length: a short one)
        sample = (jnp.zeros((1, min(sample_shape[0], 128)), jnp.int32)
                  if self._token_rows
                  else jnp.zeros((1,) + sample_shape, jnp.float32))
        params_sharded = tp > 1 or fs > 1
        # The run's mode, decided once (train/mode.py): sampler kind,
        # placement, sizes, optional state fields. All below reads this.
        self._mode = mode = StepMode.from_config(
            config, mesh_axes=dict(self.mesh.shape),
            param_specs_pinned=params_sharded)
        self.state: MercuryState = create_state(
            jax.random.key(config.seed),
            self.model,
            self.tx,
            sample,
            config.world_size,
            int(self.dataset.shard_indices.shape[1]),
            # The IID augmentation pipeline crops to 32 regardless of the raw
            # image size (exp_dataset.py:26-27); noniid/none keep the
            # dataset's own sample shape.
            pending_sample_shape=((32, 32, sample_shape[-1])
                                  if config.augmentation == "iid"
                                  else sample_shape),
            pending_label_shape=label_shape,
            init_opt=not params_sharded,
            **mode.create_state_fields(),
        )
        if params_sharded:
            # Commit params in the sharded layout — Megatron column/row
            # under tensor_parallel, per-leaf largest-dim FSDP under
            # fsdp_parallel — and re-derive the optimizer state from the
            # sharded params (its moments inherit the layout). The train
            # step is manual-SPMD over the data axis only, so GSPMD reads
            # these committed shardings and partitions every matmul /
            # inserts the weight all-gathers over the second axis
            # (parallel/tensor.py, parallel/fsdp.py).
            if tp > 1:
                from mercury_tpu.parallel.tensor import (
                    transformer_tp_shardings,
                )

                if self.model.num_heads % tp != 0:
                    raise ValueError(
                        f"num_heads={self.model.num_heads} must be divisible "
                        f"by tensor_parallel={tp}"
                    )
                param_sh = transformer_tp_shardings(
                    self.state.params, self.mesh, config.model_axis
                )
            else:
                from mercury_tpu.parallel.fsdp import fsdp_shardings

                param_sh = fsdp_shardings(self.state.params, self.mesh,
                                          config.fsdp_axis)
            if jax.process_count() == 1:
                sh_params = jax.device_put(self.state.params, param_sh)
                # create_state skipped tx.init (init_opt=False): the single
                # init below inherits the sharded layout via zeros_like — no
                # transient replicated moment tree.
                sh_opt = self.tx.init(sh_params)
                self.state = self.state.replace(params=sh_params,
                                                opt_state=sh_opt)
            # Multi-controller: device_put cannot target other hosts'
            # devices — the placement happens inside globalize_state below
            # (params_sharding=param_sh), and the optimizer init runs as an
            # SPMD program on the placed params afterwards.
            self._tp_param_sh = param_sh
        else:
            self._state_out_shardings = None
        # Every step input and the whole state are committed on the mesh
        # HERE, before the first step: left uncommitted on device 0, a
        # several-device run re-broadcasts the dataset from that device on
        # every step and compiles a second time once the step's own
        # (committed) output feeds back as its input. Multi-controller runs
        # assemble the global arrays per process (globalize_*); a single
        # process places them directly.
        # Step-input train arrays. "sharded": materialize each worker's
        # shard rows as [W, L, ...] arrays sharded over the data axis —
        # per-device memory is one shard row, and in multi-controller runs
        # each host transfers only its own workers' rows; the dataset's
        # x_train/y_train stay host-side for eval. Built BEFORE the
        # dataset is globalized (it reads the process-local host copy,
        # identical on every process by seeded construction).
        data_sharded, host_stream = mode.data_sharded, mode.host_stream
        # Which ingest the step is built with (StepMode.ingest_path reads it
        # off the rows' dtype and the augmentation). On the selection
        # ingest the step's image rows are FLAT — [N, H*W*C] uint8, a
        # host-side view of x_train — so the pool's gather is a dense row
        # gather and the step never relays the resident set out (PERF.md
        # section 6, PR 26); dataset.x_train keeps its [N, H, W, C] face
        # for evaluate() and every other reader.
        self._ingest_path = mode.ingest_path(self.dataset.x_train.dtype)
        flat_rows = is_image and self._ingest_path == "select"
        self._image_shape = sample_shape if flat_rows else None
        # Read BEFORE the dataset is globalized, like the shard arrays
        # below: the process-local host copy (a view where x_train is
        # host-side already).
        host_rows = (np.asarray(self.dataset.x_train).reshape(
            self.dataset.n_train, -1) if flat_rows else None)
        if data_sharded:
            from mercury_tpu.parallel.distributed import (
                worker_shard_global_arrays,
            )

            self._step_x, self._step_y = worker_shard_global_arrays(
                self.dataset, self.mesh, config.mesh_axis,
                flat_rows=flat_rows,
            )
        if host_stream:
            # Stashed BEFORE the dataset is globalized (the [W, L] matrix
            # becomes a non-addressable P(data) array under
            # multi-controller): the drain-side slot→global-row mapping
            # (_refill_stream_pipe) needs the full host copy, which every
            # process holds identically by seeded construction.
            self._host_shard_indices = np.asarray(self.dataset.shard_indices)
        from mercury_tpu.parallel.distributed import (
            globalize_dataset,
            make_global_array,
        )

        if jax.process_count() > 1:
            from mercury_tpu.parallel.distributed import globalize_state

            self.state = globalize_state(
                self.state, self.mesh, config.mesh_axis,
                zero_sharding=config.zero_sharding,
                params_sharding=(self._tp_param_sh if params_sharded
                                 else None),
            )
            if params_sharded:
                # SPMD optimizer init on the TP-placed params, with the
                # moment layout pinned explicitly (opt_sharding_like):
                # zeros_like gives the partitioner no constraint to
                # propagate, so an unpinned init can come back replicated
                # — which would alias-clash with the TP-sharded step
                # outputs on the first donated call.
                from mercury_tpu.parallel.tensor import opt_sharding_like

                opt_shapes = jax.eval_shape(self.tx.init, self.state.params)
                self._tp_opt_sh = opt_sharding_like(
                    opt_shapes, self.state.params, self._tp_param_sh,
                    self.mesh,
                )
                tp_opt = jax.jit(
                    self.tx.init, out_shardings=self._tp_opt_sh
                )(self.state.params)
                self.state = self.state.replace(opt_state=tp_opt)
        self.dataset = globalize_dataset(
            self.dataset, self.mesh, config.mesh_axis,
            # host_stream: pixels must STAY host numpy — the per-host
            # prefetch pipelines stream selected rows; replicating
            # x_train onto every device is the thing the placement
            # exists to avoid.
            include_train_arrays=not data_sharded and not host_stream,
        )
        if params_sharded:
            # The moment layout is DERIVED (opt_sharding_like), not
            # inferred from live leaves: the structural param-path match
            # is exact for optax states, where sharding inference from a
            # jitted init's outputs is backend-dependent. The multi-
            # controller branch above already computed it; compute here
            # only on the single-process path.
            if getattr(self, "_tp_opt_sh", None) is None:
                from mercury_tpu.parallel.tensor import opt_sharding_like

                self._tp_opt_sh = opt_sharding_like(
                    self.state.opt_state, self.state.params,
                    self._tp_param_sh, self.mesh,
                )
            self._state_out_shardings = self._state_sharding_tree(
                self._tp_param_sh, self._tp_opt_sh)
        if jax.process_count() == 1:
            # Place the whole state in the step's own layout (a no-copy
            # no-op for params/opt a TP/FSDP branch above already
            # committed): the first step then donates real mesh buffers
            # and its output layout equals its input layout. device_put
            # accepts the prefix sharding pytree, so groupwise/pending
            # subtrees are covered too. (Multi-controller state is already
            # fully placed by globalize_state.)
            self.state = jax.device_put(self.state, self._state_shardings())
        if host_stream:
            # Pixels never become a step input: _step_x is the per-step
            # streamed batch (popped from the prefetch pipeline in
            # _host_stream_step). Labels are tiny ([N] int32) and the
            # in-graph gathers index them, so they live on device.
            from jax.sharding import PartitionSpec as P

            self._step_x = None
            self._step_y = make_global_array(
                np.asarray(self.dataset.y_train, np.int32), self.mesh, P())
        elif not data_sharded:
            from jax.sharding import PartitionSpec as P

            self._step_x = (make_global_array(host_rows, self.mesh, P())
                            if flat_rows else self.dataset.x_train)
            self._step_y = self.dataset.y_train
        # What only the trace of the step knows (make_train_step fills it
        # as the step is traced: first dispatch of the first fit()).
        self._trace_facts: Dict[str, int] = {}
        build_step = functools.partial(
            make_train_step, self.model, self.tx, config, self.mesh,
            self.dataset.mean, self.dataset.std,
            state_out_shardings=self._state_out_shardings,
            scoring_model=self.scoring_model,
            image_shape=self._image_shape,
            trace_facts=self._trace_facts,
        )
        self.train_step = build_step()
        # K-step chunked variant: one dispatch per config.scan_steps steps
        # (lax.scan over the same driver).
        self.scan_steps = max(int(config.scan_steps), 1)
        if self.scan_steps > 1:
            for name in ("log_every", "eval_every", "checkpoint_every"):
                every = getattr(config, name)
                if every and every % self.scan_steps != 0:
                    print(
                        f"warning: {name}={every} is not a multiple of "
                        f"scan_steps={self.scan_steps}; cadence actions fire "
                        "at most once per chunk (at chunk boundaries)"
                    )
        self.train_step_many = (build_step(scan_steps=self.scan_steps)
                                if self.scan_steps > 1 else None)
        # Shard eval batches over the mesh so evaluation uses every device
        # (single-controller only: multi-process would need global eval
        # arrays; there the replicated path is correct, just redundant).
        # Under TP/FSDP the explicit in_shardings would force the sharded
        # params to replicate; plain jit lets GSPMD partition eval too.
        eval_mesh = (self.mesh
                     if jax.process_count() == 1 and not params_sharded
                     else None)
        if jax.process_count() > 1:
            # Not a silent restriction: multi-controller eval still RUNS
            # (plain jit over host-replicated eval arrays), but every
            # process executes the full pass redundantly instead of
            # sharding batches over the mesh — sharded eval would need
            # globally-placed eval arrays, which nothing builds yet.
            _log.warning(
                "multi-controller run (%d processes): evaluation executes "
                "replicated — every process runs the full eval pass "
                "redundantly (correct, but no eval speedup from the mesh)",
                jax.process_count(),
            )
        self.eval_epoch = make_eval_epoch(self.model, self.dataset.mean,
                                          self.dataset.std,
                                          eval_augmentation=config.augmentation
                                          if config.augmentation == "iid"
                                          else "none",
                                          mesh=eval_mesh,
                                          axis=config.mesh_axis,
                                          token_rows=self._token_rows,
                                          use_pallas=mode.use_pallas)
        # --- fault-injection plane (mercury_tpu/faults.py): built BEFORE
        # every subsystem that hooks into it (metric writer, prefetch
        # pipeline, scorer fleet, checkpoint writes, the fit loop). None
        # when disabled — each hook site is a plain attribute check and
        # the traced step program is byte-identical (Layer-2/3 digests).
        # --- control-plane event journal (obs/events.py): every host
        # appends its supervisor/scorer/fault/elastic/checkpoint/anomaly
        # decisions to events.h{p}.jsonl with causal parent_id links.
        # Built FIRST among the host-side subsystems so every producer
        # below can take it at construction. Emission is a buffered dict
        # append; IO rides the metric writer's drain thread. Host-only —
        # the traced program is byte-identical with it on or off.
        self._journal = None
        if config.log_dir and config.event_journal:
            from mercury_tpu.obs.events import EventJournal

            self._journal = EventJournal(config.log_dir,
                                         jax.process_index())
            if self._plan_decision is not None:
                # Construction-time plan resolution, scored table and
                # per-rejection reasons in detail (report.py renders it
                # as the "Plan selection" section).
                self._journal.emit("plan/selected", -1,
                                   detail=self._plan_decision.detail())
        self._faults = None
        if config.fault_spec:
            from mercury_tpu.faults import FaultPlane

            self._faults = FaultPlane(config.fault_spec,
                                      journal=self._journal)
        # --- observability: run manifest + non-blocking metric stream ---
        # The manifest (resolved config, jax/jaxlib versions, mesh/device
        # topology, git sha) makes the metrics stream interpretable later;
        # the AsyncMetricWriter replaces the seed's synchronous per-log
        # float()+flush() with an enqueue — device_get and filesystem IO
        # happen on a background thread (obs/writer.py).
        sinks = []
        pidx = jax.process_index()
        if config.log_dir and pidx == 0:
            write_run_manifest(config.log_dir, config, self.mesh)
            sinks.append(JsonlSink(config.log_dir))
            sinks.append(try_tensorboard_sink(config.log_dir))
        if config.log_dir:
            # EVERY process (host 0 included) writes its own metric +
            # heartbeat shards — non-zero hosts used to be completely
            # dark, so a wedged host 3 left no post-mortem at all. The
            # shards also feed the cross-host aggregator below.
            sinks.append(JsonlSink(config.log_dir,
                                   filename=shard_filename(pidx)))
            sinks.append(HeartbeatShardSink(config.log_dir, pidx))
        if config.heartbeat_every and pidx == 0:
            sinks.append(HeartbeatSink(every_steps=config.heartbeat_every))
        # --- cross-host aggregation (obs/aggregate.py): host/{min,max,
        # spread}/* + host/straggler_ratio merged onto host 0's records.
        # "files" tails the per-host shards on the writer's drain thread
        # (observer); "allgather" runs a small dedicated jitted gather at
        # the log gate instead. Neither touches the fused step program.
        xh_mode = config.crosshost_telemetry
        if xh_mode not in ("auto", "off", "files", "allgather"):
            raise ValueError(
                f"crosshost_telemetry={xh_mode!r}: expected one of "
                "'auto', 'off', 'files', 'allgather'")
        if xh_mode == "auto":
            xh_mode = "files" if jax.process_count() > 1 else "off"
        if xh_mode == "files" and not config.log_dir:
            xh_mode = "off"  # file aggregation needs shards to tail
        self._crosshost_mode = xh_mode
        self._host_agg: Optional[HostShardAggregator] = None
        self._crosshost_gather: Optional[CrossHostGatherAggregator] = None
        if pidx == 0:
            if xh_mode == "files":
                self._host_agg = HostShardAggregator(
                    config.log_dir,
                    processes=jax.process_count(),
                    window=config.crosshost_window,
                )
            elif xh_mode == "allgather":
                self._crosshost_gather = CrossHostGatherAggregator(
                    window=config.crosshost_window)
        elif xh_mode == "allgather":
            # Non-zero hosts still participate in the collective.
            self._crosshost_gather = CrossHostGatherAggregator(
                window=config.crosshost_window)
        # --- step-timeline tracer + flight recorder (obs layer 2) ---
        # Disabled tracing is the shared no-op NULL_TRACER: every span
        # call site below stays unconditional and costs ~100 ns
        # (benchmarks/telemetry_overhead.py measures both arms). The
        # anomaly engine's value checks ride the writer's drain thread
        # as an observer; only the ~1 µs slow-step bookkeeping runs on
        # this thread.
        self.tracer = (SpanTracer(config.trace_capacity)
                       if config.trace else NULL_TRACER)
        # The ingest is chosen at trace time, so how often it engages is
        # a fact of the compiled step: recorded once, here.
        self.tracer.instant(
            "trainer/ingest_path", cat="trainer", path=self._ingest_path,
            rows="flat" if flat_rows else "nhwc")
        self.anomaly: Optional[AnomalyEngine] = None
        if config.anomaly_detection and pidx == 0:
            self.anomaly = AnomalyEngine(
                ring_steps=config.anomaly_window,
                slow_step_factor=config.anomaly_slow_step_factor,
                ess_floor=config.slo_ess_floor,
                stall_frac_max=(config.slo_stall_frac_max
                                if mode.host_stream else 0.0),
                mfu_floor=config.slo_mfu_floor,
                straggler_factor=config.anomaly_straggler_factor,
                gini_max=config.slo_selection_gini_max,
                # Any starved class breaches — the share floor itself
                # lives in the monitor's class_spread derivation.
                starved_classes=(1.0 if config.slo_class_starvation_share
                                 > 0 else 0.0),
                var_ratio_patience=config.slo_var_ratio_patience,
                cooldown_steps=config.anomaly_cooldown_steps,
                dump_dir=config.anomaly_dir or config.log_dir,
                tracer=self.tracer,
                context_fn=self._flight_context,
                profile_steps=config.anomaly_profile_steps,
                journal=self._journal,
            )
        # --- sampler-health monitor (obs/sampler_health.py): derives the
        # coverage / Gini / class-spread / bias-audit scalars from the
        # selection-count ledger at the log gate. Single-controller only
        # — the ledger is a global array and device_get on another host's
        # shards raises (same constraint as the async scorer fleet).
        self._sampler_monitor: Optional[SamplerHealthMonitor] = None
        if mode.use_ledger and jax.process_count() == 1:
            self._sampler_monitor = SamplerHealthMonitor(
                np.asarray(self.dataset.shard_indices),
                np.asarray(self.dataset.y_train),
                self.dataset.num_classes,
                config.is_alpha,
                starvation_share=(config.slo_class_starvation_share
                                  or 0.2),
            )
        # Observer order matters: the shard aggregator attaches host/*
        # keys first, then the anomaly engine reads them (straggler).
        observers = []
        if self._host_agg is not None:
            observers.append(self._host_agg.observe_record)
        if self.anomaly is not None:
            observers.append(self.anomaly.observe_record)
        self.logger = AsyncMetricWriter(sinks, observers=observers,
                                        faults=self._faults,
                                        journal=self._journal)
        if config.model in LM_WIDTHS:
            # Routing of the last layer of routed experts, from the record
            # the log gate already fetches (the drain thread's host copy).
            self.logger.add_observer(self._note_moe_load)
        # --- host supervisor (runtime/supervisor.py): liveness + restart
        # + the degradation ladder. Units register below as the worker
        # fleets are built; the writer-observer hook makes the supervisor
        # see every host metric record (its heartbeat of the metric
        # plane). Host step stash: the supervisor's probe path must never
        # sync the device (int(self.state.step) would), so the fit loop
        # publishes the host-side step counter here each iteration.
        self._host_step = 0
        self.supervisor = None
        if config.supervise:
            from mercury_tpu.runtime.supervisor import HostSupervisor

            self.supervisor = HostSupervisor(
                restart_budget=config.supervisor_restart_budget,
                backoff_s=config.supervisor_backoff_s,
                probe_every=config.supervisor_probe_every,
                poll_s=config.supervisor_poll_s,
                anomaly=self.anomaly,
                journal=self._journal,
                plan_provider=self._plan_facts,
            )
            self.logger.add_observer(self.supervisor.observe_record)
        # On-demand jax.profiler capture window: >0 means "this many more
        # steps, then stop_trace" (armed by an anomaly trigger).
        self._profile_steps_left = 0
        self._profiling = False
        self._nan_injected = False
        # steps/s, examples/s, MFU between log ticks; the analytic FLOPs
        # estimate is filled in lazily at the first log gate (the step has
        # compiled by then, so lower().compile() is a jit-cache hit).
        self._throughput = ThroughputMeter(
            examples_per_step=config.batch_size * config.world_size,
        )
        self._flops_known = False
        self.history: List[Dict[str, float]] = []
        # Round up to a multiple of world_size so the sharded-eval batch
        # dimension always divides the mesh axis (e.g. world_size=5 → 260).
        self._eval_batch = -(-256 // config.world_size) * config.world_size
        if self._token_rows:
            # a row is a whole sequence, and the model takes rows one at a
            # time anyway: a scanned batch no wider than the test split
            self._eval_batch = -(-min(int(self.dataset.x_test.shape[0]), 8)
                                 // config.world_size) * config.world_size
        self._eval_cache: Dict[bool, tuple] = {}
        self._ckpt_thread = None  # in-flight async checkpoint write

        # --- host-stream prefetch pipeline (data_placement="host_stream"):
        # prime the in-graph selection ring with the first prefetch_depth
        # draws (uniform cold start), then keep depth gathers in flight.
        # Built BEFORE auto_resume: a restore re-seeds the ring and the
        # pipeline via _recommit_state → _refill_stream_pipe.
        self._stream_pipe = None
        self._stream_local_workers = None
        if host_stream:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P
            from mercury_tpu.parallel.distributed import host_worker_slice
            from mercury_tpu.train.step import make_host_stream_prime

            # Multi-controller: each process runs its own pipeline over
            # its local workers' rows and device_puts only to its
            # addressable shards — the global streamed batch is assembled
            # per-host with zero cross-host pixel traffic.
            shard_mode = config.stream_shard_mode
            if shard_mode not in ("auto", "local", "replicated"):
                raise ValueError(
                    f"stream_shard_mode={shard_mode!r}: expected one of "
                    "'auto', 'local', 'replicated'")
            if shard_mode == "auto":
                shard_mode = ("local" if jax.process_count() > 1
                              else "replicated")
            if shard_mode == "replicated" and jax.process_count() > 1:
                raise ValueError(
                    "stream_shard_mode='replicated' is single-process "
                    "only: a multi-controller process can read only its "
                    "addressable rows of the in-flight index output — "
                    "use 'local' (the multi-controller default)")
            if shard_mode == "local":
                self._stream_local_workers = host_worker_slice(
                    self.mesh, config.mesh_axis)
            # The selection ingest streams flat rows too: host_rows is a
            # view, so the gather reads the same bytes either way.
            self._stream_rows = (host_rows if flat_rows
                                 else np.asarray(self.dataset.x_train))
            self._stream_x_sharding = NamedSharding(
                self.mesh, P(config.mesh_axis)
            )
            self._stream_gen = 0
            self._stream_pipe = self._new_stream_pipe()
            if self.supervisor is not None:
                # escalates=False: training cannot proceed without input,
                # so past the restart budget a prefetch death propagates
                # (there is no degraded mode that synthesizes pixels).
                # alive reads the CURRENT pipe — restarts replace it.
                self.supervisor.register_unit(
                    "prefetch",
                    alive=lambda: self._stream_pipe.alive(),
                    restart=self._restart_stream_pipe,
                    escalates=False,
                )
            self._stream_prime = make_host_stream_prime(config, self.mesh)
            self.state, primed_gidx = self._stream_prime(
                self.state, self.dataset.shard_indices
            )
            self._seed_stream_pipe(primed_gidx)
            # The streamed-x step has no host-side x template for the XLA
            # cost model (analytic_flops_per_step reads _step_x); skip the
            # lazy fill — mfu reports 0.0, steps/s and examples/s remain.
            self._flops_known = True

        # --- async scorer fleet (refresh_mode="async"): background host
        # threads continuously re-score round-robin shard chunks against a
        # periodically-snapshotted copy of the params and stream (slots,
        # scores) chunks into the device table between step dispatches
        # (sampling/scorer_fleet.py; drained by _async_refresh_tick in the
        # fit loop). Built BEFORE auto_resume: a restore resets the fleet
        # via _recommit_state (queued chunks scored the old trajectory).
        self._scorer_fleet = None
        # Non-finite chunks rejected by the apply guard (scorer_nan
        # injection, or an organically diverged scoring forward) — the
        # table must never be scattered with NaN.
        self._chunks_rejected = 0
        # Highest ladder level actually ACTUATED on the device table:
        # the level-3 flatten runs exactly once per descent to uniform.
        self._actuated_level = 0
        # Runtime retrace guard (graftlint Layer P): armed explicitly via
        # arm_retrace_guard(); when live, the log gate emits
        # lint/retrace_events + lint/compile_count per tick.
        self._retrace_monitor = None
        if mode.async_refresh:
            from mercury_tpu.sampling.scorer_service import (
                ScorerService,
                validate_scorer_composition,
            )

            # Reject unsupported backend/tenancy/process compositions
            # with loud, specific errors BEFORE any thread spawns. The
            # old blanket multi-process rejection lives here now, scoped
            # to the host backend (the device backend's lockstep mode
            # supports multi-process; see sampling/scorer_service.py).
            validate_scorer_composition(config, jax.process_count())

            # The scoring forwards run OUTSIDE shard_map, where the mesh
            # data axis doesn't exist — build a local-BN scorer clone
            # (params are shared; flax modules are layout, not weights).
            # scoring_dtype applies, as it would in-graph.
            fleet_model = create_model(
                config.model,
                num_classes=self.dataset.num_classes,
                compute_dtype=config.scoring_dtype or config.compute_dtype,
                param_dtype=config.param_dtype,
                bn_axis_name=None,
                **model_kw,
            )
            scorer_args = (
                np.asarray(self.dataset.x_train),
                np.asarray(self.dataset.y_train),
                np.asarray(self.dataset.shard_indices),
                fleet_model,
                self.dataset.mean,
                self.dataset.std,
                config,
            )
            # Plain host-backend single-tenant runs keep the PR-8 fleet
            # unchanged; the device backend, any multi-tenant run, and
            # any armed scoring SLO go through the ScorerService front
            # (same external contract — the fleet has no slo_status).
            use_service = (config.scorer_backend == "device"
                           or config.scorer_tenants > 1
                           or config.slo_score_staleness_max > 0
                           or config.scorer_queue_highwater > 0)
            if use_service:
                self._scorer_fleet = ScorerService(
                    *scorer_args,
                    tracer=self.tracer,
                    faults=self._faults,
                    train_mesh=self.mesh,
                    journal=self._journal,
                )
            else:
                from mercury_tpu.sampling.scorer_fleet import ScorerFleet

                self._scorer_fleet = ScorerFleet(
                    *scorer_args,
                    tracer=self.tracer,
                    faults=self._faults,
                )
            self._apply_refresh = self._make_refresh_apply()
            self._scorer_fleet.snapshot(
                self.state.params, self.state.batch_stats,
                step=int(self.state.step),
            )
            if self.supervisor is not None:
                # escalates=True: scorer exhaustion enters the
                # degradation ladder (the table can be refreshed on the
                # trainer thread, frozen, or flattened to uniform —
                # training proceeds either way).
                self.supervisor.register_unit(
                    "scorer_service" if use_service else "scorer",
                    alive=lambda: self._scorer_fleet.alive(),
                    restart=lambda: self._scorer_fleet.restart_workers(),
                    escalates=True,
                )
                self.supervisor.set_ladder(
                    probe=self._probe_scoring,
                    revive=lambda: self._scorer_fleet.restart_workers(),
                )
                if use_service:
                    # Backpressure + staleness SLOs enter the ladder:
                    # a breach (wedged tenant, undrained queue) walks
                    # async → sync → frozen → uniform exactly as a
                    # scorer death does.
                    self.supervisor.register_slo(
                        "scorer_service",
                        lambda: self._scorer_fleet.slo_status(
                            self._host_step),
                    )

        # Crash/preemption recovery: pick up the newest checkpoint, sampler
        # state included (bit-deterministic IS resume). The NEXT fit() then
        # runs to the ORIGINAL end step, not num_epochs more (see fit) —
        # gated on this flag, so non-resumed fit() calls keep their usual
        # "train N epochs from here" semantics.
        self._auto_resumed = False
        if config.auto_resume and config.checkpoint_dir:
            if ckpt.latest_step(config.checkpoint_dir) is not None:
                # Topology change (preemption shrank the pod / it grew
                # back): the checkpoint's world size decides between the
                # bit-exact restore and the elastic one — checked BEFORE
                # deserializing into a mismatched template, because the
                # msgpack path would silently accept wrong-shaped sampler
                # leaves. Single-controller only: the probe is plain local
                # IO with no cross-process agreement, and divergent
                # branches would hang mismatched collectives — multi-host
                # auto_resume keeps the agreed restore path (which
                # broadcasts its candidate list); a multi-host topology
                # change uses an explicit restore_elastic call instead.
                w_ckpt = None
                raw = raw_step = None
                if jax.process_count() == 1:
                    from mercury_tpu.train.elastic import (
                        probe_checkpoint,
                        world_size_of_raw,
                    )

                    raw, raw_step = probe_checkpoint(config.checkpoint_dir)
                    w_ckpt = world_size_of_raw(raw)
                if w_ckpt is not None and w_ckpt != config.world_size:
                    # The probe's raw tree feeds the restore — the file is
                    # deserialized once on this (elastic) branch.
                    resumed = self.restore_elastic(step=raw_step, raw=raw)
                    _log.info(
                        "auto-resumed elastically from a %d-worker "
                        "checkpoint at step %d (now %d workers)",
                        w_ckpt, resumed, config.world_size,
                    )
                else:
                    # Same topology (the common case): the probe's tree is
                    # not a substitute for restore()'s corrupt-fallback
                    # walk, so release it before the second read rather
                    # than holding two copies of a possibly-large state.
                    del raw
                    resumed = self.restore()
                    _log.info("auto-resumed from checkpoint at step %d",
                              resumed)
                self._auto_resumed = True

        # --- live scrape plane (obs/serve.py): /healthz /statusz
        # /metricsz on host 0, started LAST so every callback target
        # exists. serve_port=0 (default) means no server object, no
        # thread, no socket — the disabled path costs nothing.
        self._status_server = None
        if config.serve_port > 0 and pidx == 0:
            from mercury_tpu.obs.serve import StatusServer

            self._status_server = StatusServer(
                config.serve_port,
                health_fn=self._serve_health,
                status_fn=self._serve_status,
                metrics_fn=self.logger.latest_record,
            )

    # ---------------------------------------------------------- scrape plane
    def _serve_health(self) -> Dict[str, Any]:
        """``/healthz`` body: liveness + ladder level. Runs on the serve
        thread — host counters only, never a device sync."""
        body: Dict[str, Any] = {"step": self._host_step}
        if self.supervisor is not None:
            s = self.supervisor.summary()
            body["level"] = s["level"]
            body["level_name"] = s["level_name"]
            body["units_down"] = sum(1 for u in s["units"] if u["down"])
        return body

    def _serve_status(self) -> Dict[str, Any]:
        """``/statusz`` body: manifest + ladder + tenant queues + the
        journal tail — the first page of any live incident."""
        doc: Dict[str, Any] = {"step": self._host_step}
        if self.config.log_dir:
            try:
                with open(os.path.join(self.config.log_dir,
                                       "run_manifest.json")) as f:
                    doc["manifest"] = json.load(f)
            except Exception:
                pass
        if self.supervisor is not None:
            doc["supervisor"] = self.supervisor.summary()
        fleet = getattr(self, "_scorer_fleet", None)
        if fleet is not None and hasattr(fleet, "summary"):
            try:
                doc["scorer"] = fleet.summary()
            except Exception:
                pass
        if self._journal is not None:
            doc["events"] = self._journal.tail()
            doc["event_counts"] = self._journal.counts()
        # The state schema this build was linted against (graftlint
        # Layer E golden) — lets a scraper correlate restore warnings
        # with the running build's schema without shell access.
        doc["state_schema_sha"] = ckpt.state_schema_sha()
        return doc

    # -------------------------------------------------------- host streaming
    def _host_stream_step(self, step: int = 0):
        """One pop→step→push cycle: train on the oldest prefetched batch,
        hand the step's emitted t+depth indices straight back to the
        pipeline (still an in-flight device value — the worker thread
        absorbs the sync)."""
        # pop blocks only when the prefetch worker fell behind — the
        # span IS the input-stall (its wall time, minus µs of queue
        # bookkeeping, is time the trainer waited on data).
        with self.tracer.span("trainer/pop", cat="trainer"):
            try:
                batch = self._stream_pipe.pop()  # graftlint: disable=GL120 -- supervisor callbacks (restart/probe/revive) run on the trainer thread only: tick()/request_restart() are fit-loop calls and the monitor thread never invokes them
            except RuntimeError:
                # Worker death. The trainer cannot take this step without
                # input, so the restart is synchronous (budget + backoff
                # via the supervisor); the rebuilt pipeline resumes from
                # the stream cursor (state.pending_sel), so the popped
                # batch is exactly the one the dead worker owed us — no
                # sample skipped or duplicated.
                if self.supervisor is None or not \
                        self.supervisor.request_restart("prefetch", step):
                    raise
                batch = self._stream_pipe.pop()
        with self.tracer.step_span("trainer/dispatch", step):
            self.state, metrics, next_gidx = self.train_step(  # graftlint: disable=GL120 -- supervisor callbacks run on the trainer thread only (see pop() above); state is never touched off-thread
                self.state, batch, self._step_y, self.dataset.shard_indices
            )
        with self.tracer.span("trainer/push", cat="trainer"):
            self._stream_pipe.push(next_gidx)
        return metrics

    def _seed_stream_pipe(self, primed_gidx) -> None:
        """Push the primed ``[depth, W, S]`` selections into the prefetch
        pipeline, reset first (queued work belongs to a previous
        trajectory). Multi-controller: only this host's worker rows of
        the ``P(None, data)``-sharded prime output are readable here —
        and they are exactly the rows this host's pipeline gathers."""
        self._stream_pipe.reset()
        lw = self._stream_local_workers
        if lw is None:
            for i in range(self.config.prefetch_depth):
                self._stream_pipe.push(primed_gidx[i])
            return
        if getattr(primed_gidx, "is_fully_addressable", True):
            local = np.asarray(jax.device_get(primed_gidx))[:, lw]
        else:
            rows: Dict[int, np.ndarray] = {}
            for sh in primed_gidx.addressable_shards:
                start = sh.index[1].start or 0
                data = np.asarray(sh.data)       # [depth, nw, S]
                for j in range(data.shape[1]):
                    rows[start + j] = data[:, j]
            local = np.stack([rows[int(g)] for g in lw], axis=1)
        for i in range(self.config.prefetch_depth):
            self._stream_pipe.push(local[i])

    def _refill_stream_pipe(self) -> None:
        """Re-seed the prefetch pipeline from ``state.pending_sel`` after a
        checkpoint restore: every in-flight batch belongs to the previous
        trajectory, but the restored ring's slots are exactly the
        selections steps t..t+depth-1 will train on — push their global
        rows so the pop→step→push cadence resumes unchanged."""
        if getattr(self, "_stream_pipe", None) is None:
            return
        with self.tracer.span("trainer/refill_stream_pipe", cat="trainer"):
            self._stream_pipe.reset()
            # [W, depth, S] shard-local slots → global ids via the HOST
            # copy of the shard index table (the globalized device copy is
            # not addressable across hosts). Multi-controller reads only
            # this host's worker rows of the P(data)-sharded slots.
            slots_arr = self.state.pending_sel.slots
            if getattr(slots_arr, "is_fully_addressable", True):
                slots = np.asarray(jax.device_get(slots_arr))
                workers = np.arange(slots.shape[0])
            else:
                owned: Dict[int, np.ndarray] = {}
                for sh in slots_arr.addressable_shards:
                    start = sh.index[0].start or 0
                    data = np.asarray(sh.data)   # [nw, depth, S]
                    for j in range(data.shape[0]):
                        owned[start + j] = data[j]
                workers = np.asarray(sorted(owned))
                slots = np.stack([owned[int(w)] for w in workers])
            shard_indices = self._host_shard_indices
            for d in range(slots.shape[1]):
                gidx = np.stack([
                    shard_indices[w][slots[i, d]]
                    for i, w in enumerate(workers)
                ])
                self._stream_pipe.push(gidx)

    def _new_stream_pipe(self):
        """A prefetch pipeline of generation ``_stream_gen`` over the rows
        the step streams, ``StepMode.emit_size`` of them per worker."""
        from mercury_tpu.data.stream import HostStreamSource, PrefetchPipeline

        cfg = self.config
        return PrefetchPipeline(
            HostStreamSource(self._stream_rows,
                             decode_workers=cfg.decode_workers),
            (cfg.world_size, self._mode.emit_size),
            self._stream_x_sharding,
            depth=cfg.prefetch_depth,
            tracer=self.tracer,
            local_workers=self._stream_local_workers,
            faults=self._faults,
            generation=self._stream_gen,
        )

    def _restart_stream_pipe(self) -> None:
        """Supervisor restart: tear down the dead pipeline and build a
        generation-bumped replacement, resuming from the stream cursor.
        ``state.pending_sel`` holds the selections for steps
        t..t+depth-1 regardless of where the worker died, and
        ``_refill_stream_pipe`` recomputes ALL depth in-flight gathers
        from it — so the restarted trajectory is bit-identical to an
        uninterrupted one (test-enforced)."""
        old = self._stream_pipe
        self._stream_gen += 1
        try:
            old.close(timeout=5.0)
        except Exception as exc:
            _log.warning("dead prefetch pipeline close() raised: %s", exc)
        self._stream_pipe = self._new_stream_pipe()
        self._refill_stream_pipe()

    # --------------------------------------------------- async scorer fleet
    def _make_refresh_apply(self):
        """Jitted ``[W]``-vmapped chunk scatter for the async fleet
        (``apply_async_chunk`` per worker row), output pinned to the
        scoretable's data-axis layout so applying a chunk never perturbs
        the step's committed state sharding (jit-cache stability)."""
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P
        from mercury_tpu.sampling.scoretable import (
            ScoreTableState,
            apply_async_chunk,
        )

        sh = NamedSharding(self.mesh, P(self.config.mesh_axis))

        def apply(tab, ema_value, slots, values, weight):
            new_scores = jax.vmap(
                apply_async_chunk, in_axes=(0, 0, 0, 0, None)
            )(tab.scores, slots, values, ema_value, weight)
            return tab._replace(scores=new_scores)

        return jax.jit(
            apply,
            out_shardings=ScoreTableState(scores=sh, cursor=sh),
        )

    def _apply_chunks(self, chunks, step: int) -> None:
        """Scatter scored chunks into the device score table
        (staleness-weighted by ``table_decay**age``, the exact in-graph
        decay an age-0 apply would have accrued). Non-finite chunks are
        REJECTED and counted (``sampler/chunks_rejected``): a corrupted
        chunk (scorer_nan injection, a diverged scoring forward) must
        never poison the sampling distribution — max(NaN, ε) semantics
        would otherwise zero that slot's probability forever."""
        fleet = self._scorer_fleet
        for chunk in chunks:
            if not np.all(np.isfinite(chunk.scores)):
                self._chunks_rejected += 1  # graftlint: disable=GL120 -- _apply_chunks runs on the trainer thread only: the supervisor probe/restart callbacks that reach it are fit-loop calls, never the monitor thread
                _log.warning(
                    "rejected a non-finite score chunk (snapshot step %d) "
                    "at step %d — table untouched", chunk.step, step)
                continue
            age = max(step - chunk.step, 0)
            weight = jnp.float32(self.config.table_decay ** age)
            new_tab = self._apply_refresh(
                self.state.scoretable, self.state.ema.value,
                jnp.asarray(chunk.slots), jnp.asarray(chunk.scores),
                weight,
            )
            self.state = self.state.replace(scoretable=new_tab)
            fleet.note_applied(age)

    def _async_refresh_tick(self, step: int, advanced: int = 1) -> None:
        """Per-iteration fleet service (ladder level 0): scatter every
        ready chunk into the device score table and re-snapshot the
        params on the ``snapshot_every`` cadence. Host ints only — no
        device sync ever happens on this thread."""
        fleet = self._scorer_fleet
        if fleet is None:
            return
        if self.supervisor is not None and not fleet.alive():
            # A worker died mid-interval: skip this drain (drain() would
            # raise) — supervisor.tick() restarts the fleet or walks the
            # ladder; queued chunks survive the restart.
            return
        if hasattr(fleet, "drain_for_step"):
            # ScorerService: the step-aware drain also advances every
            # tenant's staleness clock (the SLO input) and empties the
            # non-primary tenants' queues into their accounting.
            chunks = fleet.drain_for_step(step)
        else:
            chunks = fleet.drain()
        if chunks:
            with self.tracer.span("trainer/apply_refresh", cat="trainer",
                                  chunks=len(chunks)):
                self._apply_chunks(chunks, step)
        every = int(self.config.snapshot_every)
        if (step // every) > ((step - advanced) // every):
            # The identity-jit inside snapshot() copies — the live state
            # is donated into the next dispatch, so the fleet must never
            # hold its buffers.
            fleet.snapshot(self.state.params, self.state.batch_stats, step)

    def _sync_refresh_tick(self, step: int, advanced: int = 1) -> None:
        """Ladder level 1: the async fleet is gone, so the TRAINER thread
        scores one round-robin chunk every ``supervisor_sync_every``
        steps (``ScorerFleet.score_once`` — no worker threads involved).
        A failure here escalates the ladder one level."""
        fleet = self._scorer_fleet
        every = max(int(self.config.supervisor_sync_every), 1)
        if (step // every) <= ((step - advanced) // every):
            return
        try:
            with self.tracer.span("trainer/sync_refresh", cat="trainer"):
                # Snapshot first: level 1 has no background cadence, so
                # the sync chunk always scores the CURRENT params.
                fleet.snapshot(self.state.params, self.state.batch_stats,
                               step)
                chunk = fleet.score_once()
        except Exception as exc:
            self.supervisor.report_failure("sync refresh", step, exc)
            return
        self._apply_chunks([chunk], step)

    def _make_table_flatten(self):
        """Jitted table flatten for ladder level 3: zeroed scores make
        ``p ∝ max(score + α·EMA_mean, ε)`` a per-row constant, so the
        step's inverse-CDF draw IS uniform sampling — no retrace, no
        program change, just constant table contents. Output pinned to
        the table's committed data-axis layout (jit-cache stability)."""
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P
        from mercury_tpu.sampling.scoretable import ScoreTableState

        sh = NamedSharding(self.mesh, P(self.config.mesh_axis))
        return jax.jit(
            lambda tab: tab._replace(scores=jnp.zeros_like(tab.scores)),
            out_shardings=ScoreTableState(scores=sh, cursor=sh),
        )

    def _refresh_tick(self, step: int, advanced: int = 1) -> None:
        """Ladder-aware refresh dispatch, called once per fit iteration.
        Level 0 drains the async fleet; level 1 scores on the trainer
        thread; level 2 (frozen) does nothing — the in-graph decay keeps
        flattening the table toward the EMA mean; level 3 re-pins the
        table to a constant EVERY iteration, making the draw uniform
        (``sampler/is_active=0``). Per-iteration, not once: the step's
        free write-back re-scores the trained slots in-graph (it cannot
        be gated without a retrace), so a one-shot flatten would let S
        of L slots re-tilt each draw — the host pin bounds that tilt to
        the single in-flight step."""
        sup = self.supervisor
        level = 0 if sup is None else sup.level()
        if level == 0:
            self._async_refresh_tick(step, advanced)
        elif level == 1:
            self._sync_refresh_tick(step, advanced)
        if sup is None:
            return
        if level >= 3:
            if not hasattr(self, "_flatten_table"):
                self._flatten_table = self._make_table_flatten()
            self.state = self.state.replace(
                scoretable=self._flatten_table(self.state.scoretable))
            if self._actuated_level < 3:
                self._actuated_level = 3
                _log.warning(
                    "sampler degraded to UNIFORM at step %d: score table "
                    "flattened (sampler/is_active=0)", step)
        elif level < 3:
            # A recovery below uniform needs no inverse actuation: the
            # resumed refresh path (and the in-graph EMA updates) repaint
            # the flattened table organically.
            self._actuated_level = level

    def _probe_scoring(self) -> None:
        """Supervisor recovery probe: one trainer-thread scoring round
        against fresh params, applied to the table. Raises on any
        failure (the supervisor escalates); success climbs the ladder."""
        fleet = self._scorer_fleet
        if fleet is None:
            raise RuntimeError("no scorer fleet to probe")
        step = self._host_step
        fleet.snapshot(self.state.params, self.state.batch_stats, step)
        chunk = fleet.score_once()
        if not np.all(np.isfinite(chunk.scores)):
            raise RuntimeError("probe chunk contains non-finite scores")
        self._apply_chunks([chunk], step)

    # ---------------------------------------------------------- flight data
    def _plan_facts(self) -> Optional[Dict[str, Any]]:
        """Active auto-planner decision for status surfaces (the
        supervisor's ``summary()``/statusz ``plan`` field). None when the
        run is manually planned."""
        decision = self._plan_decision
        if decision is None:
            return None
        return {
            "requested": self.config.plan,
            "selected": decision.selected,
            "candidates_considered": len(decision.candidates),
            "feasible": [c.name for c in decision.feasible],
            "replans": self._replan_count,
        }

    def _flight_context(self) -> Dict[str, Any]:
        """Run context for flight-record dumps (obs/anomaly.py) —
        evaluated lazily, only when a trigger actually fires."""
        ctx: Dict[str, Any] = {
            "config": dataclasses.asdict(self.config),
            "manifest": build_run_manifest(self.config, self.mesh),
        }
        pipe = getattr(self, "_stream_pipe", None)
        if pipe is not None:
            ctx["pipeline"] = pipe.summary()
        fleet = getattr(self, "_scorer_fleet", None)
        if fleet is not None:
            ctx["scorer_fleet"] = fleet.summary()
        supervisor = getattr(self, "supervisor", None)
        if supervisor is not None:
            ctx["supervisor"] = supervisor.summary()
        faults = getattr(self, "_faults", None)
        if faults is not None:
            ctx["faults"] = faults.summary()
        return ctx

    def arm_retrace_guard(self):
        """Arm the Layer P runtime retrace guard for this trainer.

        Installs a :class:`mercury_tpu.lint.tracecheck.CompileMonitor`
        whose per-tick deltas the log gate emits as
        ``lint/retrace_events`` / ``lint/compile_count``. In steady state
        both should be 0 every tick; a nonzero reading names a step that
        re-entered the compiler (the offline guard,
        ``python -m mercury_tpu.lint.tracecheck``, then attributes it).
        Idempotent; returns the monitor so tests can snapshot it."""
        if self._retrace_monitor is None:
            from mercury_tpu.lint.tracecheck import CompileMonitor

            self._retrace_monitor = CompileMonitor()
            self._retrace_monitor.start()
            self._retrace_last = (0, 0)
        return self._retrace_monitor

    # ------------------------------------------------------------------ fit
    def fit(self, num_epochs: Optional[int] = None) -> Dict[str, float]:
        """Run training (``Trainer.fit``, ``pytorch_collab.py:56-72``).

        Returns the final eval metrics. Honors the step-budget break
        (``step×world_size > budget``, ``:71``)."""
        self.tracer.register_thread("train")
        # The root span: everything a call does lies under it (its own
        # self time is the loop's bookkeeping), and what lies between two
        # of them is the caller's code.
        with self.tracer.call_span("trainer/fit", cat="trainer"):
            final_metrics = self._fit(num_epochs)
            # How often the input-moments statistic engages is a fact of
            # the traced step, like the ingest: once a call, after its
            # steps (the first call's first dispatch traces the step).
            self.tracer.instant(
                "trainer/bn_moment_units", cat="trainer",
                units=self._trace_facts.get("bn_moment_units", 0))
            # Likewise how many token rows of a step take loss and hits
            # from the head's kernel over vocabulary blocks.
            self.tracer.instant(
                "trainer/head_kernel_rows", cat="trainer",
                rows=self._trace_facts.get("head_kernel_rows", 0),
                plain_rows=self._trace_facts.get("head_plain_rows", 0))
            # And how many operands of a decoder's attention (three a
            # layer) are made from their products in one pass.
            self.tracer.instant(
                "trainer/rope_kernel_sites", cat="trainer",
                sites=self._trace_facts.get("rope_kernel_sites", 0),
                plain_sites=self._trace_facts.get("rope_plain_sites", 0))
            return final_metrics

    def _note_moe_load(self, record: Dict[str, Any]) -> None:
        """The instant ``trainer/moe_load``: what share of the (token,
        expert) pairs fell on the experts held here, the busiest held
        expert's pairs over the mean, what share of the routed layers
        ran over the bounded rows (``models/moe.py::routed_experts``) and,
        where the router has a selection bias, what share of the pairs the
        bias chose, in the train pass of the logged step."""
        load = {key[len("moe/"):]: float(value)
                for key, value in record.items() if key.startswith("moe/")}
        if load:
            self.tracer.instant("trainer/moe_load", cat="trainer", **load)

    def _fit(self, num_epochs: Optional[int]) -> Dict[str, float]:
        cfg = self.config
        num_epochs = num_epochs or cfg.num_epochs
        step = int(self.state.step)
        self._throughput.reset(step)
        final_metrics: Dict[str, float] = {}

        # End of the run: num_epochs' worth of steps from here, clipped by
        # the step budget — the reference executes the first step for which
        # step×world_size > budget, then breaks (:71). After an actual
        # auto-resume the horizon is absolute (finish the original run), so
        # re-running the same script after a crash completes it instead of
        # extending it; ordinary fit() calls keep the relative horizon.
        if self._auto_resumed:
            target = self.steps_per_epoch * num_epochs
            # Consumed: the absolute horizon applies only to the first
            # fit() after the resume; later calls are ordinary.
            self._auto_resumed = False
        else:
            target = step + self.steps_per_epoch * num_epochs
        budget_cap = int(cfg.step_budget // cfg.world_size) + 1
        end = min(target, budget_cap)

        def crossed(every: int, at: int, advanced: int) -> bool:
            """Did [at-advanced, at] cross a multiple of ``every``?"""
            return bool(every) and (at // every) > ((at - advanced) // every)

        try:
            while step < end:
                # Wall time of the whole training action: under async
                # dispatch each iteration converges to the true device
                # step cadence once the dispatch queue applies
                # backpressure — exactly the signal slow_step wants.
                t_iter = time.perf_counter()
                if self._faults is not None:
                    # Advance the fault plane's step clock (workers fire
                    # against it) and run the trainer-thread hook.
                    self._faults.note_step(step)
                    slow = self._faults.fire("host_slow")
                    if slow is not None:
                        time.sleep(float(slow.get("secs", 1.0)))
                if self._stream_pipe is not None:
                    k = 1
                    metrics = self._host_stream_step(step)
                elif self.train_step_many is not None and step + self.scan_steps <= end:
                    k = self.scan_steps
                    with self.tracer.step_span("trainer/dispatch", step,
                                               steps=k):
                        self.state, metrics = self.train_step_many(
                            self.state,
                            self._step_x,
                            self._step_y,
                            self.dataset.shard_indices,
                        )
                else:
                    k = 1
                    with self.tracer.step_span("trainer/dispatch", step):
                        self.state, metrics = self.train_step(
                            self.state,
                            self._step_x,
                            self._step_y,
                            self.dataset.shard_indices,
                        )
                step += k
                self._host_step = step
                if self._scorer_fleet is not None:
                    # Scatter ready async-refresh chunks and re-snapshot on
                    # cadence — host bookkeeping + async device dispatches,
                    # nothing here waits on the step. Ladder-aware: a
                    # degraded run refreshes on this thread, freezes, or
                    # flattens to uniform (_refresh_tick).
                    self._refresh_tick(step, advanced=k)
                if self.supervisor is not None:
                    # Liveness check + restarts + recovery probing —
                    # host bookkeeping on the step cadence.
                    self.supervisor.tick(step)
                if self.anomaly is not None:
                    self.anomaly.observe_step_time(
                        step, time.perf_counter() - t_iter, steps=k)
                # On-demand profiler window: an anomaly trigger arms M
                # steps of jax.profiler capture; open it here (next
                # occurrence of a sporadic anomaly lands inside it) and
                # close it M steps later.
                if self._profile_steps_left > 0:
                    self._profile_steps_left -= k
                    if self._profile_steps_left <= 0:
                        self._stop_profiler()
                elif self.anomaly is not None:
                    want = self.anomaly.take_profile_request()
                    if want > 0:
                        self._start_profiler(want)
                if crossed(cfg.log_every, step, k):
                    if not self._flops_known:
                        # First log gate: ask XLA's cost model for the
                        # step program's FLOPs (re-traces but does NOT
                        # re-compile — see analytic_flops_per_step),
                        # enabling perf/mfu.
                        fn, ks = ((self.train_step_many, self.scan_steps)
                                  if k > 1 else (self.train_step, 1))
                        with self.tracer.span("trainer/flops_probe",
                                              cat="trainer", step=step):
                            self._throughput.flops_per_step = (
                                analytic_flops_per_step(
                                    fn, self.state, self._step_x,
                                    self._step_y,
                                    self.dataset.shard_indices,
                                    scan_steps=ks,
                                )
                            )
                        self._flops_known = True
                    # Enqueue the ON-DEVICE metric pytree: no float(), no
                    # device sync, no filesystem write on this thread. The
                    # drain thread device_gets and reduces scanned [K]
                    # metric series to their chunk MEAN (keeping only the
                    # last entry would discard (K-1)/K of the signal) —
                    # obs/writer.py:_to_host_record. Safe to hold: metric
                    # outputs are not donated (only the state is).
                    with self.tracer.span("trainer/log_gate",
                                          cat="trainer", step=step):
                        record = dict(metrics)
                        record.update(self._throughput.tick(step))
                        if self._stream_pipe is not None:
                            # Host-side floats (stall/queue/bytes since
                            # the last log): no device sync, safe to
                            # merge here.
                            record.update(self._stream_pipe.stats())
                        if self._scorer_fleet is not None:
                            # Same contract: host counters only
                            # (scorer/throughput, staleness, lag).
                            record.update(self._scorer_fleet.stats())
                            record["sampler/chunks_rejected"] = float(
                                self._chunks_rejected)
                        if self._sampler_monitor is not None:
                            # Ledger-derived distribution stats: ONE
                            # [W, L] int32 device fetch per log tick
                            # (plus the score table for the bias
                            # audit) — the only log-gate merge that
                            # touches the device, scaled by log_every.
                            record.update(
                                self._sampler_monitor.stats(self.state))
                        if self.supervisor is not None:
                            # Ladder level, restarts, degradations — and
                            # sampler/is_active (0.0 once uniform).
                            record.update(self.supervisor.stats())
                        if self._faults is not None:
                            record.update(self._faults.stats())
                        if cfg.checkpoint_dir:
                            record["checkpoint/write_failures"] = float(
                                ckpt.write_failures())
                        if self._plan_decision is not None:
                            # Auto-planner bookkeeping (host floats):
                            # decision width + elastic re-plan count.
                            record["plan/candidates_considered"] = float(
                                len(self._plan_decision.candidates))
                            record["plan/replan_count"] = float(
                                self._replan_count)
                        # Thread-fleet liveness (Layer C telemetry):
                        # process-wide census + the metric queue's own
                        # depth; the prefetch/scorer depths rode in with
                        # their stats() above. Host-only, no sync.
                        record.update(host_thread_stats())
                        record["threads/queue_depth/metrics"] = float(
                            self.logger.queue_depth())
                        if self._retrace_monitor is not None:
                            # Retrace guard armed: per-tick deltas of the
                            # process-wide trace/compile event counters.
                            # Steady state is 0/0 — anything else means a
                            # step re-entered the compiler this interval.
                            traces, compiles = \
                                self._retrace_monitor.snapshot()
                            lt, lc = self._retrace_last
                            record["lint/retrace_events"] = float(
                                traces - lt)
                            record["lint/compile_count"] = float(
                                compiles - lc)
                            self._retrace_last = (traces, compiles)
                        record["epoch"] = (step - 1) // self.steps_per_epoch
                        if self._crosshost_gather is not None:
                            # allgather mode: EVERY process participates
                            # in the (deterministic-cadence) collective;
                            # only host 0 gets a non-empty merge back.
                            record.update(
                                self._crosshost_gather.update(record))
                        # Fault injection (tests/CI): poison the HOST
                        # record so the non_finite trigger path runs
                        # end-to-end; the traced program is untouched.
                        if (cfg.anomaly_inject_nan_step
                                and not self._nan_injected
                                and step >= cfg.anomaly_inject_nan_step):
                            record["train/loss"] = float("nan")
                            self._nan_injected = True
                        self.logger.write(step, record)
                if crossed(cfg.eval_every, step, k):
                    with self.tracer.span("trainer/eval", cat="trainer",
                                          step=step):
                        final_metrics = self.evaluate(
                            include_train=not self._token_rows)
                    self.logger.log_scalars(step, final_metrics)
                    print(
                        f"  eval @ {step}: "
                        + " ".join(f"{k}={v:.4f}" for k, v in final_metrics.items())
                    )
                if cfg.checkpoint_dir and crossed(cfg.checkpoint_every, step, k):
                    with self.tracer.span("trainer/checkpoint",
                                          cat="trainer", step=step):
                        if cfg.async_checkpoint:
                            # One in-flight write at a time: join the
                            # previous before fetching the next snapshot.
                            if self._ckpt_thread is not None:
                                self._ckpt_thread.join()
                            self._ckpt_thread = ckpt.save_checkpoint_async(
                                cfg.checkpoint_dir, self.state, step,
                                failure_cb=self._ckpt_failure_cb,
                                **self._ckpt_kwargs(),
                            )
                        else:
                            ckpt.save_checkpoint(cfg.checkpoint_dir,
                                                 self.state, step,
                                                 **self._ckpt_kwargs())
        finally:
            # An exception mid-loop (KeyboardInterrupt, eval error) must not
            # leave a write in flight — a relaunched auto_resume reading a
            # half-written file would restore garbage.
            if self._ckpt_thread is not None:
                self._ckpt_thread.join()
                self._ckpt_thread = None
            # Drain the metric queue to the sinks so callers (and crashed
            # runs' postmortems) see every step logged up to here. The
            # writer itself stays open — fit() can be called again.
            with self.tracer.span("trainer/flush", cat="trainer", step=step):
                self.logger.flush()
        if not final_metrics:
            with self.tracer.span("trainer/eval", cat="trainer", step=step,
                                  closing=True):
                # (a token dataset's train split is left out: a row is a
                # whole sequence, and the split costs dozens of steps)
                final_metrics = self.evaluate(
                    include_train=not self._token_rows)
        if cfg.checkpoint_dir:
            with self.tracer.span("trainer/final_checkpoint", cat="trainer",
                                  step=step):
                ckpt.save_checkpoint(cfg.checkpoint_dir, self.state, step,
                                     **self._ckpt_kwargs())
        return final_metrics

    def _ckpt_kwargs(self) -> Dict[str, Any]:
        """Durability knobs threaded into every cadence/final save."""
        cfg = self.config
        return dict(
            keep=cfg.checkpoint_keep,
            retries=cfg.checkpoint_write_retries,
            retry_backoff_s=cfg.checkpoint_retry_backoff_s,
            manifest=cfg.checkpoint_manifest,
            faults=self._faults,
            journal=self._journal,
        )

    def _ckpt_failure_cb(self, exc: BaseException) -> None:
        """Async-writer failure hook (runs ON the ckpt-write thread):
        leave a flight record immediately — join() may be a cadence away
        and a wedged run never joins. Never raises."""
        try:
            if self.anomaly is not None:
                self.anomaly.dump_flight_record(
                    "checkpoint_write_failed", self._host_step, {
                        "error": f"{type(exc).__name__}: {exc}",
                        "write_failures": ckpt.write_failures(),
                    })
        except Exception:
            _log.warning("checkpoint failure flight record failed",
                         exc_info=True)

    # ------------------------------------------------- profiler window
    def _start_profiler(self, steps: int) -> None:
        """Open a ``jax.profiler`` capture for the next ``steps`` steps
        (anomaly-armed). Never raises — profiling is best-effort."""
        logdir = self.config.anomaly_dir or self.config.log_dir
        if not logdir or self._profiling:
            return
        path = os.path.join(logdir, "profile")
        try:
            jax.profiler.start_trace(path)
        except Exception as exc:
            _log.warning("profiler start failed: %s", exc)
            return
        self._profiling = True
        self._profile_steps_left = int(steps)
        self.tracer.instant("profiler/start", cat="trainer", steps=steps)
        _log.warning("anomaly-armed profiler capture: %d steps -> %s",
                     steps, path)

    def _stop_profiler(self) -> None:
        if not self._profiling:
            return
        self._profiling = False
        self._profile_steps_left = 0
        try:
            jax.profiler.stop_trace()
        except Exception as exc:
            _log.warning("profiler stop failed: %s", exc)
        self.tracer.instant("profiler/stop", cat="trainer")
        self._fold_back_profile()

    def _fold_back_profile(self) -> None:
        """Attribute the capture that just closed (obs/profile_parse —
        offline parse, no jax) and fold the result into the metric
        stream as prof/scope_frac/* + write device_time_breakdown.json
        next to the metrics. Best-effort: a capture format we can't
        parse must never take the run down."""
        logdir = self.config.anomaly_dir or self.config.log_dir
        if not logdir or jax.process_index() != 0:
            return
        try:
            from mercury_tpu.obs.profile_parse import (
                parse_profile,
                scope_frac_metrics,
                write_breakdown,
            )

            breakdown = parse_profile(os.path.join(logdir, "profile"))
            out_dir = self.config.log_dir or logdir
            write_breakdown(
                breakdown,
                os.path.join(out_dir, "device_time_breakdown.json"))
            if breakdown["total_device_time_us"] > 0:
                step = getattr(self._throughput, "_last_step", None) or 0
                self.logger.write(step, scope_frac_metrics(breakdown))
            _log.warning(
                "device-time breakdown written: %.1f%% attributed to "
                "named scopes",
                100.0 * (1.0 - breakdown["scopes"]
                         .get("unattributed", {}).get("frac", 0.0)))
        except Exception as exc:
            _log.warning("profile fold-back failed: %s: %s",
                         type(exc).__name__, exc)

    def close(self) -> None:
        """Shut down the trainer's background subsystems — scorer fleet,
        prefetch pipeline, armed profiler, span-trace export, metric
        writer — in dependency order: producers (threads that can still
        emit work or spans) stop before the sinks they feed.

        Idempotent (a second call is a no-op — the subsystems' own
        ``close()`` methods tolerate repeats, and the ``_closed`` latch
        skips the trace re-export) and safe on partially-constructed
        trainers: every attribute access is guarded, so a constructor
        that raised halfway still closes cleanly
        (``tests/test_async_refresh.py`` pins both). A trainer also works
        as a context manager: ``with Trainer(cfg) as t: t.fit()``."""
        if getattr(self, "_closed", False):
            return
        self._closed = True
        try:
            server = getattr(self, "_status_server", None)
            if server is not None:
                # Scrapers go first: a request arriving mid-teardown
                # would read half-closed subsystems.
                server.close()
            supervisor = getattr(self, "supervisor", None)
            if supervisor is not None:
                # A live supervisor poll/probe must not race the unit
                # teardown below (it would read restarts as deaths).
                supervisor.close()
            fleet = getattr(self, "_scorer_fleet", None)
            if fleet is not None:
                fleet.close()
            monitor = getattr(self, "_retrace_monitor", None)
            if monitor is not None:
                monitor.stop()
            if getattr(self, "_stream_pipe", None) is not None:
                self._stream_pipe.close()
            if getattr(self, "_profiling", False):
                self._stop_profiler()
            tracer = getattr(self, "tracer", None)
            config = getattr(self, "config", None)
            journal = getattr(self, "_journal", None)
            if (tracer is not None and tracer.enabled
                    and config is not None and config.log_dir
                    and jax.process_index() == 0):
                try:
                    # Merge the control-plane journal into the exported
                    # timeline: spans + decision instants + causal flow
                    # arrows land in ONE perfetto-loadable trace.json.
                    events = []
                    if journal is not None:
                        from mercury_tpu.obs.events import (
                            journal_filename,
                            read_journal,
                        )

                        journal.flush()
                        events = read_journal(os.path.join(
                            config.log_dir,
                            journal_filename(jax.process_index())))
                    tracer.export_chrome_trace(
                        os.path.join(config.log_dir, "trace.json"),
                        events=events or None)
                except Exception as exc:
                    _log.warning("trace export failed: %s", exc)
            logger = getattr(self, "logger", None)
            if logger is not None:
                logger.close()
        finally:
            # Even a teardown crash leaves the ladder history and the
            # journal on disk — they are the post-mortem.
            self._write_supervisor_summary()
            journal = getattr(self, "_journal", None)
            if journal is not None:
                journal.close()

    def _write_supervisor_summary(self) -> None:
        """Persist ``HostSupervisor.summary()`` (ladder transitions,
        restart budgets, SLO latch counts) as ``supervisor_summary.json``
        — called from ``close()``'s finally so a crashed run still
        leaves its ladder history on disk. Never raises."""
        supervisor = getattr(self, "supervisor", None)
        config = getattr(self, "config", None)
        if (supervisor is None or config is None or not config.log_dir
                or jax.process_index() != 0):
            return
        try:
            path = os.path.join(config.log_dir,
                                "supervisor_summary.json")
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(supervisor.summary(), f, indent=2, default=str)
                f.write("\n")
            os.replace(tmp, path)
        except Exception as exc:
            _log.warning("supervisor summary write failed: %s", exc)

    def __enter__(self) -> "Trainer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # ----------------------------------------------------------------- eval
    def _eval_arrays(self, train: bool):
        """Pre-batched uint8 arrays + masks for one split, cached — the
        whole split then evals in a single scanned device call."""
        if train not in self._eval_cache:
            x = self.dataset.x_train if train else self.dataset.x_test
            y = self.dataset.y_train if train else self.dataset.y_test
            n = int(x.shape[0])
            plan = eval_batches(n, self._eval_batch)
            idx = np.stack([p[0] for p in plan])                     # [nb, B]
            valid = np.stack([
                np.arange(self._eval_batch) < p[1] for p in plan
            ])                                                       # [nb, B]
            # Multi-controller: keep eval inputs as host arrays — jit treats
            # them as replicated, compatible with the global params. (A
            # committed process-local device array would conflict.) Same
            # for sharded data placement: eval reads the host copy rather
            # than committing a device-replicated full split.
            conv = (np.asarray
                    if jax.process_count() > 1
                    or self._mode.placement != "replicated"
                    else jnp.asarray)
            self._eval_cache[train] = (
                conv(np.asarray(x)[idx]),
                conv(np.asarray(y)[idx]),
                conv(valid),
            )
        return self._eval_cache[train]

    def _eval_split(self, train: bool) -> Dict[str, float]:
        prefix = "train" if train else "test"
        with self.tracer.span("eval/dispatch", cat="eval", split=prefix):
            images_b, labels_b, valid_b = self._eval_arrays(train)
            loss_sum, correct, count = self.eval_epoch(
                self.state.params, self.state.batch_stats, images_b,
                labels_b, valid_b
            )
        # The host floats are the fence: this waits for the split's epoch
        # and for every train step queued before it.
        with self.tracer.span("eval/fetch", cat="eval", split=prefix):
            count = max(float(count), 1.0)
            return {
                f"{prefix}/eval_loss": float(loss_sum) / count,
                f"{prefix}/eval_acc": float(correct) / count,
            }

    def evaluate(self, include_train: bool = True) -> Dict[str, float]:
        """Full train+test pass in inference mode
        (``Trainer.evaluate``, ``pytorch_collab.py:201-234``)."""
        out: Dict[str, float] = {}
        if include_train:
            out.update(self._eval_split(train=True))
        out.update(self._eval_split(train=False))
        return out

    # ------------------------------------------------------------- inference
    def predict(self, inputs) -> np.ndarray:
        """Inference-mode logits for raw inputs.

        ``inputs``: ``[N, H, W, C]`` images (uint8 or float — normalized
        with the dataset's statistics, as eval does) or ``[N, T, F]``
        sequences (passed through), or ``[N, T]`` rows of token ids (the
        model's inputs as they are; returns ``[N, T, vocabulary rows]``,
        the one place whole per-token logits exist: hand it few rows).
        Returns ``[N, num_classes]`` float32
        logits; ``argmax(-1)`` gives class predictions. The reference has
        no inference entry point at all — evaluation is the closest thing
        (``pytorch_collab.py:201-234``).
        """
        # Multi-controller: keep inputs host-resident (replicated by jit)
        # so they compose with the global params — same guard as
        # _eval_arrays.
        x = np.asarray(inputs)
        if x.ndim == len(self.dataset.x_train.shape[1:]):
            x = x[None]  # single sample convenience
        if not hasattr(self, "_predict_fn"):
            model = self.model
            mean, std = self.dataset.mean, self.dataset.std
            iid_eval = self.config.augmentation == "iid"
            token_rows = self._token_rows

            def fwd(params, batch_stats, x):
                from mercury_tpu.data.pipeline import normalize_images

                if token_rows:
                    return token_logits(
                        model.apply({"params": params}, x, train=False))

                # The exact eval-path preprocessing (make_eval_epoch):
                # normalize (no-op stats for sequences), and the IID
                # path's fixed-key eval transform.
                x = normalize_images(x, mean, std)
                if iid_eval:
                    from mercury_tpu.data.transforms import eval_transform_iid

                    x = eval_transform_iid(jax.random.key(0), x)
                variables = {"params": params}
                if batch_stats:
                    variables["batch_stats"] = batch_stats
                return model.apply(variables, x, train=False)

            self._predict_fn = jax.jit(fwd)
        return np.asarray(
            self._predict_fn(self.state.params, self.state.batch_stats, x),
            np.float32,
        )

    def per_class_accuracy(self, train: bool = False) -> np.ndarray:
        """Per-class accuracy over a split — the class-level view the
        reference's scalar metrics can't give (relevant under Dirichlet
        non-IID skew, where aggregate accuracy hides starved classes).
        One scanned device dispatch over the cached eval batches (same
        sharding as ``evaluate``). Returns ``[num_classes]`` float64;
        classes absent from the split are NaN."""
        if self._token_rows:
            raise ValueError("per_class_accuracy reads one class label a "
                             "row; this dataset's rows carry per-token "
                             "labels")
        if not hasattr(self, "_per_class_fn"):
            from mercury_tpu.train.step import make_per_class_epoch

            self._per_class_fn = make_per_class_epoch(
                self.model, self.dataset.mean, self.dataset.std,
                self.dataset.num_classes,
                eval_augmentation=self.config.augmentation
                if self.config.augmentation == "iid" else "none",
                mesh=(self.mesh if jax.process_count() == 1
                      and self.config.tensor_parallel == 1
                      and self.config.fsdp_parallel == 1 else None),
                axis=self.config.mesh_axis,
            )
        images_b, labels_b, valid_b = self._eval_arrays(train)
        hits, totals = self._per_class_fn(
            self.state.params, self.state.batch_stats,
            images_b, labels_b, valid_b,
        )
        hits = np.asarray(hits, np.int64)
        totals = np.asarray(totals, np.int64)
        with np.errstate(invalid="ignore"):
            return np.where(totals > 0, hits / np.maximum(totals, 1), np.nan)

    # ----------------------------------------------------- checkpoint hooks
    def save(self, directory: Optional[str] = None) -> str:
        directory = directory or self.config.checkpoint_dir
        assert directory, "no checkpoint directory configured"
        return ckpt.save_checkpoint(directory, self.state,
                                    int(self.state.step),
                                    **self._ckpt_kwargs())

    def _state_sharding_tree(self, params_sh, opt_sh):
        """``(state shardings, metrics sharding)`` for this trainer's
        state with the given params / optimizer layouts."""
        from mercury_tpu.train.step import mercury_state_out_shardings

        return mercury_state_out_shardings(
            self.mesh, self.config.mesh_axis, params_sh, opt_sh,
            **self._mode.state_fields())

    def _state_shardings(self) -> MercuryState:
        """``NamedSharding`` prefix tree of the layout the step program
        keeps ``self.state`` in: the pinned TP/FSDP layout when there is
        one, else params replicated, optimizer state replicated (chunk-
        sharded under ZeRO-1) and sampler state sharded over the data
        axis (``train.step._state_specs``)."""
        if self._state_out_shardings is not None:
            return self._state_out_shardings[0]
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        cfg = self.config
        rep = NamedSharding(self.mesh, P())
        opt = (NamedSharding(self.mesh, P(cfg.mesh_axis))
               if cfg.zero_sharding else rep)
        return self._state_sharding_tree(rep, opt)[0]

    def _recommit_state(self, reprime_stream: bool = False) -> None:
        """Re-place a host-resident ``self.state`` for this trainer's
        topology: global arrays over the cross-process mesh
        (multi-controller), and/or the committed Megatron TP layout —
        so the first post-restore step hits the jit cache (the input
        sharding signature is part of it) and the layout-stability
        invariant holds from step one. Shared by ``restore`` and
        ``restore_elastic``.

        Single-process restores must NOT skip this: the checkpoint
        reader hands back host numpy leaves, and donating those into a
        step executable replayed from the persistent compilation cache
        corrupts the transient input buffers (NaN params or SIGSEGV on
        the following step, jax 0.4.37 CPU). Committing the whole state
        to the step's layout first makes the first donated call operate
        on real device buffers."""
        if jax.process_count() > 1:
            from mercury_tpu.parallel.distributed import globalize_state

            tp_kw = {}
            if self._state_out_shardings is not None:
                state_sh, _ = self._state_out_shardings
                tp_kw = dict(params_sharding=state_sh.params,
                             opt_sharding=state_sh.opt_state)
            self.state = globalize_state(
                self.state, self.mesh, self.config.mesh_axis,
                zero_sharding=self.config.zero_sharding, **tp_kw,
            )
        else:
            state_sh = self._state_shardings()
            # Identity jit, not a bare device_put: on CPU device_put may
            # zero-copy alias the checkpoint reader's host buffers, and
            # the first donated step would then hand XLA memory it
            # doesn't own. Executable outputs are always XLA-allocated.
            self.state = jax.jit(lambda s: s, out_shardings=state_sh)(
                jax.device_put(self.state, state_sh)
            )
        if reprime_stream and getattr(self, "_stream_pipe", None) is not None:
            # Elastic restore: the live ring was drawn for the OLD (W, L)
            # topology — regenerate depth in-flight selections from the
            # restored (step-folded) rng and seed the pipeline with them.
            self.state, primed_gidx = self._stream_prime(
                self.state, self.dataset.shard_indices
            )
            self._seed_stream_pipe(primed_gidx)
        else:
            # The restored pending_sel ring defines steps t..t+depth-1's
            # selections; re-seed the prefetch pipeline with their rows.
            self._refill_stream_pipe()
        # Async fleet: queued chunks scored the pre-restore trajectory —
        # discard them and re-snapshot from the restored params (a restore
        # is already a sync point, so the int() here costs nothing new).
        fleet = getattr(self, "_scorer_fleet", None)
        if fleet is not None:
            fleet.reset()
            fleet.snapshot(self.state.params, self.state.batch_stats,
                           int(self.state.step))

    def restore_elastic(self, directory: Optional[str] = None,
                        step: Optional[int] = None, raw=None) -> int:
        """Restore a checkpoint saved at a DIFFERENT world size: model and
        optimizer state transfer exactly (ZeRO-1 chunks reshard W→W′);
        per-worker sampler state re-derives for the new topology. See
        ``mercury_tpu.train.elastic``. ``raw`` passes a pre-probed raw
        checkpoint tree (with its ``step``) to skip re-reading the file.
        The reference hangs on any topology change
        (``pytorch_collab.py:291-292``)."""
        from mercury_tpu.train.elastic import (
            elastic_restore,
            probe_checkpoint,
            world_size_of_raw,
        )

        directory = directory or self.config.checkpoint_dir
        assert directory, "no checkpoint directory configured"
        if raw is None:
            raw, step = probe_checkpoint(directory, step, strict=True)
        w_old = world_size_of_raw(raw)
        step = elastic_restore(directory, self, step, raw=raw)
        # --- auto-planner elastic re-plan: the constructor already
        # resolved plan="auto" for the NEW mesh; here the topology change
        # becomes visible (w_old → world_size), so score the OLD mesh too
        # and journal both tables — the conformance record that the plan
        # switch (or non-switch) was a scored decision, not drift. The
        # applied knobs are the construction-time resolution's (the whole
        # trainer is already built on them). DESIGN.md §16.
        if (self.config.plan == "auto" and self._plan_decision is not None
                and w_old and w_old != self.config.world_size):
            from mercury_tpu.plan.auto import decision_for_config

            old_decision = decision_for_config(
                self.config,
                device_kind=jax.devices()[0].device_kind,
                process_count=jax.process_count(),
                world_size=w_old,
            )
            self._replan_count += 1
            if self._journal is not None:
                self._journal.emit(
                    "elastic/replan", step,
                    detail={
                        "w_old": int(w_old),
                        "w_new": int(self.config.world_size),
                        "plan_old": old_decision.selected,
                        "plan_new": self._plan_decision.selected,
                        "changed": (old_decision.selected
                                    != self._plan_decision.selected),
                        "old_table": old_decision.table(),
                        "new_table": self._plan_decision.table(),
                    })
            _log.info(
                "auto-planner: re-plan W=%s→%s: %s → %s",
                w_old, self.config.world_size,
                old_decision.selected, self._plan_decision.selected,
            )
        # host_stream: the checkpointed pending_sel ring indexes the OLD
        # (W, L) shard matrix — after elastic_restore carried the score
        # table and stream cursor across, re-prime the lookahead ring for
        # the new topology (make_host_stream_prime on the restored,
        # step-folded rng) and seed each host's pipeline from it.
        self._recommit_state(reprime_stream=self._mode.host_stream)
        return step

    def restore(self, directory: Optional[str] = None, step: Optional[int] = None) -> int:
        directory = directory or self.config.checkpoint_dir
        assert directory, "no checkpoint directory configured"
        self.state, step = ckpt.restore_checkpoint(
            directory, self.state, step,
            verify=self.config.checkpoint_verify,
            journal=self._journal)
        self._recommit_state()
        return step
