"""Automatic parallelism-plan selection from the committed cost model.

``select_plan`` enumerates the graftlint plan matrix (the same ten plans
Layer 2/3/P audit — ``lint/audit.py::PLAN_NAMES``), filters it through
hard feasibility rules (model family, config addressability, controller
topology, per-device memory budget), scores every survivor with

  (a) the committed Layer P per-scope FLOP/byte + arithmetic-intensity
      attribution (``lint/perf_budgets.json``),
  (b) the committed ``memory_analysis()`` footprints
      (``lint/shard_budgets.json``) — hard budget exclusion, and
  (c) the analytic collective-latency model (``plan.latency``, re-exported
      by ``parallel.collectives``): ring/all-gather/reduce-scatter cost
      from payload bytes × mesh axis size × a per-link bandwidth table
      keyed by device kind,

and returns a ranked :class:`PlanDecision` whose every rejected candidate
carries a machine-readable reason. The module is stdlib-only: it reads
committed goldens, so the decision is reproducible on a jax-free host
(CI's ``auto-planner`` job scores candidates exactly this way) and
chip-accurate the moment a fresh roofline regen lands.

``resolve_plan_config`` is the trainer-facing entry:
``TrainConfig(plan="auto")`` resolves to concrete knob overrides at
construction, and ``restore_elastic`` re-runs it when the (W, L) mesh
changes (the ``elastic/replan`` event carries both scored tables).
"""

from __future__ import annotations

import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence, Tuple

from mercury_tpu.plan.latency import link_bandwidth, ring_allreduce_cost_s

#: The plan matrix — MUST mirror ``lint/audit.py::PLAN_NAMES`` (test-pinned;
#: not imported from there because ``lint.audit`` needs jax and this module
#: must stay stdlib-only).
PLAN_NAMES: Tuple[str, ...] = (
    "dp", "zero", "dp_bf16", "hs", "hs_local", "hs_fused",
    "sp", "pp", "async", "device_scorer",
)

#: TrainConfig knob overrides that realize each config-addressable plan.
#: These are the plan-DEFINING knobs only (parallelism / placement /
#: scorer wiring) — model, dataset, world size, and sampler hyperparams
#: stay the user's. ``sp`` / ``pp`` run through dedicated step builders
#: (``train/sp_step.py``, ``train/pp_step.py``), not TrainConfig knobs,
#: so they have no entry and are rejected with ``config_surface`` when
#: the caller needs a Trainer-resolvable plan.
PLAN_KNOBS: Dict[str, Dict[str, Any]] = {
    "dp": {"zero_sharding": False, "data_placement": "replicated",
           "refresh_mode": "sync", "scorer_backend": "host",
           "fused_input": False, "scoring_dtype": None},
    "zero": {"zero_sharding": True, "data_placement": "replicated",
             "refresh_mode": "sync", "scorer_backend": "host",
             "fused_input": False, "scoring_dtype": None},
    "dp_bf16": {"zero_sharding": False, "data_placement": "replicated",
                "refresh_mode": "sync", "scorer_backend": "host",
                "fused_input": False, "scoring_dtype": "bfloat16"},
    "hs": {"zero_sharding": False, "data_placement": "host_stream",
           "refresh_mode": "sync", "scorer_backend": "host",
           "fused_input": False, "scoring_dtype": None},
    "hs_local": {"zero_sharding": False, "data_placement": "host_stream",
                 "stream_shard_mode": "local", "refresh_mode": "sync",
                 "scorer_backend": "host", "fused_input": False,
                 "scoring_dtype": None},
    "hs_fused": {"zero_sharding": False, "data_placement": "host_stream",
                 "fused_input": True, "scoring_dtype": "bfloat16",
                 "refresh_mode": "sync", "scorer_backend": "host"},
    "async": {"zero_sharding": False, "data_placement": "replicated",
              "sampler": "scoretable", "refresh_mode": "async",
              "scorer_backend": "host", "fused_input": False,
              "scoring_dtype": None},
    "device_scorer": {"zero_sharding": False, "data_placement": "replicated",
                      "sampler": "scoretable", "refresh_mode": "async",
                      "scorer_backend": "device", "scorer_throttle_s": 0.0,
                      "fused_input": False, "scoring_dtype": None},
}

#: How each plan's per-device peak scales with the data-axis size W
#: relative to the golden's reference world: "replicated" footprints are
#: W-independent (params + full slab on every device), "sharded" ones
#: shrink ~W_ref/W (ZeRO-1 chunks the optimizer triple over the axis).
MEMORY_SCALING: Dict[str, str] = {name: "replicated" for name in PLAN_NAMES}
MEMORY_SCALING["zero"] = "sharded"

#: Plans whose golden step was built on the transformer family; image /
#: CNN models cannot take them.
_TRANSFORMER_ONLY = ("sp", "pp")
_TRANSFORMER_MODELS = ("transformer", "vit")

#: Plans whose scorer machinery is per-process (fleet snapshot + chunk
#: stream): single-controller runs only.
_SINGLE_CONTROLLER_ONLY = ("async", "device_scorer")

#: Effective host compute rate used when the device kind has no tabulated
#: peak (CPU mesh / jax-free scoring). Calibrated against the lint
#: builders' measured steps/s on the CI CPU mesh — the ranking, not the
#: absolute number, is what the planner consumes.
_CPU_FLOPS_PER_S = 5e9

#: Per-collective dispatch overhead (seconds). On a host-platform mesh
#: each HLO collective costs a scheduling round-trip that dwarfs the wire
#: time of tiny payloads; on TPU ICI it is noise. Without this term the
#: tiny-payload transformer plans look free on CPU and the ranking
#: inverts against measurement.
_COLLECTIVE_OVERHEAD_S = {"cpu": 2e-4, "default": 1e-6}

_LINT_DIR = Path(__file__).resolve().parents[1] / "lint"
PERF_BUDGETS_PATH = _LINT_DIR / "perf_budgets.json"
SHARD_BUDGETS_PATH = _LINT_DIR / "shard_budgets.json"


def load_cost_model(perf_path: Optional[Path] = None,
                    shard_path: Optional[Path] = None) -> Dict[str, Any]:
    """Read the committed goldens the planner scores from."""
    perf = json.loads(Path(perf_path or PERF_BUDGETS_PATH).read_text())
    shard = json.loads(Path(shard_path or SHARD_BUDGETS_PATH).read_text())
    return {"perf": perf, "shard": shard}


@dataclass(frozen=True)
class PlanCandidate:
    """One scored (or rejected) plan. ``reasons`` is empty iff feasible;
    each reason is a machine-readable dict with at least a ``rule`` key."""
    name: str
    feasible: bool
    est_step_s: Optional[float]
    est_steps_per_s: Optional[float]
    compute_s: Optional[float]
    collective_s: Optional[float]
    memory_bytes: Optional[int]
    memory_status: str                     # "ok" | "unavailable" | "over_budget" | "no_data"
    reasons: Tuple[Dict[str, Any], ...] = ()
    knobs: Dict[str, Any] = field(default_factory=dict)

    def as_row(self) -> Dict[str, Any]:
        return {
            "plan": self.name,
            "feasible": self.feasible,
            "est_step_s": self.est_step_s,
            "est_steps_per_s": self.est_steps_per_s,
            "compute_s": self.compute_s,
            "collective_s": self.collective_s,
            "memory_bytes": self.memory_bytes,
            "memory_status": self.memory_status,
            "reasons": list(self.reasons),
        }


@dataclass(frozen=True)
class PlanDecision:
    """Ranked plan-selection outcome: feasible candidates first (fastest
    predicted step first), rejected ones after, each with its reasons."""
    selected: Optional[str]
    candidates: Tuple[PlanCandidate, ...]
    world_size: int
    memory_budget_bytes: int
    device_kind: str
    model: str
    inputs: Dict[str, Any] = field(default_factory=dict)

    @property
    def feasible(self) -> Tuple[PlanCandidate, ...]:
        return tuple(c for c in self.candidates if c.feasible)

    def candidate(self, name: str) -> Optional[PlanCandidate]:
        for c in self.candidates:
            if c.name == name:
                return c
        return None

    def knobs_for(self, name: str) -> Dict[str, Any]:
        cand = self.candidate(name)
        return dict(cand.knobs) if cand else {}

    def table(self) -> List[Dict[str, Any]]:
        """The scored table, journal/bench-record ready (JSON-safe)."""
        return [c.as_row() for c in self.candidates]

    def detail(self) -> Dict[str, Any]:
        """Journal ``detail`` payload for ``plan/selected``."""
        return {
            "selected": self.selected,
            "world_size": self.world_size,
            "memory_budget_bytes": self.memory_budget_bytes,
            "device_kind": self.device_kind,
            "model": self.model,
            "candidates_considered": len(self.candidates),
            "feasible": [c.name for c in self.feasible],
            "table": self.table(),
            "inputs": dict(self.inputs),
        }


def _scaled_peak_bytes(name: str, memory: Dict[str, Any],
                       world_size: int, ref_world: int) -> Optional[int]:
    peak = memory.get("peak_estimate_in_bytes")
    if peak is None:
        return None
    if MEMORY_SCALING.get(name) == "sharded" and world_size > 0:
        return int(peak * ref_world / max(1, world_size))
    return int(peak)


def _compute_rate(device_kind: str, peak_flops: Optional[float]) -> float:
    """FLOP/s the score divides by: an explicit ``peak_flops``, else the
    tabulated peak; the CPU stand-in only for the CPU. An accelerator
    kind that is not tabulated raises (``obs.accounting.peak_flops``)."""
    if peak_flops:
        return float(peak_flops)
    # obs.accounting is stdlib-only; lazy to keep import cost down
    from mercury_tpu.obs.accounting import peak_flops as _peak

    return float(_peak(device_kind) or _CPU_FLOPS_PER_S)


def _collective_overhead(device_kind: str) -> float:
    kind = (device_kind or "").lower()
    if kind.startswith("cpu") or "host" in kind:
        return _COLLECTIVE_OVERHEAD_S["cpu"]
    return _COLLECTIVE_OVERHEAD_S["default"]


def select_plan(model: str = "resnet18",
                world_size: int = 4,
                memory_budget_bytes: int = 0,
                device_kind: str = "cpu",
                process_count: int = 1,
                require_config_addressable: bool = True,
                plans: Optional[Sequence[str]] = None,
                cost_model: Optional[Dict[str, Any]] = None,
                peak_flops: Optional[float] = None,
                constraints: Optional[Dict[str, Any]] = None) -> PlanDecision:
    """Enumerate, filter, and score the plan space; return the ranked
    :class:`PlanDecision`.

    ``memory_budget_bytes=0`` means unbounded. ``constraints`` carries
    config-compatibility facts (``augmentation``, ``cutout``) for plans
    with ingest preconditions. Raises ``ValueError`` on an unknown plan
    name; an empty feasible set yields ``selected=None`` (callers decide
    whether that is fatal)."""
    cm = cost_model or load_cost_model()
    perf_plans = cm["perf"].get("plans", {})
    shard_plans = cm["shard"].get("plans", {})
    cons = constraints or {}
    names = tuple(plans) if plans is not None else PLAN_NAMES
    unknown = [n for n in names if n not in PLAN_NAMES]
    if unknown:
        raise ValueError(f"unknown plan(s): {unknown}; known: {PLAN_NAMES}")

    rate = _compute_rate(device_kind, peak_flops)
    overhead = _collective_overhead(device_kind)
    bw_kind = device_kind

    scored: List[PlanCandidate] = []
    for name in names:
        reasons: List[Dict[str, Any]] = []
        perf = perf_plans.get(name)
        shard = shard_plans.get(name)

        # --- feasibility ------------------------------------------------
        if name in _TRANSFORMER_ONLY and model not in _TRANSFORMER_MODELS:
            reasons.append({"rule": "model_family", "plan_requires": "transformer",
                            "model": model})
        if require_config_addressable and name not in PLAN_KNOBS:
            reasons.append({"rule": "config_surface",
                            "note": "no TrainConfig knob set realizes this plan; "
                                    "use the dedicated step builder"})
        if name in _SINGLE_CONTROLLER_ONLY and process_count > 1:
            reasons.append({"rule": "single_controller",
                            "process_count": process_count})
        if name == "hs_fused" and (
                cons.get("augmentation", "noniid") != "noniid"
                or cons.get("cutout", False)):
            reasons.append({"rule": "ingest_precondition",
                            "requires": {"augmentation": "noniid", "cutout": False},
                            "got": {"augmentation": cons.get("augmentation"),
                                    "cutout": cons.get("cutout")}})
        if name == "sp" and world_size < 4:
            reasons.append({"rule": "mesh_shape", "plan_requires": "data×seq mesh (W ≥ 4)",
                            "world_size": world_size})
        if name == "pp" and world_size % 2 != 0:
            reasons.append({"rule": "mesh_shape", "plan_requires": "even W (2 stages)",
                            "world_size": world_size})

        # --- memory: hard budget exclusion ------------------------------
        memory = (shard or {}).get("memory") or {}
        memory_status = "ok"
        mem_bytes: Optional[int] = None
        if not shard:
            memory_status = "no_data"
        elif "unavailable" in memory:
            # lint/memory.py degraded entry: footprint could not be measured
            # on the regen host. Distinguishable from "fits": the plan stays
            # feasible but the decision records the gap.
            memory_status = "unavailable"
        else:
            ref_world = int((perf or {}).get("config", {}).get("world_size", 2) or 2)
            mem_bytes = _scaled_peak_bytes(name, memory, world_size, ref_world)
            if mem_bytes is None:
                memory_status = "no_data"
            elif memory_budget_bytes > 0 and mem_bytes > memory_budget_bytes:
                memory_status = "over_budget"
                reasons.append({"rule": "memory_budget",
                                "peak_bytes": mem_bytes,
                                "budget_bytes": memory_budget_bytes})

        # --- score ------------------------------------------------------
        est_step = compute_s = collective_s = None
        if perf:
            flops = float(perf.get("est_total_flops") or perf.get("cost_flops") or 0.0)
            compute_s = flops / rate
            sync_bytes = float((perf.get("scope_bytes") or {}).get("mercury_grad_sync", 0.0))
            n_coll = sum((shard or {}).get("hlo_collectives", {}).values()) if shard else 0
            collective_s = (ring_allreduce_cost_s(sync_bytes, world_size, bw_kind)
                            + n_coll * overhead)
            est_step = compute_s + collective_s
        else:
            reasons.append({"rule": "no_cost_data",
                            "note": "plan absent from perf_budgets.json"})

        feasible = not reasons
        scored.append(PlanCandidate(
            name=name,
            feasible=feasible,
            est_step_s=est_step,
            est_steps_per_s=(1.0 / est_step) if est_step else None,
            compute_s=compute_s,
            collective_s=collective_s,
            memory_bytes=mem_bytes,
            memory_status=memory_status,
            reasons=tuple(reasons),
            knobs=dict(PLAN_KNOBS.get(name, {})),
        ))

    feasible = sorted((c for c in scored if c.feasible),
                      key=lambda c: (c.est_step_s if c.est_step_s is not None else float("inf"), c.name))
    rejected = [c for c in scored if not c.feasible]
    ranked = tuple(feasible) + tuple(rejected)
    return PlanDecision(
        selected=feasible[0].name if feasible else None,
        candidates=ranked,
        world_size=world_size,
        memory_budget_bytes=memory_budget_bytes,
        device_kind=device_kind,
        model=model,
        inputs={
            "perf_budgets_schema": cm["perf"].get("schema"),
            "shard_budgets_schema": cm["shard"].get("schema"),
            "perf_provenance": cm["perf"].get("provenance", {}).get("jax"),
            "compute_rate_flops_per_s": rate,
            "link_bandwidth_bytes_per_s": link_bandwidth(device_kind),
        },
    )


def decision_for_config(config: Any, device_kind: str = "cpu",
                        process_count: int = 1,
                        world_size: Optional[int] = None) -> PlanDecision:
    """Run the planner against a ``TrainConfig``'s facts (model, world
    size, budget, ingest constraints). Pure read — never mutates config."""
    return select_plan(
        model=config.model,
        world_size=int(world_size if world_size is not None else config.world_size),
        memory_budget_bytes=int(getattr(config, "plan_memory_budget_bytes", 0) or 0),
        device_kind=device_kind,
        process_count=process_count,
        require_config_addressable=True,
        constraints={"augmentation": config.augmentation, "cutout": config.cutout},
    )


def resolve_plan_config(config: Any, device_kind: str = "cpu",
                        process_count: int = 1) -> Tuple[Any, Optional[PlanDecision]]:
    """Resolve ``config.plan`` to concrete knobs.

    - ``plan=""`` (manual): returned unchanged, no decision.
    - ``plan="auto"``: the ranked winner's knob overrides are applied;
      raises ``RuntimeError`` when no candidate is feasible (the decision
      table is embedded in the message for debuggability).
    - ``plan="<name>"``: that plan's knobs are applied verbatim; the
      decision table is still computed so the journal/bench record shows
      where the forced plan ranked.
    """
    requested = getattr(config, "plan", "") or ""
    if not requested:
        return config, None
    if requested != "auto" and requested not in PLAN_KNOBS:
        known = sorted(PLAN_KNOBS) + ["auto"]
        raise ValueError(f"config.plan={requested!r} is not resolvable; "
                         f"choose one of {known}")
    decision = decision_for_config(config, device_kind=device_kind,
                                   process_count=process_count)
    if requested == "auto":
        if decision.selected is None:
            raise RuntimeError(
                "auto-planner: no feasible plan under the given constraints: "
                + json.dumps(decision.table()))
        chosen = decision.selected
    else:
        chosen = requested
    new_config = config.replace(**decision.knobs_for(chosen))
    return new_config, PlanDecision(
        selected=chosen,
        candidates=decision.candidates,
        world_size=decision.world_size,
        memory_budget_bytes=decision.memory_budget_bytes,
        device_kind=decision.device_kind,
        model=decision.model,
        inputs=decision.inputs,
    )
