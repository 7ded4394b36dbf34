"""graftlint Layer 2: jaxpr/HLO structural auditor.

Traces the fused Mercury train step (and its ZeRO / bf16-scoring /
sequence-parallel / pipeline-parallel / async-scorer variants) on CPU —
trace only, no compile, no execution — and checks *structural invariants
of the traced program* as data:

- **Collective budget**: exact per-primitive counts (psum, all_gather,
  reduce_scatter, ppermute, …) per parallelism plan, globally and inside
  the ``mercury_scoring`` / ``mercury_grad_sync`` named scopes the step
  functions anchor. An extra all-gather on the ZeRO path is a budget
  diff, not a silent 2× wire cost.
- **Zero host callbacks** when ``telemetry=False`` (hard invariant — a
  stray ``debug_callback`` would put a host round-trip on every step).
- **Donation aliasing**: the count of ``tf.aliasing_output`` /
  ``jax.buffer_donor`` markers in the lowered StableHLO must cover what
  ``make_train_step`` donates (the state; under ``host_stream`` the
  streamed slab too).
- **bf16 scoring stays bf16**: with ``scoring_dtype="bfloat16"``, zero
  f32×f32 dot/conv ops inside the ``mercury_scoring`` scope (hard
  invariant — a silent upcast would erase the plan's FLOP savings).
- **Async refresh carries no scoring**: with ``refresh_mode="async"``,
  zero dot/conv ops and zero collectives inside ``mercury_scoring``
  (hard invariant — the scorer fleet owns the refresh, so any scoring
  compute in the hot program is the regression the mode exists to
  remove).
- **Seed-program digest**: the sha256 of the canonicalized jaxpr for
  ``telemetry=False`` must equal the committed digest, turning PR 2's
  compile-away benchmark claim into a checked invariant, and the dp
  plan's metric-key surface must equal the seed's exactly.

Budgets live in the committed ``lint/budgets.json`` (regenerate with
``python -m mercury_tpu.lint --layer audit --regen`` after an intentional
program change); the file header records provenance (jax/jaxlib version,
per-plan config hash). When the recorded jax version differs from the
running one, digest and collective mismatches are demoted to warnings —
jaxpr text is not stable across jax releases — while the hard invariants
above always fail loudly.
"""

from __future__ import annotations

import hashlib
import json
import os
import re
import sys
from dataclasses import dataclass, field
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

from mercury_tpu.lint import golden

SCHEMA = "graftlint_budgets_v1"
PLAN_NAMES = ("dp", "zero", "dp_bf16", "hs", "hs_local", "hs_fused", "sp",
              "pp", "async", "device_scorer")

# The seed step's metric surface — what telemetry=False must reproduce
# exactly (mirrors benchmarks/telemetry_overhead.py::BASE_KEYS).
SEED_METRIC_KEYS = frozenset({
    "train/loss", "train/acc", "train/pool_loss", "train/sparse_rate",
    "train/moe_aux",
})

COLLECTIVE_PRIMS = frozenset({
    "psum", "pmin", "pmax", "ppermute", "pbroadcast", "all_gather",
    "all_to_all", "reduce_scatter", "psum_scatter", "all_reduce",
    "reduce_precision_sum",
})
CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "outside_call",
    "host_callback_call", "python_callback",
    "debug_print",  # what jax.debug.print traces to on jax 0.9
})
SCOPES = ("mercury_scoring", "mercury_grad_sync")
DONATION_MARKERS = ("tf.aliasing_output", "jax.buffer_donor")


def default_budgets_path() -> str:
    return os.path.join(os.path.dirname(__file__), "budgets.json")


def ensure_cpu_devices(n: int = 8) -> None:
    """Force ``n`` virtual CPU devices — must run before the jax backend
    initializes (same dance as tests/conftest.py)."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        # Probe device count ONLY when a backend is already live: calling
        # jax.devices() on a merely-imported jax would itself initialize
        # a 1-device backend and make the XLA_FLAGS below a no-op (the
        # tracecheck CLI hits this — importing the builders pulls in jax).
        xb = sys.modules.get("jax._src.xla_bridge")
        if xb is not None and getattr(xb, "_backends", None):
            import jax

            if len(jax.devices()) >= n:
                return  # backend is up with enough devices (pytest)
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()
    from mercury_tpu.platform import select_cpu_if_requested

    select_cpu_if_requested()


# --------------------------------------------------------------------------
# jaxpr walking
# --------------------------------------------------------------------------

def _sub_jaxprs(params: Dict[str, Any]) -> Iterator[Any]:
    for value in params.values():
        values = value if isinstance(value, (list, tuple)) else (value,)
        for v in values:
            if hasattr(v, "eqns"):           # Jaxpr
                yield v
            elif hasattr(v, "jaxpr"):        # ClosedJaxpr
                yield v.jaxpr


def iter_eqns(jaxpr) -> Iterator[Any]:
    """Every equation in a (Closed)Jaxpr, recursing into sub-jaxprs
    (pjit, scan, cond, shard_map, custom_vjp, …)."""
    if hasattr(jaxpr, "jaxpr"):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn.params):
            yield from iter_eqns(sub)


def _name_stack(eqn) -> str:
    si = getattr(eqn, "source_info", None)
    ns = getattr(si, "name_stack", None)
    return str(ns) if ns is not None else ""


def _canonical_jaxpr_text(jaxpr) -> str:
    """Pretty-printed jaxpr with run-dependent noise removed (object
    addresses inside custom_vjp/callback thunk reprs)."""
    text = str(jaxpr)
    return re.sub(r"0x[0-9a-fA-F]+", "0xADDR", text)


def _leaf_dtypes(vars_) -> List[str]:
    out = []
    for v in vars_:
        aval = getattr(v, "aval", None)
        dtype = getattr(aval, "dtype", None)
        if dtype is not None:
            out.append(str(dtype))
    return out


# --------------------------------------------------------------------------
# measurement
# --------------------------------------------------------------------------

@dataclass
class PlanMeasurement:
    plan: str
    config: Dict[str, Any]
    collectives: Dict[str, int] = field(default_factory=dict)
    scoped_collectives: Dict[str, Dict[str, int]] = field(
        default_factory=dict)
    host_callbacks: int = 0
    donation_markers: int = 0
    expected_donated_args: int = 0
    f32_scoring_dots: int = 0
    scoring_ops: int = 0
    jaxpr_sha256: str = ""
    metric_keys: List[str] = field(default_factory=list)

    def config_hash(self) -> str:
        blob = json.dumps(self.config, sort_keys=True).encode()
        return hashlib.sha256(blob).hexdigest()[:16]

    def as_budget(self) -> Dict[str, Any]:
        return {
            "config_hash": self.config_hash(),
            "config": self.config,
            "collectives": dict(sorted(self.collectives.items())),
            "scoped_collectives": {
                scope: dict(sorted(counts.items()))
                for scope, counts in sorted(
                    self.scoped_collectives.items())
            },
            "host_callbacks": self.host_callbacks,
            "donation_markers": self.donation_markers,
            "f32_scoring_dots": self.f32_scoring_dots,
            "scoring_ops": self.scoring_ops,
            "jaxpr_sha256": self.jaxpr_sha256,
            "metric_keys": self.metric_keys,
        }


def measure_step(step_fn, args: Tuple, plan: str,
                 config: Dict[str, Any]) -> PlanMeasurement:
    """Trace ``step_fn(*args)`` (no execution) and collect the audited
    structural facts."""
    import jax

    m = PlanMeasurement(plan=plan, config=config)
    # host_stream plans donate the streamed slab (arg 1) on top of the
    # state (arg 0) — mirror make_train_step's donate_argnums so the
    # consistency check below audits what the step actually configures.
    m.expected_donated_args = (
        2 if config.get("data_placement") == "host_stream" else 1)

    closed = jax.make_jaxpr(step_fn)(*args)
    for scope in SCOPES:
        m.scoped_collectives.setdefault(scope, {})
    for eqn in iter_eqns(closed):
        name = eqn.primitive.name
        if name in COLLECTIVE_PRIMS:
            m.collectives[name] = m.collectives.get(name, 0) + 1
            stack = _name_stack(eqn)
            for scope in SCOPES:
                if scope in stack:
                    sc = m.scoped_collectives[scope]
                    sc[name] = sc.get(name, 0) + 1
        elif name in CALLBACK_PRIMS:
            m.host_callbacks += 1
        if name in ("dot_general", "conv_general_dilated") \
                and "mercury_scoring" in _name_stack(eqn):
            m.scoring_ops += 1
            dtypes = _leaf_dtypes(eqn.invars)
            if dtypes and all(d == "float32" for d in dtypes):
                m.f32_scoring_dots += 1
    m.jaxpr_sha256 = hashlib.sha256(
        _canonical_jaxpr_text(closed).encode()).hexdigest()

    lower_fn = step_fn if hasattr(step_fn, "lower") else jax.jit(step_fn)
    try:
        lowered = lower_fn.lower(*args).as_text()
        m.donation_markers = sum(
            lowered.count(marker) for marker in DONATION_MARKERS)
    except Exception:
        m.donation_markers = -1  # lowering unavailable; skip the check

    out = jax.eval_shape(step_fn, *args)
    # (state, metrics) for the fused plans; (state, metrics, next_gidx)
    # for host_stream's lookahead step.
    metrics = out[1] if isinstance(out, tuple) and len(out) >= 2 else {}
    m.metric_keys = sorted(metrics) if isinstance(metrics, dict) else []
    return m


# --------------------------------------------------------------------------
# plan builders — small, fixed configs; trace-only cost
# --------------------------------------------------------------------------

def _build_fused(variant: str):
    """dp / zero / dp_bf16: the fused SPMD step via the Trainer, exactly
    the construction benchmarks/telemetry_overhead.py benchmarks (scaled
    down: world=2)."""
    from mercury_tpu.config import TrainConfig
    from mercury_tpu.parallel.mesh import make_mesh
    from mercury_tpu.train.trainer import Trainer

    kw: Dict[str, Any] = dict(
        model="smallcnn",
        dataset="synthetic",
        world_size=2,
        batch_size=8,
        presample_batches=2,
        sampler="pool",
        num_epochs=1,
        steps_per_epoch=100,
        eval_every=0,
        log_every=0,
        scan_steps=1,
        compute_dtype="float32",
        telemetry=False,
        heartbeat_every=0,
        seed=0,
    )
    if variant == "zero":
        kw["zero_sharding"] = True
    elif variant == "dp_bf16":
        kw["scoring_dtype"] = "bfloat16"
    config = TrainConfig(**kw)
    trainer = Trainer(config, mesh=make_mesh(2, config.mesh_axis))
    ds = trainer.dataset
    args = (trainer.state, ds.x_train, ds.y_train, ds.shard_indices)
    return trainer.train_step, args, dict(kw, plan=variant)


def _build_async():
    """The async-scorer fused step (``refresh_mode="async"``): the
    scoretable sampler with the refresh forward moved onto the host
    scorer fleet. The traced program must carry ZERO scoring ops — that
    is the feature's entire claim, so it is a hard invariant here, not
    just a budget entry. The trainer's fleet is closed immediately: the
    audit traces the step program, and a live background scorer would
    burn CPU under every subsequent plan's trace."""
    from mercury_tpu.config import TrainConfig
    from mercury_tpu.parallel.mesh import make_mesh
    from mercury_tpu.train.trainer import Trainer

    kw: Dict[str, Any] = dict(
        model="smallcnn",
        dataset="synthetic",
        world_size=2,
        batch_size=8,
        presample_batches=2,
        sampler="scoretable",
        refresh_mode="async",
        scorer_workers=1,
        snapshot_every=4,
        num_epochs=1,
        steps_per_epoch=100,
        eval_every=0,
        log_every=0,
        scan_steps=1,
        compute_dtype="float32",
        telemetry=False,
        heartbeat_every=0,
        seed=0,
    )
    config = TrainConfig(**kw)
    trainer = Trainer(config, mesh=make_mesh(2, config.mesh_axis))
    trainer._scorer_fleet.close()
    ds = trainer.dataset
    args = (trainer.state, ds.x_train, ds.y_train, ds.shard_indices)
    return trainer.train_step, args, dict(kw, plan="async")


def _build_device_scorer():
    """The device-backed scorer service (``scorer_backend="device"``):
    rescoring runs as its OWN pjit program on the reserved scorer slice
    (CPU two-program degradation here), so the TRAINER's fused step must
    stay exactly as scoring-free as the ``async`` plan's — the budget
    pins that moving the scoring program onto a device slice changed
    nothing about the hot program. The trainer's service is closed
    immediately, like the async plan's fleet."""
    from mercury_tpu.config import TrainConfig
    from mercury_tpu.parallel.mesh import make_mesh
    from mercury_tpu.train.trainer import Trainer

    kw: Dict[str, Any] = dict(
        model="smallcnn",
        dataset="synthetic",
        world_size=2,
        batch_size=8,
        presample_batches=2,
        sampler="scoretable",
        refresh_mode="async",
        scorer_backend="device",
        scorer_workers=1,
        snapshot_every=4,
        num_epochs=1,
        steps_per_epoch=100,
        eval_every=0,
        log_every=0,
        scan_steps=1,
        compute_dtype="float32",
        telemetry=False,
        heartbeat_every=0,
        seed=0,
    )
    config = TrainConfig(**kw)
    trainer = Trainer(config, mesh=make_mesh(2, config.mesh_axis))
    trainer._scorer_fleet.close()
    ds = trainer.dataset
    args = (trainer.state, ds.x_train, ds.y_train, ds.shard_indices)
    return trainer.train_step, args, dict(kw, plan="device_scorer")


def _build_hs(shard_mode: str = None):
    """host_stream dp: the lookahead step (``hs_body``) — pixels arrive
    as a streamed uint8 batch, the next selection's indices leave as a
    third output. The pixel argument is a shape/dtype template: tracing
    and AOT lowering never need values, and the audit must not depend on
    the prefetch thread having produced anything.

    ``shard_mode="local"`` builds the multi-controller variant (per-host
    slab + callback assembly on the drain side): its budget pins that
    host-local assembly is a pure dataflow change — the traced step
    program (jaxpr digest, collectives, donation of state AND slab) is
    IDENTICAL to the full-slab plan's."""
    import jax

    from mercury_tpu.config import TrainConfig
    from mercury_tpu.parallel.mesh import make_mesh
    from mercury_tpu.train.trainer import Trainer

    kw: Dict[str, Any] = dict(
        model="smallcnn",
        dataset="synthetic",
        world_size=2,
        batch_size=8,
        presample_batches=2,
        sampler="pool",
        data_placement="host_stream",
        prefetch_depth=2,
        num_epochs=1,
        steps_per_epoch=100,
        eval_every=0,
        log_every=0,
        scan_steps=1,
        compute_dtype="float32",
        telemetry=False,
        heartbeat_every=0,
        seed=0,
    )
    if shard_mode is not None:
        kw["stream_shard_mode"] = shard_mode
    config = TrainConfig(**kw)
    trainer = Trainer(config, mesh=make_mesh(2, config.mesh_axis))
    staging = trainer._stream_pipe._staging[0]
    x_t = jax.ShapeDtypeStruct(staging.shape, staging.dtype)
    args = (trainer.state, x_t, trainer._step_y,
            trainer.dataset.shard_indices)
    plan = "hs" if shard_mode is None else f"hs_{shard_mode}"
    return trainer.train_step, args, dict(kw, plan=plan)


def _build_hs_fused():
    """host_stream with the fused uint8 ingest AND end-to-end bf16
    scoring: ``data.pipeline.augment_normalize`` replaces the
    normalize+augment HLO chain and the scoring forward runs bf16 from
    uint8 to score. Gets its OWN plan entry so the fused program carries its own
    ``scoring_ops`` budget and donation-consistency check — the streamed
    slab must stay donated when the fused chain consumes it."""
    import jax

    from mercury_tpu.config import TrainConfig
    from mercury_tpu.parallel.mesh import make_mesh
    from mercury_tpu.train.trainer import Trainer

    kw: Dict[str, Any] = dict(
        model="smallcnn",
        dataset="synthetic",
        world_size=2,
        batch_size=8,
        presample_batches=2,
        sampler="pool",
        data_placement="host_stream",
        prefetch_depth=2,
        fused_input=True,
        scoring_dtype="bfloat16",
        num_epochs=1,
        steps_per_epoch=100,
        eval_every=0,
        log_every=0,
        scan_steps=1,
        compute_dtype="float32",
        telemetry=False,
        heartbeat_every=0,
        seed=0,
    )
    config = TrainConfig(**kw)
    trainer = Trainer(config, mesh=make_mesh(2, config.mesh_axis))
    staging = trainer._stream_pipe._staging[0]
    x_t = jax.ShapeDtypeStruct(staging.shape, staging.dtype)
    args = (trainer.state, x_t, trainer._step_y,
            trainer.dataset.shard_indices)
    return trainer.train_step, args, dict(kw, plan="hs_fused")


def _build_sp():
    """2 data × 2 seq mesh, ring-attention transformer — the
    TestDpSpMercuryStep construction, scaled down."""
    import jax
    import numpy as np
    import optax
    from jax.sharding import Mesh

    from mercury_tpu.models import TransformerClassifier
    from mercury_tpu.train.sp_step import (
        init_sp_mercury_state,
        make_dp_sp_mercury_step,
    )

    T, F, C, N = 16, 8, 5, 32
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                ("data", "seq"))
    model = TransformerClassifier(
        num_classes=C, d_model=32, num_heads=2, num_layers=2,
        max_len=T, sp_axis="seq",
    )
    tx = optax.sgd(0.05)
    x = jax.random.normal(jax.random.key(40), (N, T, F))
    y = jax.numpy.asarray(
        np.random.default_rng(41).integers(0, C, N))
    state = init_sp_mercury_state(jax.random.key(7), model, tx, x[:1],
                                  2, N)
    step = make_dp_sp_mercury_step(model, tx, mesh, batch_size=4,
                                   presample_batches=2)
    config = dict(plan="sp", model="transformer", d=2, s=2, T=T, F=F,
                  C=C, N=N, batch_size=4, presample_batches=2,
                  telemetry=False)
    return step, (state, x, y), config


def _build_pp():
    """2-stage GPipe schedule — the test_pp_mercury construction, scaled
    down to 2 pipe devices."""
    import jax
    import numpy as np
    import optax
    from jax.sharding import Mesh

    from mercury_tpu.models import TransformerClassifier
    from mercury_tpu.train.pp_step import (
        create_pp_state,
        make_pp_mercury_step,
    )

    T, F, C, N = 16, 8, 5, 32
    mesh = Mesh(np.array(jax.devices()[:2]), ("pipe",))
    model = TransformerClassifier(num_classes=C, d_model=32, num_heads=2,
                                  num_layers=2, max_len=T)
    tx = optax.adam(1e-3)
    k1, k2 = jax.random.split(jax.random.key(0))
    x = jax.random.normal(k1, (N, T, F))
    y = jax.random.randint(k2, (N,), 0, C)
    state = create_pp_state(jax.random.key(0), model, tx, x[:1],
                            shard_len=N, mesh=mesh)
    step = make_pp_mercury_step(model, tx, mesh, batch_size=4,
                                presample_batches=2, num_microbatches=2)
    config = dict(plan="pp", model="transformer", pipe=2, T=T, F=F, C=C,
                  N=N, batch_size=4, presample_batches=2,
                  num_microbatches=2, telemetry=False)
    return step, (state, x, y), config


_BUILDERS = {
    "dp": lambda: _build_fused("dp"),
    "zero": lambda: _build_fused("zero"),
    "dp_bf16": lambda: _build_fused("dp_bf16"),
    "hs": _build_hs,
    "hs_local": lambda: _build_hs("local"),
    "hs_fused": _build_hs_fused,
    "sp": _build_sp,
    "pp": _build_pp,
    "async": _build_async,
    "device_scorer": _build_device_scorer,
}


def measure_plan(plan: str) -> PlanMeasurement:
    step, args, config = _BUILDERS[plan]()
    return measure_step(step, args, plan, config)


# --------------------------------------------------------------------------
# hard invariants (budgets-file independent)
# --------------------------------------------------------------------------

def check_invariants(m: PlanMeasurement) -> List[str]:
    errors: List[str] = []
    if m.host_callbacks != 0:
        errors.append(
            f"plan {m.plan}: {m.host_callbacks} host callback(s) in the "
            "traced program with telemetry=False (expected 0: each one "
            "is a per-step host round-trip)")
    if m.plan == "dp" and set(m.metric_keys) != SEED_METRIC_KEYS:
        errors.append(
            f"plan dp: telemetry=False metric surface "
            f"{sorted(m.metric_keys)} != seed surface "
            f"{sorted(SEED_METRIC_KEYS)} — the compile-away guarantee "
            "is broken")
    if m.config.get("scoring_dtype") == "bfloat16" \
            and m.f32_scoring_dots != 0:
        errors.append(
            f"plan {m.plan}: {m.f32_scoring_dots} f32×f32 dot/conv op(s) "
            "inside the mercury_scoring scope with "
            "scoring_dtype=bfloat16 (expected 0: a silent upcast erases "
            "the scoring FLOP savings)")
    if m.plan in ("async", "device_scorer"):
        if m.scoring_ops != 0:
            errors.append(
                f"plan {m.plan}: {m.scoring_ops} dot/conv op(s) inside "
                "the mercury_scoring scope with refresh_mode=async "
                "(expected 0: the scorer fleet/service owns the refresh "
                "— scoring compute in the hot program is the regression "
                "this plan exists to catch)")
        if m.scoped_collectives.get("mercury_scoring"):
            errors.append(
                f"plan {m.plan}: collectives inside the mercury_scoring "
                f"scope {m.scoped_collectives['mercury_scoring']} with "
                "refresh_mode=async (expected none: no scoring forward, "
                "no scoring collectives)")
    if m.donation_markers >= 0 \
            and m.donation_markers < m.expected_donated_args:
        # Donation consistency, the other direction: every configured
        # donated argument must leave at least one aliasing/buffer-donor
        # marker in the lowered program. For host_stream plans this is
        # the "streamed slab actually donated" assertion — a non-donated
        # PendingSelection output silently pinning the slab would show
        # up here as a missing marker.
        errors.append(
            f"plan {m.plan}: only {m.donation_markers} donation "
            f"marker(s) in the lowered program for "
            f"{m.expected_donated_args} donated argument(s) — a donated "
            "input (state or streamed slab) is not actually aliased")
    return errors


# --------------------------------------------------------------------------
# budgets file
# --------------------------------------------------------------------------

def budgets_doc(measurements: Sequence[PlanMeasurement]) -> Dict[str, Any]:
    return {
        "schema": SCHEMA,
        "provenance": golden.provenance(
            "python -m mercury_tpu.lint --layer audit --regen"),
        "plans": {m.plan: m.as_budget() for m in measurements},
    }


def write_budgets(measurements: Sequence[PlanMeasurement],
                  path: Optional[str] = None) -> str:
    return golden.write_golden(path or default_budgets_path(),
                               budgets_doc(measurements))


def load_budgets(path: Optional[str] = None) -> Dict[str, Any]:
    return golden.load_golden(path or default_budgets_path(), SCHEMA,
                              "--layer audit --regen")


_diff_counts = golden.diff_counts


def compare_budgets(measurements: Sequence[PlanMeasurement],
                    budgets: Dict[str, Any],
                    ) -> Tuple[List[str], List[str]]:
    """Diff measurements against the committed budgets.

    Returns ``(errors, warnings)``: hard invariants and same-jax-version
    budget mismatches are errors; budget mismatches under a *different*
    jax version than the budgets were recorded with are warnings (jaxpr
    text and primitive sets drift across releases — regenerate).
    """
    import jax

    errors: List[str] = []
    warnings: List[str] = []
    recorded_jax = budgets.get("provenance", {}).get("jax")
    version_match = recorded_jax == jax.__version__
    if not version_match:
        warnings.append(
            f"budgets recorded under jax {recorded_jax}, running "
            f"{jax.__version__}: digest/collective diffs demoted to "
            "warnings — regenerate budgets.json on the pinned version")

    plans = budgets.get("plans", {})
    for m in measurements:
        errors.extend(check_invariants(m))
        budget = plans.get(m.plan)
        if budget is None:
            errors.append(f"plan {m.plan}: no committed budget — run "
                          "--regen and review the diff")
            continue
        soft: List[str] = []
        if budget.get("config_hash") != m.config_hash():
            soft.append(
                f"  config_hash expected {budget.get('config_hash')}, "
                f"got {m.config_hash()} (the audited config changed — "
                "every downstream diff follows from this)")
        soft.extend(_diff_counts("collectives",
                                 budget.get("collectives", {}),
                                 m.collectives))
        for scope in SCOPES:
            soft.extend(_diff_counts(
                f"scoped_collectives[{scope}]",
                budget.get("scoped_collectives", {}).get(scope, {}),
                m.scoped_collectives.get(scope, {})))
        if budget.get("jaxpr_sha256") != m.jaxpr_sha256:
            soft.append(
                f"  jaxpr_sha256 expected {budget.get('jaxpr_sha256')}, "
                f"got {m.jaxpr_sha256} (the traced program changed)")
        if budget.get("metric_keys") != m.metric_keys:
            soft.append(
                f"  metric_keys expected {budget.get('metric_keys')}, "
                f"got {m.metric_keys}")
        if m.donation_markers >= 0 \
                and budget.get("donation_markers", 0) != m.donation_markers:
            soft.append(
                f"  donation_markers expected "
                f"{budget.get('donation_markers')}, got "
                f"{m.donation_markers}")
        if budget.get("f32_scoring_dots", 0) != m.f32_scoring_dots:
            soft.append(
                f"  f32_scoring_dots expected "
                f"{budget.get('f32_scoring_dots')}, got "
                f"{m.f32_scoring_dots}")
        if budget.get("scoring_ops", m.scoring_ops) != m.scoring_ops:
            soft.append(
                f"  scoring_ops expected {budget.get('scoring_ops')}, "
                f"got {m.scoring_ops}")
        if soft:
            header = (f"plan {m.plan}: traced program deviates from "
                      "committed budget:")
            block = [header] + soft + [
                "  (intentional change? regenerate: python -m "
                "mercury_tpu.lint --layer audit --regen)"]
            (errors if version_match else warnings).extend(block)
    return errors, warnings


def run_audit(plans: Sequence[str] = PLAN_NAMES,
              budgets_path: Optional[str] = None,
              regen: bool = False,
              diff_out: Optional[str] = None,
              ) -> Tuple[List[str], List[str]]:
    """Measure the requested plans and either record (``regen=True``) or
    verify them against the committed budgets. Returns
    ``(errors, warnings)``; empty errors means the audit passed."""
    ensure_cpu_devices()
    measurements = [measure_plan(p) for p in plans]
    if regen:
        path = write_budgets(measurements, budgets_path)
        errors: List[str] = []
        for m in measurements:
            errors.extend(check_invariants(m))
        return errors, [f"budgets written to {path}"]
    budgets = load_budgets(budgets_path)
    errors, warnings = compare_budgets(measurements, budgets)
    if diff_out and (errors or warnings):
        golden.write_diff_file(diff_out, "graftlint audit diff",
                               errors, warnings)
    return errors, warnings
