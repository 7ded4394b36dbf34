"""graftlint Layer P: AOT cost/roofline budgets + fusion/precision scan.

Layers 2/3 pin the traced program's *structure* (collectives, sharding,
memory); this layer pins its *cost*. For every plan in the matrix it
AOT-compiles the step on the CPU mesh and commits three families of
facts to ``lint/perf_budgets.json``:

- **Scoped cost budgets.** ``compiled.cost_analysis()`` total FLOPs and
  bytes-accessed anchor the roofline; a jaxpr walk (dot/conv FLOP
  formulas, 1 FLOP/element for elementwise, scan bodies weighted by
  trip count) attributes estimated FLOPs and operand bytes to the five
  named scopes the step factories anchor (``mercury_scoring``,
  ``mercury_grad_sync``, ``mercury_augmentation``, ``mercury_optimizer``,
  ``mercury_input_fuse``), giving per-scope arithmetic intensity.
  Estimates are deterministic per jax version — that is all a ratchet
  needs; they are not a performance model.
- **Scoring-FLOP ceiling (hard).** Scoring FLOPs as a fraction of step
  FLOPs is the paper's economics: *Not All Samples Are Created Equal*
  only pays when selection stays a small fraction of the step. Each
  plan commits a ceiling (measured fraction plus headroom at regen
  time); exceeding it is an error that is NEVER demoted, version skew
  or not. **Unscoped FLOP growth** (estimated FLOPs outside every
  mercury scope) is the companion finding, mirroring Layer 3's
  unscoped-collective rule: compute nobody claimed is compute nobody
  budgeted.
- **Fusion/precision HLO scan.** The post-optimization HLO is walked
  per computation: f32 ``convert`` results carrying a
  ``mercury_scoring`` op_name are precision leaks (hard error on bf16
  scoring plans — the post-fusion generalization of Layer 3's dataflow
  walk); ``copy``/``transpose`` ops attributed to any mercury scope are
  layout churn, ratcheted per scope; elementwise ops carrying
  ``mercury_input_fuse`` op_names that sit *outside* any fused
  computation are exactly the chains PR 11's kernel exists to fuse,
  ratcheted with named examples.

The runtime half of Layer P — the retrace guard that executes each plan
and pins steady-state compile counts — lives in
:mod:`mercury_tpu.lint.tracecheck`; its per-plan expectations are
committed in this file's ``retrace`` section so one golden carries the
whole perf contract. Regenerate with
``python -m mercury_tpu.lint --layer perf --regen`` (or the atomic
all-layer ``python -m mercury_tpu.lint --regen``).
"""

from __future__ import annotations

import hashlib
import json
import os
import re
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

from mercury_tpu.lint import golden
from mercury_tpu.lint.audit import (
    PLAN_NAMES,
    _BUILDERS,
    _name_stack,
    ensure_cpu_devices,
)

SCHEMA = "graftlint_perf_budgets_v1"

#: The named scopes the step factories anchor — the attribution targets.
PERF_SCOPES = ("mercury_scoring", "mercury_grad_sync",
               "mercury_augmentation", "mercury_optimizer",
               "mercury_input_fuse")

#: Attribution is first-match so nested scopes (the fused ingest kernel
#: runs inside the augmentation region) don't double-count: most
#: specific first.
_ATTRIBUTION_ORDER = ("mercury_input_fuse", "mercury_scoring",
                      "mercury_grad_sync", "mercury_augmentation",
                      "mercury_optimizer")

#: Relative drift tolerated on ratcheted FLOP/byte counts before a
#: finding fires (recorded in provenance so old goldens keep their own).
DEFAULT_TOLERANCE = 0.10

#: Regen-time headroom multiplier for the scoring-FLOP fraction ceiling.
SCORING_FRAC_HEADROOM = 1.25

_EW_PRIMS = frozenset({
    "add", "sub", "mul", "div", "rem", "max", "min", "neg", "abs",
    "sign", "floor", "ceil", "round", "exp", "expm1", "log", "log1p",
    "tanh", "logistic", "rsqrt", "sqrt", "cbrt", "square",
    "reciprocal", "pow", "integer_pow", "erf", "erfc", "erf_inv",
    "sin", "cos", "tan", "asin", "acos", "atan", "atan2", "sinh",
    "cosh", "select_n", "clamp", "nextafter", "add_any",
    "convert_element_type",
})
_REDUCE_PRIMS = frozenset({
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
    "reduce_and", "reduce_or", "argmax", "argmin", "cumsum", "cumprod",
    "cummax", "cummin", "reduce_precision", "psum", "all_reduce",
})

#: HLO opcodes the input-fuse scan treats as "should have fused".
_HLO_ELEMENTWISE = frozenset({
    "add", "subtract", "multiply", "divide", "maximum", "minimum",
    "exponential", "log", "tanh", "logistic", "negate", "abs", "sign",
    "sqrt", "rsqrt", "power", "convert", "compare", "select", "and",
    "or", "xor", "not", "clamp",
})

#: One HLO instruction: ``%name = <type> <opcode>(...)``.
_HLO_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\S+)\s+([\w\-]+)\((.*?)\)")
#: One HLO computation header: ``[ENTRY] %name (params) -> type {``.
_HLO_COMPUTATION_RE = re.compile(
    r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\((.*)\)\s*->\s*.+\{\s*$")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
#: One ``name: type`` parameter of a computation header.
_HLO_PARAM_RE = re.compile(r"%?([\w.\-]+):\s*(\w+\[)")
#: An operand, with the shape older HLO text prints before it:
#: ``convert(bf16[4,4]{1,0} %x)``; jax 0.9 prints ``convert(%x)``.
_HLO_OPERAND_RE = re.compile(r"(?:(\w+\[)\S*\s+)?%?([\w.\-]+)")


def default_perf_budgets_path() -> str:
    return os.path.join(os.path.dirname(__file__), "perf_budgets.json")


# --------------------------------------------------------------------------
# jaxpr cost attribution
# --------------------------------------------------------------------------

def _prod(shape) -> int:
    out = 1
    for s in shape:
        out *= int(s)
    return out


def _out_size(eqn) -> int:
    return max((_prod(v.aval.shape) for v in eqn.outvars
                if getattr(v, "aval", None) is not None
                and hasattr(v.aval, "shape")), default=0)


def _in_size(eqn) -> int:
    return max((_prod(v.aval.shape) for v in eqn.invars
                if getattr(v, "aval", None) is not None
                and hasattr(v.aval, "shape")), default=0)


def eqn_flops(eqn) -> float:
    """Deterministic FLOP estimate for one equation: exact formulas for
    dot/conv, size-proportional for elementwise/reductions, zero for
    layout/control ops. Ratchet fodder, not a performance model."""
    name = eqn.primitive.name
    try:
        if name == "dot_general":
            (lhs_c, _), _ = eqn.params["dimension_numbers"]
            lhs = eqn.invars[0].aval
            k = _prod(lhs.shape[i] for i in lhs_c)
            return 2.0 * _out_size(eqn) * k
        if name == "conv_general_dilated":
            dn = eqn.params["dimension_numbers"]
            rhs = eqn.invars[1].aval
            out_features = rhs.shape[dn.rhs_spec[0]]
            k = _prod(rhs.shape) / max(1, out_features)
            return 2.0 * _out_size(eqn) * k
        if name in _EW_PRIMS:
            return float(_out_size(eqn))
        if name in _REDUCE_PRIMS:
            return float(_in_size(eqn))
    except Exception:
        return 0.0
    return 0.0


def eqn_bytes(eqn) -> float:
    """Operand + result bytes if nothing were fused or cached — the
    denominator of the per-scope arithmetic-intensity estimate."""
    total = 0.0
    for v in list(eqn.invars) + list(eqn.outvars):
        aval = getattr(v, "aval", None)
        shape = getattr(aval, "shape", None)
        dtype = getattr(aval, "dtype", None)
        if shape is None or dtype is None:
            continue
        total += _prod(shape) * dtype.itemsize
    return total


def _sub_jaxprs_weighted(eqn):
    """(sub_jaxpr, weight) pairs for one equation — scan bodies count
    ``length`` times, every other higher-order body once."""
    weight = 1
    if eqn.primitive.name == "scan":
        weight = int(eqn.params.get("length", 1) or 1)
    for value in eqn.params.values():
        values = value if isinstance(value, (list, tuple)) else (value,)
        for v in values:
            if hasattr(v, "eqns"):
                yield v, weight
            elif hasattr(v, "jaxpr"):
                yield v.jaxpr, weight


def walk_costed_eqns(jaxpr, _mult: int = 1):
    """Yield ``(eqn, multiplier)`` over the whole program, recursing into
    sub-jaxprs with scan trip counts folded into the multiplier."""
    if hasattr(jaxpr, "jaxpr"):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn, _mult
        for sub, weight in _sub_jaxprs_weighted(eqn):
            yield from walk_costed_eqns(sub, _mult * weight)


def _attribute_scope(stack: str) -> Optional[str]:
    for scope in _ATTRIBUTION_ORDER:
        if scope in stack:
            return scope
    return None


# --------------------------------------------------------------------------
# HLO fusion / precision scan
# --------------------------------------------------------------------------

def _scope_tail(op_name: str) -> str:
    parts = op_name.split("/")
    return "/".join(parts[-2:]) if len(parts) > 2 else op_name


def scan_hlo(hlo_text: str, plan: str) -> Dict[str, Any]:
    """Walk post-optimization HLO text; returns the Layer P scan facts:

    - ``f32_scoring_converts``: messages for f32 ``convert`` results
      attributed to ``mercury_scoring`` (the post-fusion precision
      leak).
    - ``scope_layout_ops``: per-scope ``copy``/``transpose`` counts.
    - ``unfused_elementwise``: count of elementwise ops carrying a
      ``mercury_input_fuse`` op_name *outside* any fused computation,
      with up to three named examples.
    """
    f32_converts: List[str] = []
    layout: Dict[str, Dict[str, int]] = {s: {} for s in PERF_SCOPES}
    unfused = 0
    examples: List[str] = []
    in_fusion = False
    types: Dict[str, str] = {}    # value name -> type, this computation's
    for line in hlo_text.splitlines():
        header = _HLO_COMPUTATION_RE.match(line)
        if header:
            comp = header.group(1)
            in_fusion = "fused" in comp
            types = dict(_HLO_PARAM_RE.findall(header.group(2)))
            continue
        m = _HLO_INSTR_RE.match(line)
        if not m:
            continue
        name, result_type, opcode, operands = m.groups()
        types[name] = result_type
        om = _OP_NAME_RE.search(line)
        op_name = om.group(1) if om else ""
        scope = _attribute_scope(op_name)
        if scope is None:
            continue
        if (opcode == "convert" and result_type.startswith("f32")
                and scope == "mercury_scoring"
                and any((shape or types.get(value, "")).startswith("bf16[")
                        for shape, value
                        in _HLO_OPERAND_RE.findall(operands))):
            # Only a bf16→f32 upcast is a leak: the scoring region fell
            # back to f32 math. Input-pixel conversions (u8/f32 → f32
            # normalization before the bf16 downcast) are the designed
            # dataflow and land in Layer 3's walk, not here.
            f32_converts.append(
                f"plan {plan}: bf16→f32 upcast inside mercury_scoring "
                f"(result {result_type.split('{')[0]}, "
                f"op {_scope_tail(op_name)}) — the compiled program "
                "fell back to f32 math inside the bf16 scoring region")
        if opcode in ("copy", "transpose"):
            sc = layout[scope]
            sc[opcode] = sc.get(opcode, 0) + 1
        if (scope == "mercury_input_fuse" and not in_fusion
                and opcode in _HLO_ELEMENTWISE):
            unfused += 1
            if len(examples) < 3:
                examples.append(
                    f"plan {plan}: `{opcode}` escaped fusion inside "
                    f"mercury_input_fuse (op {_scope_tail(op_name)})")
    return {
        "f32_scoring_converts": f32_converts,
        "scope_layout_ops": {s: dict(sorted(c.items()))
                             for s, c in layout.items() if c},
        "unfused_elementwise": unfused,
        "unfused_examples": examples,
    }


# --------------------------------------------------------------------------
# measurement
# --------------------------------------------------------------------------

@dataclass
class PerfMeasurement:
    plan: str
    config: Dict[str, Any]
    #: compiled.cost_analysis() anchors
    cost_flops: float = 0.0
    cost_bytes: float = 0.0
    #: jaxpr-walk estimates per scope
    scope_flops: Dict[str, int] = field(default_factory=dict)
    scope_bytes: Dict[str, int] = field(default_factory=dict)
    est_total_flops: int = 0
    unscoped_flops: int = 0
    scoring_flop_frac: float = 0.0
    #: HLO scan facts
    f32_scoring_converts: List[str] = field(default_factory=list)
    scope_layout_ops: Dict[str, Dict[str, int]] = field(
        default_factory=dict)
    unfused_elementwise: int = 0
    unfused_examples: List[str] = field(default_factory=list)

    def config_hash(self) -> str:
        blob = json.dumps(self.config, sort_keys=True).encode()
        return hashlib.sha256(blob).hexdigest()[:16]

    def scope_intensity(self) -> Dict[str, float]:
        out = {}
        for scope, flops in self.scope_flops.items():
            b = self.scope_bytes.get(scope, 0)
            out[scope] = round(flops / b, 4) if b else 0.0
        return out

    def as_budget(self) -> Dict[str, Any]:
        frac = self.scoring_flop_frac
        ceiling = (round(min(1.0, frac * SCORING_FRAC_HEADROOM + 0.005),
                         4) if frac > 0 else 0.0)
        return {
            "config_hash": self.config_hash(),
            "config": self.config,
            "cost_flops": self.cost_flops,
            "cost_bytes": self.cost_bytes,
            "scope_flops": dict(sorted(self.scope_flops.items())),
            "scope_bytes": dict(sorted(self.scope_bytes.items())),
            "scope_intensity": dict(sorted(
                self.scope_intensity().items())),
            "est_total_flops": self.est_total_flops,
            "unscoped_flops": self.unscoped_flops,
            "scoring_flop_frac": round(frac, 6),
            "scoring_frac_ceiling": ceiling,
            "f32_scoring_converts": len(self.f32_scoring_converts),
            "scope_layout_ops": {s: dict(sorted(c.items()))
                                 for s, c in sorted(
                                     self.scope_layout_ops.items())},
            "unfused_elementwise": self.unfused_elementwise,
        }


def measure_perf_step(step_fn, args: Tuple, plan: str,
                      config: Dict[str, Any]) -> PerfMeasurement:
    """Trace + AOT-compile ``step_fn(*args)`` (no execution) and collect
    the Layer P cost and HLO-scan facts."""
    import jax

    m = PerfMeasurement(plan=plan, config=config)
    closed = jax.make_jaxpr(step_fn)(*args)

    scope_flops = {s: 0.0 for s in PERF_SCOPES}
    scope_bytes = {s: 0.0 for s in PERF_SCOPES}
    total = 0.0
    for eqn, mult in walk_costed_eqns(closed):
        flops = eqn_flops(eqn) * mult
        if not flops:
            continue
        total += flops
        scope = _attribute_scope(_name_stack(eqn))
        if scope is not None:
            scope_flops[scope] += flops
            scope_bytes[scope] += eqn_bytes(eqn) * mult
    m.scope_flops = {s: int(v) for s, v in scope_flops.items()}
    m.scope_bytes = {s: int(v) for s, v in scope_bytes.items()}
    m.est_total_flops = int(total)
    m.unscoped_flops = max(
        0, m.est_total_flops - sum(m.scope_flops.values()))
    if m.est_total_flops:
        m.scoring_flop_frac = (
            m.scope_flops.get("mercury_scoring", 0) / m.est_total_flops)

    lower_fn = step_fn if hasattr(step_fn, "lower") else jax.jit(step_fn)
    compiled = lower_fn.lower(*args).compile()
    cost = compiled.cost_analysis()
    m.cost_flops = float(cost.get("flops", 0.0) or 0.0)
    m.cost_bytes = float(cost.get("bytes accessed", 0.0) or 0.0)
    scan = scan_hlo(compiled.as_text(), plan)
    m.f32_scoring_converts = scan["f32_scoring_converts"]
    m.scope_layout_ops = scan["scope_layout_ops"]
    m.unfused_elementwise = scan["unfused_elementwise"]
    m.unfused_examples = scan["unfused_examples"]
    return m


def measure_perf_plan(plan: str) -> PerfMeasurement:
    step, args, config = _BUILDERS[plan]()
    return measure_perf_step(step, args, plan, config)


# --------------------------------------------------------------------------
# hard invariants (budgets-file independent)
# --------------------------------------------------------------------------

def check_perf_invariants(m: PerfMeasurement) -> List[str]:
    errors: List[str] = []
    if str(m.config.get("scoring_dtype", "")) == "bfloat16":
        # The compiled-HLO form of Layer 3's dataflow leak walk: after
        # fusion, any f32 convert still attributed to the scoring scope
        # is an upcast XLA actually scheduled.
        errors.extend(m.f32_scoring_converts)
    return errors


# --------------------------------------------------------------------------
# budgets file
# --------------------------------------------------------------------------

def perf_budgets_doc(measurements: Sequence[PerfMeasurement],
                     retrace_measurements: Optional[Sequence[Any]] = None,
                     ) -> Dict[str, Any]:
    return {
        "schema": SCHEMA,
        "provenance": golden.provenance(
            "python -m mercury_tpu.lint --layer perf --regen",
            extra={"flop_tolerance": DEFAULT_TOLERANCE,
                   "scoring_frac_headroom": SCORING_FRAC_HEADROOM}),
        "plans": {m.plan: m.as_budget() for m in measurements},
        "retrace": {r.plan: r.as_budget()
                    for r in (retrace_measurements or ())},
    }


def write_perf_budgets(measurements: Sequence[PerfMeasurement],
                       retrace_measurements: Optional[Sequence[Any]] = None,
                       path: Optional[str] = None) -> str:
    return golden.write_golden(
        path or default_perf_budgets_path(),
        perf_budgets_doc(measurements, retrace_measurements))


def load_perf_budgets(path: Optional[str] = None) -> Dict[str, Any]:
    return golden.load_golden(path or default_perf_budgets_path(),
                              SCHEMA, "--layer perf --regen")


def _diff_ratcheted(what: str, expected: float, got: float,
                    tolerance: float) -> Optional[str]:
    if expected <= 0 and got <= 0:
        return None
    base = max(abs(expected), 1.0)
    if abs(got - expected) / base > tolerance:
        return (f"  {what}: expected {expected:.6g}, got {got:.6g} "
                f"({(got - expected) / base:+.1%}, tolerance "
                f"{tolerance:.0%})")
    return None


def compare_perf_budgets(measurements: Sequence[PerfMeasurement],
                         budgets: Dict[str, Any],
                         ) -> Tuple[List[str], List[str]]:
    """Diff measurements against the committed perf budgets. Version
    skew demotes the ratcheted count/FLOP diffs to warnings (XLA
    scheduling and jax lowering drift across releases); the scoring
    FLOP-fraction ceiling and the bf16 precision-leak invariant are
    NEVER demoted — they are the contract, not a fingerprint."""
    import jax

    errors: List[str] = []
    warnings: List[str] = []
    provenance = budgets.get("provenance", {})
    tolerance = float(provenance.get("flop_tolerance", DEFAULT_TOLERANCE))
    version_match = provenance.get("jax") == jax.__version__
    if not version_match:
        warnings.append(
            f"perf budgets recorded under jax {provenance.get('jax')}, "
            f"running {jax.__version__}: FLOP/layout diffs demoted to "
            "warnings — the scoring-fraction ceiling still binds; "
            "regenerate perf_budgets.json on the pinned version")

    plans = budgets.get("plans", {})
    for m in measurements:
        errors.extend(check_perf_invariants(m))
        budget = plans.get(m.plan)
        if budget is None:
            errors.append(f"plan {m.plan}: no committed perf budget — "
                          "run --layer perf --regen and review the diff")
            continue

        # Hard ceiling: scoring cost as a fraction of the step.
        ceiling = float(budget.get("scoring_frac_ceiling", 0.0))
        if m.scoring_flop_frac > ceiling + 1e-9:
            errors.append(
                f"plan {m.plan}: scoring FLOPs are "
                f"{m.scoring_flop_frac:.1%} of the step, above the "
                f"committed ceiling {ceiling:.1%} — sampler work "
                "regressed the scoring-cost economics (hard ceiling, "
                "never demoted; if intentional, regenerate and review "
                "the new ceiling)")

        soft: List[str] = []
        if budget.get("config_hash") != m.config_hash():
            soft.append(
                f"  config_hash expected {budget.get('config_hash')}, "
                f"got {m.config_hash()} (the audited config changed — "
                "every downstream diff follows from this)")
        for what, expected, got in (
                ("cost_flops", budget.get("cost_flops", 0.0),
                 m.cost_flops),
                ("cost_bytes", budget.get("cost_bytes", 0.0),
                 m.cost_bytes),
                ("est_total_flops", budget.get("est_total_flops", 0),
                 m.est_total_flops)):
            line = _diff_ratcheted(what, float(expected), float(got),
                                   tolerance)
            if line:
                soft.append(line)
        for scope in PERF_SCOPES:
            line = _diff_ratcheted(
                f"scope_flops[{scope}]",
                float(budget.get("scope_flops", {}).get(scope, 0)),
                float(m.scope_flops.get(scope, 0)), tolerance)
            if line:
                soft.append(line)
        unscoped_line = _diff_ratcheted(
            "unscoped_flops", float(budget.get("unscoped_flops", 0)),
            float(m.unscoped_flops), tolerance)
        if unscoped_line and m.unscoped_flops > budget.get(
                "unscoped_flops", 0):
            soft.append(unscoped_line + "  <- unscoped FLOP growth: "
                        "compute outside every mercury scope (the "
                        "cost analogue of an implicit resharding)")
        elif unscoped_line:
            soft.append(unscoped_line)
        for scope in PERF_SCOPES:
            soft.extend(golden.diff_counts(
                f"scope_layout_ops[{scope}]",
                budget.get("scope_layout_ops", {}).get(scope, {}),
                m.scope_layout_ops.get(scope, {})))
        if budget.get("f32_scoring_converts", 0) != len(
                m.f32_scoring_converts):
            soft.append(
                f"  f32_scoring_converts expected "
                f"{budget.get('f32_scoring_converts', 0)}, got "
                f"{len(m.f32_scoring_converts)}")
            soft.extend(f"    {msg}" for msg in m.f32_scoring_converts)
        if m.unfused_elementwise > budget.get("unfused_elementwise", 0):
            soft.append(
                f"  unfused_elementwise expected "
                f"{budget.get('unfused_elementwise', 0)}, got "
                f"{m.unfused_elementwise} — elementwise chains escaped "
                "fusion inside mercury_input_fuse")
            soft.extend(f"    {msg}" for msg in m.unfused_examples)
        if soft:
            header = (f"plan {m.plan}: compiled cost profile deviates "
                      "from committed perf budget:")
            block = [header] + soft + [
                "  (intentional change? regenerate: python -m "
                "mercury_tpu.lint --layer perf --regen)"]
            (errors if version_match else warnings).extend(block)
    return errors, warnings


def run_perf_audit(plans: Sequence[str] = PLAN_NAMES,
                   budgets_path: Optional[str] = None,
                   regen: bool = False,
                   diff_out: Optional[str] = None,
                   retrace_steps: int = 4,
                   ) -> Tuple[List[str], List[str]]:
    """Layer P driver: measure the requested plans' compiled cost
    profiles and either record (``regen=True``, which also re-measures
    the retrace expectations — the runtime half of the golden) or verify
    them against the committed perf budgets. Returns
    ``(errors, warnings)``; empty errors means the layer passed."""
    ensure_cpu_devices()
    measurements = [measure_perf_plan(p) for p in plans]
    if regen:
        from mercury_tpu.lint.tracecheck import measure_plan_retraces

        retraces = [measure_plan_retraces(p, steps=retrace_steps)
                    for p in plans]
        path = write_perf_budgets(measurements, retraces, budgets_path)
        errors: List[str] = []
        for m in measurements:
            errors.extend(check_perf_invariants(m))
        return errors, [f"perf budgets written to {path}"]
    budgets = load_perf_budgets(budgets_path)
    errors, warnings = compare_perf_budgets(measurements, budgets)
    if diff_out and (errors or warnings):
        golden.write_diff_file(diff_out, "graftlint perf diff",
                               errors, warnings)
    return errors, warnings


#: Re-exported for golden.regen_all_goldens, which treats Layer P as one
#: unit (static budgets + retrace expectations share the golden).
def measure_plan_retraces(plan: str, steps: int = 4):
    from mercury_tpu.lint import tracecheck

    return tracecheck.measure_plan_retraces(plan, steps=steps)
