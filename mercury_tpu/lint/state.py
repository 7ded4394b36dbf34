"""graftlint Layer E: the state plane as an extracted, checked schema.

Mercury's correctness under preemption hinges on :class:`MercuryState`
surviving checkpoint and elastic resharding intact — the scoretable,
selection ledger, stream cursor and pending-selection ring all carry
hand-written reshard logic, and nothing *statically* guaranteed that a
newly added state field gets a reshard policy, a restore path and an
upgrade shim. Forgetting one is silent corruption. Layer E makes the
state plane explicit three ways, mirroring what Layer S did for the
control plane:

1. **Extract** (:func:`extract_state_facts`): an AST walk over
   ``train/state.py``, ``train/step.py``, ``train/checkpoint.py``,
   ``train/elastic.py`` and ``train/trainer.py`` pulls the structural
   facts the schema is built from — every ``MercuryState`` field with
   its shape-role (replicated / worker-sharded / rng-key, from the
   step's ``_state_specs``), its declared elastic policy
   (``train/state.py::ELASTIC_POLICIES``), the checkpoint lineage +
   upgrade shims (``train/checkpoint.py::STATE_SCHEMA_LINEAGE`` /
   ``UPGRADE_SHIMS``), and which ``elastic_restore`` replace kwarg /
   ``_carry_streamed_state`` ``extra[...]`` site / ``create_state``
   gated init / Trainer reprime handles it. Facts are semantic (no line
   numbers), so the golden only drifts on behavioral edits.
2. **Check + commit** (:func:`check_extraction`, :func:`state_doc`):
   static rules GLE01–GLE06 gate field-without-policy,
   policy-without-carry-site, restore paths that silently drop a field
   (the shim must name it), upgrade-shim lineage gaps, rng state
   resharded by copy instead of ``fold_in``, and checkpoint-manifest
   parity. The schema commits as ``lint/state_schema.json`` (schema
   ``graftlint_state_schema_v1``) with the shared ``--regen`` /
   ``--diff-out`` contract from ``lint/golden.py``, joining the
   all-or-nothing all-layer regen as the sixth golden. The doc carries
   a ``state_schema_sha`` digest over its fields + lineage; checkpoint
   manifests stamp that sha so restore can warn when a checkpoint
   predates the committed schema.
3. **Differential replay** (``python -m mercury_tpu.lint.state
   --differential``): the runtime half executes W=8 → W=4 → W=8
   round-trips per plan and asserts each policy's conformance contract
   — exact-carry fields bit-equal (GLE07), re-aggregate fields
   sum-preserving (GLE08, the sel_counts total invariant), re-seeded
   fields key-distinct (GLE09), cursors epoch-fraction-preserving
   (GLE10) — diffing per-leaf on failure and naming the violated
   policy by rule id.

The static half is stdlib-only (AST + JSON): the lint-state CI job runs
on a jax-free machine. Only ``--differential`` imports jax.
"""

from __future__ import annotations

import argparse
import ast
import hashlib
import json
import os
import sys
from typing import Any, Dict, List, Optional, Sequence, Tuple

from mercury_tpu.lint import golden

__all__ = [
    "STATE_SCHEMA", "POLICY_VOCAB", "extract_state_facts",
    "check_extraction", "state_doc", "schema_sha_of_facts",
    "default_state_schema_path", "run_state_check", "run_differential",
]

#: Golden schema tag; bump on any incompatible schema-shape change.
STATE_SCHEMA = "graftlint_state_schema_v1"

REGEN_HINT = "python -m mercury_tpu.lint --layer state --regen"

#: The modules the extractor walks, keyed by the short name facts use.
STATE_MODULES: Dict[str, str] = {
    "state": os.path.join("train", "state.py"),
    "step": os.path.join("train", "step.py"),
    "checkpoint": os.path.join("train", "checkpoint.py"),
    "elastic": os.path.join("train", "elastic.py"),
    "trainer": os.path.join("train", "trainer.py"),
}

#: The closed elastic-policy vocabulary (see the ``ELASTIC_POLICIES``
#: docstring in ``train/state.py`` for semantics). GLE01 rejects any
#: policy outside it.
POLICY_VOCAB = (
    "replicate", "reshard-exact", "re-aggregate", "re-seed",
    "cursor-fraction", "drop-on-shrink",
)

#: Policies whose carry site is a named ``replace()`` kwarg in
#: ``elastic_restore`` or an ``extra[...]`` assignment in
#: ``_carry_streamed_state`` (i.e. the field's checkpointed value flows
#: into the new state).
CARRIED_POLICIES = ("replicate", "reshard-exact", "re-aggregate",
                    "re-seed", "cursor-fraction")

#: ``create_state`` shape-argument names → schema dim symbols.
DIM_SYMBOLS: Dict[str, str] = {
    "n_workers": "W",
    "shard_len": "L",
    "stream_depth": "D",
    "stream_emit_size": "E",
    "stream_batch_size": "B",
    "pending_batch_size": "B",
    "cached_pool_size": "P",
}


def _package_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def default_state_schema_path() -> str:
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "state_schema.json")


# --------------------------------------------------------------------------
# AST fact extraction
# --------------------------------------------------------------------------

def _module_tree(key: str,
                 sources: Optional[Dict[str, str]] = None) -> ast.AST:
    rel = STATE_MODULES[key]
    if sources is not None and key in sources:
        return ast.parse(sources[key], filename=f"<fixture:{rel}>")
    path = os.path.join(_package_root(), rel)
    with open(path) as f:
        return ast.parse(f.read(), filename=path)


def _class_def(tree: ast.AST, name: str) -> Optional[ast.ClassDef]:
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef) and node.name == name:
            return node
    return None


def _function_def(tree: ast.AST, name: str) -> Optional[ast.FunctionDef]:
    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef) and node.name == name:
            return node
    return None


def _module_literal(tree: ast.AST, name: str) -> Optional[Any]:
    """Value of a module-level ``NAME = <literal>`` assignment."""
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign):
            continue
        if not any(isinstance(t, ast.Name) and t.id == name
                   for t in node.targets):
            continue
        try:
            return ast.literal_eval(node.value)
        except (ValueError, SyntaxError):
            return None
    return None


def _dotted(node: ast.AST) -> str:
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return ".".join(reversed(parts))


def _ann_fields(cls: ast.ClassDef) -> List[Tuple[str, bool]]:
    """``(name, optional)`` per annotated field, declaration order.
    Optional = a default value is present (``= None`` in practice)."""
    out: List[Tuple[str, bool]] = []
    for stmt in cls.body:
        if isinstance(stmt, ast.AnnAssign) and isinstance(stmt.target,
                                                          ast.Name):
            out.append((stmt.target.id, stmt.value is not None))
    return out


def _namedtuple_leaves(tree: ast.AST) -> Dict[str, List[str]]:
    """Leaf names of every module-level ``NamedTuple`` subclass."""
    out: Dict[str, List[str]] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef) and any(
                (isinstance(b, ast.Name) and b.id == "NamedTuple")
                or (isinstance(b, ast.Attribute) and b.attr == "NamedTuple")
                for b in node.bases):
            out[node.name] = [n for n, _ in _ann_fields(node)]
    return out


def _spec_role(node: Optional[ast.AST]) -> Optional[str]:
    """Shape-role of one ``_state_specs`` kwarg expression: ``P()`` is
    replicated, ``P(axis)`` worker-sharded; constructor calls (EMAState,
    ShardStream) take the role of their leaves; ``A if flag else None``
    takes A's role; a genuinely two-armed conditional (ZeRO's opt_state)
    reports both."""
    if node is None:
        return None
    if isinstance(node, ast.Constant) and node.value is None:
        return None
    if isinstance(node, ast.IfExp):
        body = _spec_role(node.body)
        orelse = _spec_role(node.orelse)
        if orelse is None:
            return body
        if body == orelse:
            return body
        return f"{body}-or-{orelse}"
    if isinstance(node, ast.Call):
        name = _dotted(node.func)
        if name.split(".")[-1] == "P":
            return "worker-sharded" if node.args else "replicated"
        roles = {r for r in
                 ([_spec_role(a) for a in node.args]
                  + [_spec_role(k.value) for k in node.keywords])
                 if r is not None}
        if len(roles) == 1:
            return roles.pop()
        if roles:
            return "mixed"
    return "unknown"


def _state_spec_roles(step_tree: ast.AST) -> Dict[str, Optional[str]]:
    fn = _function_def(step_tree, "_state_specs")
    roles: Dict[str, Optional[str]] = {}
    if fn is None:
        return roles
    for node in ast.walk(fn):
        if (isinstance(node, ast.Call)
                and _dotted(node.func).endswith("MercuryState")):
            for kw in node.keywords:
                if kw.arg is not None:
                    roles[kw.arg] = _spec_role(kw.value)
            break
    return roles


def _field_dims(create_fn: Optional[ast.FunctionDef]
                ) -> Dict[str, List[str]]:
    """Dim symbols per field from ``create_state``'s fresh-init
    assignments: Name ids inside tuple literals fed to array
    constructors (zeros/full/ones/broadcast_to), mapped through
    :data:`DIM_SYMBOLS`. Best-effort — fields whose shapes aren't
    literal tuples report no dims."""
    dims: Dict[str, List[str]] = {}
    if create_fn is None:
        return dims

    def tuple_dims(expr: ast.AST) -> List[str]:
        syms: List[str] = []
        for node in ast.walk(expr):
            if not (isinstance(node, ast.Call)
                    and _dotted(node.func).split(".")[-1]
                    in ("zeros", "ones", "full", "broadcast_to")):
                continue
            for arg in node.args:
                if isinstance(arg, ast.Tuple) or (
                        isinstance(arg, ast.BinOp)
                        and isinstance(arg.op, ast.Add)):
                    for sub in ast.walk(arg):
                        if (isinstance(sub, ast.Name)
                                and sub.id in DIM_SYMBOLS):
                            syms.append(DIM_SYMBOLS[sub.id])
        seen: List[str] = []
        for s in syms:
            if s not in seen:
                seen.append(s)
        return seen

    for node in ast.walk(create_fn):
        if (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)):
            got = tuple_dims(node.value)
            if got:
                dims.setdefault(node.targets[0].id, got)
    return dims


def _field_constructors(create_fn: Optional[ast.FunctionDef],
                        namedtuples: Dict[str, List[str]]
                        ) -> Dict[str, str]:
    """Field → NamedTuple constructor used in ``create_state`` (the
    annotation is ``Any`` for optional fields, so the constructor call
    is the extractable type evidence — GLE05 uses it to find fields
    that embed an ``rng`` leaf)."""
    out: Dict[str, str] = {}
    if create_fn is None:
        return out
    for node in ast.walk(create_fn):
        if (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and isinstance(node.value, ast.Call)):
            ctor = _dotted(node.value.func).split(".")[-1]
            if ctor in namedtuples:
                out[node.targets[0].id] = ctor
    return out


def _gated_inits(create_fn: Optional[ast.FunctionDef]) -> List[str]:
    """Fields constructed under an ``if <flag>:`` in ``create_state`` —
    the fresh, topology-deterministic template init that drop-on-shrink
    fields fall back to after a reshard."""
    gated: List[str] = []
    if create_fn is None:
        return gated
    for node in ast.walk(create_fn):
        if not isinstance(node, ast.If):
            continue
        for sub in ast.walk(node):
            if (isinstance(sub, ast.Assign)
                    and len(sub.targets) == 1
                    and isinstance(sub.targets[0], ast.Name)):
                gated.append(sub.targets[0].id)
    return sorted(set(gated))


def _call_names(expr: ast.AST) -> List[str]:
    """Dotted names of every call inside ``expr`` (evidence of HOW a
    value was derived — ``jax.random.fold_in`` being the one GLE05
    cares about)."""
    names: List[str] = []
    for node in ast.walk(expr):
        if isinstance(node, ast.Call):
            name = _dotted(node.func)
            if name:
                names.append(name)
    return sorted(set(names))


def _replace_kwargs(fn: Optional[ast.FunctionDef]
                    ) -> Tuple[Dict[str, List[str]], bool]:
    """The ``template.replace(...)`` carry site in ``elastic_restore``:
    field → call-name evidence (following one level of ``name = expr``
    dataflow inside the function), plus whether a ``**extra`` splat is
    present."""
    if fn is None:
        return {}, False
    assigns: Dict[str, List[str]] = {}
    for node in ast.walk(fn):
        if (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)):
            assigns.setdefault(node.targets[0].id, []).extend(
                _call_names(node.value))
    for node in ast.walk(fn):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "replace"):
            continue
        fields: Dict[str, List[str]] = {}
        splat = False
        for kw in node.keywords:
            if kw.arg is None:
                splat = True
                continue
            ev = list(_call_names(kw.value))
            if isinstance(kw.value, ast.Name):
                ev.extend(assigns.get(kw.value.id, []))
            fields[kw.arg] = sorted(set(ev))
        return fields, splat
    return {}, False


def _carry_extra(fn: Optional[ast.FunctionDef]) -> Dict[str, List[str]]:
    """``extra["<field>"] = ...`` assignments in
    ``_carry_streamed_state``: field → call-name evidence."""
    out: Dict[str, List[str]] = {}
    if fn is None:
        return out
    for node in ast.walk(fn):
        if not (isinstance(node, ast.Assign) and len(node.targets) == 1):
            continue
        tgt = node.targets[0]
        if (isinstance(tgt, ast.Subscript)
                and isinstance(tgt.value, ast.Name)
                and tgt.value.id == "extra"):
            sl = tgt.slice
            if isinstance(sl, ast.Index):  # py<3.9 compat shape
                sl = sl.value
            if isinstance(sl, ast.Constant) and isinstance(sl.value, str):
                ev = out.setdefault(sl.value, [])
                ev.extend(_call_names(node.value))
                out[sl.value] = sorted(set(ev))
    return out


def _string_constants(fn: ast.FunctionDef) -> List[str]:
    """Non-docstring string constants in ``fn``'s body — the names a
    shim declares (GLE03 requires the dropped field among them)."""
    doc = None
    if (fn.body and isinstance(fn.body[0], ast.Expr)
            and isinstance(fn.body[0].value, ast.Constant)
            and isinstance(fn.body[0].value.value, str)):
        doc = fn.body[0].value
    out: List[str] = []
    for node in ast.walk(fn):
        if (isinstance(node, ast.Constant)
                and isinstance(node.value, str) and node is not doc):
            out.append(node.value)
    return sorted(set(out))


def _shim_table(ckpt_tree: ast.AST
                ) -> Dict[str, Dict[str, Any]]:
    """``UPGRADE_SHIMS`` as ``"old->new" → {fn, names}`` where names are
    the string constants the shim function's body declares."""
    table: Dict[str, Dict[str, Any]] = {}
    fns = {node.name: node for node in ast.walk(ckpt_tree)
           if isinstance(node, ast.FunctionDef)}
    for node in ast.walk(ckpt_tree):
        if not (isinstance(node, ast.Assign)
                and any(isinstance(t, ast.Name)
                        and t.id == "UPGRADE_SHIMS"
                        for t in node.targets)
                and isinstance(node.value, ast.Dict)):
            continue
        for key, val in zip(node.value.keys, node.value.values):
            try:
                pair = ast.literal_eval(key)
            except (ValueError, SyntaxError):
                continue
            if not (isinstance(pair, tuple) and len(pair) == 2):
                continue
            fn_name = _dotted(val)
            fn = fns.get(fn_name)
            table["->".join(pair)] = {
                "fn": fn_name,
                "names": _string_constants(fn) if fn is not None else [],
            }
        break
    return table


def _raises_unknown_field(ckpt_tree: ast.AST) -> bool:
    """``apply_upgrade_shims`` raises a ValueError whose message speaks
    of unknown fields — the loud-failure half of GLE03."""
    fn = _function_def(ckpt_tree, "apply_upgrade_shims")
    if fn is None:
        return False
    for node in ast.walk(fn):
        if not (isinstance(node, ast.Raise)
                and isinstance(node.exc, ast.Call)
                and _dotted(node.exc.func).endswith("ValueError")):
            continue
        text = ""
        for sub in ast.walk(node.exc):
            if isinstance(sub, ast.Constant) and isinstance(sub.value, str):
                text += sub.value
        if "unknown" in text.lower():
            return True
    return False


def _manifest_keys(ckpt_tree: ast.AST) -> List[str]:
    fn = _function_def(ckpt_tree, "_write_manifest")
    if fn is None:
        return []
    for node in ast.walk(fn):
        if (isinstance(node, ast.Assign)
                and any(isinstance(t, ast.Name) and t.id == "doc"
                        for t in node.targets)
                and isinstance(node.value, ast.Dict)):
            return sorted(k.value for k in node.value.keys
                          if isinstance(k, ast.Constant)
                          and isinstance(k.value, str))
    return []


def _mentions_string(fn: Optional[ast.FunctionDef], needle: str) -> bool:
    if fn is None:
        return False
    for node in ast.walk(fn):
        if (isinstance(node, ast.Constant)
                and isinstance(node.value, str)
                and needle in node.value):
            return True
    return False


def _reshard_begin_detail_keys(fn: Optional[ast.FunctionDef]) -> List[str]:
    """Keys of the ``detail={...}`` dict of the ``elastic/reshard_begin``
    journal emit in ``elastic_restore``."""
    if fn is None:
        return []
    for node in ast.walk(fn):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and "emit" in node.func.attr
                and node.args
                and isinstance(node.args[0], ast.Constant)
                and node.args[0].value == "elastic/reshard_begin"):
            continue
        for kw in node.keywords:
            if kw.arg == "detail" and isinstance(kw.value, ast.Dict):
                return sorted(k.value for k in kw.value.keys
                              if isinstance(k, ast.Constant)
                              and isinstance(k.value, str))
    return []


def _calls_named(fn: Optional[ast.FunctionDef], needle: str) -> bool:
    if fn is None:
        return False
    for node in ast.walk(fn):
        if (isinstance(node, ast.Call)
                and needle in _dotted(node.func)):
            return True
    return False


def extract_state_facts(
        sources: Optional[Dict[str, str]] = None) -> Dict[str, Any]:
    """Walk the state-plane modules and return the structural facts the
    schema is built from. ``sources`` overrides module source text by
    :data:`STATE_MODULES` key (seeded-violation fixtures)."""
    state_tree = _module_tree("state", sources)
    step_tree = _module_tree("step", sources)
    ckpt_tree = _module_tree("checkpoint", sources)
    ela_tree = _module_tree("elastic", sources)
    trn_tree = _module_tree("trainer", sources)

    state_cls = _class_def(state_tree, "MercuryState")
    ann = _ann_fields(state_cls) if state_cls is not None else []
    field_order = [n for n, _ in ann]
    optional = {n: opt for n, opt in ann}
    policies = _module_literal(state_tree, "ELASTIC_POLICIES") or {}
    namedtuples = _namedtuple_leaves(state_tree)
    roles = _state_spec_roles(step_tree)
    create_fn = _function_def(state_tree, "create_state")
    dims = _field_dims(create_fn)
    constructors = _field_constructors(create_fn, namedtuples)

    fields: Dict[str, Dict[str, Any]] = {}
    for name in field_order:
        role = "rng-key" if name == "rng" else roles.get(name)
        fields[name] = {
            "optional": bool(optional.get(name)),
            "policy": policies.get(name),
            "role": role,
            "dims": dims.get(name, []),
        }

    lineage_lit = _module_literal(ckpt_tree, "STATE_SCHEMA_LINEAGE") or ()
    versions = [v for v, _ in lineage_lit]
    added = {v: sorted(f) for v, f in lineage_lit}
    head = _module_literal(ckpt_tree, "STATE_SCHEMA_VERSION")

    ela_restore = _function_def(ela_tree, "elastic_restore")
    replace_kw, extra_splat = _replace_kwargs(ela_restore)
    carry_extra = _carry_extra(
        _function_def(ela_tree, "_carry_streamed_state"))

    facts: Dict[str, Any] = {
        "modules": {k: STATE_MODULES[k].replace(os.sep, "/")
                    for k in sorted(STATE_MODULES)},
        "field_order": field_order,
        "fields": fields,
        "policies": {k: policies[k] for k in sorted(policies)},
        "namedtuple_leaves": {k: namedtuples[k]
                              for k in sorted(namedtuples)},
        "constructors": {k: constructors[k]
                         for k in sorted(constructors)},
        "carry": {
            "replace_kwargs": {k: replace_kw[k]
                               for k in sorted(replace_kw)},
            "extra_splat": extra_splat,
            "carry_extra": {k: carry_extra[k]
                            for k in sorted(carry_extra)},
            "gated_init": _gated_inits(create_fn),
            "reprime": {
                "pending_sel": _calls_named(
                    _function_def(trn_tree, "_recommit_state"),
                    "_stream_prime"),
            },
        },
        "lineage": {
            "versions": versions,
            "added": added,
            "head": head,
        },
        "shims": {
            "pairs": _shim_table(ckpt_tree),
            "unknown_field_raise": _raises_unknown_field(ckpt_tree),
        },
        "manifest": {
            "keys": _manifest_keys(ckpt_tree),
            "restore_checks_sha": _mentions_string(
                _function_def(ckpt_tree, "_restore_one"),
                "state_schema_sha"),
            "reshard_begin_detail": _reshard_begin_detail_keys(
                ela_restore),
        },
    }
    return facts


# --------------------------------------------------------------------------
# static gates (GLE01–GLE06)
# --------------------------------------------------------------------------

def check_extraction(facts: Dict[str, Any]) -> List[str]:
    """Hard gates on the extracted facts — the state-plane contract.
    Every finding names its rule id (GLE01–GLE06)."""
    errors: List[str] = []
    field_order: List[str] = facts["field_order"]
    policies: Dict[str, Optional[str]] = facts["policies"]

    if not field_order:
        errors.append("GLE01 state: MercuryState fields not extractable "
                      "from train/state.py")

    # GLE01: field ↔ policy parity, closed vocabulary.
    for name in field_order:
        pol = policies.get(name)
        if pol is None:
            errors.append(
                f"GLE01 state: MercuryState field {name!r} has no "
                f"ELASTIC_POLICIES entry — every state field must "
                f"declare its elastic policy (train/state.py)")
        elif pol not in POLICY_VOCAB:
            errors.append(
                f"GLE01 state: field {name!r} declares unknown policy "
                f"{pol!r} (vocabulary: {', '.join(POLICY_VOCAB)})")
    for name in sorted(set(policies) - set(field_order)):
        errors.append(
            f"GLE01 state: ELASTIC_POLICIES names {name!r}, which is "
            f"not a MercuryState field — stale entry")

    # GLE02: policy ↔ carry site.
    replace_kw = facts["carry"]["replace_kwargs"]
    carry_extra = facts["carry"]["carry_extra"]
    gated = set(facts["carry"]["gated_init"])
    for name in field_order:
        pol = policies.get(name)
        if pol in CARRIED_POLICIES:
            if name not in replace_kw and name not in carry_extra:
                errors.append(
                    f"GLE02 state: field {name!r} (policy {pol}) has no "
                    f"carry site — neither a replace() kwarg in "
                    f"elastic_restore nor an extra[...] assignment in "
                    f"_carry_streamed_state handles it")
        elif pol == "drop-on-shrink":
            if name in replace_kw or name in carry_extra:
                errors.append(
                    f"GLE02 state: field {name!r} declares "
                    f"drop-on-shrink but IS carried by the elastic "
                    f"restore — declare the real policy instead")
            if name not in gated:
                errors.append(
                    f"GLE02 state: drop-on-shrink field {name!r} has no "
                    f"gated fresh init in create_state — nothing "
                    f"rebuilds it for the new topology")
    if carry_extra and not facts["carry"]["extra_splat"]:
        errors.append(
            "GLE02 state: _carry_streamed_state builds extra[...] "
            "entries but elastic_restore's replace() has no **extra "
            "splat — carried fields would be silently discarded")
    if (policies.get("pending_sel") == "drop-on-shrink"
            and not facts["carry"]["reprime"].get("pending_sel")):
        errors.append(
            "GLE02 state: pending_sel is in-flight drop-on-shrink "
            "state but Trainer._recommit_state shows no _stream_prime "
            "call — the ring would restart cold instead of re-primed")

    # GLE03 + GLE04: lineage, shims, loud unknown-field failure.
    lineage = facts["lineage"]
    versions: List[str] = lineage["versions"]
    shims = facts["shims"]["pairs"]
    if not versions:
        errors.append("GLE04 state: STATE_SCHEMA_LINEAGE not "
                      "extractable from train/checkpoint.py")
    if versions and lineage["head"] != versions[-1]:
        errors.append(
            f"GLE04 state: STATE_SCHEMA_VERSION {lineage['head']!r} is "
            f"not the last lineage entry {versions[-1]!r} — the build "
            f"must write the newest schema")
    known_pairs = set()
    for old, new in zip(versions, versions[1:]):
        pair = f"{old}->{new}"
        known_pairs.add(pair)
        info = shims.get(pair)
        if info is None:
            errors.append(
                f"GLE04 state: lineage gap — no upgrade shim for "
                f"{pair}; checkpoints written at {old!r} cannot reach "
                f"HEAD ({versions[-1]!r})")
            continue
        for fld in lineage["added"].get(new, []):
            if fld not in info["names"]:
                errors.append(
                    f"GLE03 state: upgrade shim {info['fn']} ({pair}) "
                    f"does not name field {fld!r} as a string constant "
                    f"— a restore path that drops a field must say "
                    f"which field it drops")
    for pair in sorted(set(shims) - known_pairs):
        errors.append(
            f"GLE04 state: UPGRADE_SHIMS has entry {pair!r} that is "
            f"not a consecutive lineage pair")
    for ver, flds in sorted(lineage["added"].items()):
        for fld in flds:
            if field_order and fld not in field_order:
                errors.append(
                    f"GLE04 state: lineage version {ver!r} adds "
                    f"{fld!r}, which is not a MercuryState field")
    if not facts["shims"]["unknown_field_raise"]:
        errors.append(
            "GLE03 state: apply_upgrade_shims does not raise a loud "
            "ValueError on unknown checkpoint fields — a checkpoint "
            "from a newer schema would silently drop state")

    # GLE05: rng state must be re-seeded via fold_in, never copied.
    fields = facts["fields"]
    for name in field_order:
        if fields[name].get("role") == "rng-key":
            if policies.get(name) != "re-seed":
                errors.append(
                    f"GLE05 state: rng-key field {name!r} declares "
                    f"policy {policies.get(name)!r} — PRNG state must "
                    f"be re-seed (shared keys across workers break the "
                    f"sampler's independence)")
            ev = facts["carry"]["replace_kwargs"].get(name, [])
            if name in facts["carry"]["replace_kwargs"] and not any(
                    "fold_in" in e for e in ev):
                errors.append(
                    f"GLE05 state: rng-key field {name!r} is carried "
                    f"without fold_in ({ev or 'no call evidence'}) — "
                    f"resharding PRNG keys by copy replays the old "
                    f"draw sequence on the new topology")
    # A field whose NamedTuple embeds an rng leaf (pending_sel's raw
    # uint32 lookahead key) must re-derive it — drop-on-shrink reprime
    # or re-seed; a carried copy would replay the old key stream.
    for name, ctor in facts["constructors"].items():
        leaves = facts["namedtuple_leaves"].get(ctor, [])
        if "rng" in leaves and policies.get(name) not in (
                "drop-on-shrink", "re-seed"):
            errors.append(
                f"GLE05 state: field {name!r} ({ctor}) embeds an rng "
                f"leaf but declares policy {policies.get(name)!r} — "
                f"embedded key state must be re-derived, not copied")

    # GLE06: checkpoint-manifest parity.
    manifest = facts["manifest"]
    if "state_schema_sha" not in manifest["keys"]:
        errors.append(
            "GLE06 state: checkpoint manifest (_write_manifest) does "
            "not stamp state_schema_sha — restore cannot detect a "
            "checkpoint that predates the committed schema")
    if not manifest["restore_checks_sha"]:
        errors.append(
            "GLE06 state: _restore_one never references "
            "state_schema_sha — the manifest stamp is written but "
            "never checked on restore")
    if "state_schema_sha" not in manifest["reshard_begin_detail"]:
        errors.append(
            "GLE06 state: elastic/reshard_begin journal detail lacks "
            "state_schema_sha — the run report cannot tie a reshard "
            "to the schema it ran under")
    return errors


# --------------------------------------------------------------------------
# golden doc + verify / regen (the --layer state CLI contract)
# --------------------------------------------------------------------------

def schema_sha_of_facts(facts: Dict[str, Any]) -> str:
    """Digest over the schema-defining subset (fields + lineage) — NOT
    the golden file bytes, so the stamp is stable across provenance or
    carry-evidence churn and has no self-reference problem."""
    core = {"fields": facts["fields"], "lineage": facts["lineage"]}
    return hashlib.sha256(
        json.dumps(core, sort_keys=True).encode()).hexdigest()


def state_doc(facts: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """The committed golden document. Provenance carries only the regen
    command (no jax versions — the static half is stdlib-only and the
    golden must not drift on toolchain upgrades)."""
    if facts is None:
        facts = extract_state_facts()
    return {
        "schema": STATE_SCHEMA,
        "provenance": {"regenerate_with": REGEN_HINT},
        "state_schema_sha": schema_sha_of_facts(facts),
        "facts": facts,
    }


def _doc_diff(committed: Dict[str, Any],
              fresh: Dict[str, Any]) -> List[str]:
    lines: List[str] = []
    a = committed.get("facts", {})
    b = fresh.get("facts", {})
    for key in sorted(set(a) | set(b)):
        va, vb = a.get(key), b.get(key)
        if va != vb:
            lines.append(f"  facts.{key}: committed "
                         f"{json.dumps(va, sort_keys=True)[:200]} "
                         f"vs extracted "
                         f"{json.dumps(vb, sort_keys=True)[:200]}")
    sha_a = committed.get("state_schema_sha")
    sha_b = fresh.get("state_schema_sha")
    if sha_a != sha_b:
        lines.append(f"  state_schema_sha: committed {sha_a} vs "
                     f"extracted {sha_b}")
    if lines:
        lines.insert(0, "state schema drifted from committed golden "
                        f"(regenerate with {REGEN_HINT}):")
    return lines


def run_state_check(state_schema_path: Optional[str] = None,
                    regen: bool = False,
                    diff_out: Optional[str] = None,
                    ) -> Tuple[List[str], List[str]]:
    """Layer E entry: extract, gate (GLE01–GLE06), and verify (or
    ``--regen``) the committed state schema. Returns
    ``(errors, warnings)`` on the shared layer-CLI contract; raises
    FileNotFoundError when verifying with no committed golden (the CLI
    maps it to exit 2 + regen hint)."""
    path = state_schema_path or default_state_schema_path()
    facts = extract_state_facts()
    errors = check_extraction(facts)
    doc = state_doc(facts)
    warnings: List[str] = []
    if regen:
        golden.write_golden(path, doc)
        warnings.append(f"state schema written to {path}")
        return errors, warnings
    committed = golden.load_golden(path, STATE_SCHEMA, REGEN_HINT)
    diff = _doc_diff(committed, doc)
    if diff:
        errors.extend(diff)
        if diff_out:
            golden.write_diff_file(diff_out,
                                   "graftlint state-schema diff", diff)
    return errors, warnings


# --------------------------------------------------------------------------
# runtime half: differential reshard conformance (GLE07–GLE10)
# --------------------------------------------------------------------------

#: Differential plans: config knobs layered over the smoke base. The
#: scoretable plan exercises reshard-exact (table rows), re-aggregate
#: (sel_counts ledger) and cursor-fraction; the zero plan exercises the
#: ZeRO-1 reshard-exact optimizer chunks.
DIFFERENTIAL_PLANS: Dict[str, Dict[str, Any]] = {
    "scoretable": {"sampler": "scoretable", "refresh_size": 8},
    "zero": {"zero_sharding": True},
}


def _diff_cfg(world: int, workdir: str, plan: Dict[str, Any]):
    from mercury_tpu.config import TrainConfig

    base = dict(
        model="smallcnn", dataset="synthetic", world_size=world,
        batch_size=8, presample_batches=2, num_epochs=1,
        steps_per_epoch=4, eval_every=0, log_every=0,
        compute_dtype="float32", seed=0, checkpoint_dir=workdir,
    )
    base.update(plan)
    return TrainConfig(**base)


def _run_steps(trainer, n: int) -> None:
    for _ in range(n):
        trainer.state, _ = trainer.train_step(
            trainer.state, trainer._step_x, trainer._step_y,
            trainer.dataset.shard_indices)


def _global_table(trainer, state, w: int):
    """Per-sample (global) score map + selection-count totals for a
    ``[W, L]`` run — the reshard-invariant views GLE07/GLE08 compare."""
    import numpy as np

    from mercury_tpu.train.elastic import _shard_index_matrix

    sidx = _shard_index_matrix(trainer, w)
    n = int(np.asarray(trainer.dataset.y_train).size)
    scores = counts = None
    if state.scoretable is not None:
        flat = np.full((n,), np.nan, np.float32)
        flat[sidx.reshape(-1)] = np.asarray(
            state.scoretable.scores, np.float32).reshape(-1)
        scores = flat
    if state.sel_counts is not None:
        tot = np.zeros((n,), np.int64)
        np.add.at(tot, sidx.reshape(-1),
                  np.asarray(state.sel_counts, np.int64).reshape(-1))
        counts = tot
    return sidx, scores, counts


def _flat_moments(state, w: int, n_params: int):
    import jax
    import numpy as np

    out = []
    for leaf in jax.tree_util.tree_leaves(state.opt_state):
        a = np.asarray(leaf)
        if a.ndim >= 2 and a.shape[0] == w:
            out.append(a.reshape(w * a.shape[1], -1)[:n_params])
    return out


def _check_hop(findings: List[str], plan: str, hop: str,
               t_old, s_old, w_old: int, t_new, w_new: int) -> None:
    """Policy-conformance checks for one reshard hop: every violated
    invariant is reported with its rule id and the offending leaf."""
    import jax
    import numpy as np

    s_new = t_new.state

    def flag(rule: str, leaf: str, msg: str) -> None:
        findings.append(f"{rule} [{plan}] {hop}: {leaf}: {msg}")

    # GLE07 exact carry: params / batch_stats bit-equal per leaf.
    for what in ("params", "batch_stats"):
        old_l, treedef = jax.tree_util.tree_flatten_with_path(
            getattr(s_old, what))
        new_l = jax.tree_util.tree_leaves(getattr(s_new, what))
        for (kp, a), b in zip(old_l, new_l):
            if not np.array_equal(np.asarray(a), np.asarray(b)):
                flag("GLE07", what + jax.tree_util.keystr(kp),
                     "exact-carry leaf not bit-equal across reshard")
    # GLE07 exact carry: optimizer moments (ZeRO chunks re-flattened).
    if t_new.config.zero_sharding:
        from mercury_tpu.utils.tree import tree_flatten_to_vector

        pvec, _ = tree_flatten_to_vector(s_new.params)
        want = _flat_moments(s_old, w_old, int(pvec.size))
        got = _flat_moments(s_new, w_new, int(pvec.size))
        for i, (a, b) in enumerate(zip(want, got)):
            if not np.array_equal(a, b):
                flag("GLE07", f"opt_state.moment[{i}]",
                     "ZeRO moment vector not bit-equal after re-chunk")
    else:
        for i, (a, b) in enumerate(zip(
                jax.tree_util.tree_leaves(s_old.opt_state),
                jax.tree_util.tree_leaves(s_new.opt_state))):
            if np.shape(a) == np.shape(b) and not np.array_equal(
                    np.asarray(a), np.asarray(b)):
                flag("GLE07", f"opt_state.leaf[{i}]",
                     "replicated optimizer leaf changed across reshard")

    old_sidx, old_scores, old_counts = _global_table(t_old, s_old, w_old)
    new_sidx, new_scores, new_counts = _global_table(t_new, s_new, w_new)
    # GLE07 exact carry: scoretable rows the old run owned carry
    # bit-equal into the new partition.
    if old_scores is not None and new_scores is not None:
        owned = np.zeros(old_scores.shape, bool)
        owned[old_sidx.reshape(-1)] = True
        bad = np.flatnonzero(
            owned & (new_scores != old_scores)
            & ~(np.isnan(new_scores) & np.isnan(old_scores)))
        if bad.size:
            flag("GLE07", "scoretable.scores",
                 f"{bad.size} carried per-sample rows not bit-equal "
                 f"(first: sample {int(bad[0])}, "
                 f"{old_scores[bad[0]]!r} -> {new_scores[bad[0]]!r})")
    # GLE08 re-aggregate: the ledger's global total is invariant.
    if old_counts is not None and new_counts is not None:
        if int(old_counts.sum()) != int(new_counts.sum()):
            flag("GLE08", "sel_counts",
                 f"global selection total not preserved: "
                 f"{int(old_counts.sum())} -> {int(new_counts.sum())}")
    # GLE08 re-aggregate: EMA warm start equals the old workers' mean.
    ema_want = float(np.mean(np.asarray(s_old.ema.value)))
    ema_got = np.asarray(s_new.ema.value)
    if not np.allclose(ema_got, ema_want, rtol=1e-5):
        flag("GLE08", "ema.value",
             f"warm start != old mean ({ema_want} vs {ema_got[:4]})")
    # GLE09 re-seed: new keys pairwise distinct and distinct from every
    # checkpointed key (a copy would replay the old draw sequence).
    def key_rows(rng):
        arr = np.asarray(jax.random.key_data(rng))
        return [bytes(row.tobytes()) for row in arr]

    old_keys = set(key_rows(s_old.rng))
    new_keys = key_rows(s_new.rng)
    if len(set(new_keys)) != len(new_keys):
        flag("GLE09", "rng", "restored worker keys are not pairwise "
                             "distinct (copied key state)")
    for i, kb in enumerate(new_keys):
        if kb in old_keys:
            flag("GLE09", f"rng[{i}]",
                 "restored key equals a checkpointed key — re-seed "
                 "must fold_in, not copy")
    # GLE10 cursor-fraction: epoch fraction preserved to 1/L_new.
    l_old = int(np.shape(s_old.stream.perm)[1])
    l_new = int(np.shape(s_new.stream.perm)[1])
    frac_old = float(np.mean(np.asarray(s_old.stream.cursor,
                                        np.float64))) / max(l_old, 1)
    frac_new = float(np.mean(np.asarray(s_new.stream.cursor,
                                        np.float64))) / max(l_new, 1)
    if abs(frac_new - frac_old) > 1.5 / max(l_new, 1) + 1e-9:
        flag("GLE10", "stream.cursor",
             f"epoch fraction not preserved: {frac_old:.4f} -> "
             f"{frac_new:.4f} (tolerance 1.5/L_new)")


def run_differential(plans: Sequence[str] = ("scoretable", "zero"),
                     steps: int = 4, w_hi: int = 8, w_lo: int = 4,
                     workdir: Optional[str] = None) -> List[str]:
    """Execute the W=hi → W=lo → W=hi round-trip per plan and return
    policy-conformance findings (empty = conformant). Requires jax (and
    ``w_hi`` CPU devices — see :func:`main`'s XLA_FLAGS setup)."""
    import shutil
    import tempfile

    from mercury_tpu.parallel.mesh import host_cpu_mesh
    from mercury_tpu.train.trainer import Trainer

    findings: List[str] = []
    root = workdir or tempfile.mkdtemp(prefix="graftlint_state_diff_")
    try:
        for plan in plans:
            knobs = DIFFERENTIAL_PLANS[plan]
            d1 = os.path.join(root, plan, "hi")
            d2 = os.path.join(root, plan, "lo")
            os.makedirs(d1, exist_ok=True)
            os.makedirs(d2, exist_ok=True)

            t1 = Trainer(_diff_cfg(w_hi, d1, knobs),
                         mesh=host_cpu_mesh(w_hi))
            _run_steps(t1, steps)
            t1.save()
            s1 = t1.state

            t2 = Trainer(_diff_cfg(w_lo, d2, knobs),
                         mesh=host_cpu_mesh(w_lo))
            t2.restore_elastic(d1)
            _check_hop(findings, plan, f"W={w_hi}->W={w_lo}",
                       t1, s1, w_hi, t2, w_lo)
            s2 = t2.state
            t2.save()

            t3 = Trainer(_diff_cfg(w_hi, d2, knobs),
                         mesh=host_cpu_mesh(w_hi))
            t3.restore_elastic()
            _check_hop(findings, plan, f"W={w_lo}->W={w_hi}",
                       t2, s2, w_lo, t3, w_hi)
    finally:
        if workdir is None:
            shutil.rmtree(root, ignore_errors=True)
    return findings


# --------------------------------------------------------------------------
# module CLI
# --------------------------------------------------------------------------

def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m mercury_tpu.lint.state",
        description="graftlint Layer E: state-schema golden verify "
                    "(static, stdlib-only) or --differential reshard "
                    "conformance (requires jax).")
    ap.add_argument("--state-schema", default=None, metavar="PATH",
                    help="state_schema.json to verify against / "
                         "regenerate")
    ap.add_argument("--regen", action="store_true",
                    help="re-extract and WRITE the golden instead of "
                         "verifying")
    ap.add_argument("--diff-out", default=None, metavar="PATH",
                    help="write the schema diff to this file on "
                         "mismatch (CI artifact)")
    ap.add_argument("--differential", action="store_true",
                    help="run the W=8->4->8 reshard round-trips and "
                         "check policy conformance (GLE07-GLE10)")
    ap.add_argument("--plans", default=None,
                    help="comma-separated differential plans "
                         "(default: scoretable,zero)")
    ap.add_argument("--steps", type=int, default=4,
                    help="train steps before the first save "
                         "(differential)")
    ap.add_argument("--json", action="store_true", dest="as_json")
    args = ap.parse_args(argv)

    if args.differential:
        # 8 virtual CPU devices before jax initializes; idempotent when
        # conftest/CI already set it.
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
        plans = (tuple(p.strip() for p in args.plans.split(","))
                 if args.plans else tuple(DIFFERENTIAL_PLANS))
        unknown = [p for p in plans if p not in DIFFERENTIAL_PLANS]
        if unknown:
            print(f"unknown differential plan(s): {', '.join(unknown)} "
                  f"(known: {', '.join(DIFFERENTIAL_PLANS)})",
                  file=sys.stderr)
            return 2
        findings = run_differential(plans=plans, steps=args.steps)
        if args.as_json:
            print(json.dumps({"schema": "graftlint_findings_v2",
                              "findings": [
                                  {"layer": "state",
                                   "severity": "error", "message": f}
                                  for f in findings]}, indent=2))
        else:
            for line in findings:
                print(line)
            if not findings:
                print(f"graftlint state: differential reshard "
                      f"conformant ({', '.join(plans)}; GLE07-GLE10)")
        return 1 if findings else 0

    try:
        errors, warnings = run_state_check(
            state_schema_path=args.state_schema,
            regen=args.regen, diff_out=args.diff_out)
    except FileNotFoundError as exc:
        print(f"graftlint state: state schema missing ({exc}) — run "
              f"with --regen first", file=sys.stderr)
        return 2
    except (OSError, ValueError) as exc:
        print(f"graftlint state: {exc}", file=sys.stderr)
        return 2
    if args.as_json:
        print(json.dumps({"schema": "graftlint_findings_v2",
                          "findings": (
                              [{"layer": "state", "severity": "warning",
                                "message": w} for w in warnings]
                              + [{"layer": "state", "severity": "error",
                                  "message": e} for e in errors])},
                         indent=2))
    else:
        for line in warnings:
            print(f"warning: {line}")
        for line in errors:
            print(line)
        if not errors:
            print("graftlint state: schema verified against "
                  "lint/state_schema.json; GLE01-GLE06 hold")
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
