"""graftlint Layer M: metric-key registry auditor (pure stdlib).

Every metric tag the training path emits must exist in the central
registry (``mercury_tpu/obs/registry.py::METRIC_KEYS``) and be
documented in the ``docs/API.md`` metric-key glossary — otherwise
dashboards silently accumulate unexplained streams and the glossary
rots. This layer closes the loop statically:

- **error** — a ``category/name`` string literal in the package that is
  not a registered key (typo, or a new metric added without registering
  and documenting it);
- **error** — a registered key with no backticked mention in
  ``docs/API.md`` (registered but undocumented);
- **warning** — a registered key never seen as a literal in the package
  (dead registry entry, or a key built only via f-strings — e.g. the
  ``{train,test}/eval_*`` family, constructed from a split prefix).

**GLM04** applies the same three-way parity contract to control-plane
event kinds: every first-argument literal of a ``*journal*.emit(...)``
call must be registered in ``obs/registry.py::EVENT_KINDS`` and carry a
backticked entry in ``docs/OBSERVABILITY.md``'s kind catalog; a
registered kind never emitted is a warning. Journal-emit first
arguments are *excluded* from the metric-key scan — ``supervisor/…``
event kinds share the slash grammar with metric keys, and the receiver
name (anything containing ``journal``) is what disambiguates the two
planes statically.

Like Layer 1 this never imports the package under lint (the registry is
read by AST ``literal_eval`` of its source), so it runs on CI machines
with no jax installed.
"""

from __future__ import annotations

import ast
import os
import re
from typing import Dict, List, Set, Tuple

#: A metric tag: one of the registered categories, a slash, a snake_case
#: name — optionally one more ``/segment`` (the ``host/{min,max,spread}/*``
#: and ``prof/scope_frac/*`` families are two levels deep). Anything
#: matching this shape in package source is treated as an emitted metric
#: key and checked against the registry.
KEY_RE = re.compile(
    r"^(train|test|sampler|sampler_dist|perf|time|data|obs|anomaly|host"
    r"|prof|scorer|threads|lint|fault|supervisor|checkpoint|plan|moe)"
    r"/[a-z0-9_]+(/[a-z0-9_]+)?$")

#: Backticked tokens in the docs, brace families included
#: (``sampler/table_age_{min,mean,max}``). No newlines inside a token,
#: and fenced ``` blocks are stripped first — a code fence would pair a
#: stray backtick with the rest of the document.
_DOC_TOKEN_RE = re.compile(r"`([^`\n]+)`")
_FENCE_RE = re.compile(r"^```.*?^```[^\S\n]*$", re.M | re.S)
_BRACE_RE = re.compile(r"\{([^{}]+)\}")

#: A control-plane event kind: exactly ``subsystem/name`` (obs/events.py
#: schema). Only literals at journal-emit call sites are judged against
#: this, so the broad shape cannot false-positive on paths or metrics.
EVENT_KIND_RE = re.compile(r"^[a-z0-9_]+/[a-z0-9_]+$")

#: Files whose key literals are definitional, not emissions: the
#: registry itself and Layer S's control-plane model (``control.py``
#: names journal kinds in its parent/rule tables, never emits them).
_SKIP_FILES = frozenset({"registry.py", "control.py", "modelcheck.py"})


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))


def _default_registry_path() -> str:
    return os.path.join(_repo_root(), "mercury_tpu", "obs", "registry.py")


def _default_docs_path() -> str:
    return os.path.join(_repo_root(), "docs", "API.md")


def _default_event_docs_path() -> str:
    return os.path.join(_repo_root(), "docs", "OBSERVABILITY.md")


def _load_literal(path: str, name: str) -> Dict[str, str]:
    """A module-level pure-literal dict from SOURCE — no import of the
    package (and thus no jax) is needed; fails loudly if missing or not
    a literal."""
    with open(path) as f:
        tree = ast.parse(f.read(), filename=path)
    for node in tree.body:
        targets = []
        if isinstance(node, ast.Assign):
            targets = [t.id for t in node.targets
                       if isinstance(t, ast.Name)]
        elif isinstance(node, ast.AnnAssign) and isinstance(
                node.target, ast.Name):
            targets = [node.target.id]
        if name in targets and node.value is not None:
            return ast.literal_eval(node.value)
    raise ValueError(f"no {name} literal found in {path}")


def load_registry(path: str) -> Dict[str, str]:
    """``METRIC_KEYS`` from the registry module's source."""
    return _load_literal(path, "METRIC_KEYS")


def load_event_registry(path: str) -> Dict[str, str]:
    """``EVENT_KINDS`` (the control-plane event-kind registry) from the
    registry module's source. A registry module without one is treated
    as an empty registry (journal emissions against it are then GLM04
    errors), so metric-only registries stay valid."""
    try:
        return _load_literal(path, "EVENT_KINDS")
    except ValueError:
        return {}


def _iter_py_files(paths: List[str]) -> List[str]:
    out: List[str] = []
    for p in paths:
        if os.path.isfile(p):
            out.append(p)
            continue
        for dirpath, dirnames, filenames in os.walk(p):
            dirnames[:] = [d for d in dirnames
                           if d not in ("__pycache__", ".git")]
            out.extend(os.path.join(dirpath, f)
                       for f in sorted(filenames) if f.endswith(".py"))
    return out


def _receiver_name(func: ast.AST) -> str:
    """Dotted receiver of an ``x.y.emit`` attribute chain, best-effort
    (``self._journal.emit`` -> ``self._journal``)."""
    parts: List[str] = []
    node = func
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return ".".join(reversed(parts))


def _journal_emit_args(tree: ast.AST) -> Dict[int, ast.Constant]:
    """``id(node) -> node`` for every first-positional-argument string
    Constant of a journal-emission call — the static signature every
    producer call site follows: the called attribute contains ``emit``
    and the full dotted callable name contains ``journal``
    (``self._journal.emit(...)``, ``journal.emit(...)``, or a wrapper
    like ``self._journal_emit(...)``)."""
    out: Dict[int, ast.Constant] = {}
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and "emit" in node.func.attr
                and node.args
                and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)):
            continue
        if "journal" in _receiver_name(node.func).lower():
            out[id(node.args[0])] = node.args[0]
    return out


def _kind_compare_args(tree: ast.AST) -> Dict[int, ast.Constant]:
    """String Constants compared against a ``kind`` expression
    (``e.get("kind") == "supervisor/degrade"``, ``kind != "fault/fired"``)
    — the *consumer*-side dual of :func:`_journal_emit_args`: event-kind
    filters in journal readers, not metric emissions."""
    def mentions_kind(node: ast.AST) -> bool:
        for sub in ast.walk(node):
            if isinstance(sub, ast.Name) and "kind" in sub.id.lower():
                return True
            if isinstance(sub, ast.Attribute) and "kind" in sub.attr.lower():
                return True
            if (isinstance(sub, ast.Constant)
                    and isinstance(sub.value, str) and sub.value == "kind"):
                return True
        return False

    out: Dict[int, ast.Constant] = {}
    for node in ast.walk(tree):
        if not isinstance(node, ast.Compare):
            continue
        operands = [node.left] + list(node.comparators)
        consts = [o for o in operands
                  if isinstance(o, ast.Constant) and isinstance(o.value, str)]
        if consts and any(mentions_kind(o) for o in operands
                          if not isinstance(o, ast.Constant)):
            out.update({id(c): c for c in consts})
    return out


def emitted_keys(paths: List[str]) -> Dict[str, List[Tuple[str, int]]]:
    """``key -> [(file, line), ...]`` for every plain string literal in
    ``paths`` matching :data:`KEY_RE`. Constants inside f-strings are
    skipped: a JoinedStr fragment is a key *prefix*, not a key, and
    judging it would false-positive on every dynamic tag. Journal-emit
    first arguments are skipped too — those are event kinds (GLM04's
    plane), not metric keys, even when the subsystem prefix collides
    with a metric category — as are kind-comparison literals in journal
    consumers (the same plane, read side)."""
    found: Dict[str, List[Tuple[str, int]]] = {}
    for path in _iter_py_files(paths):
        if os.path.basename(path) in _SKIP_FILES:
            continue
        try:
            with open(path) as f:
                tree = ast.parse(f.read(), filename=path)
        except SyntaxError:
            continue  # Layer 1 reports unparseable files
        skip = {id(c) for node in ast.walk(tree)
                if isinstance(node, ast.JoinedStr)
                for c in ast.walk(node)}
        skip |= set(_journal_emit_args(tree))
        skip |= set(_kind_compare_args(tree))
        for node in ast.walk(tree):
            if (isinstance(node, ast.Constant)
                    and isinstance(node.value, str)
                    and id(node) not in skip
                    and KEY_RE.match(node.value)):
                found.setdefault(node.value, []).append(
                    (path, node.lineno))
    return found


def emitted_event_kinds(paths: List[str]
                        ) -> Dict[str, List[Tuple[str, int]]]:
    """``kind -> [(file, line), ...]`` for every journal-emit first
    argument in ``paths`` (the GLM04 emission census)."""
    found: Dict[str, List[Tuple[str, int]]] = {}
    for path in _iter_py_files(paths):
        if os.path.basename(path) in _SKIP_FILES:
            continue
        try:
            with open(path) as f:
                tree = ast.parse(f.read(), filename=path)
        except SyntaxError:
            continue
        for const in _journal_emit_args(tree).values():
            found.setdefault(const.value, []).append(
                (path, const.lineno))
    return found


def _documented_tokens(docs_path: str, pattern) -> Set[str]:
    """Backticked tokens in the docs file matching ``pattern``, with
    ``{a,b,c}`` families expanded."""
    with open(docs_path) as f:
        text = _FENCE_RE.sub("", f.read())
    keys: Set[str] = set()
    for token in _DOC_TOKEN_RE.findall(text):
        m = _BRACE_RE.search(token)
        variants = ([_BRACE_RE.sub(alt, token, count=1)
                     for alt in m.group(1).split(",")]
                    if m else [token])
        keys.update(v for v in variants if pattern.match(v))
    return keys


def documented_keys(docs_path: str) -> Set[str]:
    """Metric keys mentioned in backticks anywhere in the docs file."""
    return _documented_tokens(docs_path, KEY_RE)


def documented_event_kinds(docs_path: str) -> Set[str]:
    """Event kinds mentioned in backticks in the event docs file."""
    return _documented_tokens(docs_path, EVENT_KIND_RE)


def run_metrics_check(paths: List[str] = None,
                      registry_path: str = None,
                      docs_path: str = None,
                      event_docs_path: str = None
                      ) -> Tuple[List[str], List[str]]:
    """The Layer M audit; returns ``(errors, warnings)`` of formatted
    finding lines (the Layer 2/3 CLI contract)."""
    registry_path = registry_path or _default_registry_path()
    docs_path = docs_path or _default_docs_path()
    event_docs_path = event_docs_path or _default_event_docs_path()
    if not paths:
        paths = [os.path.join(_repo_root(), "mercury_tpu")]
    registry = load_registry(registry_path)
    emitted = emitted_keys(paths)
    documented = documented_keys(docs_path)

    errors: List[str] = []
    warnings: List[str] = []
    root = _repo_root()
    for key in sorted(emitted):
        if key not in registry:
            f, line = emitted[key][0]
            errors.append(
                f"{os.path.relpath(f, root)}:{line}: GLM01 metric key "
                f"{key!r} is not in obs/registry.py::METRIC_KEYS "
                f"({len(emitted[key])} use(s)) — register and document "
                "it, or fix the typo")
    for key in sorted(registry):
        if key not in documented:
            errors.append(
                f"{os.path.relpath(docs_path, root)}: GLM02 registered "
                f"metric key {key!r} has no backticked entry in the "
                "docs — add it to the metric-key glossary")
        if key not in emitted:
            warnings.append(
                f"GLM03 registered metric key {key!r} never appears as "
                "a literal in the package (f-string-built or dead "
                "entry)")

    # GLM04: event-kind parity — emitted ⊆ EVENT_KINDS ⊆ documented.
    kinds = load_event_registry(registry_path)
    emitted_kinds = emitted_event_kinds(paths)
    documented_kinds = documented_event_kinds(event_docs_path)
    for kind in sorted(emitted_kinds):
        if kind not in kinds:
            f, line = emitted_kinds[kind][0]
            errors.append(
                f"{os.path.relpath(f, root)}:{line}: GLM04 event kind "
                f"{kind!r} is not in obs/registry.py::EVENT_KINDS "
                f"({len(emitted_kinds[kind])} emit(s)) — register and "
                "document it, or fix the typo")
    for kind in sorted(kinds):
        if kind not in documented_kinds:
            errors.append(
                f"{os.path.relpath(event_docs_path, root)}: GLM04 "
                f"registered event kind {kind!r} has no backticked "
                "entry in the event-kind catalog — add it")
        if kind not in emitted_kinds:
            warnings.append(
                f"GLM04 registered event kind {kind!r} is never "
                "emitted by a journal call site (dead registry entry)")
    return errors, warnings
