"""graftlint Layer P runtime half: the retrace guard.

The static half (:mod:`mercury_tpu.lint.perf`) pins what the compiled
program costs; this module pins *how often it compiles*. A weak-type
flip (python float one step, ``np.float32`` the next), a shape-dependent
host branch, or an unhashable static argument silently turns one
executable into a compile-per-step treadmill — the profile looks fine,
the wall clock doesn't.

The harness builds each plan from the shared Layer 2 builder matrix,
then *executes* the step ``steps`` times on the CPU mesh while counting
jax trace/compile events: one process-wide ``jax.monitoring`` listener
counts ``jaxpr_trace_duration`` / ``backend_compile_duration`` events
and fans them out to the active :class:`CompileMonitor`\\ s.

The first :data:`WARMUP_CALLS` calls are the *warmup*: call 1 traces
and compiles; call 2 is kept in the warmup for the step builders that
do not go through ``Trainer`` (sp / pp hand the step an uncommitted
initial state, whose committed output then feeds back in — one
"placement settle" compile). ``Trainer`` commits state and step inputs
on the mesh at construction, so its plans compile on call 1 only. Calls
3..N are *steady state*, where the committed expectation is zero. Every
call also records the argument
signature — ``(shape, dtype, weak_type, sharding)`` per leaf — so when
steady state does compile, the finding names exactly which argument
leaf churned (or states that the signatures were identical, pointing
the finger at closure/global state).

Expectations live in the ``retrace`` section of the Layer P golden
(``lint/perf_budgets.json``): ``steady_compiles``/``steady_traces`` are
hard invariants (never demoted), ``warmup_*`` counts are warn-only
documentation of the recorded run. Run standalone as::

    python -m mercury_tpu.lint.tracecheck --plans dp,hs,async

The trainer exposes the same machinery for live runs:
``Trainer.arm_retrace_guard()`` attaches a monitor whose counters are
emitted as the ``lint/retrace_events`` / ``lint/compile_count`` metric
keys at every log step.
"""

from __future__ import annotations

import argparse
import sys
import threading
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

from mercury_tpu.lint.audit import PLAN_NAMES, _BUILDERS, ensure_cpu_devices

_TRACE_SUFFIX = "jaxpr_trace_duration"
_COMPILE_SUFFIX = "backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"

#: Calls whose trace/compile events count as warmup, not steady state:
#: call 1 primes, call 2 settles the state placement (see module doc).
WARMUP_CALLS = 2

_lock = threading.Lock()
_active: List["CompileMonitor"] = []
_listener_installed = False


def _dispatch(event: str, duration_secs: float = 0.0, **kwargs) -> None:
    del kwargs
    if event.endswith(_TRACE_SUFFIX):
        kind = "trace"
    elif event.endswith(_COMPILE_SUFFIX):
        kind = "compile"
    elif event == _CACHE_HIT:
        kind = "cache_hit"
    elif event == _CACHE_MISS:
        kind = "cache_miss"
    else:
        return
    with _lock:
        monitors = list(_active)
    for m in monitors:
        m._record(kind, duration_secs)


def _ensure_listener() -> None:
    """Install the process-wide listener once. jax offers no per-listener
    unregister (only a clear-all that would stomp other subscribers), so
    ONE permanent callback fans out to whichever monitors are active."""
    global _listener_installed
    with _lock:
        if _listener_installed:
            return
        _listener_installed = True
    from jax import monitoring

    monitoring.register_event_duration_secs_listener(_dispatch)
    monitoring.register_event_listener(_dispatch)


class CompileMonitor:
    """Counts jax trace/compile events between ``start()`` and
    ``stop()``. Usable as a context manager; thread-safe (scorer-fleet
    threads compile too, and their events belong in the count).

    ``compiles`` counts every trip into the backend compiler, including
    those the persistent compilation cache answers; ``compile_secs`` is
    their total duration, and ``cache_hits`` / ``cache_misses`` say how
    many of them the persistent cache did and did not have."""

    def __init__(self) -> None:
        self.traces = 0
        self.compiles = 0
        self.compile_secs = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        _ensure_listener()

    def _record(self, kind: str, duration_secs: float) -> None:
        with _lock:
            if kind == "trace":
                self.traces += 1
            elif kind == "compile":
                self.compiles += 1
                self.compile_secs += duration_secs
            elif kind == "cache_hit":
                self.cache_hits += 1
            else:
                self.cache_misses += 1

    def start(self) -> "CompileMonitor":
        with _lock:
            if self not in _active:
                _active.append(self)
        return self

    def stop(self) -> None:
        with _lock:
            if self in _active:
                _active.remove(self)

    def snapshot(self) -> Tuple[int, int]:
        with _lock:
            return self.traces, self.compiles

    def __enter__(self) -> "CompileMonitor":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()


# --------------------------------------------------------------------------
# argument signatures
# --------------------------------------------------------------------------

def _shard_desc(x) -> str:
    s = getattr(x, "sharding", None)
    if s is None:
        return ""
    spec = getattr(s, "spec", None)
    desc = type(s).__name__
    return f"{desc}({spec})" if spec is not None else desc


def _leaf_sig(x) -> Tuple[Tuple[int, ...], str, bool, str]:
    aval = getattr(x, "aval", None)
    if aval is not None and hasattr(aval, "shape"):
        return (tuple(aval.shape), str(aval.dtype),
                bool(getattr(aval, "weak_type", False)), _shard_desc(x))
    if isinstance(x, (bool, int, float, complex)):
        # python scalars enter traced code weakly typed — the classic
        # churn partner to a strongly-typed np scalar on the next call
        return ((), type(x).__name__, True, "")
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        return tuple(shape), str(dtype), False, _shard_desc(x)
    return ((), type(x).__name__, False, "")


def signature_of(args) -> Dict[str, Tuple]:
    """``{leaf_path: (shape, dtype, weak_type, sharding)}`` over an
    argument pytree — the identity jax's jit cache keys on."""
    from jax.tree_util import keystr, tree_flatten_with_path

    leaves, _ = tree_flatten_with_path(args)
    return {keystr(path): _leaf_sig(leaf) for path, leaf in leaves}


def describe_churn(prev: Dict[str, Tuple], cur: Dict[str, Tuple],
                   max_lines: int = 6) -> List[str]:
    """Human-readable diff between two call signatures; empty when they
    are identical (churn came from closures/globals, not arguments)."""
    lines = []
    changed = 0
    for path in sorted(set(prev) | set(cur)):
        p, c = prev.get(path), cur.get(path)
        if p == c:
            continue
        changed += 1
        if len(lines) >= max_lines:
            continue

        def fmt(sig):
            if sig is None:
                return "<absent>"
            shape, dtype, weak, shard = sig
            out = f"{dtype}{list(shape)}"
            if weak:
                out += " weak"
            if shard:
                out += f" @{shard}"
            return out

        lines.append(f"arg{path}: {fmt(p)} -> {fmt(c)}")
    if changed > len(lines):
        lines.append(f"... and {changed - len(lines)} more churned "
                     "argument leaves")
    return lines


# --------------------------------------------------------------------------
# per-plan harness
# --------------------------------------------------------------------------

def _materialize(args: Tuple) -> Tuple:
    """Replace ShapeDtypeStruct templates (the host_stream pixel slab)
    with concrete host zeros so the step can execute. np arrays on
    purpose: device transfer of a host buffer never fires a compile
    event, so the prime count stays deterministic."""
    import numpy as np

    out = []
    for a in args:
        if type(a).__name__ == "ShapeDtypeStruct":
            out.append(np.zeros(a.shape, a.dtype))
        else:
            out.append(a)
    return tuple(out)


def _fresh_donated(args: Tuple, config: Dict[str, Any], state) -> Tuple:
    """Next call's arguments: thread the new state through slot 0 and
    re-materialize the donated streamed slab (host_stream donates arg 1
    alongside the state, so the consumed buffer cannot be reused)."""
    import numpy as np

    out = list(args)
    out[0] = state
    if config.get("data_placement") == "host_stream":
        slab = out[1]
        out[1] = np.zeros(slab.shape, slab.dtype)
    return tuple(out)


@dataclass
class RetraceMeasurement:
    plan: str
    steps: int = 0
    warmup_traces: int = 0
    warmup_compiles: int = 0
    steady_traces: int = 0
    steady_compiles: int = 0
    #: which call compiled in steady state, and what churned
    churn: List[str] = field(default_factory=list)

    def as_budget(self) -> Dict[str, Any]:
        return {
            "steps": self.steps,
            "warmup_calls": WARMUP_CALLS,
            "warmup_traces": self.warmup_traces,
            "warmup_compiles": self.warmup_compiles,
            "steady_traces": self.steady_traces,
            "steady_compiles": self.steady_compiles,
            "backend": "events",  # golden schema field; the only backend
        }


def measure_step_retraces(step_fn, args: Tuple, plan: str,
                          config: Dict[str, Any],
                          steps: int = 4) -> RetraceMeasurement:
    """Execute ``step_fn`` ``steps`` times, counting trace/compile
    events per call. The first :data:`WARMUP_CALLS` calls may compile;
    the rest must not."""
    m = RetraceMeasurement(plan=plan, steps=steps)
    args = _materialize(args)
    prev_sig = None
    with CompileMonitor() as monitor:
        for call in range(steps):
            before = monitor.snapshot()
            sig = signature_of(args)
            out = step_fn(*args)
            after = monitor.snapshot()
            traces = after[0] - before[0]
            compiles = after[1] - before[1]
            if call < WARMUP_CALLS:
                m.warmup_traces += traces
                m.warmup_compiles += compiles
            else:
                m.steady_traces += traces
                m.steady_compiles += compiles
                if compiles or traces:
                    diff = describe_churn(prev_sig or {}, sig)
                    if diff:
                        m.churn.extend(
                            f"plan {plan} call {call + 1}: {line}"
                            for line in diff)
                    else:
                        m.churn.append(
                            f"plan {plan} call {call + 1}: argument "
                            "signatures identical to the previous call "
                            "— the retrace came from closure/global "
                            "state, not an argument")
            prev_sig = sig
            state = out[0] if isinstance(out, tuple) else out
            args = _fresh_donated(args, config, state)
    return m


def measure_plan_retraces(plan: str, steps: int = 4) -> RetraceMeasurement:
    step, args, config = _BUILDERS[plan]()
    try:
        return measure_step_retraces(step, args, plan, config,
                                     steps=steps)
    finally:
        closer = getattr(step, "close", None)
        if callable(closer):
            closer()


# --------------------------------------------------------------------------
# comparison against the committed expectations
# --------------------------------------------------------------------------

def compare_retraces(measurements: Sequence[RetraceMeasurement],
                     budgets: Dict[str, Any],
                     ) -> Tuple[List[str], List[str]]:
    """Diff measured retrace counts against the golden's ``retrace``
    section. Steady-state compile/trace counts are hard (a retrace
    treadmill is broken on any jax version); warmup counts are
    warn-only — they depend on which process-wide jnp/jit helper caches
    were already warm when the plan ran, so they document the recorded
    run rather than pin an invariant."""
    errors: List[str] = []
    warnings: List[str] = []
    expectations = budgets.get("retrace", {})
    for m in measurements:
        expected = expectations.get(m.plan)
        if expected is None:
            errors.append(
                f"plan {m.plan}: no committed retrace expectation — "
                "run --layer perf --regen and review the diff")
            continue
        want_sc = int(expected.get("steady_compiles", 0))
        want_st = int(expected.get("steady_traces", 0))
        if m.steady_compiles != want_sc or m.steady_traces != want_st:
            errors.append(
                f"plan {m.plan}: steady state re-entered the compiler "
                f"({m.steady_traces} trace(s), {m.steady_compiles} "
                f"compile(s) over calls {WARMUP_CALLS + 1}..{m.steps}; "
                f"expected {want_st}/{want_sc}) — one executable became "
                "a compile-per-step treadmill")
            errors.extend(f"  {line}" for line in m.churn)
        for key, got in (("warmup_traces", m.warmup_traces),
                         ("warmup_compiles", m.warmup_compiles)):
            want = int(expected.get(key, 0))
            if got != want:
                warnings.append(
                    f"plan {m.plan}: {key} recorded {want}, got {got} "
                    "(informational — warmup counts vary with which "
                    "process-wide helper caches were already warm)")
    return errors, warnings


def run_retrace_guard(plans: Sequence[str] = ("dp",),
                      budgets_path: Optional[str] = None,
                      steps: int = 4,
                      ) -> Tuple[List[str], List[str]]:
    """Drive each plan ``steps`` steps and verify the committed retrace
    expectations. Raises FileNotFoundError when the Layer P golden is
    missing (run ``--layer perf --regen`` first)."""
    from mercury_tpu.lint.perf import load_perf_budgets

    ensure_cpu_devices()
    budgets = load_perf_budgets(budgets_path)
    measurements = [measure_plan_retraces(p, steps=steps) for p in plans]
    return compare_retraces(measurements, budgets)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m mercury_tpu.lint.tracecheck",
        description="graftlint Layer P retrace guard: execute each plan "
                    "N steps and assert steady-state compile count "
                    "matches lint/perf_budgets.json")
    ap.add_argument("--plans", default="dp",
                    help="comma-separated plans (default: dp; known: "
                         + ",".join(PLAN_NAMES))
    ap.add_argument("--steps", type=int, default=4,
                    help="calls per plan; the first 2 warm up (prime + "
                         "placement settle), the rest must not compile "
                         "(default: 4)")
    ap.add_argument("--budgets", default=None, metavar="PATH",
                    help="perf_budgets.json to verify against")
    args = ap.parse_args(argv)

    plans = tuple(p.strip() for p in args.plans.split(",") if p.strip())
    unknown = [p for p in plans if p not in PLAN_NAMES]
    if unknown:
        print(f"unknown plan(s): {', '.join(unknown)} "
              f"(known: {', '.join(PLAN_NAMES)})", file=sys.stderr)
        return 2
    try:
        errors, warnings = run_retrace_guard(
            plans, budgets_path=args.budgets, steps=args.steps)
    except FileNotFoundError as exc:
        print(f"graftlint tracecheck: perf budgets missing ({exc}) — "
              "run python -m mercury_tpu.lint --layer perf --regen "
              "first", file=sys.stderr)
        return 2
    for line in warnings:
        print(f"warning: {line}")
    for line in errors:
        print(line)
    if not errors:
        print(f"graftlint tracecheck: {len(plans)} plan(s) steady-state "
              f"clean ({', '.join(plans)})")
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
