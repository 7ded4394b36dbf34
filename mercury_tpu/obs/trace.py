"""Host-side step-timeline tracing: where the wall-clock actually went.

The fused XLA step is opaque from the host, but everything *around* it —
prefetch pop waits, host gathers, H2D commits, dispatch, eval,
checkpoint writes, metric drains — is host code, and that is exactly
where Mercury's overlap claims live or die. :class:`SpanTracer` records
named spans from any thread into a fixed-capacity ring (steady-state
memory and cost are bounded regardless of run length) and exports them
as Chrome trace-event JSON, loadable directly in Perfetto
(https://ui.perfetto.dev) or ``chrome://tracing``.

Overhead discipline (measured by ``benchmarks/telemetry_overhead.py``):

- **enabled**: one ``perf_counter_ns`` pair, a thread-local stack
  push/pop, a profiler annotation and a deque append per span —
  single-digit microseconds, invisible next to a training step;
- **disabled**: :data:`NULL_TRACER` returns one shared no-op context
  manager, so an instrumented call site costs an attribute lookup and
  two empty method calls (~100 ns) and allocates nothing. The traced
  device program is untouched either way — tracing is host-only.

Span schema (one Chrome ``"ph": "X"`` complete event per span)::

    {"name": "stream/gather", "cat": "stream", "ph": "X",
     "ts": <µs since tracer epoch>, "dur": <µs>,
     "pid": <os pid>, "tid": <thread id>,
     "args": {"id": <n>, "parent": <id>, "call": <n>, "step": <n>, ...}}

``id`` numbers the span; ``parent`` is the id of the span that was open
on the same thread when this one began (the span that caused it);
``call`` is the ordinal of the ``fit()`` call and ``step`` the train step
it belongs to — what joins a span from the prefetch or scorer thread to
the step it served.

One timeline: every span is also a ``jax.profiler.TraceAnnotation`` of
the same name, so whenever a ``jax.profiler`` capture is open (a
benchmark's traced run, the anomaly engine's window) the program's
spans lie on the capture's host lane, on the profiler's clock, next to
the device's lanes. With no capture open an annotation is a flag test.

``docs/OBSERVABILITY.md`` documents the schema and the fixed span
vocabulary the trainer and prefetch pipeline emit.
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

__all__ = ["SpanTracer", "NULL_TRACER", "NullTracer",
           "journal_lane_events", "merge_events_into_trace"]

#: Synthetic Chrome ``tid`` base for the per-subsystem journal lanes.
#: Real thread ids on linux are pthread addresses (very large), so a
#: small fixed base cannot collide with a recorded span's tid.
_EVENT_LANE_TID_BASE = 0xE000


def journal_lane_events(events: List[Dict[str, Any]],
                        epoch_unix_s: float,
                        pid: Optional[int] = None) -> List[Dict[str, Any]]:
    """Convert control-plane journal rows (``obs/events.py``) into Chrome
    trace events: one instant per event on a synthetic per-subsystem
    lane (``events/supervisor``, ``events/fault``, ...), plus a flow
    arrow (``ph:"s"``/``ph:"f"``) for every ``parent_id`` link — so
    Perfetto draws the causal chain breach → degrade → probe → recover
    on top of the span timeline.

    ``epoch_unix_s`` is the span tracer's wall-clock epoch
    (``otherData.epoch_unix_s`` of an exported trace): journal events
    carry absolute ``wall_s`` and are aligned into the tracer's
    microsecond timebase here. Pure stdlib — usable offline against an
    exported ``trace.json`` + journal file (see
    :func:`merge_events_into_trace`)."""
    pid = os.getpid() if pid is None else pid
    out: List[Dict[str, Any]] = []
    lanes: Dict[str, int] = {}
    placed: Dict[str, tuple] = {}  # event_id -> (ts_us, tid)
    for evt in events:
        kind = str(evt.get("kind", "?/?"))
        subsystem = kind.split("/", 1)[0]
        tid = lanes.setdefault(subsystem,
                               _EVENT_LANE_TID_BASE + len(lanes))
        ts = (float(evt.get("wall_s", epoch_unix_s)) - epoch_unix_s) * 1e6
        eid = evt.get("event_id")
        if isinstance(eid, str):
            placed[eid] = (ts, tid)
        out.append({
            "name": kind, "cat": "events", "ph": "i", "s": "p",
            "ts": ts, "pid": pid, "tid": tid,
            "args": {"event_id": eid,
                     "parent_id": evt.get("parent_id"),
                     "step": evt.get("step"),
                     "host": evt.get("host"),
                     "detail": evt.get("detail")},
        })
    flows = 0
    for evt in events:
        parent, eid = evt.get("parent_id"), evt.get("event_id")
        if not (isinstance(parent, str) and parent in placed
                and isinstance(eid, str) and eid in placed):
            continue
        p_ts, p_tid = placed[parent]
        c_ts, c_tid = placed[eid]
        flows += 1
        fid = f"evt-flow-{flows}"
        out.append({"name": "causes", "cat": "events", "ph": "s",
                    "id": fid, "ts": p_ts, "pid": pid, "tid": p_tid})
        out.append({"name": "causes", "cat": "events", "ph": "f",
                    "bp": "e", "id": fid, "ts": c_ts, "pid": pid,
                    "tid": c_tid})
    for subsystem, tid in lanes.items():
        out.append({"name": "thread_name", "ph": "M", "pid": pid,
                    "tid": tid, "args": {"name": f"events/{subsystem}"}})
    return out


def merge_events_into_trace(doc: Dict[str, Any],
                            events: List[Dict[str, Any]]
                            ) -> Dict[str, Any]:
    """Offline merge: append journal lanes to an already-exported Chrome
    trace document (mutates and returns ``doc``). The document must
    carry ``otherData.epoch_unix_s`` (every SpanTracer export does)."""
    other = doc.setdefault("otherData", {})
    epoch = float(other.get("epoch_unix_s", 0.0))
    pids = [e.get("pid") for e in doc.get("traceEvents", [])
            if e.get("pid") is not None]
    pid = pids[0] if pids else None
    doc.setdefault("traceEvents", []).extend(
        journal_lane_events(events, epoch, pid=pid))
    other["journal_events"] = len(events)
    return doc


class _NullSpan:
    """Shared reusable no-op context manager — the entire disabled path."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False


_NULL_SPAN = _NullSpan()


class NullTracer:
    """Disabled tracer: same surface as :class:`SpanTracer`, no state.

    Call sites keep their instrumentation unconditionally and pay only
    the shared no-op context manager when tracing is off — no branches
    at the call site, no per-span allocation."""

    enabled = False

    def span(self, name: str, cat: str = "trainer", **args) -> _NullSpan:
        return _NULL_SPAN

    def call_span(self, name: str, cat: str = "trainer",
                  **args) -> _NullSpan:
        return _NULL_SPAN

    def step_span(self, name: str, step: int, cat: str = "trainer",
                  **args) -> _NullSpan:
        return _NULL_SPAN

    def instant(self, name: str, cat: str = "trainer", **args) -> None:
        return None

    def register_thread(self, name: str) -> None:
        return None

    def snapshot(self) -> List[Dict[str, Any]]:
        return []

    def export_chrome_trace(self, path: str,
                            events: Optional[List[Dict[str, Any]]] = None
                            ) -> Optional[str]:
        return None


#: The process-wide disabled tracer. ``tracer or NULL_TRACER`` is the
#: idiom for optional-tracer parameters.
NULL_TRACER = NullTracer()


class _Span:
    """One live span: measures ``perf_counter_ns`` across the body and
    appends a ring tuple on exit. Exceptions propagate (the span still
    records — a span that died mid-body is exactly what a post-mortem
    wants to see). ``annotation`` is the profiler annotation held open
    across the body."""

    __slots__ = ("_tracer", "_name", "_cat", "_args", "_annotation",
                 "_stack", "_id", "_parent", "_call", "_step", "_t0")

    def __init__(self, tracer: "SpanTracer", name: str, cat: str,
                 args: Optional[Dict[str, Any]], annotation: Any) -> None:
        self._tracer = tracer
        self._name = name
        self._cat = cat
        self._args = args
        self._annotation = annotation

    def __enter__(self) -> "_Span":
        tr = self._tracer
        stack = self._stack = tr._stack()
        self._id = next(tr._ids)
        self._parent = stack[-1] if stack else None
        self._call, self._step = tr.call, tr.step
        stack.append(self._id)
        self._annotation.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter_ns()
        self._annotation.__exit__(*exc)
        self._stack.pop()
        tr = self._tracer
        # deque.append is atomic under the GIL: spans land from the
        # training thread, the prefetch worker, and the metric drain
        # thread without a lock on the hot path.
        tr._ring.append((self._name, self._cat, threading.get_ident(),
                         self._t0, t1 - self._t0, self._args, self._id,
                         self._parent, self._call, self._step))
        tr._total += 1
        return False


class SpanTracer:
    """Ring-buffered host span tracer with Chrome-trace export.

    ``capacity`` bounds memory and export size: a week-long run keeps
    the *last* ``capacity`` spans (the flight recorder's post-mortem
    window), and ``dropped`` says how many rotated out."""

    enabled = True

    def __init__(self, capacity: int = 4096) -> None:
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self._ring: deque = deque(maxlen=self.capacity)
        self._total = 0
        self._epoch_ns = time.perf_counter_ns()
        self._epoch_unix = time.time()
        self._thread_names: Dict[int, str] = {}
        # jax only here, not at import: journal_lane_events and the
        # offline merge stay stdlib-only.
        from jax.profiler import StepTraceAnnotation, TraceAnnotation

        self._annotation = TraceAnnotation
        self._step_annotation = StepTraceAnnotation
        self._ids = itertools.count(1)
        self._local = threading.local()  # .stack: ids of the open spans
        #: What the train thread is at, read by every span as it begins
        #: (plain attributes: one writer, reads atomic under the GIL).
        #: ``call``: ordinal of the ``fit()`` call (:meth:`call_span`);
        #: ``step``: the train step last dispatched (:meth:`step_span`).
        self.call = 0
        self.step: Optional[int] = None

    def _stack(self) -> List[int]:
        try:
            return self._local.stack
        except AttributeError:
            stack = self._local.stack = []
            return stack

    # ------------------------------------------------------------ recording
    def span(self, name: str, cat: str = "trainer", **args) -> _Span:
        """Context manager timing its body as one complete event."""
        return _Span(self, name, cat, args or None, self._annotation(name))

    def call_span(self, name: str, cat: str = "trainer", **args) -> _Span:
        """The root span of one call into the program (``fit()``): counts
        the call, and every span that begins until the next one records
        that ordinal as ``call``."""
        self.call += 1
        return self.span(name, cat, **args)

    def step_span(self, name: str, step: int, cat: str = "trainer",
                  **args) -> _Span:
        """The span that issues train step ``step`` (the first of
        ``steps=k`` for a scanned chunk): a ``StepTraceAnnotation`` in an
        open capture, and every span that begins until the next one, on
        any thread, records ``step``."""
        self.step = int(step)
        return _Span(self, name, cat, args or None,
                     self._step_annotation(name, step_num=self.step))

    def instant(self, name: str, cat: str = "trainer", **args) -> None:
        """Zero-duration marker event (trigger points, mode switches)."""
        with self._annotation(name):
            now = time.perf_counter_ns()
        stack = self._stack()
        self._ring.append((name, cat, threading.get_ident(), now, -1,
                           args or None, next(self._ids),
                           stack[-1] if stack else None, self.call,
                           self.step))
        self._total += 1

    def register_thread(self, name: str) -> None:
        """Name the calling thread in the exported trace's track list."""
        self._thread_names[threading.get_ident()] = name

    @property
    def dropped(self) -> int:
        """Spans rotated out of the ring since construction."""
        return self._total - len(self._ring)

    # -------------------------------------------------------------- export
    def snapshot(self) -> List[Dict[str, Any]]:
        """Ring contents as Chrome trace events (oldest first). A point-
        in-time copy — safe while other threads keep recording."""
        pid = os.getpid()
        events: List[Dict[str, Any]] = []
        for (name, cat, tid, t0_ns, dur_ns, args, span_id, parent, call,
             step) in list(self._ring):
            ev: Dict[str, Any] = {
                "name": name,
                "cat": cat,
                "ts": (t0_ns - self._epoch_ns) / 1e3,  # µs, tracer epoch
                "pid": pid,
                "tid": tid,
            }
            if dur_ns < 0:
                ev["ph"] = "i"
                ev["s"] = "t"  # instant scoped to its thread
            else:
                ev["ph"] = "X"
                ev["dur"] = dur_ns / 1e3
            ev["args"] = {"id": span_id}
            if parent is not None:
                ev["args"]["parent"] = parent
            if call:
                ev["args"]["call"] = call
            if step is not None:
                ev["args"]["step"] = step
            if args:
                ev["args"].update(args)  # a call site's own step= wins
            events.append(ev)
        return events

    def chrome_trace(self, events: Optional[List[Dict[str, Any]]] = None
                     ) -> Dict[str, Any]:
        """The full trace document: spans + thread-name metadata, plus —
        when ``events`` (control-plane journal rows) is given — one
        instant-event lane per subsystem and flow arrows for causal
        ``parent_id`` links, all on the tracer's shared timebase."""
        pid = os.getpid()
        trace_events = self.snapshot()
        for tid, name in list(self._thread_names.items()):
            trace_events.append({
                "name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
                "args": {"name": name},
            })
        other: Dict[str, Any] = {
            "tracer": "mercury_tpu.obs.trace",
            "epoch_unix_s": self._epoch_unix,
            "span_capacity": self.capacity,
            "spans_recorded": self._total,
            "spans_dropped": self.dropped,
        }
        if events:
            trace_events.extend(
                journal_lane_events(events, self._epoch_unix, pid=pid))
            other["journal_events"] = len(events)
        return {
            "traceEvents": trace_events,
            "displayTimeUnit": "ms",
            "otherData": other,
        }

    def export_chrome_trace(self, path: str,
                            events: Optional[List[Dict[str, Any]]] = None
                            ) -> str:
        """Write the trace JSON atomically; returns the path. The file
        loads as-is in Perfetto / ``chrome://tracing``."""
        doc = self.chrome_trace(events=events)
        directory = os.path.dirname(os.path.abspath(path))
        os.makedirs(directory, exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f)
            f.write("\n")
        os.replace(tmp, path)
        return path
