"""One timeline: the program's host spans as the profiler wrote them into
the capture, beside the device's lanes and on the same clock. Not a reducer
but what the ``idle_attribution`` reducer stands on; kept in ``ctx`` so
that the capture is read once more, not once per metric.

The harness's ``ctx["capture"]`` keeps the device lanes only, so the host
lane is read again from where the traced run wrote it (``perfbench/_trace``,
through ``trace_reduce.load_events``). A program without these spans in its
captures (any commit before PR 25) gives ``None`` everywhere.
"""

from __future__ import annotations

import glob
import os
from typing import Any, Dict, List, Optional, Tuple

from perfbench import trace_reduce

#: Where ``run.py`` writes a traced run's capture (its ``TRACE_DIR``; that
#: file is the command, and importing it here would run it a second time).
TRACE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "_trace")

ROOT = "trainer/fit"
#: The root's children whose share of the idle time has a metric of its
#: own; idle time under the root and under neither is the loop's.
EVAL, DISPATCH = "trainer/eval", "trainer/dispatch"

Intervals = List[Tuple[float, float]]


def host_spans(ctx: Dict[str, Any]) -> Dict[str, Intervals]:
    """``name -> merged (start, end)`` in µs, on the capture's clock, of
    the events of the capture's host lanes named ``ROOT``, ``EVAL`` or
    ``DISPATCH``. Empty where there is no capture to read."""
    if "_host_spans" not in ctx:
        ctx["_host_spans"] = _read_host_spans(TRACE_DIR)
    return ctx["_host_spans"]


def _read_host_spans(root: str) -> Dict[str, Intervals]:
    # the newest raw capture, else whatever the directory holds: as
    # run.py's load_capture chooses
    planes = sorted(glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                              recursive=True), key=os.path.getmtime)
    try:
        events, _ = trace_reduce.load_events(planes[-1] if planes else root)
    except FileNotFoundError:
        return {}
    pnames, _ = trace_reduce._lane_names(events)
    found: Dict[str, Intervals] = {}
    for e in events:
        if e.get("ph") != "X" or e.get("name") not in (ROOT, EVAL, DISPATCH):
            continue
        pname = e.get("_pname", pnames.get(e.get("pid", 0), ""))
        if trace_reduce._is_device_lane(pname):
            continue
        start = float(e["ts"])
        found.setdefault(e["name"], []).append(
            (start, start + float(e.get("dur", 0))))
    return {name: trace_reduce._merged(iv) for name, iv in found.items()}


def idle_gaps(capture) -> Optional[Intervals]:
    """The first chip's idle gaps between the first step program's start and
    the last one's end, ``(start, end)`` in µs: ``Capture.step_idle``'s own
    gaps, which it reports by length and position only."""
    if not capture.planes:
        return None
    plane = capture.planes[0]
    ops = [(float(e["ts"]), float(e["ts"]) + float(e["dur"]))
           for e in plane["ops"]]
    steps = plane["steps"]
    lo = steps[0][0] if steps else min(s for s, _ in ops)
    hi = steps[-1][1] if steps else max(e for _, e in ops)
    busy = trace_reduce._merged([(max(s, lo), min(e, hi)) for s, e in ops
                                 if e > lo and s < hi])
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def idle_partition(ctx: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """The first chip's idle time (µs) by what the program's train thread
    was in: ``eval``, ``dispatch``, ``loop`` (under the root span and
    neither of those) and ``unattributed`` (under no root span: the caller's
    code between two ``fit()`` calls); ``total`` is their sum. ``None``
    where there are no device lanes or the capture holds no root span."""
    if "_idle_partition" not in ctx:
        ctx["_idle_partition"] = _idle_partition(ctx)
    return ctx["_idle_partition"]


def _idle_partition(ctx: Dict[str, Any]) -> Optional[Dict[str, float]]:
    gaps = idle_gaps(ctx["capture"])
    if gaps is None:
        return None
    spans = host_spans(ctx)
    if not spans.get(ROOT):
        return None
    total = sum(b - a for a, b in gaps)
    under_root = trace_reduce._overlap(gaps, spans[ROOT])
    # children of one thread's root span: disjoint, and inside it
    in_eval = trace_reduce._overlap(gaps, spans.get(EVAL, []))
    in_dispatch = trace_reduce._overlap(gaps, spans.get(DISPATCH, []))
    return {"eval": in_eval, "dispatch": in_dispatch,
            "loop": under_root - in_eval - in_dispatch,
            "unattributed": total - under_root, "total": total}
