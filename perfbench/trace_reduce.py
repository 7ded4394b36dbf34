"""The benchmark's reduction from a profiler capture to numbers.

The first part of this file is a COPY of the reduction in
``mercury_tpu/obs/profile_parse.py`` as of PR 21 (capture discovery, the
Chrome-trace and ``*.xplane.pb`` readers, lane naming, self times, scope
attribution, idle share): later PRs may change the program, not the
yardstick, so the benchmark keeps its own. ``tests/perfbench`` holds the
copy to the original's numbers on ``tests/fixtures/profile_trace.json``.
The second part (``Capture``) is the benchmark's own: the same events cut
to the intervals in which a named XLA module (the train step) ran on the
device, which is how the closing ``evaluate()`` is kept out of the step's
numbers. jax-free.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
from typing import Any, Dict, Iterable, Iterator, List, Optional, Tuple

#: Schema tag for ``device_time_breakdown.json``; bump on shape changes.
BREAKDOWN_SCHEMA = "mercury_device_time_breakdown_v1"

#: Scope buckets, in match priority order — the named-scope anchors the
#: step factories emit (lint/audit.py::SCOPES plus the augmentation and
#: optimizer scopes). First substring hit wins, so a nested
#: ``mercury_scoring/mercury_augmentation`` event attributes to the
#: outer anchor listed first.
SCOPES: Tuple[str, ...] = (
    "mercury_scoring",
    "mercury_grad_sync",
    "mercury_augmentation",
    "mercury_input_fuse",
    "mercury_optimizer",
)

#: The explicit catch-all bucket: device-lane time that matched no scope
#: is still counted, never dropped.
UNATTRIBUTED = "unattributed"

_H2D_MARKERS = ("memcpy", "infeed", "h2d", "hosttodevice", "transfer")


# --------------------------------------------------------------- loading
def _read_maybe_gz(path: str) -> bytes:
    with open(path, "rb") as f:
        data = f.read()
    if data[:2] == b"\x1f\x8b":
        data = gzip.decompress(data)
    return data


def load_chrome_events(path: str) -> List[dict]:
    """Raw Chrome trace events from ``path`` (``.json`` / ``.json.gz``;
    either the ``{"traceEvents": [...]}`` envelope or a bare list)."""
    doc = json.loads(_read_maybe_gz(path).decode("utf-8"))
    if isinstance(doc, dict):
        events = doc.get("traceEvents", [])
    else:
        events = doc
    return [e for e in events if isinstance(e, dict)]


# ------------------------------------------------- xplane.pb wire reader
# A minimal protobuf wire-format walker — enough of
# tsl/profiler/protobuf/xplane.proto to pull (plane name, line name,
# event name, timestamp, duration) out of a raw capture without any
# protobuf runtime. Field numbers are stable public API of the profiler.
def _varint(buf: memoryview, pos: int) -> Tuple[int, int]:
    result = 0
    shift = 0
    while True:
        b = buf[pos]
        pos += 1
        result |= (b & 0x7F) << shift
        if not b & 0x80:
            return result, pos
        shift += 7
        if shift > 70:
            raise ValueError("malformed varint")


def _wire_fields(buf: memoryview) -> Iterator[Tuple[int, int, Any]]:
    """Yield ``(field_number, wire_type, value)``; length-delimited
    values come back as memoryviews, scalars as ints."""
    pos = 0
    end = len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        field, wtype = key >> 3, key & 0x7
        if wtype == 0:  # varint
            value, pos = _varint(buf, pos)
        elif wtype == 1:  # fixed64
            value = int.from_bytes(buf[pos:pos + 8], "little")
            pos += 8
        elif wtype == 2:  # length-delimited
            length, pos = _varint(buf, pos)
            value = buf[pos:pos + length]
            pos += length
        elif wtype == 5:  # fixed32
            value = int.from_bytes(buf[pos:pos + 4], "little")
            pos += 4
        else:
            raise ValueError(f"unsupported wire type {wtype}")
        yield field, wtype, value


def _decode_xevent(buf: memoryview) -> Dict[str, int]:
    ev = {"metadata_id": 0, "offset_ps": 0, "duration_ps": 0}
    for field, _, value in _wire_fields(buf):
        if field == 1:
            ev["metadata_id"] = int(value)
        elif field == 2:
            ev["offset_ps"] = int(value)
        elif field == 3:
            ev["duration_ps"] = int(value)
    return ev


def _decode_xline(buf: memoryview) -> Dict[str, Any]:
    line: Dict[str, Any] = {"name": "", "timestamp_ns": 0, "events": []}
    for field, _, value in _wire_fields(buf):
        if field == 2:
            line["name"] = bytes(value).decode("utf-8", "replace")
        elif field == 3:
            line["timestamp_ns"] = int(value)
        elif field == 4:
            line["events"].append(_decode_xevent(value))
        elif field == 11 and not line["name"]:
            line["name"] = bytes(value).decode("utf-8", "replace")
    return line


def _decode_metadata_entry(buf: memoryview) -> Tuple[int, str, str]:
    """One ``map<int64, XEventMetadata>`` entry -> ``(id, name,
    stats_text)``: the metadata's string-valued stats, joined. On a TPU
    capture an op's name is its HLO text and the named-scope path
    (``jit(step)/mercury_scoring/...``) is its ``tf_op`` stat, so the
    stats are where attribution has to look."""
    key = 0
    name = ""
    stats: List[str] = []
    for field, _, value in _wire_fields(buf):
        if field == 1:
            key = int(value)
        elif field == 2:
            for f2, _, v2 in _wire_fields(value):
                if f2 == 2:
                    name = bytes(v2).decode("utf-8", "replace")
                elif f2 == 5:  # XStat; field 5 of it is str_value
                    stats.extend(
                        bytes(v3).decode("utf-8", "replace")
                        for f3, w3, v3 in _wire_fields(v2)
                        if f3 == 5 and w3 == 2)
    return key, name, " ".join(stats)


def _decode_xplane(buf: memoryview) -> Dict[str, Any]:
    plane: Dict[str, Any] = {"name": "", "lines": [], "event_names": {},
                             "event_stats": {}}
    for field, _, value in _wire_fields(buf):
        if field == 2:
            plane["name"] = bytes(value).decode("utf-8", "replace")
        elif field == 3:
            plane["lines"].append(_decode_xline(value))
        elif field == 4:
            k, name, stats = _decode_metadata_entry(value)
            plane["event_names"][k] = name
            if stats:
                plane["event_stats"][k] = stats
    return plane


def load_xplane_events(path: str) -> List[dict]:
    """Normalized events (Chrome-shaped dicts) from a raw
    ``*.xplane.pb`` capture."""
    buf = memoryview(_read_maybe_gz(path))
    events: List[dict] = []
    pid = 0
    for field, _, value in _wire_fields(buf):
        if field != 1:  # XSpace.planes
            continue
        plane = _decode_xplane(value)
        pid += 1
        tid = 0
        for line in plane["lines"]:
            tid += 1
            t0_us = line["timestamp_ns"] / 1e3
            for ev in line["events"]:
                name = plane["event_names"].get(ev["metadata_id"], "")
                event = {
                    "ph": "X",
                    "name": name,
                    "ts": t0_us + ev["offset_ps"] / 1e6,
                    "dur": ev["duration_ps"] / 1e6,
                    "pid": pid,
                    "tid": tid,
                    "_pname": plane["name"],
                    "_tname": line["name"],
                }
                stats = plane["event_stats"].get(ev["metadata_id"])
                if stats:
                    event["args"] = {"stats": stats}
                events.append(event)
    return events


# ----------------------------------------------------------- discovery
_CHROME_PATTERNS = ("*.trace.json.gz", "*.trace.json", "trace.json",
                    "trace.json.gz")
_XPLANE_PATTERNS = ("*.xplane.pb",)


def discover_capture_files(root: str) -> List[str]:
    """Capture files under a profile directory, newest capture first.
    Chrome traces win over xplane when both exist (same data, cheaper
    parse); multiple same-format files (one per host) all return."""
    for patterns in (_CHROME_PATTERNS, _XPLANE_PATTERNS):
        found: List[str] = []
        for pat in patterns:
            found.extend(glob.glob(os.path.join(root, "**", pat),
                                   recursive=True))
        if found:
            found = sorted(set(found), key=os.path.getmtime, reverse=True)
            newest_dir = os.path.dirname(found[0])
            return sorted(f for f in found
                          if os.path.dirname(f) == newest_dir)
    return []


def load_events(path: str) -> Tuple[List[dict], str]:
    """Events + the resolved source description for ``path`` (a capture
    file or a directory to search)."""
    if os.path.isdir(path):
        files = discover_capture_files(path)
        if not files:
            raise FileNotFoundError(
                f"no trace capture (*.trace.json[.gz] or *.xplane.pb) "
                f"under {path}")
    else:
        files = [path]
    events: List[dict] = []
    for f in files:
        if f.endswith(".xplane.pb"):
            events.extend(load_xplane_events(f))
        else:
            events.extend(load_chrome_events(f))
    return events, ";".join(files)


# --------------------------------------------------------- normalization
def _lane_names(events: Iterable[dict]) -> Tuple[Dict[int, str],
                                                 Dict[Tuple[int, int], str]]:
    """``pid -> process_name`` and ``(pid, tid) -> thread_name`` from
    Chrome metadata events (xplane-normalized events carry their names
    inline instead)."""
    pnames: Dict[int, str] = {}
    tnames: Dict[Tuple[int, int], str] = {}
    for e in events:
        if e.get("ph") == "M":
            name = (e.get("args") or {}).get("name", "")
            if e.get("name") == "process_name":
                pnames[e.get("pid", 0)] = name
            elif e.get("name") == "thread_name":
                tnames[(e.get("pid", 0), e.get("tid", 0))] = name
    return pnames, tnames


def _is_device_lane(pname: str) -> bool:
    low = pname.lower()
    return ("/device:" in low or low.startswith("tpu")
            or low.startswith("gpu"))


def _merged(intervals: List[Tuple[float, float]]
            ) -> List[Tuple[float, float]]:
    """Possibly-overlapping ``(start, end)`` as disjoint sorted spans."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _merged_busy(intervals: List[Tuple[float, float]]) -> float:
    """Total covered time of possibly-overlapping ``(start, end)``."""
    return sum(e - s for s, e in _merged(intervals))


def _self_times(events: List[dict]) -> List[float]:
    """Exclusive duration of each event: its own minus that of the events
    nested directly in it on the same lane. A real TPU op lane nests — a
    ``while`` op's event spans the events of its body's ops (every op of
    a ``scan_steps`` chunk, every batch of an eval epoch) — so summing
    plain durations would count the body twice and halve every scope's
    share."""
    self_us = [float(e["dur"]) for e in events]
    order = sorted(range(len(events)), key=lambda i: (
        events[i].get("pid", 0), events[i].get("tid", 0),
        float(events[i]["ts"]), -float(events[i]["dur"])))
    stack: List[int] = []  # indices of the open enclosing events
    lane = None
    for i in order:
        e = events[i]
        if (e.get("pid", 0), e.get("tid", 0)) != lane:
            lane, stack = (e.get("pid", 0), e.get("tid", 0)), []
        start = float(e["ts"])
        end = start + float(e["dur"])
        while stack:
            top = events[stack[-1]]
            top_end = float(top["ts"]) + float(top["dur"])
            if end <= top_end + 1e-6 and start < top_end:
                break  # e lies inside the open event on top
            stack.pop()
        if stack:
            self_us[stack[-1]] -= float(e["dur"])
        stack.append(i)
    return [max(us, 0.0) for us in self_us]


def _overlap(a: List[Tuple[float, float]],
             b: List[Tuple[float, float]]) -> float:
    """Total time where interval sets ``a`` and ``b`` overlap."""
    a, b = sorted(a), sorted(b)
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        s = max(a[i][0], b[j][0])
        e = min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _searchable_text(event: dict) -> str:
    parts = [str(event.get("name", ""))]
    args = event.get("args")
    if isinstance(args, dict):
        parts.extend(str(v) for v in args.values()
                     if isinstance(v, (str, int)))
    return " ".join(parts).lower()


# ----------------------------------------------------------- attribution
def attribute_device_time(events: List[dict],
                          scopes: Tuple[str, ...] = SCOPES
                          ) -> Dict[str, Any]:
    """Bucket device-lane time by named scope; every microsecond of
    device-lane busy time lands in a scope bucket or ``unattributed``
    (the accounting identity ``attributed_frac == 1.0`` is part of the
    contract — tests pin it)."""
    pnames, tnames = _lane_names(events)

    complete = [e for e in events if e.get("ph") == "X"
                and float(e.get("dur", 0)) > 0]
    for e in complete:  # xplane events carry names inline
        e.setdefault("_pname", pnames.get(e.get("pid", 0), ""))
        e.setdefault("_tname", tnames.get(
            (e.get("pid", 0), e.get("tid", 0)), ""))

    device = [e for e in complete if _is_device_lane(e["_pname"])]

    def _is_h2d(e: dict) -> bool:
        text = (e["_tname"] + " " + str(e.get("name", ""))).lower()
        return any(m in text for m in _H2D_MARKERS)

    h2d = [e for e in complete if _is_h2d(e)]
    h2d_ids = {id(e) for e in h2d}
    device_compute = [e for e in device if id(e) not in h2d_ids]

    # The op-level lane ("XLA Ops" in both jax and TF exports) is the
    # attribution target; step/module container lanes would double-count
    # every nanosecond. When no lane is tagged, fall back to the busiest
    # single lane — deterministic, and honest about granularity.
    # An exact match: "Async XLA Ops" is another lane of the same plane
    # (in-flight copies and collectives, overlapping the op lane).
    op_lanes = [e for e in device_compute if e["_tname"].lower() == "xla ops"]
    if op_lanes:
        compute = op_lanes
        lane_note = "xla_ops"
    elif device_compute:
        by_lane: Dict[Tuple[int, int], float] = {}
        for e in device_compute:
            key = (e.get("pid", 0), e.get("tid", 0))
            by_lane[key] = by_lane.get(key, 0.0) + float(e["dur"])
        busiest = max(by_lane, key=lambda k: by_lane[k])
        compute = [e for e in device_compute
                   if (e.get("pid", 0), e.get("tid", 0)) == busiest]
        lane_note = "busiest_device_lane"
    else:
        compute = []
        lane_note = "none"

    bucket_us: Dict[str, float] = {s: 0.0 for s in scopes}
    bucket_us[UNATTRIBUTED] = 0.0
    self_us = _self_times(compute)
    for e, us in zip(compute, self_us):
        text = _searchable_text(e)
        for scope in scopes:
            if scope in text:
                bucket_us[scope] += us
                break
        else:
            bucket_us[UNATTRIBUTED] += us

    total_us = sum(self_us)
    attributed_us = sum(bucket_us.values())

    compute_iv = _merged([(float(e["ts"]), float(e["ts"]) + float(e["dur"]))
                          for e in compute])
    h2d_iv = [(float(e["ts"]), float(e["ts"]) + float(e["dur"]))
              for e in h2d]
    h2d_iv = _merged(h2d_iv)
    h2d_total = _merged_busy(h2d_iv)
    h2d_overlap = _overlap(compute_iv, h2d_iv)

    busy_us = _merged_busy(compute_iv)
    span_us = ((max(e[1] for e in compute_iv)
                - min(e[0] for e in compute_iv)) if compute_iv else 0.0)
    idle_us = max(span_us - busy_us, 0.0)

    return {
        "schema": BREAKDOWN_SCHEMA,
        "scopes": {
            name: {"time_us": round(us, 3),
                   "frac": (us / total_us if total_us else 0.0)}
            for name, us in bucket_us.items()
        },
        "total_device_time_us": round(total_us, 3),
        "attributed_frac": (attributed_us / total_us if total_us else 0.0),
        "h2d": {
            "total_us": round(h2d_total, 3),
            "overlap_us": round(h2d_overlap, 3),
            "overlap_frac": (h2d_overlap / h2d_total if h2d_total else 0.0),
        },
        "idle": {
            "span_us": round(span_us, 3),
            "busy_us": round(busy_us, 3),
            "idle_us": round(idle_us, 3),
            "idle_frac": (idle_us / span_us if span_us else 0.0),
        },
        "counts": {
            "events": len(events),
            "device_events": len(compute),
            "h2d_events": len(h2d),
            "lane": lane_note,
        },
    }


def parse_profile(path: str,
                  scopes: Tuple[str, ...] = SCOPES) -> Dict[str, Any]:
    """Load + attribute in one call; ``path`` is a capture file or a
    profile directory."""
    events, source = load_events(path)
    breakdown = attribute_device_time(events, scopes=scopes)
    breakdown["source"] = source
    return breakdown


# ====================================================================
# The benchmark's own part: one capture, cut to a named module's runs.
# ====================================================================
class Capture:
    """The device lanes of one capture, per chip.

    ``ops`` are the events of each device plane's "XLA Ops" lane with
    their self times; ``modules`` those of its "XLA Modules" lane, one per
    execution of a compiled program. ``step_module`` is a substring of the
    train step's module name (``jit_<function name>``): the intervals in
    which it ran are the step's device time, and whatever else ran (the
    closing ``evaluate()``, a log gate's fetch) lies outside them."""

    def __init__(self, events: List[dict], step_module: str = "") -> None:
        pnames, tnames = _lane_names(events)
        planes: Dict[Any, Dict[str, List[dict]]] = {}
        #: device plane -> lane -> events, for the run's log
        self.census: Dict[str, Dict[str, int]] = {}
        for e in events:
            if e.get("ph") != "X" or float(e.get("dur", 0)) <= 0:
                continue
            pname = e.get("_pname", pnames.get(e.get("pid", 0), ""))
            if not _is_device_lane(pname):
                continue
            tname = e.get("_tname", tnames.get(
                (e.get("pid", 0), e.get("tid", 0)), "")).lower()
            lanes = self.census.setdefault(pname, {})
            lanes[tname] = lanes.get(tname, 0) + 1
            kind = {"xla ops": "ops", "xla modules": "modules"}.get(tname)
            if kind:
                planes.setdefault(e.get("pid", 0), {"ops": [], "modules": []}
                                  )[kind].append(e)
        # A plane with no op ran nothing (a chip the mesh does not use).
        self.planes = [p for _, p in sorted(planes.items()) if p["ops"]]
        for p in self.planes:
            p["self_us"] = _self_times(p["ops"])
            p["steps"] = sorted(
                (float(m["ts"]), float(m["ts"]) + float(m["dur"]))
                for m in p["modules"]
                if step_module and step_module in str(m.get("name", "")))
        self.step_module = step_module

    # ------------------------------------------------------------ helpers
    @staticmethod
    def _index(ts: float, intervals: List[Tuple[float, float]]) -> int:
        """Index of the interval that holds ``ts``, or -1."""
        i = bisect.bisect_right(intervals, (ts, float("inf"))) - 1
        return i if i >= 0 and ts < intervals[i][1] else -1

    def _inside(self, ts: float, intervals) -> bool:
        return self._index(ts, intervals) >= 0

    def _step_ops(self, plane) -> List[Tuple[dict, float]]:
        """(op, self time) of the ops that ran inside the step module's
        intervals; every op where the capture names no such module."""
        pairs = list(zip(plane["ops"], plane["self_us"]))
        if not plane["steps"]:
            return pairs
        return [(e, us) for e, us in pairs
                if self._inside(float(e["ts"]), plane["steps"])]

    def _mean(self, per_plane: List[float]) -> Optional[float]:
        return sum(per_plane) / len(per_plane) if per_plane else None

    # ------------------------------------------------------------ numbers
    def step_count(self) -> int:
        """Executions of the step module on the first chip."""
        return len(self.planes[0]["steps"]) if self.planes else 0

    def steps_held(self, asked: int) -> int:
        """The step programs to divide a per-step reading by: the ``asked``
        steps of the traced calls, or the executions the capture holds
        where it lost some of them (the first traced run on a just-compiled
        step does: 93 and 115 of 120, my chip runs, PR 26; the lost
        programs' ops are gone with them). A capture that names no such
        module counts every op as the step's, so ``asked`` stands."""
        held = self.step_count()
        return held if 0 < held < asked else asked

    def lost_step_us(self, asked: int) -> float:
        """Wall time of the step programs the capture lost of the ``asked``
        (they ran between its first and its last and left no op there),
        each at the mean duration of those the first chip's lane holds."""
        held = self.steps_held(asked)
        if held == asked:
            return 0.0
        runs = self.planes[0]["steps"]
        return (asked - held) * sum(hi - lo for lo, hi in runs) / len(runs)

    def step_device_us(self) -> Optional[float]:
        """Device time of the step module's executions (self times of the
        ops inside them), mean over chips."""
        return self._mean([sum(us for _, us in self._step_ops(p))
                           for p in self.planes])

    def scope_share(self, scope: str) -> Optional[float]:
        """Share of the step's device time under ``scope``, by the copied
        reduction's rule: an op belongs to the first of ``SCOPES`` that its
        name or stats mention."""
        order = SCOPES if scope in SCOPES else SCOPES + (scope,)
        shares = []
        for p in self.planes:
            total = hit = 0.0
            for e, us in self._step_ops(p):
                total += us
                text = _searchable_text(e)
                if next((s for s in order if s in text), None) == scope:
                    hit += us
            if total:
                shares.append(hit / total)
        return self._mean(shares)

    def step_idle(self) -> Optional[Dict[str, Any]]:
        """Busy and idle time between the first step's start and the last
        step's end, mean over chips, with the idle gaps of the first chip
        split by where they lie: inside a step program's run, or between
        two runs (the device waiting for the host's next dispatch)."""
        spans, busys, gaps = [], [], []
        for k, p in enumerate(self.planes):
            steps = p["steps"]
            ops = [(float(e["ts"]), float(e["ts"]) + float(e["dur"]))
                   for e in p["ops"]]
            lo = steps[0][0] if steps else min(s for s, _ in ops)
            hi = steps[-1][1] if steps else max(e for _, e in ops)
            busy = _merged([(max(s, lo), min(e, hi)) for s, e in ops
                            if e > lo and s < hi])
            spans.append(hi - lo)
            busys.append(sum(e - s for s, e in busy))
            if k == 0:
                edges = [lo] + [t for iv in busy for t in iv] + [hi]
                for a, b in zip(edges[0::2], edges[1::2]):
                    if b > a:
                        where = ("inside_step_program"
                                 if self._index(a, steps) >= 0
                                 and self._index(a, steps)
                                 == self._index(b - 1e-6, steps)
                                 else "between_step_programs")
                        gaps.append((where, b - a))
        if not spans:
            return None
        span, busy = self._mean(spans), self._mean(busys)
        return {"span_us": span, "busy_us": busy,
                "idle_frac": max(span - busy, 0.0) / span if span else 0.0,
                "gaps": gaps}

    def module_share(self, module: str) -> Optional[float]:
        """Share of the device's busy time over the whole capture that lies
        inside the executions of the modules whose name holds ``module``,
        mean over chips."""
        shares = []
        for p in self.planes:
            runs = sorted((float(m["ts"]), float(m["ts"]) + float(m["dur"]))
                          for m in p["modules"]
                          if module in str(m.get("name", "")))
            total = sum(p["self_us"])
            inside = sum(us for e, us in zip(p["ops"], p["self_us"])
                         if self._inside(float(e["ts"]), runs))
            if total and runs:
                shares.append(inside / total)
        return self._mean(shares)

    def whole_busy_us(self) -> Optional[float]:
        """Time in which any op ran on the device over the whole capture
        (step programs, evaluate, fetches), mean over chips."""
        return self._mean([
            _merged_busy([(float(e["ts"]), float(e["ts"]) + float(e["dur"]))
                          for e in p["ops"]]) for p in self.planes])

    def top_ops(self, n: int = 10) -> List[List[Any]]:
        """The ``n`` device ops with the most self time over the whole
        capture on the first chip, ``[name, seconds]``; an op's name is its
        event name, with the innermost ``mercury_*`` scope it ran under."""
        if not self.planes:
            return []
        p = self.planes[0]
        total: Dict[str, float] = {}
        for e, us in zip(p["ops"], p["self_us"]):
            name = str(e.get("name", ""))[:80]
            text = _searchable_text(e)
            scope = next((s for s in SCOPES if s in text), "")
            key = f"{scope}:{name}" if scope else name
            total[key] = total.get(key, 0.0) + us
        top = sorted(total.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / 1e6] for k, v in top]

    def gap_summary(self, n: int = 10) -> List[List[Any]]:
        """Idle time of the first chip by where it lies (totals first),
        then the longest single gaps: ``[name, seconds]``. What the host
        was doing in a gap is not known to this capture."""
        idle = self.step_idle()
        if not idle:
            return []
        totals: Dict[str, float] = {}
        for where, us in idle["gaps"]:
            totals[where] = totals.get(where, 0.0) + us
        out = [[f"total:{k}", v / 1e6] for k, v in sorted(totals.items())]
        longest = sorted(idle["gaps"], key=lambda g: -g[1])[:n - len(out)]
        return out + [[f"longest:{w}", us / 1e6] for w, us in longest]
