"""Offline device-time attribution (obs/profile_parse.py): scope
bucketing against the committed anonymized capture fixture, the
accounting identity (every device microsecond lands in a bucket),
H2D-overlap and idle interval math, the protobuf wire-format xplane
reader against a hand-encoded capture, capture discovery, and the CLI.

The module is deliberately jax-free — one test pins that by running the
CLI in a subprocess and asserting jax never entered sys.modules.
"""

import gzip
import json
import os
import subprocess
import sys

import pytest

from mercury_tpu.obs.profile_parse import (
    BREAKDOWN_SCHEMA,
    SCOPES,
    UNATTRIBUTED,
    attribute_device_time,
    discover_capture_files,
    load_chrome_events,
    load_events,
    load_xplane_events,
    main,
    parse_profile,
    scope_frac_metrics,
    write_breakdown,
)

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "profile_trace.json")


def meta_events(pid=1, pname="/device:TPU:0", lanes=((3, "XLA Ops"),)):
    evs = [{"ph": "M", "name": "process_name", "pid": pid,
            "args": {"name": pname}}]
    for tid, tname in lanes:
        evs.append({"ph": "M", "name": "thread_name", "pid": pid,
                    "tid": tid, "args": {"name": tname}})
    return evs


def op(name, ts, dur, pid=1, tid=3):
    return {"ph": "X", "name": name, "ts": ts, "dur": dur,
            "pid": pid, "tid": tid, "cat": "tpu_op"}


class TestFixtureAttribution:
    def test_fixture_meets_attribution_floor(self):
        bd = parse_profile(FIXTURE)
        assert bd["schema"] == BREAKDOWN_SCHEMA
        # The acceptance bar: >= 95% of device-lane time named (the
        # explicit unattributed bucket counts as named).
        assert bd["attributed_frac"] >= 0.95
        fracs = {k: v["frac"] for k, v in bd["scopes"].items()}
        assert set(fracs) == set(SCOPES) | {UNATTRIBUTED}
        assert sum(fracs.values()) == pytest.approx(1.0)
        # Scoring dominates the synthetic step, as on the real chip.
        assert max(fracs, key=fracs.get) == "mercury_scoring"

    def test_container_lanes_not_double_counted(self):
        bd = parse_profile(FIXTURE)
        # 3 step windows x 8 XLA Ops events; the "Steps" and "XLA
        # Modules" container lanes span the same time and must be
        # excluded from the op-lane attribution.
        assert bd["counts"]["device_events"] == 24
        assert bd["counts"]["lane"] == "xla_ops"

    def test_h2d_and_idle_measured(self):
        bd = parse_profile(FIXTURE)
        assert bd["counts"]["h2d_events"] == 6
        assert 0.0 < bd["h2d"]["overlap_frac"] <= 1.0
        assert 0.0 < bd["idle"]["idle_frac"] < 1.0


class TestAttributionMath:
    def test_accounting_identity_with_unknown_ops(self):
        events = meta_events() + [
            op("fusion.1 mercury_scoring/dot", 0, 100),
            op("all-reduce mercury_grad_sync", 100, 50),
            op("some-unknown-fusion.7", 150, 25),
        ]
        bd = attribute_device_time(events)
        assert bd["total_device_time_us"] == pytest.approx(175.0)
        assert bd["attributed_frac"] == pytest.approx(1.0)
        assert bd["scopes"]["mercury_scoring"]["frac"] == pytest.approx(
            100 / 175)
        assert bd["scopes"][UNATTRIBUTED]["time_us"] == pytest.approx(25.0)

    def test_scope_match_priority_first_wins(self):
        # A nested scope name attributes to the FIRST matching anchor in
        # SCOPES order, not to both.
        events = meta_events() + [
            op("mercury_scoring/mercury_augmentation/crop", 0, 10)]
        bd = attribute_device_time(events)
        assert bd["scopes"]["mercury_scoring"]["time_us"] == 10.0
        assert bd["scopes"]["mercury_augmentation"]["time_us"] == 0.0

    def test_scope_in_args_counts(self):
        # jax exports sometimes put the name stack in args, not name.
        events = meta_events() + [
            {"ph": "X", "name": "fusion.3", "ts": 0, "dur": 10, "pid": 1,
             "tid": 3, "args": {"tf_op": "mercury_grad_sync/psum"}}]
        bd = attribute_device_time(events)
        assert bd["scopes"]["mercury_grad_sync"]["time_us"] == 10.0

    def test_nested_ops_count_once(self):
        """What a real v5e op lane looks like (chip capture, PR 21): a
        ``while`` op's event spans the events of its body — a scan chunk,
        an eval epoch. Time is attributed exclusively: the body's ops keep
        their scopes, the loop keeps only its own overhead, and the total
        equals the lane's busy time."""
        events = meta_events() + [
            op("while.2", 0, 100),
            {"ph": "X", "name": "fusion.7", "ts": 0, "dur": 60, "pid": 1,
             "tid": 3, "args": {"tf_op": "jit(f)/mercury_scoring/conv"}},
            op("fusion.8", 60, 30),
            op("copy.1", 100, 10),        # after the loop, not inside it
        ]
        bd = attribute_device_time(events)
        assert bd["total_device_time_us"] == pytest.approx(110.0)
        assert bd["total_device_time_us"] == pytest.approx(
            bd["idle"]["busy_us"])
        assert bd["scopes"]["mercury_scoring"]["time_us"] == \
            pytest.approx(60.0)
        assert bd["scopes"][UNATTRIBUTED]["time_us"] == pytest.approx(50.0)

    def test_async_op_lane_is_not_the_op_lane(self):
        # A TPU plane also carries "Async XLA Ops" (in-flight copies,
        # overlapping the op lane); only "XLA Ops" is attributed.
        events = meta_events(lanes=((3, "XLA Ops"),
                                    (4, "Async XLA Ops"))) + [
            op("mercury_scoring/x", 0, 10),
            op("copy-start.1", 0, 500, tid=4),
        ]
        bd = attribute_device_time(events)
        assert bd["counts"]["device_events"] == 1
        assert bd["total_device_time_us"] == pytest.approx(10.0)

    def test_host_lanes_ignored(self):
        events = meta_events() + [
            {"ph": "M", "name": "process_name", "pid": 9,
             "args": {"name": "python"}},
            op("mercury_scoring/x", 0, 10),
            op("mercury_scoring/host_side", 0, 999, pid=9, tid=1),
        ]
        bd = attribute_device_time(events)
        assert bd["total_device_time_us"] == pytest.approx(10.0)

    def test_busiest_lane_fallback_without_xla_ops_tag(self):
        # No "XLA Ops" thread name anywhere: fall back to the busiest
        # device lane, deterministically.
        events = meta_events(lanes=((1, "lane a"), (2, "lane b"))) + [
            op("mercury_scoring/a", 0, 10, tid=1),
            op("mercury_scoring/b", 0, 100, tid=2),
        ]
        bd = attribute_device_time(events)
        assert bd["counts"]["lane"] == "busiest_device_lane"
        assert bd["total_device_time_us"] == pytest.approx(100.0)

    def test_h2d_overlap_intervals(self):
        # Compute [0,100]; copies [50,70] (hidden) and [200,210]
        # (exposed): overlap = 20 of 30 total copy time.
        events = meta_events(lanes=((3, "XLA Ops"),
                                    (4, "XLA Async Ops #memcpy"))) + [
            op("mercury_scoring/x", 0, 100),
            op("MemcpyH2D.0", 50, 20, tid=4),
            op("MemcpyH2D.1", 200, 10, tid=4),
        ]
        bd = attribute_device_time(events)
        assert bd["h2d"]["total_us"] == pytest.approx(30.0)
        assert bd["h2d"]["overlap_us"] == pytest.approx(20.0)
        assert bd["h2d"]["overlap_frac"] == pytest.approx(20 / 30)

    def test_idle_gaps_over_span(self):
        # Busy [0,10] and [40,50] over span [0,50]: 30/50 idle.
        events = meta_events() + [
            op("mercury_scoring/a", 0, 10),
            op("mercury_optimizer/b", 40, 10),
        ]
        bd = attribute_device_time(events)
        assert bd["idle"]["span_us"] == pytest.approx(50.0)
        assert bd["idle"]["idle_us"] == pytest.approx(30.0)
        assert bd["idle"]["idle_frac"] == pytest.approx(0.6)

    def test_empty_capture_is_all_zeros_not_crash(self):
        bd = attribute_device_time([])
        assert bd["total_device_time_us"] == 0.0
        assert bd["attributed_frac"] == 0.0
        assert bd["counts"]["lane"] == "none"


class TestScopeFracMetrics:
    def test_registered_keys_only(self):
        from mercury_tpu.obs.registry import METRIC_KEYS

        bd = parse_profile(FIXTURE)
        metrics = scope_frac_metrics(bd)
        assert set(metrics) <= set(METRIC_KEYS)
        assert metrics["prof/scope_frac/mercury_scoring"] > 0.0
        assert "prof/h2d_overlap_frac" in metrics
        assert "prof/idle_frac" in metrics


def encode_varint(v):
    out = b""
    while True:
        b7 = v & 0x7F
        v >>= 7
        if v:
            out += bytes([b7 | 0x80])
        else:
            return out + bytes([b7])


def field(num, payload):
    if isinstance(payload, int):
        return encode_varint(num << 3) + encode_varint(payload)
    return encode_varint((num << 3) | 2) + encode_varint(len(payload)) \
        + payload


def encode_xplane_capture():
    """Hand-encode a one-plane xplane.pb on the profiler's stable field
    numbers: enough for the wire reader to reconstruct two named ops."""
    ev1 = field(1, 1) + field(2, 0) + field(3, 100_000_000)  # 100 us
    ev2 = field(1, 2) + field(2, 100_000_000) + field(3, 50_000_000)
    line = (field(2, b"XLA Ops") + field(3, 1_000_000)
            + field(4, ev1) + field(4, ev2))
    md1 = field(1, 1) + field(2, field(1, 1)
                                + field(2, b"mercury_scoring/dot.1"))
    md2 = field(1, 2) + field(2, field(1, 2)
                                + field(2, b"loop_fusion.9"))
    plane = (field(2, b"/device:TPU:0") + field(3, line)
             + field(4, md1) + field(4, md2))
    return field(1, plane)  # XSpace.planes


class TestXplaneWireReader:
    def test_decode_and_attribute(self, tmp_path):
        path = str(tmp_path / "host0.xplane.pb")
        with open(path, "wb") as f:
            f.write(encode_xplane_capture())
        events = load_xplane_events(path)
        assert [e["name"] for e in events] == [
            "mercury_scoring/dot.1", "loop_fusion.9"]
        # ps -> us conversion, line timestamp offset applied.
        assert events[0]["dur"] == pytest.approx(100.0)
        assert events[0]["ts"] == pytest.approx(1000.0)
        bd = attribute_device_time(events)
        assert bd["scopes"]["mercury_scoring"]["frac"] == pytest.approx(
            100 / 150)
        assert bd["scopes"][UNATTRIBUTED]["frac"] == pytest.approx(50 / 150)
        assert bd["attributed_frac"] == pytest.approx(1.0)

    def test_scope_is_read_from_the_tf_op_stat(self, tmp_path):
        """On a TPU capture the event name is the HLO text and the
        named-scope path is the metadata's ``tf_op`` string stat."""
        ev = field(1, 1) + field(2, 0) + field(3, 100_000_000)
        line = field(2, b"XLA Ops") + field(3, 0) + field(4, ev)
        tf_op = field(1, 7) + field(5, b"jit(f)/mercury_scoring/conv:")
        md = field(1, 1) + field(2, field(1, 1)
                                   + field(2, b"%fusion.3 = bf16[8] fusion()")
                                   + field(5, tf_op))
        plane = field(2, b"/device:TPU:0") + field(3, line) + field(4, md)
        path = str(tmp_path / "tpu.xplane.pb")
        with open(path, "wb") as f:
            f.write(field(1, plane))
        bd = attribute_device_time(load_xplane_events(path))
        assert bd["scopes"]["mercury_scoring"]["frac"] == pytest.approx(1.0)

    def test_display_name_fallback(self, tmp_path):
        line = field(11, b"XLA Ops") + field(3, 0)  # display_name only
        plane = field(2, b"/device:TPU:0") + field(3, line)
        path = str(tmp_path / "x.xplane.pb")
        with open(path, "wb") as f:
            f.write(field(1, plane))
        assert load_xplane_events(path) == []  # no events, but no crash


class TestLoadingAndDiscovery:
    def test_gzip_chrome_trace(self, tmp_path):
        doc = {"traceEvents": meta_events() + [op("mercury_scoring/x",
                                                  0, 10)]}
        path = str(tmp_path / "t.trace.json.gz")
        with gzip.open(path, "wt") as f:
            json.dump(doc, f)
        events = load_chrome_events(path)
        assert len(events) == 3
        bd = attribute_device_time(events)
        assert bd["total_device_time_us"] == pytest.approx(10.0)

    def test_bare_list_document(self, tmp_path):
        path = str(tmp_path / "trace.json")
        with open(path, "w") as f:
            json.dump([op("x", 0, 1)], f)
        assert len(load_chrome_events(path)) == 1

    def test_directory_discovery_prefers_chrome_and_newest(self, tmp_path):
        prof = tmp_path / "profile" / "plugins" / "profile" / "run1"
        prof.mkdir(parents=True)
        chrome = prof / "host0.trace.json.gz"
        with gzip.open(str(chrome), "wt") as f:
            json.dump({"traceEvents": []}, f)
        (prof / "host0.xplane.pb").write_bytes(encode_xplane_capture())
        found = discover_capture_files(str(tmp_path))
        assert found == [str(chrome)]  # chrome wins over xplane

    def test_load_events_from_directory(self, tmp_path):
        with open(str(tmp_path / "trace.json"), "w") as f:
            json.dump({"traceEvents": meta_events()
                       + [op("mercury_scoring/x", 0, 10)]}, f)
        events, source = load_events(str(tmp_path))
        assert len(events) == 3
        assert source.endswith("trace.json")

    def test_empty_directory_raises(self, tmp_path):
        with pytest.raises(FileNotFoundError):
            load_events(str(tmp_path))


class TestCli:
    def test_main_writes_breakdown(self, tmp_path, capsys):
        out = str(tmp_path / "bd.json")
        assert main([FIXTURE, "--out", out]) == 0
        bd = json.load(open(out))
        assert bd["schema"] == BREAKDOWN_SCHEMA
        assert bd["attributed_frac"] >= 0.95
        stdout = capsys.readouterr().out
        assert "mercury_scoring" in stdout

    def test_main_bad_capture_is_rc2(self, tmp_path, capsys):
        bad = str(tmp_path / "trace.json")
        with open(bad, "w") as f:
            f.write("{not json")
        assert main([bad, "--out", str(tmp_path / "o.json")]) == 2
        assert "error" in capsys.readouterr().err

    def test_write_breakdown_is_atomic_named(self, tmp_path):
        path = str(tmp_path / "sub" / "bd.json")
        write_breakdown({"schema": BREAKDOWN_SCHEMA}, path)
        assert json.load(open(path))["schema"] == BREAKDOWN_SCHEMA
        assert not os.path.exists(path + ".tmp")

    def test_cli_never_imports_jax(self, tmp_path):
        # The tentpole contract: offline attribution must run on a
        # jax-less analysis box.
        code = (
            "import sys\n"
            "from mercury_tpu.obs.profile_parse import main\n"
            f"rc = main([{FIXTURE!r}, '--out', "
            f"{str(tmp_path / 'bd.json')!r}])\n"
            "assert rc == 0, rc\n"
            "assert 'jax' not in sys.modules, 'jax was imported'\n"
        )
        r = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True, timeout=120,
                           cwd=os.path.dirname(os.path.dirname(
                               os.path.abspath(__file__))))
        assert r.returncode == 0, r.stderr
