"""The reducers of PR 25 off the chip: the leaf scopes that split the step,
the self time of the program's root span, and the device's idle time laid
against the program's spans on the capture's host lane — on a hand-built
capture with known answers, on a capture of a program that has none of
this, and on the tiny traced rehearsal. Nothing here is a device
measurement."""

import json

import pytest

from perfbench import cell as cell_mod
from perfbench import run, trace_reduce
from perfbench.reducers import leaf_scope_share, timeline
from test_perfbench import CELLS, MANIFEST, RESULT_KEYS, _last_line, _tiny

NEW = ["score_ingest_share", "score_forward_share", "score_loss_share",
       "draw_share", "train_forward_share", "train_backward_share",
       "dispatch_ms_per_step", "host_loop_self_ms_per_step",
       "idle_in_eval_ms_per_step", "idle_in_dispatch_ms_per_step",
       "idle_in_loop_ms_per_step", "idle_unattributed_share"]
IDLE = [n for n in NEW if n.startswith("idle_")]
STEP = "jit_step"

#: One step program's ops: (scope path, µs). 1,000 µs a step.
STEP_OPS = [
    ("mercury_scoring/mercury_pool_ingest/gather", 40),
    ("mercury_scoring/mercury_pool_ingest/mercury_augmentation/select", 60),
    ("mercury_scoring/mercury_score_forward/M/conv", 600),
    ("mercury_scoring/mercury_score_loss/mercury_nll_kernel", 20),
    ("mercury_draw/mercury_score_draw_kernel", 30),
    ("mercury_train/jvp(M)/conv", 70),
    ("mercury_train/jvp(M)/transpose", 10),       # an op, not the pass
    ("mercury_train/transpose(jvp(M))/conv", 150),
    ("mercury_train/transpose(mercury_train)/jvp(M)/mul", 10),
    ("mercury_optimizer/adam", 10),
]
WANT_SHARES = {"score_ingest_share": 10.0, "score_forward_share": 60.0,
               "score_loss_share": 2.0, "draw_share": 3.0,
               "train_forward_share": 8.0, "train_backward_share": 16.0}

#: Device: steps at 0, 1,200 and 3,600 µs, the eval program at 2,300-3,000;
#: so three gaps: 1,000-1,200, 2,200-2,300 and 3,000-3,600 (900 µs).
STEPS_AT = (0, 1200, 3600)
EVAL_RUN = (2300, 3000)
#: Host: two fit() calls with the harness's loop between them.
HOST = [("trainer/fit", -100, 3100), ("trainer/dispatch", -50, 20),
        ("trainer/dispatch", 1000, 1150), ("trainer/eval", 2150, 3050),
        ("eval/fetch", 2160, 2310), ("trainer/fit", 3400, 5000),
        ("trainer/dispatch", 3450, 3620), ("PjitFunction(step)", 3460, 3600)]
#: ... which puts the 900 µs of idle time: in eval 100 + 50, in dispatch
#: 150 + 150, in the loop 50 + 50 + 50, under no fit() 300.
WANT_IDLE_US = {"eval": 150.0, "dispatch": 300.0, "loop": 150.0,
                "unattributed": 300.0, "total": 900.0}


def _x(pid, tid, name, start, end, **args):
    return {"ph": "X", "pid": pid, "tid": tid, "name": name, "ts": start,
            "dur": end - start, "args": args}


def _capture_events(step_ops=STEP_OPS, host=HOST):
    events = [
        {"ph": "M", "name": "process_name", "pid": 1,
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "name": "process_name", "pid": 2,
         "args": {"name": "/host:CPU"}},
        {"ph": "M", "name": "thread_name", "pid": 1, "tid": 1,
         "args": {"name": "XLA Ops"}},
        {"ph": "M", "name": "thread_name", "pid": 1, "tid": 2,
         "args": {"name": "XLA Modules"}},
        {"ph": "M", "name": "thread_name", "pid": 2, "tid": 1,
         "args": {"name": "python"}},
    ]
    for t0 in STEPS_AT:
        events.append(_x(1, 2, f"{STEP}(123)", t0, t0 + 1000))
        at = t0
        for k, (path, us) in enumerate(step_ops):
            events.append(_x(1, 1, f"fusion.{k}", at, at + us,
                             tf_op=f"jit(step)/jit(main)/{path}"))
            at += us
    events.append(_x(1, 2, "jit_eval_epoch(7)", *EVAL_RUN))
    events.append(_x(1, 1, "while.1", *EVAL_RUN, tf_op="jit(eval_epoch)/w"))
    events += [_x(2, 1, name, a, b) for name, a, b in host]
    return events


def _ctx(tmp_path, monkeypatch, events):
    """The harness's ``ctx`` over a capture written where the reducers look
    for it."""
    path = tmp_path / "hand.trace.json"
    path.write_text(json.dumps({"traceEvents": events}))
    monkeypatch.setattr(timeline, "TRACE_DIR", str(tmp_path))
    loaded, _ = trace_reduce.load_events(str(path))
    return dict(capture=trace_reduce.Capture(loaded, STEP), steps=3,
                spans=[], programs={"step": STEP})


def _metric(ctx, name):
    spec = cell_mod.layer_metric(name)
    return cell_mod.reducer(spec["reducer"])(ctx, **spec.get("args", {}))


# ------------------------------------------------------ the leaf scopes
@pytest.mark.parametrize("name", sorted(WANT_SHARES))
def test_leaf_scope_shares_of_a_hand_built_step(tmp_path, monkeypatch, name):
    ctx = _ctx(tmp_path, monkeypatch, _capture_events())
    assert _metric(ctx, name) == pytest.approx(WANT_SHARES[name])


def test_leaf_scopes_and_the_optimizer_partition_the_step(tmp_path,
                                                          monkeypatch):
    ctx = _ctx(tmp_path, monkeypatch, _capture_events())
    parts = [_metric(ctx, n) for n in WANT_SHARES]
    parts.append(_metric(ctx, "optimizer_share"))
    assert sum(parts) == pytest.approx(100.0)
    # the accepted reducer reads the outer scope as before: what is nested
    # in mercury_scoring stays there, the draw and the train pass do not
    assert _metric(ctx, "scoring_share") == pytest.approx(
        sum(_metric(ctx, n) for n in ("score_ingest_share",
                                      "score_forward_share",
                                      "score_loss_share")))
    assert _metric(ctx, "device_ms_per_step") == pytest.approx(1.0)


@pytest.mark.parametrize("text, want", [
    ("jit(s)/mercury_scoring/mercury_score_forward/m/conv",
     "mercury_score_forward"),
    ("jit(s)/mercury_scoring/mercury_pool_ingest/mercury_augmentation/x",
     "mercury_pool_ingest"),
    ("jit(s)/mercury_train/mercury_optimizer/x", "mercury_optimizer"),
    ("jit(s)/mercury_optimizer/x /root/repo/mercury_tpu/train/step.py",
     "mercury_optimizer"),
    ("jit(s)/mercury_scoring/gather", None),
    ("fusion.12", None),
])
def test_innermost_leaf_scope_wins(text, want):
    assert leaf_scope_share.leaf_of(text) == want


# ------------------------------------------- idle time against host spans
def test_idle_gaps_are_step_idles_own(tmp_path, monkeypatch):
    ctx = _ctx(tmp_path, monkeypatch, _capture_events())
    gaps = timeline.idle_gaps(ctx["capture"])
    assert gaps == [(1000.0, 1200.0), (2200.0, 2300.0), (3000.0, 3600.0)]
    idle = ctx["capture"].step_idle()
    assert sorted(b - a for a, b in gaps) == sorted(
        us for _, us in idle["gaps"])
    assert sum(b - a for a, b in gaps) == pytest.approx(
        idle["span_us"] - idle["busy_us"])


def test_idle_time_by_host_span_exact(tmp_path, monkeypatch):
    ctx = _ctx(tmp_path, monkeypatch, _capture_events())
    assert timeline.idle_partition(ctx) == pytest.approx(WANT_IDLE_US)
    assert _metric(ctx, "idle_in_eval_ms_per_step") == pytest.approx(0.05)
    assert _metric(ctx, "idle_in_dispatch_ms_per_step") == pytest.approx(0.1)
    assert _metric(ctx, "idle_in_loop_ms_per_step") == pytest.approx(0.05)
    assert _metric(ctx, "idle_unattributed_share") == pytest.approx(
        100.0 / 3.0)


def test_the_four_idle_metrics_sum_to_the_idle_time(tmp_path, monkeypatch):
    ctx = _ctx(tmp_path, monkeypatch, _capture_events())
    idle = ctx["capture"].step_idle()
    idle_ms = (idle["span_us"] - idle["busy_us"]) / 1e3
    named = sum(_metric(ctx, n) * ctx["steps"] for n in IDLE
                if n.endswith("_ms_per_step"))
    rest = _metric(ctx, "idle_unattributed_share") / 100.0 * idle_ms
    assert named + rest == pytest.approx(idle_ms)
    assert idle_ms / (idle["span_us"] / 1e3) == pytest.approx(
        _metric(ctx, "device_idle_share") / 100.0)


def test_the_capture_is_read_once_for_all_idle_metrics(tmp_path,
                                                       monkeypatch):
    ctx = _ctx(tmp_path, monkeypatch, _capture_events())
    reads = []
    real = trace_reduce.load_events
    monkeypatch.setattr(trace_reduce, "load_events",
                        lambda p: reads.append(p) or real(p))
    for name in IDLE:
        assert _metric(ctx, name) is not None
    assert len(reads) == 1


# ----------------------------- a program with none of this (the parent)
def test_a_program_without_the_spans_and_scopes_reports_none(tmp_path,
                                                             monkeypatch):
    """A commit before PR 25 under this benchmark: its capture has the
    runtime's host events only, its step's ops the outer scopes only, its
    tracer's spans no ``id``. Every new reader finds nothing, raises
    nothing, and the accepted metrics read as they did."""
    old_ops = [("mercury_scoring/M/conv", 700),
               ("mercury_scoring/mercury_augmentation/select", 20),
               ("jvp(M)/conv", 100), ("transpose(jvp(M))/conv", 170),
               ("mercury_optimizer/adam", 10)]
    events = _capture_events(old_ops, [h for h in HOST
                                       if h[0].startswith("Pjit")])
    ctx = _ctx(tmp_path, monkeypatch, events)
    ctx["spans"] = [
        {"name": "trainer/dispatch", "ph": "X", "ts": 0.0, "dur": 900.0},
        {"name": "trainer/log_gate", "ph": "X", "ts": 950.0, "dur": 30.0,
         "args": {"step": 100}}]
    got = {n: _metric(ctx, n) for n in NEW}
    assert got.pop("dispatch_ms_per_step") == pytest.approx(0.3)
    assert set(got.values()) == {None}
    assert _metric(ctx, "scoring_share") == pytest.approx(72.0)
    assert _metric(ctx, "optimizer_share") == pytest.approx(1.0)
    assert 0 < _metric(ctx, "device_idle_share") < 100


def test_no_capture_on_disk_reports_none(tmp_path, monkeypatch):
    monkeypatch.setattr(timeline, "TRACE_DIR", str(tmp_path / "absent"))
    ctx = dict(capture=trace_reduce.Capture(_capture_events(), STEP),
               steps=3, spans=[])
    assert [_metric(ctx, n) for n in IDLE] == [None] * 4
    empty = dict(ctx, capture=trace_reduce.Capture([], STEP))
    assert [_metric(empty, n) for n in NEW] == [None] * len(NEW)


# ------------------------------------------------ the root span's self time
def test_self_time_is_duration_minus_children():
    spans = [
        {"name": "trainer/dispatch", "ph": "X", "dur": 600.0,
         "args": {"id": 2, "parent": 1, "call": 1}},
        {"name": "eval/fetch", "ph": "X", "dur": 250.0,   # a grandchild
         "args": {"id": 4, "parent": 3, "call": 1}},
        {"name": "trainer/eval", "ph": "X", "dur": 300.0,
         "args": {"id": 3, "parent": 1, "call": 1}},
        {"name": "trainer/fit", "ph": "X", "dur": 1000.0,
         "args": {"id": 1, "call": 1}},
        {"name": "stream/gather", "ph": "X", "dur": 5000.0,  # other thread
         "args": {"id": 5, "call": 1}},
        {"name": "trainer/fit", "ph": "X", "dur": 500.0,
         "args": {"id": 6, "call": 2}},
        {"name": "anomaly/x", "ph": "i", "args": {"id": 7, "parent": 6}},
    ]
    ctx = dict(spans=spans, steps=10)
    assert _metric(ctx, "host_loop_self_ms_per_step") == pytest.approx(
        (100.0 + 500.0) / 1e3 / 10)
    assert _metric(ctx, "dispatch_ms_per_step") == pytest.approx(0.06)
    assert _metric(dict(ctx, steps=0), "host_loop_self_ms_per_step") is None


# ------------------------------------------------------- the manifest
def test_new_metrics_are_entries_added_in_their_order():
    """PR 25's twelve stand together, in their order, where they were
    added, and list the cell they were added for (the first: the others
    came later, and until a ``benchmark`` PR proves these readings there
    they report nothing in them); entries of later PRs follow."""
    names = [m["name"] for m in MANIFEST["per_layer"]]
    at = names.index(NEW[0])
    assert names[at:at + len(NEW)] == NEW
    for m in MANIFEST["per_layer"][at:at + len(NEW)]:
        assert m["workloads"] == CELLS[:1]
        assert m["moves"] == "train_examples_per_s"


# --------------------------------------------- the tiny traced rehearsal
def test_traced_rehearsal_reports_the_host_side_metrics(capsys, tmp_path,
                                                        monkeypatch):
    """``--trace 1`` of the whole command at tiny size on the CPU: the
    program's spans are in the capture and in the tracer, so the two
    host-side metrics are reported; the CPU has no device lanes, so every
    metric that reads them is left out."""
    for module in (run, timeline):
        monkeypatch.setattr(module, "TRACE_DIR", str(tmp_path / "_trace"))
    run.run_cell(CELLS[0], 2 ** 31 + 11, 0.5, True, rehearsal=_tiny())
    line = _last_line(capsys)
    assert set(line) == RESULT_KEYS | {"breakdown"}
    assert line["correct"] is True and line["attempted"] == 20
    got = set(line["metrics"])
    assert {"dispatch_ms_per_step", "host_loop_self_ms_per_step",
            "log_gate_ms_per_step", "compiles_in_window"} <= got
    assert not got & (set(NEW) - {"dispatch_ms_per_step",
                                  "host_loop_self_ms_per_step"})
    assert line["metrics"]["dispatch_ms_per_step"]["value"] > 0
    assert line["metrics"]["host_loop_self_ms_per_step"]["value"] > 0
    # the capture itself holds the program's spans, on its host lane
    spans = timeline._read_host_spans(str(tmp_path / "_trace"))
    assert set(spans) == {"trainer/fit", "trainer/eval", "trainer/dispatch"}
    fits, evals = spans["trainer/fit"], spans["trainer/eval"]
    assert len(fits) == 2 and len(evals) == 2 and all(
        f[0] <= e[0] and e[1] <= f[1] for f, e in zip(fits, evals))
