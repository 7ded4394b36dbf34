"""One timeline (PR 25): the program's host spans enter any open
``jax.profiler`` capture on the profiler's clock, every span names the span
that caused it and the ``fit()`` call and step it belongs to, all of
``fit()`` lies under a root span, and the lowered step names the scopes the
device trace splits it by."""

import glob
import os
import re
import threading

import jax
import jax.numpy as jnp
import pytest

from mercury_tpu.config import TrainConfig
from mercury_tpu.obs.trace import NULL_TRACER, SpanTracer
from mercury_tpu.parallel.mesh import host_cpu_mesh
from mercury_tpu.train.trainer import Trainer
from perfbench.trace_reduce import load_events


def _capture(tmp_path, body):
    """Run ``body`` under a capture with the benchmark's profiler options;
    return the events of the capture's host lanes."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (pb,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    events, _ = load_events(pb)
    return [e for e in events if e["_pname"].startswith("/host:")]


def _inside(inner, outer):
    return (outer["ts"] <= inner["ts"]
            and inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"])


# ------------------------------------------------- spans in the capture
def test_spans_come_back_from_an_open_capture(tmp_path):
    tr = SpanTracer(capacity=16)
    double = jax.jit(lambda x: x * 2)
    x = jnp.ones((8, 8))

    def body():
        with tr.call_span("trainer/fit"):
            for step in (7, 8):
                with tr.step_span("trainer/dispatch", step):
                    y = double(x)
            with tr.span("trainer/eval", closing=True):
                float(y.sum())
            tr.instant("profiler/stop")

    host = _capture(tmp_path, body)
    by_name = {}
    for e in host:
        by_name.setdefault(e["name"], []).append(e)
    assert len(by_name["trainer/fit"]) == 1
    assert len(by_name["trainer/dispatch"]) == 2
    assert len(by_name["trainer/eval"]) == 1
    assert len(by_name["profiler/stop"]) == 1
    (fit,) = by_name["trainer/fit"]
    for child in by_name["trainer/dispatch"] + by_name["trainer/eval"]:
        assert _inside(child, fit)
    # one file, one clock: the runtime's own launch of the jitted function
    # lies inside the dispatch span that issued it
    launches = [e for e in host if e["name"] == "PjitFunction(<lambda>)"]
    assert launches and all(
        any(_inside(e, d) for d in by_name["trainer/dispatch"])
        for e in launches)
    # ... and the tracer's own record has the same spans, with the same
    # durations to within the annotation's own cost
    mine = {e["name"]: e for e in tr.snapshot() if e["ph"] == "X"}
    assert abs(mine["trainer/fit"]["dur"] - fit["dur"]) < 2000.0


def test_null_tracer_emits_nothing_into_a_capture(tmp_path):
    def body():
        with NULL_TRACER.call_span("trainer/fit"):
            with NULL_TRACER.step_span("trainer/dispatch", 3):
                with NULL_TRACER.span("trainer/eval"):
                    NULL_TRACER.instant("profiler/stop")

    names = {e["name"] for e in _capture(tmp_path, body)}
    assert not {n for n in names if "/" in n and n.split("/")[0] in (
        "trainer", "eval", "profiler")}
    assert NULL_TRACER.snapshot() == []


def test_no_capture_open_still_records():
    tr = SpanTracer(capacity=4)
    with tr.span("trainer/flush"):
        pass
    (ev,) = tr.snapshot()
    assert ev["name"] == "trainer/flush" and ev["args"] == {"id": 1}


# ------------------------------------------------ parent / call / step
def test_parent_call_step_of_nested_spans():
    tr = SpanTracer(capacity=32)
    with tr.span("before/any_call"):
        pass
    for _ in range(2):
        with tr.call_span("trainer/fit"):
            with tr.step_span("trainer/dispatch", 40, steps=2):
                pass
            with tr.span("trainer/eval", step=42, closing=True):
                with tr.span("eval/fetch", cat="eval", split="train"):
                    pass
                tr.instant("anomaly/x")
    ev = {(e["name"], e["args"].get("call")): e["args"]
          for e in tr.snapshot()}
    assert ev[("before/any_call", None)] == {"id": 1}
    fit1, fit2 = ev[("trainer/fit", 1)], ev[("trainer/fit", 2)]
    assert "parent" not in fit1 and "parent" not in fit2
    assert "step" not in fit1          # nothing dispatched yet
    assert fit2["step"] == 40          # the step the first call left at
    for call, fit in ((1, fit1), (2, fit2)):
        dispatch = ev[("trainer/dispatch", call)]
        assert dispatch["parent"] == fit["id"]
        assert dispatch["step"] == 40 and dispatch["steps"] == 2
        evaluate = ev[("trainer/eval", call)]
        assert evaluate["parent"] == fit["id"]
        assert evaluate["step"] == 42  # the call site's own step= wins
        assert evaluate["closing"] is True
        fetch = ev[("eval/fetch", call)]
        assert fetch["parent"] == evaluate["id"] and fetch["step"] == 40
        assert fetch["split"] == "train"
        assert ev[("anomaly/x", call)]["parent"] == evaluate["id"]
    ids = [e["args"]["id"] for e in tr.snapshot()]
    assert len(ids) == len(set(ids))


def test_spans_of_other_threads_carry_the_step_and_their_own_parent():
    tr = SpanTracer(capacity=32)
    go, done = threading.Event(), threading.Event()

    def worker():
        go.wait(10)
        with tr.span("stream/gather", cat="stream"):
            with tr.span("stream/h2d", cat="stream"):
                pass
        done.set()

    t = threading.Thread(target=worker)
    t.start()
    with tr.call_span("trainer/fit"):
        with tr.step_span("trainer/dispatch", 11):
            go.set()
            assert done.wait(10)
    t.join(10)
    assert not t.is_alive()
    ev = {e["name"]: e for e in tr.snapshot()}
    gather, h2d = ev["stream/gather"], ev["stream/h2d"]
    assert gather["tid"] != ev["trainer/fit"]["tid"]
    # the train thread's open spans are not this thread's parents
    assert "parent" not in gather["args"]
    assert h2d["args"]["parent"] == gather["args"]["id"]
    for e in (gather, h2d):
        assert e["args"]["call"] == 1 and e["args"]["step"] == 11


def test_a_span_that_raises_still_closes_its_level():
    tr = SpanTracer(capacity=8)
    with pytest.raises(RuntimeError):
        with tr.span("outer"):
            with tr.span("inner"):
                raise RuntimeError("boom")
    with tr.span("after"):
        pass
    ev = {e["name"]: e["args"] for e in tr.snapshot()}
    assert ev["inner"]["parent"] == ev["outer"]["id"]
    assert "parent" not in ev["after"]


# ----------------------------------------------------- fit() under spans
def _tiny(**kw):
    base = dict(model="smallcnn", dataset="synthetic", world_size=1,
                batch_size=4, presample_batches=2, steps_per_epoch=1,
                num_epochs=6, eval_every=0, log_every=4,
                heartbeat_every=0, compute_dtype="float32", seed=0)
    base.update(kw)
    return TrainConfig(**base)


@pytest.fixture(scope="module")
def traced_events(tmp_path_factory):
    """Two ``fit()`` calls of a tiny traced trainer; the tracer's record."""
    tr = Trainer(_tiny(trace=True, use_importance_sampling=True,
                       checkpoint_dir=str(tmp_path_factory.mktemp("ckpt"))),
                 mesh=host_cpu_mesh(1))
    try:
        tr.fit()
        tr.fit(num_epochs=5)
        return tr.tracer.snapshot()
    finally:
        tr.close()


@pytest.fixture(scope="module")
def traced_fit(traced_events):
    """The spans of those two calls."""
    return [e for e in traced_events if e["ph"] == "X"]


def test_each_fit_reports_its_bn_moment_units(traced_events, traced_fit):
    """Once a call, under its root span: how many conv+BN units of the
    traced step's scoring forward take their statistic from input moments
    (smallcnn has no ``Bottleneck``; the ResNet-50 fit below counts 16)."""
    fits = [e for e in traced_fit if e["name"] == "trainer/fit"]
    marks = [e for e in traced_events
             if e["name"] == "trainer/bn_moment_units"]
    assert [e["args"]["units"] for e in marks] == [0, 0]
    assert [e["args"]["parent"] for e in marks] == [
        e["args"]["id"] for e in fits]


def test_an_image_models_fit_reports_no_operand_sites(traced_events):
    """``trainer/rope_kernel_sites`` is a decoder's: once a call all the
    same, with no operand of an attention made either way."""
    marks = [e["args"] for e in traced_events
             if e["name"] == "trainer/rope_kernel_sites"]
    assert [(m["sites"], m["plain_sites"]) for m in marks] == [(0, 0)] * 2


@pytest.mark.parametrize("model, counted", [("resnet50", 16), ("resnet18", 0)])
def test_bn_moment_units_are_the_traced_steps_count(model, counted,
                                                    monkeypatch):
    """A ``Bottleneck`` model's ``fit()`` reports what its step counted as
    it was traced: every closing unit of ResNet-50's scoring forward, none
    for ResNet-18's ``BasicBlock``s. The step is traced, not run (XLA:CPU
    would compile it for minutes), and the call's loop is empty."""
    config = TrainConfig(
        model=model, dataset="synthetic", world_size=1, batch_size=4,
        presample_batches=2, log_every=0, eval_every=0, heartbeat_every=0,
        use_importance_sampling=True, trace=True)
    with Trainer(config, mesh=host_cpu_mesh(1)) as tr:
        jax.eval_shape(tr.train_step, tr.state, tr._step_x, tr._step_y,
                       tr.dataset.shard_indices)
        monkeypatch.setattr(tr, "_fit", lambda num_epochs: {})
        tr.fit()
        (mark,) = [e for e in tr.tracer.snapshot()
                   if e["name"] == "trainer/bn_moment_units"]
    assert mark["args"]["units"] == counted


def test_fit_has_a_root_span_per_call(traced_fit):
    fits = [e for e in traced_fit if e["name"] == "trainer/fit"]
    assert [e["args"]["call"] for e in fits] == [1, 2]
    assert all("parent" not in e["args"] for e in fits)
    # every other span of the train thread lies under one of them
    ids = {e["args"]["id"] for e in traced_fit}
    for e in traced_fit:
        if e["name"] != "trainer/fit" and e["tid"] == fits[0]["tid"]:
            assert e["args"]["parent"] in ids, e["name"]


@pytest.mark.parametrize("call, steps, first_step", [(1, 6, 0), (2, 5, 6)])
def test_fit_is_covered_by_its_children(traced_fit, call, steps, first_step):
    (fit,) = [e for e in traced_fit if e["name"] == "trainer/fit"
              and e["args"]["call"] == call]
    children = sorted((e for e in traced_fit
                       if e["args"].get("parent") == fit["args"]["id"]),
                      key=lambda e: e["ts"])
    names = [e["name"] for e in children]
    dispatched = [e["args"]["step"] for e in children
                  if e["name"] == "trainer/dispatch"]
    assert dispatched == list(range(first_step, first_step + steps))
    assert names.count("trainer/log_gate") == 1  # steps 4 and 8
    # the step is traced a second time at the first log gate only
    assert names.count("trainer/flops_probe") == (1 if call == 1 else 0)
    assert names[-3:] == ["trainer/flush", "trainer/eval",
                          "trainer/final_checkpoint"]
    assert children[-2]["args"]["closing"] is True
    # Children lie inside the root and do not overlap, so the root's
    # duration is theirs plus its self time: the time between them.
    end = fit["ts"] + fit["dur"]
    between, at = 0.0, fit["ts"]
    for e in children:
        assert at - 1e-3 <= e["ts"] and e["ts"] + e["dur"] <= end + 1e-3
        between += e["ts"] - at
        at = e["ts"] + e["dur"]
    between += end - at
    self_time = fit["dur"] - sum(e["dur"] for e in children)
    assert self_time >= 0
    assert abs(between - self_time) <= 0.01 * fit["dur"]


def test_evaluate_names_its_dispatch_and_its_fence(traced_fit):
    evals = [e for e in traced_fit if e["name"] == "trainer/eval"]
    assert len(evals) == 2
    for ev in evals:
        inner = sorted((e for e in traced_fit
                        if e["args"].get("parent") == ev["args"]["id"]),
                       key=lambda e: e["ts"])
        assert [(e["name"], e["args"]["split"]) for e in inner] == [
            ("eval/dispatch", "train"), ("eval/fetch", "train"),
            ("eval/dispatch", "test"), ("eval/fetch", "test")]
        assert all(e["cat"] == "eval" and _inside(e, ev) for e in inner)


def test_tracing_off_runs_fit_with_the_null_tracer():
    tr = Trainer(_tiny(num_epochs=2, log_every=0), mesh=host_cpu_mesh(1))
    try:
        assert tr.tracer is NULL_TRACER
        out = tr.fit()
        assert set(out) >= {"train/eval_loss", "test/eval_loss"}
    finally:
        tr.close()


# ---------------------------------------------- scopes in the lowered step
SCORING_CHILDREN = ("mercury_pool_ingest", "mercury_score_forward",
                    "mercury_score_loss")


@pytest.mark.parametrize("fields", [
    dict(),                                                # body, inline
    dict(pipelined_scoring=True),                          # body, pipelined
    dict(data_placement="host_stream", prefetch_depth=2),  # hs_body
], ids=["inline", "pipelined", "host_stream"])
def test_lowered_step_names_the_layer_scopes(fields):
    tr = Trainer(_tiny(use_importance_sampling=True, log_every=0, **fields),
                 mesh=host_cpu_mesh(1))
    try:
        x = (tr._stream_pipe.pop() if "data_placement" in fields
             else tr._step_x)
        text = tr.train_step.lower(
            tr.state, x, tr._step_y, tr.dataset.shard_indices
        ).as_text(debug_info=True)
    finally:
        tr.close()
    paths = set(re.findall(r'loc\("([^"]*mercury_[^"]*)"', text))
    for scope in SCORING_CHILDREN:
        under = [p for p in paths if scope in p]
        assert under, scope
        # nested inside mercury_scoring, never around it
        assert all(p.index("mercury_scoring/") < p.index(scope)
                   for p in under), scope
    # the existing augmentation scope stays inside the pool's ingest
    assert any("mercury_pool_ingest/mercury_augmentation" in p
               for p in paths)
    for scope in ("mercury_draw", "mercury_train"):
        under = [p for p in paths if scope in p]
        assert under, scope
        assert not any("mercury_scoring" in p for p in under), scope
    train = [p for p in paths if "mercury_train" in p]
    assert any("transpose(" in p for p in train)       # the backward pass
    assert any("transpose(" not in p for p in train)   # the forward pass
    # no scope is nested in another of the partition
    leaves = SCORING_CHILDREN + ("mercury_draw", "mercury_train",
                                 "mercury_optimizer", "mercury_grad_sync")
    assert not [p for p in paths if sum(s in p for s in leaves) > 1]
