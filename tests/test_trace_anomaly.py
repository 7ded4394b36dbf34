"""Observability layer 2 tests: the host span tracer's ring/export
contract, the anomaly engine's five triggers + debounce + flight-record
dumps, and one end-to-end trainer run with an injected NaN (the CI smoke
in test form: fault in → flight record + perfetto trace out).

The tracer/engine tests are pure host code — records and step times are
synthesized, so every trigger path is exercised deterministically with
no model and no timing dependence.
"""

import glob
import json
import math
import os
import threading
import time

import numpy as np
import pytest

from mercury_tpu.config import TrainConfig
from mercury_tpu.obs.anomaly import (
    FLIGHT_RECORD_SCHEMA,
    AnomalyEngine,
    device_memory_stats,
)
from mercury_tpu.obs.trace import NULL_TRACER, NullTracer, SpanTracer


class TestSpanTracer:
    def test_span_is_complete_event_with_args(self):
        tr = SpanTracer(capacity=16)
        with tr.span("trainer/dispatch", cat="trainer", steps=4):
            time.sleep(0.002)
        (ev,) = tr.snapshot()
        assert ev["name"] == "trainer/dispatch"
        assert ev["cat"] == "trainer"
        assert ev["ph"] == "X"
        assert ev["dur"] >= 1000.0  # µs — the 2 ms body, minus clock slop
        assert ev["ts"] >= 0.0  # µs since tracer epoch
        assert ev["args"] == {"id": 1, "steps": 4}  # a root, no fit() call
        assert ev["pid"] == os.getpid()
        assert ev["tid"] == threading.get_ident()

    def test_instant_event_is_thread_scoped_marker(self):
        tr = SpanTracer(capacity=4)
        tr.instant("anomaly/non_finite", cat="anomaly", step=7)
        (ev,) = tr.snapshot()
        assert ev["ph"] == "i"
        assert ev["s"] == "t"
        assert "dur" not in ev
        assert ev["args"] == {"id": 1, "step": 7}

    def test_ring_keeps_last_capacity_and_counts_dropped(self):
        tr = SpanTracer(capacity=8)
        for i in range(20):
            with tr.span(f"s{i}", cat="bench"):
                pass
        events = tr.snapshot()
        assert len(events) == 8
        assert tr.dropped == 12
        assert [e["name"] for e in events] == [f"s{i}" for i in range(12, 20)]

    def test_span_records_even_when_body_raises(self):
        tr = SpanTracer(capacity=4)
        with pytest.raises(RuntimeError):
            with tr.span("trainer/eval"):
                raise RuntimeError("mid-span death")
        assert [e["name"] for e in tr.snapshot()] == ["trainer/eval"]

    def test_chrome_trace_document_shape(self):
        tr = SpanTracer(capacity=16)
        tr.register_thread("train")
        with tr.span("trainer/dispatch"):
            pass
        doc = tr.chrome_trace()
        assert doc["displayTimeUnit"] == "ms"
        other = doc["otherData"]
        assert other["span_capacity"] == 16
        assert other["spans_recorded"] == 1
        assert other["spans_dropped"] == 0
        assert other["epoch_unix_s"] > 0
        metas = [e for e in doc["traceEvents"] if e.get("ph") == "M"]
        assert metas and metas[0]["name"] == "thread_name"
        assert metas[0]["args"] == {"name": "train"}

    def test_export_creates_dirs_and_loads_as_json(self, tmp_path):
        tr = SpanTracer(capacity=4)
        with tr.span("stream/h2d", cat="stream", bytes=1024):
            pass
        path = tr.export_chrome_trace(str(tmp_path / "sub" / "trace.json"))
        doc = json.load(open(path))
        assert any(e["name"] == "stream/h2d" and e["ph"] == "X"
                   for e in doc["traceEvents"])
        assert not os.path.exists(path + ".tmp")  # atomic replace, no litter

    def test_threads_interleave_without_loss(self):
        tr = SpanTracer(capacity=4096)

        def worker():
            for _ in range(500):
                with tr.span("w", cat="bench"):
                    pass

        threads = [threading.Thread(target=worker) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(tr.snapshot()) + tr.dropped == 2000

    def test_capacity_validated(self):
        with pytest.raises(ValueError):
            SpanTracer(capacity=0)

    def test_null_tracer_is_free_surface(self):
        assert NULL_TRACER.enabled is False
        assert isinstance(NULL_TRACER, NullTracer)
        # The disabled span is one shared object — no per-call allocation.
        assert NULL_TRACER.span("a") is NULL_TRACER.span("b", cat="x", k=1)
        with NULL_TRACER.span("trainer/dispatch"):
            pass
        NULL_TRACER.instant("x")
        NULL_TRACER.register_thread("train")
        assert NULL_TRACER.snapshot() == []
        assert NULL_TRACER.export_chrome_trace("/nonexistent/t.json") is None


def record(step, loss=1.0, **extra):
    """A minimal host metric record as the drain thread sees it."""
    r = {"step": float(step), "time": 1000.0 + step, "train/loss": loss}
    r.update(extra)
    return r


class TestAnomalyEngine:
    def test_non_finite_loss_dumps_flight_record(self, tmp_path):
        eng = AnomalyEngine(ring_steps=4, dump_dir=str(tmp_path))
        for s in range(1, 4):
            eng.observe_record(record(s))
        bad = record(4, loss=float("nan"))
        eng.observe_record(bad)
        assert eng.triggers == 1
        assert eng.trigger_counts == {"non_finite": 1}
        assert bad["anomaly/triggers"] == 1.0
        (path,) = eng.dumps
        assert os.path.basename(path) == "flight_record_step4_non_finite.json"
        doc = json.load(open(path))
        assert doc["schema"] == FLIGHT_RECORD_SCHEMA
        assert doc["trigger"]["kind"] == "non_finite"
        assert doc["trigger"]["step"] == 4
        assert doc["trigger"]["detail"]["key"] == "train/loss"
        assert [int(r["step"]) for r in doc["ring"]] == [1, 2, 3, 4]
        assert doc["triggers_total"] == 1
        assert isinstance(doc["device_memory"], dict)

    def test_inf_grad_norm_triggers(self):
        eng = AnomalyEngine(ring_steps=4)
        eng.observe_record(record(1, **{"train/grad_norm": float("inf")}))
        assert eng.trigger_counts == {"non_finite": 1}

    def test_ring_is_last_n_records(self):
        eng = AnomalyEngine(ring_steps=4)
        for s in range(1, 11):
            eng.observe_record(record(s))
        assert [int(r["step"]) for r in eng.ring] == [7, 8, 9, 10]

    def test_ess_collapse_gated_on_floor(self):
        hot = AnomalyEngine(ring_steps=4, ess_floor=0.5)
        hot.observe_record(record(1, **{"sampler/ess": 0.4}))
        assert hot.trigger_counts == {"ess_collapse": 1}
        cold = AnomalyEngine(ring_steps=4, ess_floor=0.0)
        cold.observe_record(record(1, **{"sampler/ess": 0.01}))
        assert cold.triggers == 0

    def test_stall_breach_needs_interval_and_budget(self):
        eng = AnomalyEngine(ring_steps=8, stall_frac_max=0.25)
        # First record: no previous timestamp, never judged.
        eng.observe_record({"step": 1.0, "time": 100.0,
                            "data/stall_s": 99.0})
        assert eng.triggers == 0
        # 0.5 s stall over a 4 s interval = 12.5% — inside budget.
        eng.observe_record({"step": 2.0, "time": 104.0,
                            "data/stall_s": 0.5})
        assert eng.triggers == 0
        # 2 s over 4 s = 50% — breach.
        eng.observe_record({"step": 3.0, "time": 108.0,
                            "data/stall_s": 2.0})
        assert eng.trigger_counts == {"stall_breach": 1}

    def test_mfu_floor_ignores_unknown_peak(self):
        eng = AnomalyEngine(ring_steps=4, mfu_floor=0.1)
        # mfu == 0.0 means the device peak is unknown (CPU) — not a breach.
        eng.observe_record(record(1, **{"perf/mfu": 0.0}))
        assert eng.triggers == 0
        eng.observe_record(record(2, **{"perf/mfu": 0.05}))
        assert eng.trigger_counts == {"mfu_floor": 1}

    def test_slow_step_arms_only_after_min_samples(self):
        eng = AnomalyEngine(ring_steps=4, slow_step_factor=3.0)
        # A spike before the median window fills must not false-positive
        # (compile steps look exactly like this).
        eng.observe_step_time(0, 5.0)
        for s in range(1, eng.MIN_STEP_SAMPLES + 1):
            eng.observe_step_time(s, 0.010)
        assert eng.triggers == 0
        eng.observe_step_time(20, 0.050)  # 5× the 10 ms median
        assert eng.trigger_counts == {"slow_step": 1}
        detail_factor = 0.050 / 0.010
        assert detail_factor > eng.slow_step_factor

    def test_slow_step_normalizes_scan_chunks(self):
        eng = AnomalyEngine(ring_steps=4, slow_step_factor=3.0)
        for s in range(eng.MIN_STEP_SAMPLES):
            eng.observe_step_time(s, 0.010)
        # An 8-step chunk at 80 ms is 10 ms/step — on-pace, no trigger.
        eng.observe_step_time(24, 0.080, steps=8)
        assert eng.triggers == 0

    def test_cooldown_debounces_dumps_not_counts(self, tmp_path):
        eng = AnomalyEngine(ring_steps=4, cooldown_steps=100,
                            dump_dir=str(tmp_path))
        eng.observe_record(record(10, loss=float("nan")))
        eng.observe_record(record(50, loss=float("nan")))
        assert eng.triggers == 2  # both counted...
        assert len(eng.dumps) == 1  # ...one dump inside the cooldown
        eng.observe_record(record(200, loss=float("nan")))
        assert len(eng.dumps) == 2

    def test_max_dumps_caps_files(self, tmp_path):
        eng = AnomalyEngine(ring_steps=4, cooldown_steps=0, max_dumps=2,
                            dump_dir=str(tmp_path))
        for s in (1, 2, 3, 4):
            eng.observe_record(record(s, loss=float("nan")))
        assert eng.triggers == 4
        assert len(eng.dumps) == 2
        assert len(glob.glob(str(tmp_path / "flight_record_*.json"))) == 2

    def test_no_dump_dir_counts_only(self):
        eng = AnomalyEngine(ring_steps=4)
        eng.observe_record(record(1, loss=float("nan")))
        assert eng.triggers == 1
        assert eng.dumps == []
        assert eng.dump_flight_record("non_finite", 1) is None

    def test_profile_request_armed_once_per_dumpworthy_trigger(self):
        eng = AnomalyEngine(ring_steps=4, cooldown_steps=100,
                            profile_steps=20)
        assert eng.take_profile_request() == 0
        eng.observe_record(record(10, loss=float("nan")))
        assert eng.take_profile_request() == 20
        assert eng.take_profile_request() == 0  # consumed
        # Debounced trigger (inside cooldown) must not re-arm.
        eng.observe_record(record(20, loss=float("nan")))
        assert eng.take_profile_request() == 0

    def test_context_fn_merges_and_errors_are_contained(self, tmp_path):
        ok = AnomalyEngine(ring_steps=4, dump_dir=str(tmp_path / "ok"),
                           context_fn=lambda: {"config": {"model": "x"}})
        ok.observe_record(record(1, loss=float("nan")))
        doc = json.load(open(ok.dumps[0]))
        assert doc["config"] == {"model": "x"}

        def boom():
            raise RuntimeError("context unavailable")

        bad = AnomalyEngine(ring_steps=4, dump_dir=str(tmp_path / "bad"),
                            context_fn=boom)
        bad.observe_record(record(1, loss=float("nan")))
        doc = json.load(open(bad.dumps[0]))
        assert doc["context_error"] == "RuntimeError: context unavailable"

    def test_tracer_spans_ride_in_dump_and_trigger_marks(self, tmp_path):
        tracer = SpanTracer(capacity=16)
        eng = AnomalyEngine(ring_steps=4, dump_dir=str(tmp_path),
                            tracer=tracer)
        with tracer.span("trainer/dispatch"):
            pass
        eng.observe_record(record(3, loss=float("nan")))
        doc = json.load(open(eng.dumps[0]))
        assert any(e["name"] == "trainer/dispatch" for e in doc["spans"])
        # The trigger itself lands in the timeline as an instant marker.
        marks = [e for e in tracer.snapshot()
                 if e["name"] == "anomaly/non_finite"]
        assert marks and marks[0]["ph"] == "i"

    def test_dump_failure_never_raises(self, tmp_path):
        blocker = tmp_path / "not_a_dir"
        blocker.write_text("")
        eng = AnomalyEngine(ring_steps=4, dump_dir=str(blocker))
        eng.observe_record(record(1, loss=float("nan")))  # must not raise
        assert eng.triggers == 1
        assert eng.dumps == []

    def test_device_memory_stats_shape(self):
        stats = device_memory_stats()
        assert isinstance(stats, dict)
        for per_device in stats.values():
            assert all(isinstance(v, int) for v in per_device.values())

    def test_ring_steps_validated(self):
        with pytest.raises(ValueError):
            AnomalyEngine(ring_steps=0)


class TestDebounceAcrossRestore:
    """``restore_elastic`` resumes an earlier step with the SAME
    per-process engine — the trainer never rebuilds or resets it. The
    step counter runs backward once and part of the old window replays;
    the debounce state must carry over: the replayed window cannot
    re-dump (no re-trigger storm), ``max_dumps`` stays spent, and the
    slow-step median ring stays armed. All counters
    (``triggers``/``trigger_counts``/``dumps``) are per-process
    cumulative — a restored run keeps counting where its process left
    off, which is exactly what the flight records' tallies mean."""

    def test_backward_step_replay_is_debounced(self, tmp_path):
        eng = AnomalyEngine(ring_steps=4, cooldown_steps=100,
                            dump_dir=str(tmp_path))
        eng.observe_record(record(50, loss=float("nan")))
        assert len(eng.dumps) == 1
        # Restore to step 10: the replayed NaN fires the counter but
        # the negative step delta sits inside the cooldown — no second
        # dump for an episode the process already dumped.
        eng.observe_record(record(10, loss=float("nan")))
        assert eng.triggers == 2
        assert len(eng.dumps) == 1
        # The cooldown is anchored at the PRE-restore trigger step, so
        # the engine re-arms once the replay runs past it.
        eng.observe_record(record(155, loss=float("nan")))
        assert len(eng.dumps) == 2

    def test_debounced_replay_does_not_rearm_profiler(self, tmp_path):
        eng = AnomalyEngine(ring_steps=4, cooldown_steps=100,
                            profile_steps=20, dump_dir=str(tmp_path))
        eng.observe_record(record(50, loss=float("nan")))
        assert eng.take_profile_request() == 20
        eng.observe_record(record(10, loss=float("nan")))  # replayed
        assert eng.take_profile_request() == 0

    def test_max_dumps_stays_spent_across_restore(self, tmp_path):
        eng = AnomalyEngine(ring_steps=4, cooldown_steps=0, max_dumps=2,
                            dump_dir=str(tmp_path))
        for s in (30, 40):
            eng.observe_record(record(s, loss=float("nan")))
        assert len(eng.dumps) == 2
        # Replay from step 1: the per-process dump budget does not
        # refill on restore — a crash-restore loop cannot fill the disk.
        for s in (1, 2, 3):
            eng.observe_record(record(s, loss=float("nan")))
        assert eng.triggers == 5
        assert len(eng.dumps) == 2
        assert len(glob.glob(str(tmp_path / "flight_record_*.json"))) == 2

    def test_slow_step_ring_stays_armed_after_restore(self):
        eng = AnomalyEngine(ring_steps=4, slow_step_factor=3.0)
        for s in range(eng.MIN_STEP_SAMPLES):
            eng.observe_step_time(s, 0.010)
        # Post-restore the loop re-observes EARLIER step numbers; the
        # median ring is per-process wall time, not step-indexed, so a
        # genuine stall right after restore still triggers (no 16-step
        # re-arming blackout).
        eng.observe_step_time(3, 0.050)
        assert eng.trigger_counts == {"slow_step": 1}

    def test_debounced_replay_is_still_journaled(self, tmp_path):
        # The journal is the decision audit: "fired but suppressed" is
        # a decision, so the replayed trigger lands there with
        # debounced=true and no flight-record link.
        from mercury_tpu.obs.events import EventJournal, read_journal

        journal = EventJournal(str(tmp_path), 0)
        eng = AnomalyEngine(ring_steps=4, cooldown_steps=100,
                            dump_dir=str(tmp_path), journal=journal)
        eng.observe_record(record(50, loss=float("nan")))
        eng.observe_record(record(10, loss=float("nan")))  # replayed
        journal.close()
        events = read_journal(journal.path)
        assert [e["kind"] for e in events] == ["anomaly/triggered"] * 2
        first, second = events
        assert first["detail"]["debounced"] is False
        assert first["detail"]["flight_record"]
        assert second["detail"]["debounced"] is True
        assert second["detail"]["flight_record"] is None


class TestTrainerIntegration:
    """The CI smoke as a test: inject a NaN into the host record stream
    mid-run and require a flight record + a loadable perfetto trace."""

    def test_injected_nan_yields_flight_record_and_trace(self, tmp_path):
        from mercury_tpu.parallel.mesh import host_cpu_mesh
        from mercury_tpu.train.trainer import Trainer

        logdir = str(tmp_path / "run")
        cfg = TrainConfig(
            model="smallcnn", dataset="synthetic", world_size=8,
            batch_size=8, presample_batches=3, num_epochs=1,
            steps_per_epoch=5, eval_every=0, log_every=1,
            heartbeat_every=0, compute_dtype="float32", seed=0,
            trace=True, anomaly_inject_nan_step=3, log_dir=logdir,
        )
        tr = Trainer(cfg, mesh=host_cpu_mesh(8))
        try:
            assert tr.tracer.enabled
            assert tr.anomaly is not None
            tr.fit()
        finally:
            tr.close()

        # Flight record: non_finite trigger at the injection step, ring
        # carrying the poisoned record.
        recs = glob.glob(os.path.join(logdir, "flight_record_*.json"))
        assert len(recs) == 1, recs
        doc = json.load(open(recs[0]))
        assert doc["schema"] == FLIGHT_RECORD_SCHEMA
        assert doc["trigger"]["kind"] == "non_finite"
        assert doc["trigger"]["detail"]["key"] == "train/loss"
        assert doc["trigger"]["step"] >= cfg.anomaly_inject_nan_step
        assert any(not math.isfinite(r.get("train/loss", 0.0))
                   for r in doc["ring"])
        assert doc["config"]["model"] == "smallcnn"  # context_fn merged
        assert "manifest" in doc

        # Perfetto trace: dispatch spans + the named training track.
        trace = json.load(open(os.path.join(logdir, "trace.json")))
        events = trace["traceEvents"]
        assert any(e["name"] == "trainer/dispatch" and e["ph"] == "X"
                   for e in events)
        assert any(e["name"] == "trainer/log_gate" for e in events)
        assert any(e.get("ph") == "M" and e["args"]["name"] == "train"
                   for e in events)
        assert any(e["name"] == "anomaly/non_finite" for e in events)

        # The metric stream saw the cumulative trigger count.
        lines = [json.loads(l) for l in
                 open(os.path.join(logdir, "metrics.jsonl"))]
        assert any(r.get("anomaly/triggers", 0) >= 1 for r in lines)

        # Dark-host fix: this process wrote its own telemetry shard and
        # flushed heartbeat shard alongside the primary stream.
        shard = [json.loads(l) for l in
                 open(os.path.join(logdir, "metrics.h0.jsonl"))]
        assert [r["step"] for r in shard] == [r["step"] for r in lines]
        hb = [json.loads(l) for l in
              open(os.path.join(logdir, "heartbeat.h0.jsonl"))]
        assert len(hb) == len(lines)
        assert all(r["host"] == 0 for r in hb)
        # ~every post-injection loss is the injected NaN exactly once —
        # the injection latches after one poisoned record.
        nans = [r for r in lines
                if not math.isfinite(r.get("train/loss", 0.0))]
        assert len(nans) == 1
