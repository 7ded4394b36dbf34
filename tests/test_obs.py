"""Telemetry subsystem unit tests: in-graph diagnostics math, the
non-blocking metric writer's queue policy, and run accounting.

Everything here is pure-CPU and fast — no model, no train step. The
diagnostics are checked against independent numpy derivations (not
against themselves), and the writer tests use ``start=False`` so the
queue policy is observed deterministically without thread timing.
"""

import io
import json
import os
import threading

import numpy as np
import pytest

import jax.numpy as jnp

from mercury_tpu.config import TrainConfig
from mercury_tpu.obs.accounting import (
    ThroughputMeter,
    analytic_flops_per_step,
    peak_flops,
)
from mercury_tpu.obs.diagnostics import (
    clip_fraction,
    ema_drift,
    ess_fraction,
    global_grad_norm,
    table_age_summary,
    table_ages,
)
from mercury_tpu.obs.manifest import build_run_manifest, write_run_manifest
from mercury_tpu.obs.writer import (
    AsyncMetricWriter,
    HeartbeatSink,
    HeartbeatShardSink,
    JsonlSink,
)
from mercury_tpu.sampling.scoretable import refresh_period


# ------------------------------------------------------------- diagnostics
class TestEssFraction:
    def test_uniform_weights_are_exactly_one(self):
        # The uniform baseline feeds scaled_probs == 1 (unit weights):
        # ESS must land exactly at 1.0, not merely near it.
        assert float(ess_fraction(jnp.ones(64))) == 1.0

    def test_equal_nonunit_probs_still_one(self):
        b = 16
        probs = jnp.full((b,), 1.0 / b)
        assert float(ess_fraction(probs)) > 0.999

    def test_single_dominant_sample_approaches_one_over_b(self):
        b = 32
        # One tiny scaled prob → one huge weight dominating the batch.
        probs = jnp.ones(b).at[0].set(1e-6)
        ess = float(ess_fraction(probs))
        assert abs(ess - 1.0 / b) < 1e-3

    def test_matches_numpy_formula(self, rng):
        probs = rng.uniform(0.1, 2.0, size=24).astype(np.float32)
        w = 1.0 / probs
        expect = (w.sum() ** 2) / (24 * (w**2).sum())
        assert abs(float(ess_fraction(jnp.asarray(probs))) - expect) < 1e-5


class TestClipFraction:
    def test_counts_floored_scores(self):
        # With EMA 0 and alpha 0.5, smoothed score == loss: the two zero
        # losses sit at/below the floor, the positive one doesn't.
        scores = jnp.array([0.0, 0.0, 1.0])
        ema = jnp.zeros(())
        assert abs(float(clip_fraction(scores, ema, 0.5)) - 2 / 3) < 1e-6

    def test_positive_ema_lifts_everything_off_floor(self):
        scores = jnp.zeros(8)
        ema = jnp.asarray(2.0)
        assert float(clip_fraction(scores, ema, 0.5)) == 0.0


class TestEmaDrift:
    def test_signed_difference(self):
        assert float(ema_drift(jnp.asarray(3.0), jnp.asarray(1.0))) == 2.0
        assert float(ema_drift(jnp.asarray(0.5), jnp.asarray(1.0))) == -0.5


class TestTableAges:
    def test_window_is_age_zero_and_oldest_is_period_minus_one(self):
        n_slots, refresh = 12, 3
        period = refresh_period(n_slots, refresh)  # 4 sweeps cover the table
        ages = np.asarray(table_ages(jnp.asarray(0), n_slots, refresh))
        # This step's window [0, 3) is fresh.
        assert ages[:refresh].tolist() == [0.0, 0.0, 0.0]
        # The slot just behind the window is the oldest.
        assert ages.max() == period - 1
        assert ages[refresh] == period - 1

    def test_cursor_advance_rotates_ages(self):
        n_slots, refresh = 12, 3
        a0 = np.asarray(table_ages(jnp.asarray(0), n_slots, refresh))
        a1 = np.asarray(table_ages(jnp.asarray(refresh), n_slots, refresh))
        # One refresh later every slot's age pattern rotates by one window.
        assert np.array_equal(np.roll(a0, refresh), a1)

    def test_summary_min_mean_max(self):
        n_slots, refresh = 10, 3
        mn, mean, mx = table_age_summary(jnp.asarray(3), n_slots, refresh)
        ages = np.asarray(table_ages(jnp.asarray(3), n_slots, refresh))
        assert float(mn) == ages.min() == 0.0
        assert float(mx) == ages.max()
        assert abs(float(mean) - ages.mean()) < 1e-6


class TestGlobalGradNorm:
    def test_matches_flat_l2_over_pytree(self, rng):
        tree = {
            "w": jnp.asarray(rng.normal(size=(4, 3)).astype(np.float32)),
            "b": jnp.asarray(rng.normal(size=(3,)).astype(np.float32)),
        }
        flat = np.concatenate([np.asarray(v).ravel() for v in tree.values()])
        assert abs(float(global_grad_norm(tree))
                   - np.linalg.norm(flat)) < 1e-5


# ------------------------------------------------------------------ writer
class ListSink:
    def __init__(self):
        self.records = []
        self.closed = 0

    def write(self, record):
        self.records.append(record)

    def close(self):
        self.closed += 1


class TestAsyncMetricWriter:
    def test_records_arrive_in_order(self):
        sink = ListSink()
        w = AsyncMetricWriter([sink], start=False)
        for step in range(1, 6):
            w.write(step, {"train/loss": float(step)})
        w.flush()
        assert [r["step"] for r in sink.records] == [1, 2, 3, 4, 5]
        assert [r["train/loss"] for r in sink.records] == [1, 2, 3, 4, 5]

    def test_bounded_queue_drops_oldest_and_counts(self):
        sink = ListSink()
        w = AsyncMetricWriter([sink], capacity=3, start=False)
        for step in range(1, 6):
            w.write(step, {"v": step})
        assert w.dropped == 2
        w.flush()
        # Oldest two (steps 1, 2) were dropped; survivors carry the count.
        assert [r["step"] for r in sink.records] == [3, 4, 5]
        assert all(r["obs/dropped"] == 2.0 for r in sink.records)

    def test_device_arrays_and_chunk_series_reduce_to_floats(self):
        sink = ListSink()
        w = AsyncMetricWriter([sink], start=False)
        # Scan-chunked [K] series must reduce to the chunk mean.
        w.write(7, {"train/loss": jnp.array([1.0, 2.0, 3.0]),
                    "train/acc": jnp.asarray(0.5)})
        w.flush()
        (rec,) = sink.records
        assert rec["train/loss"] == 2.0
        assert rec["train/acc"] == 0.5
        assert isinstance(rec["train/loss"], float)

    def test_background_thread_drains_and_close_joins(self):
        sink = ListSink()
        before = threading.active_count()
        w = AsyncMetricWriter([sink])
        # Lazy start: no thread until the first write.
        assert threading.active_count() == before
        for step in range(1, 4):
            w.write(step, {"v": step})
        w.close()
        assert [r["step"] for r in sink.records] == [1, 2, 3]
        assert sink.closed == 1

    def test_close_is_idempotent_and_write_after_close_is_noop(self):
        sink = ListSink()
        w = AsyncMetricWriter([sink], start=False)
        w.write(1, {"v": 1})
        w.close()
        w.close()
        w.write(2, {"v": 2})
        assert [r["step"] for r in sink.records] == [1]
        assert sink.closed == 1

    def test_context_manager_closes(self):
        sink = ListSink()
        with AsyncMetricWriter([sink], start=False) as w:
            w.log_scalars(1, {"v": 1.0})  # MetricsLogger-compatible alias
        assert sink.closed == 1
        assert sink.records[0]["v"] == 1.0

    def test_failing_sink_never_raises_into_caller(self):
        class Boom:
            def write(self, record):
                raise RuntimeError("sink down")

            def close(self):
                raise RuntimeError("still down")

        ok = ListSink()
        w = AsyncMetricWriter([Boom(), ok], start=False)
        w.write(1, {"v": 1})
        w.flush()
        w.close()
        assert [r["step"] for r in ok.records] == [1]
        assert w.errors >= 1

    def test_none_sinks_are_filtered(self):
        # try_tensorboard_sink returns None when TB is absent; the
        # writer must accept that directly.
        w = AsyncMetricWriter([None, ListSink()], start=False)
        assert len(w.sinks) == 1
        w.close()

    def test_close_racing_inflight_drain_loses_nothing(self):
        # close() while the drain thread is mid-queue: every record
        # written before close() must reach the sink exactly once —
        # close drains the queue after joining the thread, and the two
        # paths must not double-emit. A slow sink keeps the race window
        # open for real.
        import time as _time

        class SlowSink(ListSink):
            def write(self, record):
                _time.sleep(0.002)
                super().write(record)

        sink = SlowSink()
        w = AsyncMetricWriter([sink])
        for step in range(1, 21):
            w.write(step, {"v": step})
        w.close()  # thread mid-drain: ~40 ms of sink work is queued
        assert [r["step"] for r in sink.records] == list(range(1, 21))
        assert sink.closed == 1

    def test_wedged_sink_drops_oldest_not_training(self):
        # A sink that blocks forever on its first write (wedged NFS /
        # TB): write() must keep returning instantly, the bounded queue
        # must rotate (drop-OLDEST), and close() must come back despite
        # the thread being stuck inside the sink.
        release = threading.Event()

        class WedgedSink(ListSink):
            def write(self, record):
                release.wait(timeout=30.0)
                super().write(record)

        import time as _time

        sink = WedgedSink()
        w = AsyncMetricWriter([sink], capacity=4)
        w.write(1, {"v": 1})
        # Wait until the drain thread has TAKEN record 1 (it is now
        # wedged inside the sink), so the drop accounting below is
        # deterministic rather than racing thread startup.
        deadline = _time.monotonic() + 10.0
        while _time.monotonic() < deadline:
            with w._lock:
                if not w._q and w._busy:
                    break
            _time.sleep(0.001)
        for step in range(2, 11):
            w.write(step, {"v": step})  # returns instantly every time
        # 1 record wedged in the sink, 4 queued (7..10), 2..6 dropped.
        assert w.dropped == 5
        release.set()
        w.close()
        # The wedged record plus the queue's newest survivors landed,
        # in order, exactly once; survivors carry the drop count.
        assert [r["step"] for r in sink.records] == [1, 7, 8, 9, 10]
        assert sink.records[-1]["obs/dropped"] == 5.0

    def test_observer_sees_host_record_and_mutation_reaches_sinks(self):
        sink = ListSink()
        seen = []

        def observer(record):
            seen.append(dict(record))
            record["anomaly/triggers"] = 1.0  # may mutate in place

        w = AsyncMetricWriter([sink], start=False, observers=(observer,))
        w.write(3, {"train/loss": jnp.asarray(2.0)})
        w.flush()
        assert seen[0]["train/loss"] == 2.0  # host float, post device_get
        assert sink.records[0]["anomaly/triggers"] == 1.0

    def test_observer_exception_is_counted_not_raised(self):
        sink = ListSink()

        def bad(record):
            raise RuntimeError("observer down")

        w = AsyncMetricWriter([sink, None], start=False,
                              observers=(bad, None))
        w.write(1, {"v": 1.0})
        w.flush()
        assert [r["step"] for r in sink.records] == [1]
        assert w.errors == 1


class TestJsonlSink:
    def test_buffered_writes_land_on_close(self, tmp_path):
        sink = JsonlSink(str(tmp_path), flush_every=100)
        sink.write({"step": 1, "train/loss": 2.5})
        sink.write({"step": 2, "train/loss": 2.0})
        sink.close()
        lines = (tmp_path / "metrics.jsonl").read_text().splitlines()
        recs = [json.loads(l) for l in lines]
        assert [r["step"] for r in recs] == [1, 2]
        assert recs[0]["train/loss"] == 2.5
        sink.close()  # idempotent


class TestHeartbeatShardSink:
    def test_one_flushed_row_per_record_with_liveness_subset(self, tmp_path):
        sink = HeartbeatShardSink(str(tmp_path), process_index=3)
        sink.write({"step": 5.0, "time": 1005.0, "time/step": 0.1,
                    "train/loss": 2.0, "data/stall_s": 0.02})
        # Flushed on write — readable BEFORE close (the post-mortem
        # contract: a SIGKILLed host leaves its last state on disk).
        lines = (tmp_path / "heartbeat.h3.jsonl").read_text().splitlines()
        (row,) = [json.loads(l) for l in lines]
        assert row["step"] == 5 and row["host"] == 3
        assert row["time/step"] == 0.1
        assert row["data/stall_s"] == 0.02
        assert "train/loss" not in row  # liveness subset only
        sink.close()
        sink.close()  # idempotent
        sink.write({"step": 6.0})  # write-after-close is a no-op
        assert len((tmp_path / "heartbeat.h3.jsonl")
                   .read_text().splitlines()) == 1

    def test_size_capped_rotation_bounds_growth(self, tmp_path):
        # Rows are ~60 bytes; a 200-byte cap forces rotation every few
        # writes. The live shard must stay under cap+one row, with one
        # prior generation kept at <name>.1 — a flush-per-write sink can
        # no longer grow without bound.
        sink = HeartbeatShardSink(str(tmp_path), process_index=0,
                                  max_bytes=200)
        for step in range(40):
            sink.write({"step": float(step), "time": 1000.0 + step})
        sink.close()
        live = tmp_path / "heartbeat.h0.jsonl"
        prior = tmp_path / "heartbeat.h0.jsonl.1"
        assert sink.rotations > 1
        assert prior.exists()
        assert live.stat().st_size <= 300
        # Both generations hold intact JSON lines; the newest row is the
        # last write (nothing lost at the rotation boundary).
        rows = [json.loads(l) for l in
                (prior.read_text() + live.read_text()).splitlines()]
        assert rows[-1]["step"] == 39
        steps = [r["step"] for r in rows]
        assert steps == sorted(steps)

    def test_max_bytes_zero_disables_rotation(self, tmp_path):
        sink = HeartbeatShardSink(str(tmp_path), process_index=0,
                                  max_bytes=0)
        for step in range(50):
            sink.write({"step": float(step)})
        sink.close()
        assert sink.rotations == 0
        assert not (tmp_path / "heartbeat.h0.jsonl.1").exists()


class TestHeartbeatSink:
    def test_rate_limited_by_step_cadence(self):
        out = io.StringIO()
        hb = HeartbeatSink(every_steps=2, min_interval_s=0.0, stream=out)
        for step in range(1, 7):
            hb.write({"step": step, "train/loss": 1.0, "sampler/ess": 0.9})
        lines = out.getvalue().splitlines()
        # First record always prints; then only on every_steps boundaries.
        assert lines[0].startswith("step 1")
        assert [l.split()[1] for l in lines] == ["1", "2", "4", "6"]
        assert "ess 0.9" in lines[0]

    def test_optional_keys_absent_and_present(self):
        # Non-host_stream runs have no data/stall_s; pre-trigger runs
        # have no anomaly/triggers — the line simply omits them, and
        # grows the fields once the keys appear.
        out = io.StringIO()
        hb = HeartbeatSink(every_steps=1, min_interval_s=0.0, stream=out)
        hb.write({"step": 1, "train/loss": 1.0})
        hb.write({"step": 2, "train/loss": 0.9, "data/stall_s": 0.25,
                  "obs/dropped": 3.0, "anomaly/triggers": 2.0})
        first, second = out.getvalue().splitlines()
        assert "stall_s" not in first and "triggers" not in first
        assert first == "step 1  loss 1"
        assert "stall_s 0.25" in second
        assert "dropped 3" in second
        assert "triggers 2" in second


# -------------------------------------------------------------- accounting
class TestThroughputMeter:
    def test_tick_math_with_explicit_clock(self):
        m = ThroughputMeter(examples_per_step=10, flops_per_step=1e9,
                            device_kind="TPU v4")
        m.reset(0, now=100.0)
        out = m.tick(10, now=102.0)  # 10 steps in 2 s
        assert out["perf/steps_per_s"] == 5.0
        assert out["perf/examples_per_s"] == 50.0
        assert out["time/step"] == 0.2
        assert out["perf/flops_per_step"] == 1e9
        assert abs(out["perf/mfu"] - 1e9 * 5.0 / 275e12) < 1e-18

    def test_unknown_device_reports_zero_mfu(self):
        m = ThroughputMeter(examples_per_step=8, flops_per_step=1e9,
                            device_kind="CPU-of-some-kind")
        m.reset(0, now=0.0)
        out = m.tick(4, now=1.0)
        assert out["perf/mfu"] == 0.0
        assert out["perf/steps_per_s"] == 4.0

    def test_first_tick_without_reset_is_empty(self):
        m = ThroughputMeter(examples_per_step=8)
        assert m.tick(5, now=1.0) == {}
        assert m.tick(10, now=2.0)["perf/steps_per_s"] == 5.0


class TestPeakFlops:
    def test_known_and_unknown_kinds(self):
        assert peak_flops("TPU v4") == 275e12
        assert peak_flops("TPU v5 lite") == 197e12
        assert peak_flops("cpu") is None
        assert peak_flops(None) is None

    def test_untabulated_accelerator_raises(self):
        """An accelerator the table does not know is an error, not a
        silent 0.0 MFU."""
        with pytest.raises(ValueError, match="TPU v9"):
            peak_flops("TPU v9 hyper")
        with pytest.raises(ValueError, match="PEAK_FLOPS"):
            ThroughputMeter(examples_per_step=8, device_kind="NVIDIA H100")


class TestAnalyticFlops:
    def test_jitted_matmul_reports_positive_flops(self):
        import jax

        @jax.jit
        def f(a, b):
            return a @ b

        a = jnp.ones((16, 16))
        flops = analytic_flops_per_step(f, a, a)
        # CPU's cost model may legitimately be absent (None); when it
        # answers, the number must be positive and scale down with scan.
        if flops is not None:
            assert flops > 0
            assert analytic_flops_per_step(f, a, a, scan_steps=2) == flops / 2

    def test_unlowerable_fn_returns_none(self):
        assert analytic_flops_per_step(lambda x: x, 1.0) is None


# ---------------------------------------------------------------- manifest
class TestRunManifest:
    def test_build_has_required_fields(self):
        import jax

        from mercury_tpu.parallel.mesh import make_mesh

        config = TrainConfig(model="smallcnn", dataset="synthetic",
                             world_size=2, batch_size=8)
        mesh = make_mesh(2, config.mesh_axis)
        man = build_run_manifest(config, mesh, extra={"note": "test"})
        assert man["schema"] == "mercury_run_manifest_v1"
        assert man["config"]["model"] == "smallcnn"
        assert man["jax_version"] == jax.__version__
        assert man["mesh_shape"] == {config.mesh_axis: 2}
        assert man["device_count"] == jax.device_count()
        assert man["note"] == "test"
        assert "peak_flops" in man  # null on CPU — but always present

    def test_write_produces_json_file(self, tmp_path):
        config = TrainConfig(model="smallcnn", dataset="synthetic",
                             world_size=1, batch_size=8)
        path = write_run_manifest(str(tmp_path), config)
        assert os.path.basename(path) == "run_manifest.json"
        man = json.loads(open(path).read())
        assert man["run_name"] == config.run_name()
        assert man["config"]["batch_size"] == 8
