"""Async scorer fleet (``config.refresh_mode = "async"``): importance
refresh moves off the training step onto background host threads that
rescore round-robin shard chunks with periodically-snapshotted params and
stream ``(slots, scores)`` into the device-resident table between steps.
The fused step keeps only decay → draw — zero scoring FLOPs in the hot
program (pinned by the graftlint ``async`` plan budget).

The contract tested here: an async chunk applied at age 0 is
BIT-identical to the in-graph refresh writing the same scores
(``apply_async_chunk`` routes through the same ``scatter_mean``, and
``stale_weighted``'s convex form makes ``age_weight == 1.0`` an IEEE
identity), and a chunk applied at age ``a`` equals applying it fresh and
letting the step's decay act ``a`` times — the host-side staleness
discount composes with the in-graph decay instead of fighting it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mercury_tpu.config import TrainConfig
from mercury_tpu.parallel.mesh import host_cpu_mesh
from mercury_tpu.train.trainer import Trainer


@pytest.fixture(scope="module")
def mesh():
    return host_cpu_mesh(4)


def async_cfg(**kw) -> TrainConfig:
    base = dict(
        model="smallcnn",
        dataset="synthetic",
        world_size=4,
        batch_size=8,
        presample_batches=2,
        num_epochs=1,
        steps_per_epoch=6,
        eval_every=0,
        log_every=0,
        heartbeat_every=0,
        checkpoint_every=0,
        compute_dtype="float32",
        seed=0,
        sampler="scoretable",
        refresh_size=8,
        refresh_mode="async",
        scorer_workers=1,
        snapshot_every=2,
    )
    base.update(kw)
    return TrainConfig(**base)


class TestAsyncApplyUnits:
    """Pure-function contract between the in-graph refresh and the
    host-side async apply."""

    def _fixture(self, L=64, R=16):
        key = jax.random.key(7)
        scores = jax.random.uniform(
            jax.random.fold_in(key, 0), (L,), minval=0.1, maxval=4.0)
        slots = (jnp.arange(R) * 3) % L  # distinct for R*3 <= 2L
        values = jax.random.uniform(
            jax.random.fold_in(key, 1), (R,), minval=0.1, maxval=4.0)
        ema = jnp.mean(scores)
        return key, scores, slots, values, ema

    def test_age0_bit_identical_to_ingraph_refresh(self):
        """apply_async_chunk at age_weight=1.0 on the decayed table IS
        the in-graph refresh — same scatter, bit-exact weighting."""
        from mercury_tpu.sampling.scoretable import (
            apply_async_chunk,
            decay_scores,
            table_refresh_draw,
        )

        key, scores, slots, values, ema = self._fixture()
        refreshed, _, _, _ = table_refresh_draw(
            key, scores, slots, values, ema, 8, decay=0.98)
        via_async = apply_async_chunk(
            decay_scores(scores.astype(jnp.float32), ema, 0.98),
            slots, values, ema, jnp.float32(1.0))
        np.testing.assert_array_equal(
            np.asarray(refreshed), np.asarray(via_async))

    def test_aged_apply_equals_fresh_apply_then_decay(self):
        """With a constant EMA mean, applying a chunk at age ``a`` with
        weight γ^a equals applying it fresh and decaying the table ``a``
        times — staleness discounting commutes with the step's decay."""
        from mercury_tpu.sampling.scoretable import (
            apply_async_chunk,
            decay_scores,
        )

        _, scores, slots, values, mu = self._fixture()
        gamma, age = 0.9, 3

        def decay_n(t, n):
            for _ in range(n):
                t = decay_scores(t, mu, gamma)
            return t

        stale = apply_async_chunk(
            decay_n(scores, age), slots, values, mu,
            jnp.float32(gamma ** age))
        fresh_then_decayed = decay_n(
            apply_async_chunk(scores, slots, values, mu,
                              jnp.float32(1.0)), age)
        np.testing.assert_allclose(
            np.asarray(stale), np.asarray(fresh_then_decayed), rtol=1e-5)


class TestAsyncTrainer:
    def test_fit_runs_and_fleet_reports(self, mesh):
        t = Trainer(async_cfg(), mesh=mesh)
        try:
            out = t.fit(num_epochs=1)
            assert np.isfinite(out["test/eval_loss"])
            assert int(t.state.step) == 6
            fleet = t._scorer_fleet
            assert fleet is not None
            summary = fleet.summary()
            assert summary["chunks_scored"] >= 1
            assert summary["snapshots"] >= 1  # construction + cadence
            stats = fleet.stats()
            assert set(stats) == {
                "scorer/throughput",
                "sampler/refresh_lag_chunks",
                "sampler/score_staleness_mean",
                "sampler/score_staleness_max",
                "threads/queue_depth/scorer",
            }
            assert all(np.isfinite(v) for v in stats.values())
        finally:
            t.close()

    def test_applied_chunk_lands_bitwise(self, mesh):
        """A chunk scored synchronously and pushed through the trainer's
        jitted apply lands in the table bit-identically: at weight 1.0
        every touched slot holds exactly the fleet's score, every other
        slot is untouched."""
        t = Trainer(async_cfg(scorer_workers=1), mesh=mesh)
        try:
            fleet = t._scorer_fleet
            chunk = fleet.score_once()
            W, R = chunk.slots.shape
            assert (W, R) == (4, t.config.refresh_size)
            old = np.asarray(t.state.scoretable.scores)
            new_tab = t._apply_refresh(
                t.state.scoretable, t.state.ema.value,
                jnp.asarray(chunk.slots), jnp.asarray(chunk.scores),
                jnp.float32(1.0))
            new = np.asarray(new_tab.scores)
            for w in range(W):
                np.testing.assert_array_equal(
                    new[w, chunk.slots[w]], chunk.scores[w])
                mask = np.ones(old.shape[1], bool)
                mask[chunk.slots[w]] = False
                np.testing.assert_array_equal(new[w, mask], old[w, mask])
            # Cursor is fleet-owned under async: the apply leaves it be.
            np.testing.assert_array_equal(
                np.asarray(new_tab.cursor),
                np.asarray(t.state.scoretable.cursor))
        finally:
            t.close()

    @pytest.mark.parametrize("bad", [
        dict(sampler="pool"),
        dict(use_importance_sampling=False),
        dict(refresh_mode="weird"),
        dict(scorer_workers=0),
        dict(snapshot_every=0),
    ])
    def test_invalid_compositions_rejected(self, mesh, bad):
        with pytest.raises(ValueError):
            Trainer(async_cfg(**bad), mesh=mesh)

    def test_multiprocess_rejected_names_fleet_constraint(self, mesh,
                                                          monkeypatch):
        """Multi-controller async refresh is rejected, and the message
        names the REAL constraint — the fleet's per-process params
        snapshot and (slots, scores) chunk stream — not a stale
        single-controller precedent (host_stream no longer is one)."""
        monkeypatch.setattr(jax, "process_count", lambda: 2)
        with pytest.raises(ValueError,
                           match="scorer fleet.*per-process"):
            Trainer(async_cfg(), mesh=mesh)


class TestTrainerClose:
    """Trainer.close() regression: idempotent, ordering-safe, and safe on
    partially-constructed trainers (the fleet makes close() load-bearing
    — a leaked daemon thread would keep scoring a dead run)."""

    def test_close_is_idempotent(self, mesh):
        t = Trainer(async_cfg(), mesh=mesh)
        t.close()
        t.close()  # second close is a no-op, not an error
        assert t._scorer_fleet.summary()["closed"]

    def test_close_on_partially_constructed_trainer(self):
        # __init__ never ran: no config, logger, fleet, or stream pipe.
        Trainer.__new__(Trainer).close()

    def test_close_without_fleet(self, mesh):
        t = Trainer(async_cfg(refresh_mode="sync"), mesh=mesh)
        assert t._scorer_fleet is None
        t.close()
        t.close()


class TestAsyncHostStreamMatrix:
    """host_stream + async on a 4-way mesh — compile cost belongs in the
    slow tier (same budget call as TestHostStreamMatrix)."""

    pytestmark = pytest.mark.slow

    def test_w4_host_stream_async_fit(self, mesh):
        t = Trainer(async_cfg(data_placement="host_stream",
                              prefetch_depth=2, steps_per_epoch=6),
                    mesh=mesh)
        try:
            out = t.fit(num_epochs=1)
            assert np.isfinite(out["test/eval_loss"])
            assert int(t.state.step) == 6
            assert t._scorer_fleet.summary()["chunks_scored"] >= 1
        finally:
            t.close()
