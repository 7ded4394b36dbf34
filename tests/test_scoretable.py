"""Scoretable sampler (``config.sampler = "scoretable"``): a device-resident
``[L]`` float32 score table over each worker's whole shard. Per step only
``refresh_size`` slots are rescored (round-robin window + the trained
batch's scores, which fall out of the training forward for free); the rest
age-decay toward the EMA mean; the train batch is drawn from the FULL
shard's distribution. Scoring FLOPs scale with ``refresh_size`` instead of
``pool_size`` while the draw sees every sample."""

import numpy as np
import pytest

from mercury_tpu.config import TrainConfig
from mercury_tpu.parallel.mesh import host_cpu_mesh
from mercury_tpu.train.trainer import Trainer


def table_config(**kw) -> TrainConfig:
    base = dict(
        model="smallcnn",
        dataset="synthetic",
        world_size=8,
        batch_size=8,
        presample_batches=3,
        num_epochs=1,
        steps_per_epoch=6,
        eval_every=0,
        log_every=0,
        compute_dtype="float32",
        seed=0,
        sampler="scoretable",
        refresh_size=8,
    )
    base.update(kw)
    return TrainConfig(**base)


@pytest.fixture(scope="module")
def mesh():
    return host_cpu_mesh(8)


class TestScoreTableUnits:
    """Pure-function properties of sampling/scoretable.py."""

    def test_unbiasedness(self):
        """The realized reweighted estimator mean_B(l_i/(L·p_i)) is
        unbiased for the uniform mean over the table, for ANY table
        contents — the reweight divides by the probabilities the batch
        was actually drawn from."""
        import jax
        import jax.numpy as jnp

        from mercury_tpu.sampling.scoretable import table_refresh_draw

        L, B = 64, 16
        key = jax.random.key(0)
        losses = jax.random.uniform(key, (L,), minval=0.1, maxval=3.0)
        scores = losses  # a sharp, non-uniform table
        slots = jnp.arange(4)
        ests = []
        for i in range(300):
            _, probs, sel, scaled = table_refresh_draw(
                jax.random.fold_in(key, i), scores, slots, losses[slots],
                jnp.mean(losses), B,
            )
            ests.append(float(jnp.mean(losses[sel] / scaled)))
        np.testing.assert_allclose(
            np.mean(ests), float(jnp.mean(losses)), rtol=0.03
        )

    def test_round_robin_covers_every_slot(self):
        """Successive refresh windows tile the table: every slot is
        rescored within ceil(L/R) steps, including when R ∤ L (the
        window wraps modularly, never skipping the tail)."""
        import jax.numpy as jnp

        from mercury_tpu.sampling.scoretable import (
            ScoreTableState,
            advance_cursor,
            init_score_table,
            refresh_window,
        )

        for L, R in [(10, 3), (12, 4), (7, 7), (9, 2)]:
            state = init_score_table(L)
            seen = set()
            for _ in range(-(-L // R)):
                seen |= set(np.asarray(refresh_window(state, R)).tolist())
                state = ScoreTableState(
                    scores=state.scores,
                    cursor=advance_cursor(state, R),
                )
            assert seen == set(range(L)), (L, R)
            # ...and the cursor is back where a full cycle ends.
            assert int(state.cursor) == (-(-L // R) * R) % L

    def test_decay_converges_to_uniform(self):
        """With refresh disabled, repeated age-decay pulls every entry to
        the EMA mean — the sampling distribution converges to uniform
        (staleness degrades gracefully toward the uniform baseline,
        never toward a stuck sharp distribution)."""
        import jax
        import jax.numpy as jnp

        from mercury_tpu.sampling.scoretable import decay_scores, table_probs

        L = 32
        scores = jax.random.uniform(jax.random.key(1), (L,), minval=0.0,
                                    maxval=10.0)
        mu = jnp.asarray(1.7)
        for _ in range(400):
            scores = decay_scores(scores, mu, 0.95)
        probs = np.asarray(table_probs(scores, mu))
        np.testing.assert_allclose(probs, 1.0 / L, atol=1e-6)

    def test_scatter_mean_averages_duplicates(self):
        import jax.numpy as jnp

        from mercury_tpu.sampling.scoretable import scatter_mean

        scores = jnp.zeros((5,))
        out = np.asarray(scatter_mean(
            scores, jnp.array([1, 1, 3]), jnp.array([2.0, 4.0, 7.0])
        ))
        np.testing.assert_allclose(out, [0.0, 3.0, 0.0, 7.0, 0.0])

    def test_pallas_matches_native(self):
        """The Pallas path's draw kernel (interpret mode on CPU), fed the
        jax-native decayed + refreshed table as the step feeds it, agrees
        with the native probs; the draws use different RNG pipelines
        (inverse-CDF on uniforms vs categorical), so those are compared
        distributionally."""
        import jax
        import jax.numpy as jnp

        from mercury_tpu.ops import score_and_draw_pallas
        from mercury_tpu.sampling.scoretable import table_refresh_draw

        key = jax.random.key(3)
        for L in [64, 96, 320]:
            scores = jax.random.uniform(
                jax.random.fold_in(key, L), (L,), minval=0.1, maxval=4.0
            )
            slots = (jnp.arange(16) * 3) % L
            rscores = jax.random.uniform(
                jax.random.fold_in(key, L + 1), (16,), minval=0.1, maxval=4.0
            )
            ema = jnp.mean(scores)
            n_table, n_probs, _, _ = table_refresh_draw(
                key, scores, slots, rscores, ema, 8
            )
            p_probs, p_sel, p_scaled = score_and_draw_pallas(
                key, n_table, ema, 8
            )
            np.testing.assert_allclose(np.asarray(n_probs),
                                       np.asarray(p_probs), atol=1e-6)
            # Pallas scaled probs are consistent with its own draw.
            np.testing.assert_allclose(
                np.asarray(p_scaled),
                np.asarray(p_probs)[np.asarray(p_sel)] * L, atol=1e-5,
            )

    def test_pallas_draw_matches_distribution(self):
        import jax
        import jax.numpy as jnp

        from mercury_tpu.ops import score_and_draw_pallas

        L, B = 64, 4096
        scores = jnp.linspace(0.1, 3.0, L)
        counts = np.zeros(L)
        probs = None
        for i in range(4):
            probs, sel, _ = score_and_draw_pallas(
                jax.random.key(i), scores, jnp.mean(scores), B,
            )
            counts += np.bincount(np.asarray(sel), minlength=L)
        np.testing.assert_allclose(
            counts / counts.sum(), np.asarray(probs), atol=0.02
        )


class TestScoreTableTrainer:
    def test_trains_and_loss_decreases(self, mesh):
        t = Trainer(table_config(num_epochs=2), mesh=mesh)
        first = None
        for _ in range(12):
            t.state, metrics = t.train_step(
                t.state, t._step_x, t._step_y, t.dataset.shard_indices
            )
            if first is None:
                first = float(metrics["train/loss"])
        last = float(metrics["train/loss"])
        assert np.isfinite(last)
        assert last < first

    def test_table_state_advances(self, mesh):
        t = Trainer(table_config(), mesh=mesh)
        shard_len = int(t.dataset.shard_indices.shape[1])
        assert t.state.scoretable.scores.shape == (8, shard_len)
        for _ in range(4):
            t.state, _ = t.train_step(
                t.state, t._step_x, t._step_y, t.dataset.shard_indices
            )
        cursors = np.asarray(t.state.scoretable.cursor)
        assert (cursors == (4 * t.config.refresh_size) % shard_len).all()
        scores = np.asarray(t.state.scoretable.scores)
        assert np.isfinite(scores).all()
        # The refresh + write-back touched entries away from the uniform
        # init value.
        assert not np.allclose(scores, scores.flat[0])
        # EMA updates every step (each step runs a refresh forward).
        assert int(np.asarray(t.state.ema.count).max()) == 4

    def test_other_samplers_keep_reference_path(self, mesh):
        """sampler='pool' must be the untouched pre-feature path: no
        table in the state (its presence would change donation/jit
        signatures) and no scoretable arm in the step program."""
        from mercury_tpu.train.step import _state_specs

        t = Trainer(table_config(sampler="pool"), mesh=mesh)
        assert t.state.scoretable is None
        assert _state_specs("data").scoretable is None
        for _ in range(2):
            t.state, _ = t.train_step(
                t.state, t._step_x, t._step_y, t.dataset.shard_indices
            )
        assert t.state.scoretable is None

    def test_checkpoint_roundtrip_is_deterministic(self, mesh, tmp_path):
        """The table is part of the state pytree: save mid-cycle,
        restore, and the continued trajectory is bit-identical."""
        cfg = table_config(checkpoint_dir=str(tmp_path), checkpoint_every=0)
        t = Trainer(cfg, mesh=mesh)
        for _ in range(3):
            t.state, _ = t.train_step(
                t.state, t._step_x, t._step_y, t.dataset.shard_indices
            )
        t.save()
        for _ in range(3):
            t.state, _ = t.train_step(
                t.state, t._step_x, t._step_y, t.dataset.shard_indices
            )
        import jax

        want = np.asarray(jax.tree_util.tree_leaves(t.state.params)[0])

        t2 = Trainer(cfg, mesh=mesh)
        t2.restore()
        assert int(t2.state.step) == 3
        shard_len = int(t2.dataset.shard_indices.shape[1])
        assert t2.state.scoretable.scores.shape == (8, shard_len)
        assert (np.asarray(t2.state.scoretable.cursor)
                == (3 * cfg.refresh_size) % shard_len).all()
        for _ in range(3):
            t2.state, _ = t2.train_step(
                t2.state, t2._step_x, t2._step_y, t2.dataset.shard_indices
            )
        got = np.asarray(jax.tree_util.tree_leaves(t2.state.params)[0])
        np.testing.assert_array_equal(want, got)

    def test_scoring_dtype_runs(self, mesh):
        t = Trainer(table_config(scoring_dtype="bfloat16"), mesh=mesh)
        for _ in range(2):
            t.state, metrics = t.train_step(
                t.state, t._step_x, t._step_y, t.dataset.shard_indices
            )
        assert np.isfinite(float(metrics["train/loss"]))
        # Params are shared with the train model — still float32.
        import jax

        leaf = jax.tree_util.tree_leaves(t.state.params)[0]
        assert leaf.dtype == np.float32

    def test_rejects_bad_compositions(self, mesh):
        with pytest.raises(ValueError, match="scoretable"):
            Trainer(table_config(pipelined_scoring=True), mesh=mesh)
        with pytest.raises(ValueError, match="scoretable"):
            Trainer(table_config(score_refresh_every=3), mesh=mesh)
        with pytest.raises(ValueError, match="refresh_size"):
            Trainer(table_config(refresh_size=0), mesh=mesh)
        with pytest.raises(ValueError, match="table_decay"):
            Trainer(table_config(table_decay=1.5), mesh=mesh)
        with pytest.raises(ValueError, match="scoring_dtype"):
            Trainer(table_config(use_importance_sampling=False,
                                 scoring_dtype="bfloat16"), mesh=mesh)

    def test_scan_steps_compose(self, mesh):
        t = Trainer(table_config(scan_steps=3, num_epochs=2), mesh=mesh)
        t.state, metrics = t.train_step_many(
            t.state, t._step_x, t._step_y, t.dataset.shard_indices
        )
        assert int(t.state.step) == 3
        assert np.isfinite(np.asarray(metrics["train/loss"])).all()
        cursors = np.asarray(t.state.scoretable.cursor)
        shard_len = int(t.dataset.shard_indices.shape[1])
        assert (cursors == (3 * t.config.refresh_size) % shard_len).all()
