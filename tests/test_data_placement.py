"""Non-replicated data placements.

``data_placement="sharded"``: each worker's shard rows materialized as
[W, L, ...] arrays sharded over the data axis — per-device train-data
memory is one shard row instead of the full dataset (the scaling-past-
CIFAR path; parity with ``load_partition_data_distributed_cifar10``,
``cifar10/data_loader.py:214-245``). Must be numerically IDENTICAL to the
replicated placement: the sharded gather x_shard[0][slots] reads the same
bytes as the replicated x_train[shard_indices[0][slots]].

``data_placement="host_stream"``: pixels never resident on device — the
in-graph selection runs ``prefetch_depth`` steps ahead and a background
thread streams each selected batch in (``data/stream.py``,
``train/step.py::hs_body``). The uniform and pool samplers must be
BIT-identical to replicated (the lookahead replays the same RNG chain);
the scoretable sampler accepts depth-step-stale selection by design, so
it gets a smoke + telemetry check instead."""

import jax
import numpy as np
import pytest

from mercury_tpu.config import TrainConfig
from mercury_tpu.parallel.mesh import host_cpu_mesh
from mercury_tpu.train.trainer import Trainer


@pytest.fixture(scope="module")
def mesh():
    return host_cpu_mesh(4)


@pytest.fixture(scope="module")
def mesh1():
    return host_cpu_mesh(1)


def cfg(**kw):
    base = dict(model="smallcnn", dataset="synthetic", world_size=4,
                batch_size=4, presample_batches=2, steps_per_epoch=3,
                num_epochs=1, eval_every=0, log_every=0,
                compute_dtype="float32", seed=0)
    base.update(kw)
    return TrainConfig(**base)


def steps(tr, n):
    out = []
    for _ in range(n):
        tr.state, m = tr.train_step(
            tr.state, tr._step_x, tr._step_y, tr.dataset.shard_indices)
        out.append(float(m["train/loss"]))
    return out


def stream_steps(tr, n):
    return [float(tr._host_stream_step()["train/loss"]) for _ in range(n)]


class TestShardedPlacement:
    # parallelism-matrix compile cost blows the tier-1 budget
    pytestmark = pytest.mark.slow

    def test_matches_replicated_bitwise(self, mesh):
        rep = Trainer(cfg(), mesh=mesh)
        shd = Trainer(cfg(data_placement="sharded"), mesh=mesh)
        np.testing.assert_array_equal(steps(rep, 3), steps(shd, 3))

    def test_per_device_memory_is_shard_sized(self, mesh):
        shd = Trainer(cfg(data_placement="sharded"), mesh=mesh)
        full = np.asarray(shd.dataset.x_train).nbytes
        per_dev = shd._step_x.addressable_shards[0].data.nbytes
        # One cyclically-tiled shard row ≈ max-shard/N of the dataset —
        # strictly below half even with Dirichlet skew at W=4.
        assert per_dev < 0.5 * full, (per_dev, full)
        # The full train array stays host-side (numpy), not on a device.
        assert isinstance(shd.dataset.x_train, np.ndarray)

    def test_fit_eval_and_scan_compose(self, mesh):
        tr = Trainer(cfg(data_placement="sharded", scan_steps=3), mesh=mesh)
        out = tr.fit(num_epochs=1)
        assert np.isfinite(out["test/eval_loss"])
        assert int(tr.state.step) == 3

    def test_groupwise_and_pipelined_compose(self, mesh):
        for extra in ({"sampler": "groupwise"}, {"pipelined_scoring": True}):
            rep = Trainer(cfg(**extra), mesh=mesh)
            shd = Trainer(cfg(data_placement="sharded", **extra), mesh=mesh)
            np.testing.assert_array_equal(steps(rep, 2), steps(shd, 2))

    def test_unknown_placement_rejected(self, mesh):
        with pytest.raises(ValueError, match="data_placement"):
            Trainer(cfg(data_placement="nope"), mesh=mesh)


def hs_cfg(**kw):
    base = dict(model="smallcnn", dataset="synthetic", world_size=1,
                batch_size=8, presample_batches=2, steps_per_epoch=8,
                num_epochs=1, eval_every=0, log_every=0, heartbeat_every=0,
                checkpoint_every=0, compute_dtype="float32", seed=0)
    base.update(kw)
    return TrainConfig(**base)


class TestHostStream:
    """Tier-1: 1-device CPU mesh, small model — one compile per sampler."""

    # ISSUE acceptance: loss-trajectory-identical for >= 3 steps after
    # warmup. depth+4 = 6 steps covers cold-start AND steady state.
    N_STEPS = 6

    def _pair(self, mesh1, **kw):
        rep = Trainer(hs_cfg(**kw), mesh=mesh1)
        hs = Trainer(hs_cfg(data_placement="host_stream", prefetch_depth=2,
                            **kw), mesh=mesh1)
        return rep, hs

    def test_uniform_bitwise_identical(self, mesh1):
        rep, hs = self._pair(mesh1, use_importance_sampling=False)
        try:
            np.testing.assert_array_equal(
                steps(rep, self.N_STEPS), stream_steps(hs, self.N_STEPS))
        finally:
            hs.close()

    def test_pool_bitwise_identical(self, mesh1):
        rep, hs = self._pair(mesh1)
        try:
            np.testing.assert_array_equal(
                steps(rep, self.N_STEPS), stream_steps(hs, self.N_STEPS))
        finally:
            hs.close()

    def test_scoretable_smoke_and_telemetry(self, mesh1):
        hs = Trainer(hs_cfg(data_placement="host_stream", prefetch_depth=2,
                            sampler="scoretable"), mesh=mesh1)
        try:
            losses = stream_steps(hs, self.N_STEPS)
            assert np.all(np.isfinite(losses)), losses
            stats = hs._stream_pipe.stats()
            assert set(stats) == {"data/stall_s", "data/queue_depth",
                                  "data/h2d_bytes",
                                  "threads/queue_depth/prefetch"}
            # 6 batches streamed: prime pushed 2, each step pushed 1 more.
            assert stats["data/h2d_bytes"] > 0
            assert hs._stream_pipe.pops == self.N_STEPS
        finally:
            hs.close()

    def test_fit_streams_and_logs(self, mesh1):
        hs = Trainer(hs_cfg(data_placement="host_stream", steps_per_epoch=3),
                     mesh=mesh1)
        try:
            out = hs.fit(num_epochs=1)
            assert np.isfinite(out["test/eval_loss"])
            assert int(hs.state.step) == 3
        finally:
            hs.close()

    @pytest.mark.parametrize("bad", [
        dict(prefetch_depth=0),
        dict(pipelined_scoring=True),
        dict(score_refresh_every=2),
        dict(sampler="groupwise"),
        dict(scan_steps=3),
    ])
    def test_incompatible_configs_rejected(self, mesh1, bad):
        with pytest.raises(ValueError):
            Trainer(hs_cfg(data_placement="host_stream", **bad), mesh=mesh1)

    def test_restore_elastic_resumes_mid_epoch(self, tmp_path):
        """W=2 → W=1 elastic restore mid-stream: the shard-stream cursor
        carries as an epoch fraction (``config.stream_checkpoint_cursor``),
        the lookahead ring re-primes for the new topology, and training
        resumes with finite losses."""
        t1 = Trainer(hs_cfg(data_placement="host_stream", world_size=2,
                            checkpoint_dir=str(tmp_path)),
                     mesh=host_cpu_mesh(2))
        try:
            stream_steps(t1, 3)
            t1.save()
        finally:
            t1.close()

        t2 = Trainer(hs_cfg(data_placement="host_stream", world_size=1,
                            checkpoint_dir=str(tmp_path)),
                     mesh=host_cpu_mesh(1))
        try:
            fresh_cursor = np.asarray(t2.state.stream.cursor).copy()
            assert t2.restore_elastic() == 3
            assert int(t2.state.step) == 3
            carried = np.asarray(t2.state.stream.cursor)
            # A fresh trainer primes its ring from cursor 0; the elastic
            # carry resumes the shard sweep mid-epoch, so the re-primed
            # cursor sits strictly past the fresh-primed one.
            assert np.all(carried > fresh_cursor), (carried, fresh_cursor)
            losses = stream_steps(t2, 3)
            assert np.all(np.isfinite(losses)), losses
        finally:
            t2.close()

        # Gate off: stream_checkpoint_cursor=False restarts the sweep
        # near the epoch start (only the init + restore primes have
        # advanced it), well short of the mid-epoch carried cursor.
        t3 = Trainer(hs_cfg(data_placement="host_stream", world_size=1,
                            stream_checkpoint_cursor=False,
                            checkpoint_dir=str(tmp_path)),
                     mesh=host_cpu_mesh(1))
        try:
            t3.restore_elastic()
            assert np.all(np.asarray(t3.state.stream.cursor) < carried)
        finally:
            t3.close()

    def test_restore_elastic_carries_scoretable(self, tmp_path):
        """W=2 → W=1 elastic restore repartitions the per-sample score
        table by new worker ownership: every sample the old run owned
        keeps its learned score bit-exactly under the new ``[W', L']``
        index matrix (samples nobody owned warm-start at the EMA mean)."""
        from mercury_tpu.train.elastic import _shard_index_matrix

        t1 = Trainer(hs_cfg(data_placement="host_stream", world_size=2,
                            sampler="scoretable",
                            checkpoint_dir=str(tmp_path)),
                     mesh=host_cpu_mesh(2))
        try:
            stream_steps(t1, 3)
            t1.save()
            old_scores = np.asarray(
                jax.device_get(t1.state.scoretable.scores), np.float32)
            ema_val = float(np.mean(np.asarray(t1.state.ema.value)))
        finally:
            t1.close()
        # The old run actually trained its table (the in-step refresh ran)
        # — otherwise the carry equality below would hold vacuously.
        assert not np.all(old_scores == old_scores.reshape(-1)[0])

        t2 = Trainer(hs_cfg(data_placement="host_stream", world_size=1,
                            sampler="scoretable",
                            checkpoint_dir=str(tmp_path)),
                     mesh=host_cpu_mesh(1))
        try:
            assert t2.restore_elastic() == 3
            old_sidx = _shard_index_matrix(t2, 2)
            new_sidx = _shard_index_matrix(t2, 1)
            n = int(np.asarray(t2.dataset.y_train).size)
            want = np.full((n,), ema_val, np.float32)
            want[old_sidx.reshape(-1)] = old_scores.reshape(-1)
            np.testing.assert_array_equal(
                np.asarray(jax.device_get(t2.state.scoretable.scores)),
                want[new_sidx])
            losses = stream_steps(t2, 2)
            assert np.all(np.isfinite(losses)), losses
        finally:
            t2.close()

    def test_local_shard_mode_bitwise_identical(self, mesh1):
        """stream_shard_mode='local' forced in a single-process run takes
        the per-host slab + callback-assembly path (the multi-controller
        code) and must stay bit-identical to the replicated full-slab
        path."""
        rep = Trainer(hs_cfg(), mesh=mesh1)
        hs = Trainer(hs_cfg(data_placement="host_stream", prefetch_depth=2,
                            stream_shard_mode="local"), mesh=mesh1)
        try:
            assert hs._stream_local_workers is not None
            np.testing.assert_array_equal(
                steps(rep, self.N_STEPS), stream_steps(hs, self.N_STEPS))
        finally:
            hs.close()

    def test_bad_shard_mode_rejected(self, mesh1):
        with pytest.raises(ValueError, match="stream_shard_mode"):
            Trainer(hs_cfg(data_placement="host_stream",
                           stream_shard_mode="nope"), mesh=mesh1)


class TestFusedInput:
    """fused_input=True: the ``data.pipeline.augment_normalize`` ingest must
    replay the unfused normalize→augment trajectory BIT-identically — the
    kernel replays ``augment_batch``'s exact RNG consumption, so fusing is
    a pure lowering change, never a numerics change. Tier-1 pins the
    1-device stream paths; the world-4 matrix entry lives in
    ``TestHostStreamMatrix`` (slow)."""

    N_STEPS = 6

    def test_uniform_stream_fused_matches_replicated_unfused(self, mesh1):
        rep = Trainer(hs_cfg(use_importance_sampling=False), mesh=mesh1)
        hs = Trainer(hs_cfg(data_placement="host_stream", prefetch_depth=2,
                            fused_input=True,
                            use_importance_sampling=False), mesh=mesh1)
        try:
            np.testing.assert_array_equal(
                steps(rep, self.N_STEPS), stream_steps(hs, self.N_STEPS))
        finally:
            hs.close()

    def test_pool_stream_fused_matches_replicated_unfused(self, mesh1):
        rep = Trainer(hs_cfg(), mesh=mesh1)
        hs = Trainer(hs_cfg(data_placement="host_stream", prefetch_depth=2,
                            fused_input=True), mesh=mesh1)
        try:
            np.testing.assert_array_equal(
                steps(rep, self.N_STEPS), stream_steps(hs, self.N_STEPS))
        finally:
            hs.close()

    def test_scoretable_stream_fused_matches_unfused(self, mesh1):
        """Streamed scoretable is depth-stale vs replicated by design, so
        the invariant here is fused-stream == unfused-stream."""
        a = Trainer(hs_cfg(data_placement="host_stream", prefetch_depth=2,
                           sampler="scoretable"), mesh=mesh1)
        b = Trainer(hs_cfg(data_placement="host_stream", prefetch_depth=2,
                           sampler="scoretable", fused_input=True),
                    mesh=mesh1)
        try:
            np.testing.assert_array_equal(
                stream_steps(a, self.N_STEPS), stream_steps(b, self.N_STEPS))
        finally:
            a.close()
            b.close()

    @pytest.mark.parametrize("bad", [
        dict(cutout=True),
        dict(augmentation="iid"),
    ])
    def test_unfusable_configs_rejected(self, mesh1, bad):
        with pytest.raises(ValueError, match="fused_input"):
            Trainer(hs_cfg(fused_input=True, **bad), mesh=mesh1)


class TestHostStreamMatrix:
    """4-way parallelism matrix — compile cost belongs in the slow tier."""

    pytestmark = pytest.mark.slow

    @pytest.mark.parametrize("kw", [
        dict(use_importance_sampling=False),
        dict(),  # pool
    ])
    def test_w4_bitwise_identical(self, mesh, kw):
        rep = Trainer(cfg(steps_per_epoch=8, **kw), mesh=mesh)
        hs = Trainer(cfg(data_placement="host_stream", prefetch_depth=2,
                         steps_per_epoch=8, **kw), mesh=mesh)
        try:
            np.testing.assert_array_equal(steps(rep, 6), stream_steps(hs, 6))
        finally:
            hs.close()

    @pytest.mark.parametrize("kw", [
        dict(use_importance_sampling=False),
        dict(),  # pool
    ])
    def test_w4_fused_bitwise_identical(self, mesh, kw):
        rep = Trainer(cfg(steps_per_epoch=8, **kw), mesh=mesh)
        hs = Trainer(cfg(data_placement="host_stream", prefetch_depth=2,
                         fused_input=True, steps_per_epoch=8, **kw),
                     mesh=mesh)
        try:
            np.testing.assert_array_equal(steps(rep, 6), stream_steps(hs, 6))
        finally:
            hs.close()

    def test_w4_scoretable_runs(self, mesh):
        hs = Trainer(cfg(data_placement="host_stream", prefetch_depth=2,
                         sampler="scoretable", steps_per_epoch=8), mesh=mesh)
        try:
            losses = stream_steps(hs, 6)
            assert np.all(np.isfinite(losses)), losses
        finally:
            hs.close()

    def test_w4_depth3_uniform_identical(self, mesh):
        rep = Trainer(cfg(steps_per_epoch=8,
                          use_importance_sampling=False), mesh=mesh)
        hs = Trainer(cfg(data_placement="host_stream", prefetch_depth=3,
                         steps_per_epoch=8,
                         use_importance_sampling=False), mesh=mesh)
        try:
            np.testing.assert_array_equal(steps(rep, 6), stream_steps(hs, 6))
        finally:
            hs.close()


class TestPlacedAtConstruction:
    """``Trainer`` commits every step input and the whole state on the
    mesh BEFORE the first step. Left uncommitted on device 0 (how a
    single-process run used to start), a several-device run re-broadcasts
    the dataset from that device every step and compiles twice — once for
    the uncommitted inputs, once more when its own committed output feeds
    back ("placement settle"). Neither cost shows on the virtual CPU mesh,
    so the layout and the compile count are asserted directly."""

    @pytest.mark.parametrize("kw", [
        {},
        dict(zero_sharding=True, sampler="scoretable", refresh_size=8,
             telemetry=True),
        dict(data_placement="sharded"),
        dict(data_placement="host_stream"),
    ], ids=["pool", "zero-scoretable-ledger", "sharded", "host_stream"])
    def test_committed_before_step_one_and_one_compile(self, mesh, kw):
        from jax.sharding import NamedSharding

        from mercury_tpu.lint.tracecheck import CompileMonitor

        with Trainer(cfg(**kw), mesh=mesh) as tr:
            inputs = {"state": tr.state, "y": tr._step_y,
                      "shard_indices": tr.dataset.shard_indices}
            if tr._step_x is not None:  # host_stream pixels stay host-side
                inputs["x"] = tr._step_x
            for path, leaf in jax.tree_util.tree_flatten_with_path(inputs)[0]:
                where = jax.tree_util.keystr(path)
                assert isinstance(leaf.sharding, NamedSharding), where
                assert leaf.sharding.mesh.devices.size == 4, where
                assert len(leaf.sharding.device_set) == 4, where
                assert leaf.committed, where
            per_call = []
            with CompileMonitor() as monitor:
                for _ in range(4):
                    before = monitor.snapshot()[1]
                    if tr._step_x is None:
                        tr._host_stream_step()
                    else:
                        steps(tr, 1)
                    per_call.append(monitor.snapshot()[1] - before)
            assert per_call[0] >= 1          # the one compile
            assert per_call[1:] == [0, 0, 0]  # no "placement settle"

    def test_restore_recommits_zero_opt_state_chunked(self, mesh, tmp_path):
        """restore() re-places the state in the step's own layout —
        under ZeRO-1 the optimizer state is chunk-sharded over the data
        axis, not replicated (which the next step would have to reshard
        and recompile for)."""
        from mercury_tpu.lint.tracecheck import CompileMonitor

        with Trainer(cfg(zero_sharding=True), mesh=mesh) as tr:
            steps(tr, 2)
            tr.save(str(tmp_path))
            tr.restore(str(tmp_path))
            specs = {str(leaf.sharding.spec) for leaf in
                     jax.tree_util.tree_leaves(tr.state.opt_state)}
            assert specs == {"PartitionSpec('data',)"}
            with CompileMonitor() as monitor:
                steps(tr, 1)
            assert monitor.compiles == 0


def _old_chain_ingest(key, raw, mean, std, pad=4, out_dtype=None,
                      image_shape=None):
    """The ingest ``make_train_step`` had before the selection pass:
    ``normalize_images`` then ``augment_batch`` (pad, two gathers, flip) on
    ``[N, H, W, C]``; flat rows are reshaped first. Kept here as the
    reference the new step's state is held to."""
    import jax.numpy as jnp
    from mercury_tpu.data.pipeline import augment_batch, normalize_images

    if raw.ndim == 2:
        raw = raw.reshape((raw.shape[0],) + tuple(image_shape))
    out = augment_batch(key, normalize_images(raw, mean, std), pad=pad)
    return out if out_dtype is None else out.astype(out_dtype)


def _state_leaves(state):
    return [np.asarray(jax.random.key_data(x)
                       if jax.dtypes.issubdtype(x.dtype, jax.dtypes.prng_key)
                       else x)
            for x in jax.tree_util.tree_leaves(state)]


class TestSelectIngestTrajectory:
    """The one-pass uint8 ingest inside the step: after two steps the whole
    state (parameters, Adam moments, the pending batch's f32 images, EMA,
    stream, rng) is BITWISE the state of a step built on the chain it
    replaced, on every placement. The old chain is swapped in where the
    step looks its ingest up, so everything else is the same program."""

    @pytest.mark.parametrize("placement", [
        "replicated", "sharded", "host_stream"])
    def test_two_steps_bitwise_old_chain(self, mesh1, monkeypatch, placement):
        import mercury_tpu.train.stages as step_mod  # the ingest stage

        # host_stream pipelines its own selection; the others pipeline
        # the scoring (the benchmark cell's path: state.pending.images).
        kw = (dict(data_placement="host_stream", prefetch_depth=2)
              if placement == "host_stream"
              else dict(data_placement=placement, pipelined_scoring=True))
        run = stream_steps if placement == "host_stream" else steps

        def two_steps():
            tr = Trainer(hs_cfg(**kw), mesh=mesh1)
            try:
                losses = run(tr, 2)
                return tr, losses, _state_leaves(tr.state)
            finally:
                tr.close()

        new, new_losses, new_leaves = two_steps()
        assert new._ingest_path == "select"
        if placement != "host_stream":
            assert new._step_x.shape[-1] == 32 * 32 * 3   # flat rows
            assert new.state.pending.images.shape[-3:] == (32, 32, 3)
            assert new.state.pending.images.dtype == np.float32
        assert new.dataset.x_train.shape[1:] == (32, 32, 3)
        traced = []

        def old_chain(*args, **kwargs):
            traced.append(args[1].shape)
            return _old_chain_ingest(*args, **kwargs)

        monkeypatch.setattr(step_mod, "augment_normalize", old_chain)
        _, old_losses, old_leaves = two_steps()
        assert traced, "the reference step did not trace the old chain"
        assert new_losses == old_losses
        assert len(new_leaves) == len(old_leaves)
        for a, b in zip(new_leaves, old_leaves):
            np.testing.assert_array_equal(a, b)

    @pytest.mark.parametrize("fields, path, rows", [
        (dict(), "select", "flat"),
        (dict(cutout=True), "chain", "nhwc"),
    ])
    def test_the_instant_says_which_ingest_the_step_is_built_with(
            self, mesh1, fields, path, rows):
        """``trace=True`` leaves one ``trainer/ingest_path`` instant, what
        ``_ingest_path`` says: no metric reads it, this test holds it."""
        with Trainer(hs_cfg(trace=True, **fields), mesh=mesh1) as tr:
            marks = [e["args"] for e in tr.tracer.snapshot()
                     if e["name"] == "trainer/ingest_path"]
            assert [(m["path"], m["rows"]) for m in marks] == [(path, rows)]
            assert tr._ingest_path == path
