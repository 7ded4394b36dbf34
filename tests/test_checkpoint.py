"""Checkpoint/resume tests: the full MercuryState (params, opt, BN, EMA,
streams, RNG) roundtrips and training resumes deterministically — the
capability the reference lacks entirely (SURVEY.md §5: no torch.save
anywhere)."""

import jax
import numpy as np
import pytest

from mercury_tpu.config import TrainConfig
from mercury_tpu.parallel.mesh import host_cpu_mesh
from mercury_tpu.train import latest_step, restore_checkpoint, save_checkpoint
from mercury_tpu.train.trainer import Trainer


@pytest.fixture(scope="module")
def mesh():
    return host_cpu_mesh(8)


def tiny(**kw):
    base = dict(model="smallcnn", dataset="synthetic", world_size=8,
                batch_size=4, presample_batches=2, steps_per_epoch=3,
                num_epochs=1, eval_every=0, log_every=0,
                compute_dtype="float32", seed=0)
    base.update(kw)
    return TrainConfig(**base)


def run_steps(tr, n):
    out = []
    for _ in range(n):
        tr.state, m = tr.train_step(
            tr.state, tr.dataset.x_train, tr.dataset.y_train,
            tr.dataset.shard_indices,
        )
        out.append(float(m["train/loss"]))
    return out


class TestCheckpointRoundtrip:
    def test_save_restore_preserves_state(self, mesh, tmp_path):
        tr = Trainer(tiny(), mesh=mesh)
        run_steps(tr, 2)
        ema_before = np.asarray(tr.state.ema.value).copy()
        save_checkpoint(str(tmp_path), tr.state, int(tr.state.step))
        assert latest_step(str(tmp_path)) == 2
        restored, step = restore_checkpoint(str(tmp_path), tr.state)
        assert step == 2
        np.testing.assert_array_equal(np.asarray(restored.ema.value), ema_before)
        p0 = jax.tree_util.tree_leaves(tr.state.params)[0]
        r0 = jax.tree_util.tree_leaves(restored.params)[0]
        np.testing.assert_array_equal(np.asarray(p0), np.asarray(r0))

    def test_resume_is_deterministic(self, mesh, tmp_path):
        """Train 4 steps straight vs. train 2 → checkpoint → restore into a
        FRESH trainer → train 2 more: identical losses (sampler RNG +
        streams + EMA all in the checkpoint)."""
        cfg = tiny()
        tr_a = Trainer(cfg, mesh=mesh)
        losses_a = run_steps(tr_a, 4)

        tr_b = Trainer(cfg, mesh=mesh)
        run_steps(tr_b, 2)
        save_checkpoint(str(tmp_path), tr_b.state, 2)

        tr_c = Trainer(cfg, mesh=mesh)
        tr_c.state, _ = restore_checkpoint(str(tmp_path), tr_c.state)
        losses_c = run_steps(tr_c, 2)
        np.testing.assert_allclose(losses_c, losses_a[2:], rtol=1e-5)

    def test_pipelined_resume_is_deterministic(self, mesh, tmp_path):
        """The carried PendingBatch (pipelined scoring) is part of the
        checkpoint: resume mid-pipeline reproduces the straight run."""
        cfg = tiny(pipelined_scoring=True)
        tr_a = Trainer(cfg, mesh=mesh)
        losses_a = run_steps(tr_a, 4)

        tr_b = Trainer(cfg, mesh=mesh)
        run_steps(tr_b, 2)
        save_checkpoint(str(tmp_path), tr_b.state, 2)

        tr_c = Trainer(cfg, mesh=mesh)
        tr_c.state, _ = restore_checkpoint(str(tmp_path), tr_c.state)
        losses_c = run_steps(tr_c, 2)
        np.testing.assert_allclose(losses_c, losses_a[2:], rtol=1e-5)

    def test_multiple_checkpoints_latest_wins(self, mesh, tmp_path):
        tr = Trainer(tiny(), mesh=mesh)
        save_checkpoint(str(tmp_path), tr.state, 1)
        run_steps(tr, 1)
        save_checkpoint(str(tmp_path), tr.state, 5)
        assert latest_step(str(tmp_path)) == 5

    def test_restore_missing_raises(self, tmp_path):
        with pytest.raises(FileNotFoundError):
            restore_checkpoint(str(tmp_path / "nope"), {})


class TestCrashSafety:
    """A SIGKILL mid-write (the exact scenario auto_resume targets) must
    never cost the run more than one checkpoint interval."""

    def test_msgpack_write_is_atomic(self, tmp_path):
        """_write_msgpack stages through a .tmp + os.replace; a crash
        mid-serialize leaves only the stray temp, which latest_step and
        the restore scan both ignore."""
        from mercury_tpu.train import checkpoint as ckpt

        state = {"w": np.arange(4, dtype=np.float32)}
        ckpt._write_msgpack(str(tmp_path / "ckpt_3"), state)
        assert (tmp_path / "ckpt_3.msgpack").exists()
        assert not (tmp_path / "ckpt_3.msgpack.tmp").exists()
        # Simulate a crash that left a half-written temp for a NEWER step:
        (tmp_path / "ckpt_9.msgpack.tmp").write_bytes(b"\x81partial")
        assert latest_step(str(tmp_path)) == 3
        restored, step = restore_checkpoint(str(tmp_path), state)
        assert step == 3
        np.testing.assert_array_equal(restored["w"], state["w"])

    def test_corrupt_latest_falls_back_to_older(self, mesh, tmp_path):
        """auto_resume path: latest checkpoint truncated (pre-atomic-write
        crash or torn filesystem) → restore skips it with a warning and
        loads the next-older step instead of aborting."""
        tr = Trainer(tiny(), mesh=mesh)
        run_steps(tr, 1)
        save_checkpoint(str(tmp_path), tr.state, 1)
        run_steps(tr, 1)
        save_checkpoint(str(tmp_path), tr.state, 2)
        newest = tmp_path / "ckpt_2.msgpack"
        if newest.exists():  # msgpack fallback backend — truncate in place
            data = newest.read_bytes()
            newest.write_bytes(data[: len(data) // 2])
        else:  # orbax backend writes a directory — replace with a torn file
            import shutil

            shutil.rmtree(tmp_path / "ckpt_2")
            (tmp_path / "ckpt_2.msgpack").write_bytes(b"\x81torn")
        restored, step = restore_checkpoint(str(tmp_path), tr.state)
        assert step == 1

    def test_all_corrupt_raises(self, tmp_path):
        from mercury_tpu.train import checkpoint as ckpt

        (tmp_path / "ckpt_1.msgpack").write_bytes(b"garbage")
        with pytest.raises(RuntimeError, match="failed to restore"):
            ckpt.restore_checkpoint(str(tmp_path), {"w": np.zeros(2)})

    def test_explicit_step_never_falls_back(self, tmp_path):
        from mercury_tpu.train import checkpoint as ckpt

        (tmp_path / "ckpt_2.msgpack").write_bytes(b"garbage")
        with pytest.raises(Exception):
            ckpt.restore_checkpoint(str(tmp_path), {"w": np.zeros(2)}, step=2)


class TestProfile:
    def test_trace_context_writes_profile(self, tmp_path):
        """jax.profiler trace wrapper produces trace artifacts."""
        import jax.numpy as jnp

        from mercury_tpu.train.profile import trace

        with trace(str(tmp_path)):
            jnp.ones((8, 8)).sum().block_until_ready()
        dumped = list(tmp_path.rglob("*"))
        assert dumped, "no profiler output written"


class TestAsyncCheckpoint:
    def test_async_save_roundtrips(self, tmp_path):
        """Background-written checkpoint restores bit-identically; fit()
        with async_checkpoint joins all writes before returning."""
        from mercury_tpu.config import TrainConfig
        from mercury_tpu.parallel.mesh import host_cpu_mesh
        from mercury_tpu.train import checkpoint as ckpt
        from mercury_tpu.train.trainer import Trainer

        cfg = TrainConfig(
            model="smallcnn", dataset="synthetic", world_size=4, batch_size=4,
            presample_batches=2, steps_per_epoch=6, num_epochs=1,
            checkpoint_dir=str(tmp_path), checkpoint_every=3,
            async_checkpoint=True, eval_every=0, log_every=0,
            compute_dtype="float32", seed=0,
        )
        tr = Trainer(cfg, mesh=host_cpu_mesh(4))
        tr.fit()
        # Cadence checkpoints at 3 and 6 plus the final sync save.
        assert ckpt.latest_step(str(tmp_path)) == 6
        tr2 = Trainer(cfg.replace(auto_resume=True), mesh=host_cpu_mesh(4))
        assert int(tr2.state.step) == 6
        for a, b in zip(jax.tree_util.tree_leaves(tr.state.params),
                        jax.tree_util.tree_leaves(tr2.state.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_async_thread_api(self, tmp_path):
        from mercury_tpu.config import TrainConfig
        from mercury_tpu.parallel.mesh import host_cpu_mesh
        from mercury_tpu.train import checkpoint as ckpt
        from mercury_tpu.train.trainer import Trainer

        cfg = TrainConfig(
            model="smallcnn", dataset="synthetic", world_size=4, batch_size=4,
            presample_batches=2, steps_per_epoch=1, num_epochs=1,
            eval_every=0, log_every=0, compute_dtype="float32", seed=0,
        )
        tr = Trainer(cfg, mesh=host_cpu_mesh(4))
        th = ckpt.save_checkpoint_async(str(tmp_path), tr.state, 0)
        assert th is not None
        th.join()
        restored, step = ckpt.restore_checkpoint(str(tmp_path), tr.state, 0)
        assert step == 0
        for a, b in zip(jax.tree_util.tree_leaves(tr.state.params),
                        jax.tree_util.tree_leaves(restored.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestDurability:
    """The fault-tolerant write/restore stack: sha256 manifest sidecars,
    verified restore with bit-identical fallback, transient-OSError
    retries (counted in ``checkpoint/write_failures``), and keep_n
    pruning."""

    def _state(self, seed=0):
        rng = np.random.default_rng(seed)
        return {"w": rng.normal(size=(4, 3)).astype(np.float32),
                "b": rng.normal(size=(3,)).astype(np.float32)}

    def test_manifest_sidecar_written_and_verified(self, tmp_path):
        import json

        from mercury_tpu.train import checkpoint as ckpt

        state = self._state()
        ckpt.save_checkpoint(str(tmp_path), state, 7, manifest=True)
        man = tmp_path / "ckpt_7.manifest.json"
        assert man.exists()
        doc = json.loads(man.read_text())
        assert doc["schema"] == "mercury-ckpt-manifest-v1"
        assert doc["step"] == 7
        assert doc["bytes"] == (tmp_path / "ckpt_7.msgpack").stat().st_size
        assert set(doc["leaves"]) == {"['b']", "['w']"}
        restored, step = ckpt.restore_checkpoint(
            str(tmp_path), state, verify=True)
        assert step == 7
        np.testing.assert_array_equal(restored["w"], state["w"])

    def test_bitflip_detected_falls_back_bit_identically(self, tmp_path):
        """A single flipped byte in the NEWEST checkpoint (which still
        deserializes — the silent-corruption case a torn-file check
        misses) is caught by the manifest digest; restore falls back to
        the older generation BIT-identically."""
        from mercury_tpu.train import checkpoint as ckpt

        old, new = self._state(1), self._state(2)
        ckpt.save_checkpoint(str(tmp_path), old, 1, manifest=True)
        ckpt.save_checkpoint(str(tmp_path), new, 2, manifest=True)
        blob = bytearray((tmp_path / "ckpt_2.msgpack").read_bytes())
        blob[len(blob) // 2] ^= 0xFF
        (tmp_path / "ckpt_2.msgpack").write_bytes(bytes(blob))
        restored, step = ckpt.restore_checkpoint(
            str(tmp_path), old, verify=True)
        assert step == 1
        np.testing.assert_array_equal(restored["w"], old["w"])
        np.testing.assert_array_equal(restored["b"], old["b"])
        # verify=False restores whatever deserializes — the knob exists,
        # and it is what makes the verified path's rejection observable.
        with pytest.raises(ValueError, match="sha256 mismatch"):
            ckpt._restore_one(str(tmp_path), old, 2, verify=True)

    def test_per_leaf_digest_localizes_corruption(self, tmp_path):
        """Whole-file sha passing but a leaf digest failing (a tampered
        or bit-rotted manifest entry) still rejects the candidate, and
        the error NAMES the leaf."""
        import json

        from mercury_tpu.train import checkpoint as ckpt

        state = self._state()
        ckpt.save_checkpoint(str(tmp_path), state, 3, manifest=True)
        man = tmp_path / "ckpt_3.manifest.json"
        doc = json.loads(man.read_text())
        doc["leaves"]["['w']"] = "0" * 64
        man.write_text(json.dumps(doc))
        with pytest.raises(ValueError, match=r"\['w'\]. sha256 mismatch"):
            ckpt._restore_one(str(tmp_path), state, 3, verify=True)

    def test_missing_manifest_restores_unverified(self, tmp_path):
        """Back-compat: checkpoints without a sidecar (every pre-manifest
        generation) restore exactly as before."""
        from mercury_tpu.train import checkpoint as ckpt

        state = self._state()
        ckpt._write_msgpack(str(tmp_path / "ckpt_4"), state)
        restored, step = ckpt.restore_checkpoint(
            str(tmp_path), state, verify=True)
        assert step == 4
        np.testing.assert_array_equal(restored["w"], state["w"])

    def test_keep_n_prunes_payload_and_sidecar(self, tmp_path):
        from mercury_tpu.train import checkpoint as ckpt

        state = self._state()
        for step in (1, 2, 3, 4):
            ckpt.save_checkpoint(str(tmp_path), state, step, keep=2,
                                 manifest=True)
        assert ckpt.all_steps(str(tmp_path)) == [3, 4]
        names = {p.name for p in tmp_path.iterdir()}
        assert names == {"ckpt_3.msgpack", "ckpt_3.manifest.json",
                         "ckpt_4.msgpack", "ckpt_4.manifest.json"}

    def test_retry_absorbs_transient_failure_and_counts_it(self, tmp_path):
        from mercury_tpu.faults import FaultPlane
        from mercury_tpu.train import checkpoint as ckpt

        fp = FaultPlane("ckpt_io_error@step=0")
        fp.note_step(0)
        before = ckpt.write_failures()
        ckpt.save_checkpoint(str(tmp_path), self._state(), 5, retries=1,
                             retry_backoff_s=0.01, manifest=True, faults=fp)
        assert (tmp_path / "ckpt_5.msgpack").exists()
        assert ckpt.write_failures() == before + 1
        assert fp.stats()["fault/injected"] == 1.0

    def test_retries_exhausted_raises_with_all_attempts_counted(
            self, tmp_path):
        from mercury_tpu.faults import FaultPlane
        from mercury_tpu.train import checkpoint as ckpt

        # Two one-shot schedules: one per attempt — the retry loop's
        # second try hits the second injection and gives up.
        fp = FaultPlane("ckpt_io_error@step=0;ckpt_io_error@step=0")
        fp.note_step(0)
        before = ckpt.write_failures()
        with pytest.raises(OSError, match="ckpt_io_error"):
            ckpt.save_checkpoint(str(tmp_path), self._state(), 6, retries=1,
                                 retry_backoff_s=0.01, manifest=True,
                                 faults=fp)
        assert ckpt.write_failures() == before + 2
        assert not (tmp_path / "ckpt_6.msgpack").exists()
        assert not (tmp_path / "ckpt_6.msgpack.tmp").exists()

    def test_async_failure_cb_fires_and_join_reraises(self, tmp_path):
        from mercury_tpu.faults import FaultPlane
        from mercury_tpu.train import checkpoint as ckpt

        fp = FaultPlane("ckpt_io_error@step=0")
        fp.note_step(0)
        seen = []
        th = ckpt.save_checkpoint_async(
            str(tmp_path), self._state(), 8, retries=0, faults=fp,
            failure_cb=seen.append)
        with pytest.raises(OSError, match="ckpt_io_error"):
            th.join()
        assert th.done() and th.failed() is not None
        (exc,) = seen
        assert isinstance(exc, OSError)
        assert not (tmp_path / "ckpt_8.msgpack.tmp").exists()

    def test_trainer_cadence_writes_verified_manifests(self, mesh, tmp_path):
        """fit() with the config durability defaults (manifest=True,
        keep, retries) writes sidecars on the checkpoint cadence and
        the final state restores verified."""
        from mercury_tpu.train import checkpoint as ckpt

        cfg = tiny(steps_per_epoch=4, checkpoint_dir=str(tmp_path),
                   checkpoint_every=2, checkpoint_keep=2)
        tr = Trainer(cfg, mesh=mesh)
        tr.fit()
        assert (tmp_path / "ckpt_4.manifest.json").exists()
        assert len(ckpt.all_steps(str(tmp_path))) <= 2
        restored, step = ckpt.restore_checkpoint(str(tmp_path), tr.state,
                                                 verify=True)
        assert step == 4
        for a, b in zip(jax.tree_util.tree_leaves(tr.state.params),
                        jax.tree_util.tree_leaves(restored.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestUpgradeShims:
    """State-schema lineage (graftlint Layer E contract): every vintage
    reaches HEAD through the shim chain, and a checkpoint from a NEWER
    schema fails loudly instead of silently dropping state."""

    def _template(self, mesh):
        import jax.numpy as jnp
        tr = Trainer(tiny(), mesh=mesh)
        return tr.state.replace(
            pending_sel=np.zeros((2, 4), np.int32),
            sel_counts=jnp.zeros((8, 4), jnp.int32))

    def test_v1_raw_restores_through_both_shims(self, mesh):
        """A v1-vintage checkpoint (predates pending_sel AND sel_counts)
        restored into a HEAD template walks two shims: both fields drop
        from the template so restore proceeds with fresh inits."""
        from mercury_tpu.train import checkpoint as ckpt

        template = self._template(mesh)
        raw = {"step": 0, "params": {}}  # v1 shape: neither field
        out = ckpt.apply_upgrade_shims(raw, template)
        assert out.pending_sel is None
        assert out.sel_counts is None
        # Untouched fields keep the template's values.
        assert out.step is template.step

    def test_shims_are_idempotent_on_head_checkpoints(self, mesh):
        """A raw tree that already carries the fields passes through
        untouched — the chain is walked unconditionally, so HEAD
        checkpoints must survive every shim."""
        from mercury_tpu.train import checkpoint as ckpt

        template = self._template(mesh)
        raw = {"step": 0, "pending_sel": 1, "sel_counts": 1}
        out = ckpt.apply_upgrade_shims(raw, template)
        assert out.pending_sel is not None
        assert out.sel_counts is not None

    def test_v2_raw_walks_only_the_second_shim(self, mesh):
        from mercury_tpu.train import checkpoint as ckpt

        template = self._template(mesh)
        raw = {"step": 0, "pending_sel": 1}  # v2_cursor vintage
        out = ckpt.apply_upgrade_shims(raw, template)
        assert out.pending_sel is not None
        assert out.sel_counts is None

    def test_unknown_future_field_fails_loudly(self, mesh):
        """A checkpoint written by a newer schema carries a field this
        build has never heard of: refuse with a ValueError that names
        it — NEVER restore-and-drop."""
        from mercury_tpu.train import checkpoint as ckpt

        template = self._template(mesh)
        raw = {"step": 0, "future_fp8_scale": 7}
        with pytest.raises(ValueError, match="future_fp8_scale"):
            ckpt.apply_upgrade_shims(raw, template)

    def test_version_literal_is_lineage_head(self):
        from mercury_tpu.train import checkpoint as ckpt

        assert ckpt.STATE_SCHEMA_VERSION == ckpt.STATE_SCHEMA_LINEAGE[-1][0]
        pairs = list(zip([v for v, _ in ckpt.STATE_SCHEMA_LINEAGE],
                         [v for v, _ in ckpt.STATE_SCHEMA_LINEAGE][1:]))
        assert set(ckpt.UPGRADE_SHIMS) == set(pairs)

    def test_manifest_stamps_state_schema_sha(self, mesh, tmp_path):
        """Every new manifest carries the schema sha of the committed
        golden, so restore can flag drift across builds."""
        import json as _json

        from mercury_tpu.train import checkpoint as ckpt

        tr = Trainer(tiny(), mesh=mesh)
        run_steps(tr, 1)
        ckpt.save_checkpoint(str(tmp_path), tr.state, 1, manifest=True)
        doc = _json.loads((tmp_path / "ckpt_1.manifest.json").read_text())
        assert doc["state_schema_sha"] == ckpt.state_schema_sha()
        assert doc["state_schema_sha"] is not None


class TestSweepStaleTmps:
    """Crash-orphan cleanup: only OLD .msgpack.tmp strays are swept —
    a concurrent writer's in-flight tmp must never be unlinked."""

    def _tmp(self, d, name, age_secs):
        import time as _time
        path = d / name
        path.write_bytes(b"x")
        old = _time.time() - age_secs
        import os as _os
        _os.utime(str(path), (old, old))
        return path

    def test_age_boundary(self, tmp_path):
        from mercury_tpu.train.checkpoint import _sweep_stale_tmps

        stale = self._tmp(tmp_path, "ckpt_3.msgpack.tmp", 400.0)
        at_boundary = self._tmp(tmp_path, "ckpt_4.msgpack.tmp", 301.0)
        fresh = self._tmp(tmp_path, "ckpt_5.msgpack.tmp", 0.0)
        _sweep_stale_tmps(str(tmp_path))
        assert not stale.exists()
        assert not at_boundary.exists()  # >= min_age: crash orphan
        assert fresh.exists()            # concurrent writer: untouched

    def test_non_tmp_files_never_swept(self, tmp_path):
        from mercury_tpu.train.checkpoint import _sweep_stale_tmps

        payload = self._tmp(tmp_path, "ckpt_1.msgpack", 9999.0)
        sidecar = self._tmp(tmp_path, "ckpt_1.manifest.json", 9999.0)
        _sweep_stale_tmps(str(tmp_path), min_age_secs=1.0)
        assert payload.exists()
        assert sidecar.exists()

    def test_custom_min_age(self, tmp_path):
        from mercury_tpu.train.checkpoint import _sweep_stale_tmps

        young = self._tmp(tmp_path, "a.msgpack.tmp", 5.0)
        _sweep_stale_tmps(str(tmp_path), min_age_secs=60.0)
        assert young.exists()
        _sweep_stale_tmps(str(tmp_path), min_age_secs=1.0)
        assert not young.exists()

    def test_non_zero_process_never_sweeps(self, tmp_path, monkeypatch):
        """Only process 0 cleans the (shared) directory — N hosts racing
        unlinks would multiply the very race the age gate closes."""
        from mercury_tpu.train import checkpoint as ckpt_mod

        stale = self._tmp(tmp_path, "a.msgpack.tmp", 9999.0)
        monkeypatch.setattr(ckpt_mod.jax, "process_index", lambda: 1)
        ckpt_mod._sweep_stale_tmps(str(tmp_path), min_age_secs=1.0)
        assert stale.exists()

    def test_missing_directory_is_a_no_op(self, tmp_path):
        from mercury_tpu.train.checkpoint import _sweep_stale_tmps

        _sweep_stale_tmps(str(tmp_path / "never_created"))  # no raise
