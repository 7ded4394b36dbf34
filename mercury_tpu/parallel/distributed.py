"""Multi-host (pod / multi-slice) support.

The reference's distributed backend is ``dist.init_process_group('gloo')``
over localhost with a hardcoded master address/port
(``pytorch_collab.py:269-276``) — single-node only, and every collective is
a host-side TCP round trip. The TPU-native backend is
``jax.distributed.initialize`` + one global ``Mesh`` spanning all hosts'
devices: collectives are compiled into the step and ride ICI within a slice
and DCN across slices, with no per-step host involvement.

Multi-host data loading parity: ``load_partition_data_distributed_cifar10``
(``cifar10/data_loader.py:214-245``) gives each process only its own
shard's loaders. :func:`host_worker_slice` is the SPMD analogue — which
rows of the ``[W, L]`` shard-index matrix this host's devices own — so each
host materializes only its local shard data when the dataset is too big to
replicate.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from mercury_tpu.parallel.mesh import make_mesh

#: SHARDING CONTRACT (enforced by graftlint Layer 3, lint/sharding.py):
#: multi-host placement promises. Global arrays are assembled from
#: per-host shards with explicit NamedShardings (GL111: no bare
#: device_put); the global mesh's data axis spans all hosts, so the
#: in-graph collectives of the single-host plans carry over unchanged.
SHARDING_CONTRACT = {
    "global batch": "P(data) over the pod-wide mesh",
    "host slices": "host_worker_slice rows only — no cross-host gather",
    "params": "replicated (or fsdp/tp shardings from their modules)",
}


def initialize(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Initialize the JAX distributed runtime for multi-host pods.

    On Cloud TPU all three arguments are discovered from the environment
    (``jax.distributed.initialize()`` with no args); pass them explicitly
    for manual clusters. Idempotent: repeated calls are no-ops.
    """
    try:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
    except RuntimeError as e:  # already initialized
        if "already" not in str(e).lower():
            raise


def global_mesh(axis_name: str = "data") -> Mesh:
    """1-D data-parallel mesh over every device of every host. XLA routes
    the psum over ICI within a slice and DCN across slices; no code
    difference."""
    return make_mesh(axis_name=axis_name, devices=jax.devices())


def process_info() -> Tuple[int, int]:
    """(process_index, process_count) — the SPMD analogue of the
    reference's (rank, world_size) from gloo (``pytorch_collab.py:44-45``),
    but per *host*, not per worker: workers are mesh positions."""
    return jax.process_index(), jax.process_count()


def make_global_array(value: Any, mesh: Mesh, spec: P) -> jax.Array:
    """Host value → global ``jax.Array`` with ``NamedSharding(mesh, spec)``.

    The multi-controller placement primitive: every process must call this
    with the **identical** full value (true for anything derived
    deterministically from the config seed — ``create_state``, the
    partitioner); each process then keeps only its addressable shards.
    Typed PRNG key arrays are handled by round-tripping through
    ``key_data``/``wrap_key_data``.
    """
    if hasattr(value, "dtype") and jax.dtypes.issubdtype(
        value.dtype, jax.dtypes.prng_key
    ):
        impl = jax.random.key_impl(value)
        data = np.asarray(jax.random.key_data(value))
        return jax.random.wrap_key_data(
            _from_host(data, mesh, spec), impl=impl
        )
    return _from_host(np.asarray(value), mesh, spec)


def _from_host(value: np.ndarray, mesh: Mesh, spec: P) -> jax.Array:
    sharding = NamedSharding(mesh, spec)
    return jax.make_array_from_callback(
        value.shape, sharding, lambda idx: value[idx]
    )


def globalize_state(state, mesh: Mesh, axis_name: str = "data",
                    zero_sharding: bool = False,
                    params_sharding=None, opt_sharding=None):
    """Re-place a host-created ``MercuryState`` as global arrays on a
    (possibly multi-process) mesh: model/optimizer state replicated,
    per-worker sampler state (EMA/streams/RNG/groupwise/pending/
    cached-pool) sharded along ``axis_name`` — the multi-controller twin
    of ``train.step._state_specs``. Under ZeRO-1 (``zero_sharding``) the
    optimizer state is chunk-sharded along ``axis_name`` too, matching the
    step's specs (each host only materializes its workers' moment chunks).
    Each process must hold the identical host state (``create_state`` is
    deterministic in the seed), mirroring the reference's implicit
    same-seed init before ``average_model`` (``pytorch_collab.py:84-87``).

    ``params_sharding``/``opt_sharding``: optional trees of committed
    ``NamedSharding`` leaves (the tensor-parallel Megatron layout,
    ``parallel/tensor.py``) — with them the model state is placed in that
    layout instead of replicated, which is what lets dp×tp run
    multi-controller: every process holds the same full host value and
    materializes only its addressable shards of the TP split. A ``None``
    ``opt_state`` (deferred TP optimizer init) passes through — the
    caller inits it from the placed params."""
    rep = lambda t: jax.tree.map(lambda x: make_global_array(x, mesh, P()), t)
    shd = lambda t: jax.tree.map(
        lambda x: make_global_array(x, mesh, P(axis_name)), t
    )

    def committed(t, sh_tree):
        # NamedSharding is not a pytree node, so each spec arrives whole.
        return jax.tree.map(
            lambda x, sh: jax.make_array_from_callback(
                np.shape(x), sh, lambda idx: np.asarray(x)[idx]
            ),
            t, sh_tree,
        )

    if params_sharding is not None:
        params = committed(state.params, params_sharding)
    else:
        params = rep(state.params)
    if state.opt_state is None:
        opt_state = None
    elif opt_sharding is not None:
        opt_state = committed(state.opt_state, opt_sharding)
    elif zero_sharding:
        opt_state = shd(state.opt_state)
    else:
        opt_state = rep(state.opt_state)
    return state.replace(
        step=make_global_array(state.step, mesh, P()),
        params=params,
        batch_stats=rep(state.batch_stats),
        opt_state=opt_state,
        ema=shd(state.ema),
        stream=shd(state.stream),
        rng=shd(state.rng),
        groupwise=None if state.groupwise is None else shd(state.groupwise),
        pending=None if state.pending is None else shd(state.pending),
        cached_pool=(None if state.cached_pool is None
                     else shd(state.cached_pool)),
        scoretable=(None if state.scoretable is None
                    else shd(state.scoretable)),
        pending_sel=(None if state.pending_sel is None
                     else shd(state.pending_sel)),
    )


def globalize_dataset(dataset, mesh: Mesh, axis_name: str = "data",
                      include_train_arrays: bool = True):
    """Re-place a ``ShardedDataset``'s train-step inputs as global arrays:
    the full train arrays replicated, the ``[W, L]`` shard-index matrix
    sharded along ``axis_name`` (each host only stores its workers' rows
    on its devices — the SPMD analogue of
    ``load_partition_data_distributed_cifar10``).

    ``include_train_arrays=False`` (the ``data_placement="sharded"`` path)
    leaves x_train/y_train as host arrays — the step consumes the
    materialized per-worker arrays from :func:`worker_shard_global_arrays`
    instead, and eval reads the host copy."""
    replaced = dict(
        shard_indices=make_global_array(dataset.shard_indices, mesh,
                                        P(axis_name)),
        shard_sizes=make_global_array(dataset.shard_sizes, mesh,
                                      P(axis_name)),
    )
    if include_train_arrays:
        replaced.update(
            x_train=make_global_array(dataset.x_train, mesh, P()),
            y_train=make_global_array(dataset.y_train, mesh, P()),
        )
    return dataclasses.replace(dataset, **replaced)


def worker_shard_global_arrays(
    dataset, mesh: Mesh, axis_name: str = "data", flat_rows: bool = False
) -> Tuple[jax.Array, jax.Array]:
    """Materialize the per-worker train data as ``[W, L, ...]`` global
    arrays sharded ``P(axis_name)`` — each host constructs and transfers
    ONLY the rows its devices own (``host_worker_slice``), so no device
    and no host→device path ever carries the full dataset. This is the
    scaling-past-CIFAR data path (``data_placement="sharded"``),
    capability parity with ``load_partition_data_distributed_cifar10``
    (``cifar10/data_loader.py:214-245``). ``flat_rows`` flattens each
    sample (``[W, L, H*W*C]``): the layout the step's selection ingest
    gathers from without a relayout (``StepMode.ingest_path``)."""
    sidx = np.asarray(dataset.shard_indices)
    xs = np.asarray(dataset.x_train)
    if flat_rows:
        xs = xs.reshape(xs.shape[0], -1)
    ys = np.asarray(dataset.y_train)
    W, L = sidx.shape
    sharding = NamedSharding(mesh, P(axis_name))

    def build(values, shape_tail, dtype):
        def cb(idx):
            rows = range(*idx[0].indices(W))
            # astype makes the dtype contract real (not merely inherited
            # from values): the global array's declared dtype below must
            # match every callback block.
            block = np.stack([values[sidx[w]] for w in rows]).astype(
                dtype, copy=False
            )
            return block[(slice(None),) + tuple(idx[1:])]

        return jax.make_array_from_callback(
            (W, L) + shape_tail, sharding, cb, dtype=dtype
        )

    return (build(xs, xs.shape[1:], xs.dtype),
            build(ys, (), ys.dtype))


def host_worker_slice(mesh: Mesh, axis_name: str = "data") -> np.ndarray:
    """Worker (mesh-position) indices whose devices live on this host.

    Use to materialize only this host's shard rows when the dataset is not
    replicated (the ``load_partition_data_distributed_cifar10`` pattern,
    ``cifar10/data_loader.py:214-245``).
    """
    devices = mesh.devices.reshape(-1)
    me = jax.process_index()
    return np.asarray(
        [i for i, d in enumerate(devices) if d.process_index == me], np.int64
    )
