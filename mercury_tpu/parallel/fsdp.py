"""FSDP-style fully-sharded parameters via GSPMD (the ZeRO-3 analogue).

The reference has only replicated-parameter data parallelism
(SURVEY.md §2.5). `zero_sharding` (ZeRO-1, ``train/step.py``) already
shards the optimizer state; this module completes the memory-sharding
ladder by sharding the **parameters themselves** over the data axis —
per-device parameter memory drops by W, and XLA's SPMD partitioner
inserts the per-layer all-gathers (weights, forward and backward) and the
gradient reduce-scatters that hand-written FSDP implementations schedule
manually. Optimizer state inherits the param shardings, so moments are
sharded too (ZeRO-2 falls out for free).

Done the idiomatic XLA way (same stance as ``parallel/tensor.py``): a
sharding annotation per leaf + plain ``jax.jit`` — no shard_map, no
manual collectives. Each leaf is sharded along its largest axis divisible
by the mesh-axis size (kernels split on features, 1-D biases on their
only axis when divisible); tiny/indivisible leaves stay replicated, which
matches hand-written FSDP's practice of not sharding small tensors.
"""

from __future__ import annotations

from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


#: SHARDING CONTRACT (enforced by graftlint Layer 3, lint/sharding.py):
#: params/opt-state leaves carry fsdp_shardings (largest divisible dim
#: over the data axis, small leaves replicated); gradients are pinned to
#: the SAME layout with with_sharding_constraint inside the step, so the
#: backward's reduce-scatters land sharded instead of GSPMD choosing to
#: all-gather; batch inputs ride P(data); loss comes back replicated.
SHARDING_CONTRACT = {
    "params": "fsdp_shardings(params): largest W-divisible dim sharded",
    "opt_state": "inherits the param shardings (ZeRO-2 for free)",
    "grads": "with_sharding_constraint to the param shardings",
    "x, y": "P(data) on the batch axis",
    "loss": "replicated",
}


def fsdp_shardings(params, mesh: Mesh, axis: str = "data",
                   min_size: int = 1024):
    """``NamedSharding`` pytree: each leaf split along its largest
    ``axis_size``-divisible dimension; leaves smaller than ``min_size``
    elements (or with no divisible dim) replicated."""
    w = mesh.shape[axis]

    def spec_for(x) -> P:
        shape = jnp.shape(x)
        if int(jnp.size(x)) < min_size:
            return P()
        divisible = [i for i, d in enumerate(shape) if d % w == 0]
        if not divisible:
            return P()
        i = max(divisible, key=lambda i: shape[i])
        return P(*([None] * i + [axis]))

    return jax.tree_util.tree_map(
        lambda x: NamedSharding(mesh, spec_for(x)), params
    )


def shard_params_fsdp(params, mesh: Mesh, axis: str = "data",
                      min_size: int = 1024):
    """Place a param tree fully-sharded (each device holds ~1/W of every
    large leaf)."""
    return jax.device_put(params, fsdp_shardings(params, mesh, axis,
                                                 min_size))


def make_fsdp_train_step(
    model,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    axis: str = "data",
) -> Callable[..., Tuple[dict, tuple, jax.Array]]:
    """Jitted train step over FSDP-sharded params.

    ``step(params, opt_state, x, y) → (params, opt_state, loss)`` with
    ``x: [B, ...]`` / ``y: [B]`` sharded ``P(axis)`` (data parallel),
    params (and therefore optimizer state) placed by
    :func:`shard_params_fsdp` — the step takes its layouts from the
    inputs, so sharding granularity is controlled there. ``out_shardings``
    pins the updated params to the same layout, so the FSDP placement is
    stable across steps (no silent gather-back, buffers donated).
    """
    from mercury_tpu.parallel.mesh import data_sharding, replicated_sharding
    from mercury_tpu.sampling.importance import per_sample_loss

    batch_sharding = data_sharding(mesh, axis)
    replicated = replicated_sharding(mesh)

    def canon(x):
        """Leaves created off-mesh (e.g. optax's scalar ``count`` from
        ``jnp.zeros``) join the mesh replicated; mesh-placed leaves pass
        through untouched."""
        s = getattr(x, "sharding", None)
        if isinstance(s, NamedSharding) and s.mesh == mesh:
            return x
        return jax.device_put(x, replicated)

    def shardings_of(tree):
        return jax.tree_util.tree_map(lambda x: x.sharding, tree)

    # The FIRST call canonicalizes placements and fixes the layout (pinned
    # thereafter by out_shardings + donation); later calls go straight to
    # the jitted function — no per-step tree traversals, so the C++ jit
    # fastpath is the actual per-step cost. Contract: feed back the
    # returned params/opt_state. A foreign layout is NOT an error — jit
    # recompiles and reshards to the pinned out_shardings each step (with
    # unusable-donation warnings), so keep the returned trees to avoid
    # that hidden per-step reshard.
    cache = {}

    def jitted(params, opt_state, x, y):
        if "fn" not in cache:
            params = jax.tree_util.tree_map(canon, params)
            opt_state = jax.tree_util.tree_map(canon, opt_state)
            param_shardings = shardings_of(params)

            def step(params, opt_state, x, y):
                def loss_fn(p):
                    logits = model.apply({"params": p}, x, train=True)
                    return jnp.mean(per_sample_loss(logits, y))

                loss, grads = jax.value_and_grad(loss_fn)(params)
                # SHARDING CONTRACT: pin the gradient tree to the param
                # layout so the backward's reductions land sharded —
                # without the constraint GSPMD may elect to all-gather
                # grads before the update, a silent Wx memory/wire cost
                # (graftlint Layer 3 budgets the compiled collectives).
                grads = jax.lax.with_sharding_constraint(
                    grads, param_shardings)
                updates, opt_state = tx.update(grads, opt_state, params)
                params = optax.apply_updates(params, updates)
                return params, opt_state, loss

            cache["fn"] = jax.jit(
                step,
                out_shardings=(param_shardings, shardings_of(opt_state),
                               replicated),
                donate_argnums=(0, 1),
            )
        x = jax.device_put(x, batch_sharding)
        y = jax.device_put(y, batch_sharding)
        return cache["fn"](params, opt_state, x, y)

    return jitted
