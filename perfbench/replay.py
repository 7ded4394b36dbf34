"""The train step against the plain reference: a replay of the first steps.

Set-up drives the trainer's first ``STEPS + 1`` steps through ``fit()``
itself with a recorder around ``trainer.train_step`` — the compiled step
the window then drives, fed by ``fit()``'s own call — and keeps, after each
step, what the state shows of it: the drawn batch of the NEXT step (the
``pending`` batch of pipelined scoring, read by position: the inputs as
augmented, the labels, and ``scaled_probs = N p_i``, each ``[W, B, ...]``),
the step's scalars, after the first two steps Adam's first moment (the
first replayed gradient is read from it), and at both ends the parameters.
Step 1 primes the pipeline and trains on a batch no state ever shows, so
the replay starts from the state after it.

Once the window has closed and the program's state is freed, the reference
(``reference.py`` around the configuration's family file, float32 at
``highest``) follows steps 2..STEPS+1 on its own trajectory from that
state: the reweighted loss and its gradient on each recorded batch, Adam
under the cosine schedule. Compared:

- ``loss_gap``: each step's ``train/loss`` against the reference's, the
  widest relative gap;
- ``grad_norm_gap``: the gradient of the first replayed step as the
  optimizer got it (from Adam's first moment before and after):
  |program's norm - reference's norm| over the reference's, over all
  parameters together. Norms, not the norm of the difference: at seeded
  weights the batch's gradient is what is left of 256 per-example
  gradients that all but cancel, and any rounding turns its direction
  (bfloat16 program against float32 reference: norm of the difference
  0.95-1.1 of the norm, my chip runs, PR 24). Not by the worst leaf either:
  that reads 0.14-0.51 in sound runs, no steadier than the control;
- ``update_norm_gap``: the parameters' change over the replayed steps, by
  the worst leaf: |program's norm - reference's norm| over the larger of
  the reference's norm of that leaf and of the median leaf;
- ``weight_gap`` (one worker): the pool of the first replayed step rebuilt
  from the state before it (stream, key, EMA) and scored by the reference
  with the program's parameters; each drawn row is found in the rebuilt
  pool (as a flat vector, whatever its shape; integer rows exactly) and
  its ``N p_i`` compared: the root mean square of the relative gaps. This
  is the scoring forward and the input pipeline at the timed pool size.

The draw itself (which rows the uniforms pick) is not replayed: a row
whose CDF edge moves by a rounding is drawn differently, and rightly so.

**The host's room.** The gaps read of a gradient or a change only its
leaves' norms, so either side is a vector of one float64 a leaf and no tree:
whatever is read of a parameter-sized tree is read a leaf at a time, in
float64 no larger than that leaf, and a tree that has been read is let go
before the next is made. The replay therefore consumes the recorder's
steps: ``system_steps`` takes step 2's ``mu`` and the last step's
``params`` out of them, ``reference_steps`` the start's ``mu`` and ``nu``
(the reference's own from then on). At the worst instant, right after the
window, the host holds seven float32 trees of the parameters' size (the
start's ``params``, ``mu``, ``nu``, step 2's ``mu``, the last ``params``,
the run's warm and final weights), and five while the reference follows the
steps.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np

from perfbench import reference

#: Steps the reference follows (after the priming step).
STEPS = 3


def _host(tree):
    import jax

    def leaf(a):
        if jax.numpy.issubdtype(a.dtype, jax.dtypes.prng_key):
            a = jax.random.key_data(a)
        return np.asarray(a)

    return jax.tree.map(leaf, tree)


def _adam_state(opt_state):
    """The ``(count, mu, nu)`` node of an optax Adam state."""
    import jax

    nodes = [n for n in jax.tree.leaves(
        opt_state, is_leaf=lambda n: hasattr(n, "mu") and hasattr(n, "nu"))
        if hasattr(n, "mu")]
    if len(nodes) != 1:
        raise NotImplementedError(
            "the replay follows Adam; this optimizer state holds "
            f"{len(nodes)} Adam node(s)")
    return nodes[0]


class Recorder:
    """Stands around ``trainer.train_step`` for the first steps and keeps a
    host copy of what the replay needs of each new state."""

    def __init__(self, trainer) -> None:
        self.trainer, self.real = trainer, trainer.train_step
        self.steps: List[Dict[str, Any]] = []

    def __enter__(self) -> "Recorder":
        self.trainer.train_step = self
        return self

    def __exit__(self, *exc) -> None:
        self.trainer.train_step = self.real
        # the steps alone outlive the recording: a recorder that kept the
        # trainer would keep its state on the chip, and the host copy that
        # each of its arrays caches, through the whole reference check
        self.trainer = self.real = None

    def __call__(self, *args):
        state, metrics = self.real(*args)
        adam = _adam_state(state.opt_state)
        first, last = not self.steps, len(self.steps) == STEPS
        kept = dict(
            metrics={k: float(v) for k, v in _host(metrics).items()
                     if np.ndim(v) == 0},
            pending=_host(state.pending))
        if len(self.steps) < 2:     # the first gradient: mu before and after
            kept.update(mu=_host(adam.mu))
        if first:
            kept.update(nu=_host(adam.nu), count=int(adam.count),
                        stream=_host(state.stream), rng=_host(state.rng),
                        ema=_host(state.ema))
        if first or last:
            kept.update(params=_host(state.params))
        self.steps.append(kept)
        return state, metrics


def _leaf_norms(leaf, *trees) -> np.ndarray:
    """The float64 norm of ``leaf(*leaves)`` for each leaf of ``trees``
    (all of one structure): one number a leaf, and of the float64 array
    that ``leaf`` makes only one at a time."""
    import jax

    return np.array([float(np.linalg.norm(leaf(*leaves)))
                     for leaves in zip(*map(jax.tree.leaves, trees))])


def _minus(a, b) -> np.ndarray:
    """``a - b`` of one leaf in float64: one new array."""
    d = np.array(a, np.float64)
    d -= b
    return d


def worst_leaf_gap(p: np.ndarray, r: np.ndarray) -> float:
    """|program's norm - reference's norm| of the worst leaf, over the
    reference's norm of that leaf or of the median leaf, whichever is
    larger (some gradients are all but zero). ``p``, ``r``: the two sides'
    leaf norms."""
    over = np.maximum(r, np.median(r))
    if not over.all():      # a reference that does not move at all
        return 0.0 if (p == r).all() else float("inf")
    return float(np.max(np.abs(p - r) / over))


def norm_gap(p: np.ndarray, r: np.ndarray) -> float:
    """|program's norm - reference's norm| over the reference's norm, all
    leaves together, from the two sides' leaf norms."""
    p = float(np.sqrt(np.sum(np.square(p))))
    r = float(np.sqrt(np.sum(np.square(r))))
    return abs(p - r) / r if r else (0.0 if p == r else float("inf"))


#: The drawn batch in the state, by position (``PendingBatch``'s fields).
INPUTS, LABELS, SCALED_PROBS = 0, 1, 2


def _match_rows(rows: np.ndarray, pool: np.ndarray) -> np.ndarray:
    """For each of ``rows`` the index of the nearest row of ``pool``, rows
    taken as flat vectors, and -1 where none lies within a rounding of it
    (integer rows: where none is the same)."""
    exact = np.issubdtype(rows.dtype, np.integer)
    a = rows.reshape(rows.shape[0], -1).astype(np.float64)
    b = pool.reshape(pool.shape[0], -1).astype(np.float64)
    d2 = ((a * a).sum(1)[:, None] + (b * b).sum(1)[None, :]
          - 2.0 * a @ b.T)
    nearest = d2.argmin(axis=1)
    ok = d2[np.arange(len(a)), nearest] <= (0.0 if exact
                                            else 1e-6 * a.shape[1])
    return np.where(ok, nearest, -1)


# ------------------------------------------------- the two sides of a step
def system_steps(steps, arch) -> Dict[str, Any]:
    """What the program made of the replayed steps: each step's loss, and
    by the leaf the norm of the first gradient as the optimizer got it and
    of the parameters' change. Takes step 2's ``mu`` and the last step's
    ``params`` out of ``steps``: nothing reads them again."""
    b1 = float(arch["adam"]["b1"])

    def first_grad(m1, m0):
        g = _minus(m1, b1 * m0)
        g /= 1.0 - b1
        return g

    return dict(
        losses=[s["metrics"]["train/loss"] for s in steps[1:]],
        grad=_leaf_norms(first_grad, steps[1].pop("mu"), steps[0]["mu"]),
        change=_leaf_norms(_minus, steps[STEPS].pop("params"),
                           steps[0]["params"]))


def reference_steps(steps, arch, fields, quantize=None,
                    train_block_rows=None) -> Dict[str, Any]:
    """The same of the reference, which follows the recorded batches on
    its own trajectory from the state after the priming step
    (``train_block_rows`` rows of a batch at a time, where the
    configuration says so). Takes the start's ``mu`` and ``nu`` out of
    ``steps``: they are the reference's own from then on, each leaf let go
    as its update exists. The start's ``params`` stay (the change's norm
    and ``reference_weights`` read them)."""
    import jax

    start, adam = steps[0], arch["adam"]
    world = int(fields["world_size"])
    if world > 1 and fields.get("batch_norm", "sync") != "sync":
        raise NotImplementedError("replay across workers needs synced "
                                  "batch statistics")
    peak_lr = float(fields["base_lr"]) * world
    decay = int(fields["steps_per_epoch"]) * int(fields["num_epochs"])
    loss_and_grad = reference.make_loss_and_grad(arch, quantize,
                                                 train_block_rows)
    flat = lambda a: a.reshape((-1,) + a.shape[2:])  # noqa: E731  [W,B]->[WB]
    tree, count = jax.tree.structure(start["params"]), start["count"]
    params = jax.tree.leaves(start["params"])
    mu, nu = (jax.tree.leaves(start.pop(name)) for name in ("mu", "nu"))
    out: Dict[str, Any] = dict(losses=[])
    for i in range(STEPS):
        batch = steps[i]["pending"]
        loss, grads = loss_and_grad(
            jax.tree.unflatten(tree, params), flat(batch[INPUTS]),
            flat(batch[LABELS]), flat(batch[SCALED_PROBS]))
        grads = [np.asarray(g) for g in jax.tree.leaves(grads)]
        out["losses"].append(float(loss))
        if i == 0:
            out["grad"] = _leaf_norms(lambda g: np.asarray(g, np.float64),
                                      grads)
        reference.adam_update(
            params, mu, nu, count, grads,
            reference.cosine_lr(count, peak_lr, decay),
            float(adam["b1"]), float(adam["b2"]), float(adam["eps"]))
        del grads       # before the next step's gradient comes to the host
        count += 1
    out["change"] = _leaf_norms(_minus, params,
                                jax.tree.leaves(start["params"]))
    return out


def step_gaps(system, ref) -> Dict[str, float]:
    return dict(
        loss_gap=max(abs(a - b) / b
                     for a, b in zip(system["losses"], ref["losses"])),
        grad_norm_gap=norm_gap(system["grad"], ref["grad"]),
        update_norm_gap=worst_leaf_gap(system["change"], ref["change"]))


def reference_weights(steps, dataset, arch, fields, quantize=None,
                      train_block_rows=None):
    """``N p_i`` of the rows the program drew in the first replayed step:
    its pool rebuilt from the state before it and scored by the reference
    with the program's parameters. None where a drawn row is not in the
    rebuilt pool, or carries another label there."""
    import jax

    start, drawn = steps[0], steps[1]["pending"]
    x_train, y_train, shard_indices = dataset
    pool_size = int(fields["batch_size"]) * int(fields["presample_batches"])
    inputs, labels, _, scaled = reference.score_pool(
        start["params"],
        jax.random.wrap_key_data(jax.numpy.asarray(start["rng"][0])),
        start["stream"].perm[0], int(start["stream"].cursor[0]),
        float(start["ema"].value[0]), int(start["ema"].count[0]),
        x_train, y_train, shard_indices[0], arch, pool_size, quantize,
        train_block_rows)
    at = _match_rows(drawn[INPUTS][0], inputs)
    found = at >= 0
    print(f"[perfbench] replay pool: {int(found.sum())} of {len(at)} drawn "
          f"rows found in the rebuilt pool of {pool_size}", flush=True)
    if not found.all() or (labels[at] != drawn[LABELS][0]).any():
        return None
    return scaled[at]


def weight_gap(system, ref) -> float:
    """Root mean square of the drawn rows' relative gaps of ``N p_i``."""
    if system is None or ref is None:
        return float("inf")
    gaps = np.abs(np.asarray(system, np.float64) - ref) / ref
    return float(np.sqrt(np.mean(np.square(gaps))))


def compare(system: Dict[str, Any], steps: List[Dict[str, Any]], dataset,
            arch: Dict[str, Any], fields: Dict[str, Any],
            control: Optional[str] = None,
            train_block_rows: Optional[int] = None):
    """The replay's numbers: ``system``, what ``system_steps`` read of a
    ``Recorder``'s ``steps``, against the reference, which follows those
    steps now. ``dataset`` is ``(x_train, y_train, shard_indices)`` on the
    host, rows as ``trainer.dataset`` holds them; ``fields`` the job's
    ``TrainConfig`` fields (learning rate, schedule length, batch and
    pool); ``train_block_rows`` the rows the reference scores and
    differentiates at a time (``check.train_block_rows``; None: the whole
    pool and the whole batch). With ``control`` (a lower precision) returns
    a second dict as well: the reference in that precision, put in the
    program's place; it follows the steps first, from a start of its own
    that shares the recorded trees, which the float32 reference then
    consumes."""
    if len(steps) != STEPS + 1:
        raise ValueError(f"recorded {len(steps)} steps, want {STEPS + 1}")
    if steps[0]["pending"] is None:
        raise NotImplementedError(
            "the replay reads the drawn batch from the state's pending "
            "batch: the cell needs pipelined_scoring")
    lower_side = (reference_steps([dict(steps[0])] + steps[1:], arch, fields,
                                  control, train_block_rows)
                  if control else None)
    ref = reference_steps(steps, arch, fields, None, train_block_rows)
    for i, (a, b) in enumerate(zip(system["losses"], ref["losses"])):
        print(f"[perfbench] replay step {i + 2}: train/loss {a!r} "
              f"reference {b!r}", flush=True)
    out = step_gaps(system, ref)
    lower = step_gaps(lower_side, ref) if control else None
    if int(fields["world_size"]) == 1:
        weights = reference_weights(steps, dataset, arch, fields, None,
                                    train_block_rows)
        out["weight_gap"] = weight_gap(
            steps[1]["pending"][SCALED_PROBS][0], weights)
        if control:
            lower["weight_gap"] = weight_gap(reference_weights(
                steps, dataset, arch, fields, control, train_block_rows),
                weights)
    else:
        print("[perfbench] replay pool: not rebuilt across workers",
              flush=True)
    return (out, lower) if control else out
