"""Plain reference, the part every model family shares: the loader of a
family's file, the Mercury training step around a family's forward pass
(pool, scores, reweighted loss, Adam under the cosine schedule), and the
block-wise passes over held-out rows that the ``correct`` check compares.

**A family's file.** The configuration's ``reference`` group (``arch``
below) names it: ``"file": "perfbench/references/<family>.py"``, a path
from the root of the repo. It is plain ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, imports nothing of the program
and reads a parameter tree by the names and shapes flax gives the program's
modules. It offers ``INTERFACE``:

- ``prepare(raw_rows, arch)``: dataset rows as ``trainer.dataset`` holds
  them -> model inputs (a family whose rows are inputs returns them);
- ``augment(key, inputs, arch)``: the training-time augmentation under the
  program's key discipline (a family without one returns the inputs);
- ``forward(params, model_state, inputs, arch, quantize)``: float32
  outputs; ``model_state`` None is the training mode, and a family without
  such state ignores it. ``quantize="fp8"`` is the control of the
  ``correct`` check: where it rounds (``round_to``) is the family's
  decision, written in its docstring;
- ``example_loss(outputs, labels)`` -> ``[N]`` float32, in ``jax.numpy``:
  the loss the sampler scores by and the training loss averages;
- ``eval_example_loss(outputs, labels)`` -> ``[N]`` float64 on the host:
  the same loss of one block of held-out rows, whose mean the evaluate side
  compares (``outputs`` as ``forward`` returned them, still on the device);
- ``fwd_flops_per_example(config)``: the operations one example's forward
  pass requires, from the whole configuration file; the file's constant is
  held to it.

Beside them it may declare ``ROWS_INDEPENDENT = True``: in training mode
each row's outputs are a function of that row and the parameters alone (no
batch statistic, no dropout). Only then may the configuration size the
training side's blocks (``check.train_block_rows``): the pool is scored and
the batch differentiated so many rows at a time, and no more than a block's
outputs and activations ever exist.

Nothing here looks inside ``inputs``, ``outputs`` or ``labels`` beyond
their leading row axis, and of ``arch`` it reads ``file``, ``sampling`` and
``adam`` alone.

**The training step** follows Mercury (``pytorch_collab.py:95-148`` of the
reference implementation): a shuffled wrapping stream hands out the next
pool of shard slots; the pool is prepared and augmented; one training-mode
forward scores it; ``p_i = (loss_i + alpha * EMA) / sum`` is the sampling
distribution; the drawn batch trains on ``mean(loss_i / (N p_i))``; Adam
under a cosine schedule applies the gradient. The random draws use
``jax.random`` with the key discipline the program documents (an 8-way
split of the step key, whose second key augments the pool), so that the
pool can be rebuilt row for row from a state the program hands over.
"""

from __future__ import annotations

import functools
import importlib.util
import os
from typing import Any, Mapping, Optional

import jax
import jax.numpy as jnp
from jax import lax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: What a family's file offers (the module docstring says what each does).
INTERFACE = ("prepare", "augment", "forward", "example_loss",
             "eval_example_loss", "fwd_flops_per_example")

_E4M3_MAX = 448.0


def family(arch: Mapping[str, Any]):
    """The module of the family file that ``arch`` (a configuration's
    ``reference`` group) names."""
    return _load(os.path.abspath(os.path.join(ROOT, arch["file"])))


@functools.lru_cache(maxsize=None)
def _load(path: str):
    name = "perfbench_family_" + os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    missing = [n for n in INTERFACE if not callable(getattr(module, n, None))]
    if missing:
        raise TypeError(f"{path} offers no {', '.join(missing)}: a family's "
                        f"file offers {', '.join(INTERFACE)}")
    if not isinstance(getattr(module, "ROWS_INDEPENDENT", False), bool):
        raise TypeError(f"{path}: ROWS_INDEPENDENT is "
                        f"{module.ROWS_INDEPENDENT!r}, want True or False")
    return module


def rows_independent(arch: Mapping[str, Any]) -> bool:
    """Whether the family's file declares that in training mode a row's
    outputs depend on that row and the parameters alone."""
    return getattr(family(arch), "ROWS_INDEPENDENT", False)


def _need_independent_rows(arch: Mapping[str, Any]) -> None:
    if not rows_independent(arch):
        raise ValueError(f"{arch['file']} does not declare ROWS_INDEPENDENT "
                         "= True: its training-mode forward takes the whole "
                         "pool and the whole batch at once")


def round_to(x, quantize: Optional[str]):
    """``x`` as float32 after a round trip through the lower precision
    (fp8 e4m3, one scale per tensor); a gradient passes straight through
    the rounding."""
    if quantize is None:
        return x
    if quantize != "fp8":
        raise ValueError(f"unknown precision {quantize!r}")
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _E4M3_MAX
    r = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + lax.stop_gradient(r - x)


# -------------------------------------------------- passes over held-out rows
def _blocks(n_rows: int, block_rows: int):
    return [slice(i, i + block_rows) for i in range(0, n_rows, block_rows)]


def _forward_raw(arch: Mapping[str, Any], quantize: Optional[str]):
    fam = family(arch)
    return jax.jit(lambda p, s, x: fam.forward(
        p, s, fam.prepare(x, arch), arch, quantize))


def outputs(params, model_state, raw_rows, arch: Mapping[str, Any],
            quantize: Optional[str] = None, block_rows: int = 64):
    """Reference outputs for raw dataset rows, in blocks of ``block_rows``
    rows so that float32 activations at the real widths stay small beside
    the program's own peak. For the inference check's sample: the whole of
    the outputs comes back, so the sample has to be one the host can hold."""
    import numpy as np

    fwd = _forward_raw(arch, quantize)
    return np.concatenate(
        [np.asarray(fwd(params, model_state, raw_rows[rows]))
         for rows in _blocks(raw_rows.shape[0], block_rows)], axis=0)


def eval_loss(params, model_state, raw_rows, labels,
              arch: Mapping[str, Any], quantize: Optional[str] = None,
              block_rows: int = 64) -> float:
    """Mean (float64) of the family's per-example loss over held-out rows,
    one block of ``block_rows`` rows at a time: no more than a block's
    outputs ever exist."""
    import numpy as np

    fam, fwd = family(arch), _forward_raw(arch, quantize)
    losses = [np.asarray(fam.eval_example_loss(
        fwd(params, model_state, raw_rows[rows]), labels[rows]), np.float64)
        for rows in _blocks(raw_rows.shape[0], block_rows)]
    return float(np.concatenate(losses).mean())


# ------------------------------------------------------ the training step
def make_loss_and_grad(arch: Mapping[str, Any],
                       quantize: Optional[str] = None,
                       block_rows: Optional[int] = None):
    """``(params, inputs, labels, scaled_probs) -> (loss, grads)`` of the
    reweighted training loss ``mean(loss_i / (N p_i))``, the family's
    forward in training mode over the batch, float32. With ``block_rows``
    (a family of independent rows) each block of so many rows is
    differentiated alone, ``sum_i(loss_i / (N p_i))`` over its rows; loss
    and gradients add up in float32 on the device and are divided by the
    batch's ``N`` once."""
    fam = family(arch)

    def weighted(params, inputs, labels, scaled_probs):
        z = fam.forward(params, None, inputs, arch, quantize)
        return fam.example_loss(z, labels) / scaled_probs

    if block_rows is None:
        def loss(params, inputs, labels, scaled_probs):
            return jnp.mean(weighted(params, inputs, labels, scaled_probs))

        return jax.jit(jax.value_and_grad(loss))
    _need_independent_rows(arch)

    @functools.partial(jax.jit, donate_argnums=0)
    def add_block(so_far, params, *block):
        part = jax.value_and_grad(
            lambda *a: jnp.sum(weighted(*a)))(params, *block)
        return jax.tree.map(jnp.add, so_far, part)

    @functools.partial(jax.jit, donate_argnums=0)
    def over(so_far, n):
        return jax.tree.map(lambda a: a / n, so_far)

    def loss_and_grad(params, inputs, labels, scaled_probs):
        params = jax.device_put(params)
        so_far = (jnp.zeros((), jnp.float32),
                  jax.tree.map(jnp.zeros_like, params))
        for rows in _blocks(inputs.shape[0], block_rows):
            so_far = add_block(so_far, params, inputs[rows], labels[rows],
                               scaled_probs[rows])
        return over(so_far, jnp.float32(inputs.shape[0]))

    return loss_and_grad


def cosine_lr(count: int, peak: float, decay_steps: int) -> float:
    """Cosine annealing to zero over ``decay_steps`` updates (Loshchilov &
    Hutter 2017), read at the number of updates already applied."""
    import math

    frac = min(count, decay_steps) / decay_steps
    return peak * 0.5 * (1.0 + math.cos(math.pi * frac))


def adam_update(params, mu, nu, count: int, grads, lr: float,
                b1: float, b2: float, eps: float) -> None:
    """One Adam update (Kingma & Ba 2015, bias-corrected) on the host, in
    place: ``params``, ``mu``, ``nu`` and ``grads`` are lists of float32
    leaves in one order, and each leaf of the first three is replaced by
    its update as soon as that exists, so that no second tree does;
    ``count`` is the number of updates already applied. A leaf's three new
    arrays are all that is allocated for it: every other term is written
    into two buffers of the largest leaf's size (a fresh array of this size
    costs more in page faults than its arithmetic)."""
    import numpy as np

    t = count + 1
    grads = [np.asarray(g, np.float32) for g in grads]
    room = max(g.size for g in grads)
    buffers = np.empty(room, np.float32), np.empty(room, np.float32)
    for i, g in enumerate(grads):
        a, b = (buffer[:g.size].reshape(g.shape) for buffer in buffers)
        m = np.multiply(b1, mu[i], dtype=np.float32)
        m += np.multiply(1.0 - b1, g, out=a)
        v = np.multiply(b2, nu[i], dtype=np.float32)
        np.square(g, out=a)
        v += np.multiply(1.0 - b2, a, out=a)
        # step = (m / (1 - b1^t)) / (sqrt(v / (1 - b2^t)) + eps)
        np.sqrt(np.divide(v, 1.0 - b2 ** t, out=a), out=a)
        a += eps
        np.divide(m, 1.0 - b1 ** t, out=b)
        b /= a
        params[i] = np.subtract(params[i], np.multiply(lr, b, out=b),
                                dtype=np.float32)
        mu[i], nu[i] = m, v


def pool_slots(key, perm, cursor: int, pool_size: int):
    """The next ``pool_size`` positions of the shuffled wrapping stream:
    a stream that cannot serve them is reshuffled with ``key`` and read
    from its start."""
    import numpy as np

    perm = np.asarray(perm)
    if cursor + pool_size > perm.shape[0]:
        perm = np.asarray(jax.random.permutation(key, perm.shape[0]))
        cursor = 0
    return perm[cursor:cursor + pool_size]


def score_pool(params, step_key, perm, cursor: int, ema_value: float,
               ema_count: int, x_train, y_train, shard_row,
               arch: Mapping[str, Any], pool_size: int,
               quantize: Optional[str] = None,
               block_rows: Optional[int] = None):
    """What one Mercury step makes of its pool, from the state before it:
    ``(inputs [N, ...], labels [N, ...], losses [N], scaled_probs [N] =
    N p)``. ``step_key`` is the state's key; of its 8-way split the first
    shuffles the stream and the second augments the pool. The pool is
    prepared and augmented whole, under the one key; with ``block_rows``
    (a family of independent rows) it is then scored so many rows at a
    time."""
    import numpy as np

    fam, sampling = family(arch), arch["sampling"]
    keys = jax.random.split(step_key, 8)
    slots = pool_slots(keys[0], perm, cursor, pool_size)
    rows = np.asarray(shard_row)[slots]
    labels = jnp.asarray(np.asarray(y_train)[rows])
    raw = jnp.asarray(np.asarray(x_train)[rows])

    def ingest(raw, key):
        return fam.augment(key, fam.prepare(raw, arch), arch)

    def score(params, inputs, labels):
        return fam.example_loss(
            fam.forward(params, None, inputs, arch, quantize), labels)

    if block_rows is None:
        @jax.jit
        def run(params, raw, labels, key):
            inputs = ingest(raw, key)
            return inputs, score(params, inputs, labels)

        inputs, losses = run(params, raw, labels, keys[1])
        losses = np.asarray(losses, np.float64)
    else:
        _need_independent_rows(arch)
        inputs, params = jax.jit(ingest)(raw, keys[1]), jax.device_put(params)
        score = jax.jit(score)
        losses = np.concatenate(
            [np.asarray(score(params, inputs[block], labels[block]),
                        np.float64)
             for block in _blocks(pool_size, block_rows)])
    return (np.asarray(inputs), np.asarray(labels), losses,
            scaled_probs(losses, ema_value, ema_count, sampling))


def scaled_probs(losses, ema_value: float, ema_count: int, sampling):
    """``N p_i`` over a scored pool: the EMA of the mean pool loss takes
    this pool in first (its first update sets it), then
    ``p_i = (loss_i + is_alpha * EMA) / sum``."""
    import numpy as np

    losses = np.asarray(losses, np.float64)
    mean = float(losses.mean())
    a = float(sampling["ema_alpha"])
    ema = mean if ema_count == 0 else a * ema_value + (1.0 - a) * mean
    scores = np.maximum(losses + float(sampling["is_alpha"]) * ema, 1e-12)
    return scores / scores.sum() * len(losses)
