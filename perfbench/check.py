"""What decides ``correct``: every number here is printed beside its limit
in every run, and one over its limit makes the run ``correct: false``.

**The train step, replayed** (``perfbench/replay.py``): the first steps of
the very trainer the window then drives, through ``fit()``, against the
plain reference on the recorded batches: ``loss_gap``, ``grad_norm_gap``,
``update_norm_gap`` and, from the rebuilt pool, ``weight_gap``. This is the
timed program at the timed batch and pool: scoring forward, input
pipeline, reweighted loss, backward pass, Adam and its schedule.

**The inference and evaluate paths.** After warm-up ``trainer.predict``
gives the outputs of ``sample_rows`` held-out rows drawn from the seed (the
configuration's ``check.sample_rows``, 256 where it names none: as many as
the host can hold the outputs of), in the precision the configuration
states; the reference (the configuration's family file) computes them in
float32 from a host copy of the same weights:

    logit_gap = rms(system - reference) / rms(reference)

over every number of those outputs, whatever their shape (the root mean
square reads the same from seed to seed; the widest single output swings
by its nature).

The window's last ``fit()`` call closes with ``evaluate()``, which returns
``test/eval_loss``; the reference computes the same mean per-example loss
over the whole test split from the final weights, ``block_rows`` rows at a
time (``check.block_rows``), keeping one float a row:

    eval_loss_gap = |system - reference| / reference

**The window itself.** Every ``train/loss`` logged in it is finite;
``state.step`` advanced by exactly the steps the harness counted; nothing
compiled; and the parameters moved: ``window_update_rms``, the root mean
square over all parameters of their change across the window, per step,
lies above a floor (a step that stops updating mid-run reads 0).

No level of the loss is among them. On the 5,000-image stand-in ResNet-50
under Adam memorises the data within a hundred steps and is chaotic from
then on: of the 33 closing evaluations of eleven windows, nine read
0.85-226 and the others 0.0002-0.03 (``train/eval_loss``; my chip runs,
PR 24), so with three evaluations to a window no level separates a sound
run from a fault without failing a sound run in fifty. The replay holds the
loss of each of its steps to the reference instead.

**How the limits are set** (readings beside each limit in the
configuration's ``check`` block and in PERF.md): the largest value sound
runs give over a dozen seeds on the chip, and the smallest the control
gives: the reference itself with the inputs and weights of its matrix
products rounded to fp8 (e4m3; the family's file says which), the nearest
precision below the bfloat16 the configurations state, put in the program's
place. The limit stands between the two. A number the control hardly moves
is held against the fault it is there to catch, at about three times the
sound runs' largest. Where the configuration sizes the training side's
blocks (``check.train_block_rows``) the control's one scale per tensor
spans a block of rows, so its readings are that size's own.

**Not covered.** Which rows the draw picks (replay.py says why); the step
that primes the pipeline (its batch is in no state); a cell without
``pipelined_scoring``, whose drawn batch no state shows.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

#: Held-out rows whose outputs the inference check compares, where the
#: configuration's ``check`` block names no ``sample_rows``.
SAMPLE = 256


class Number:
    """One number compared, beside its limit."""

    def __init__(self, name: str, value: float, limit: float,
                 rule: str = "<=") -> None:
        self.name, self.value, self.limit, self.rule = name, value, limit, rule
        if rule == "<=":
            self.ok = bool(np.isfinite(value) and value <= limit)
        elif rule == ">=":
            self.ok = bool(np.isfinite(value) and value >= limit)
        elif rule == "==":
            self.ok = bool(value == limit)
        else:
            raise ValueError(rule)

    def line(self) -> str:
        return (f"[perfbench] check {self.name}: {self.value!r} "
                f"{self.rule} {self.limit!r} -> {'ok' if self.ok else 'FAIL'}")

    def entry(self) -> Dict[str, Any]:
        """The same for the result line (JSON has no infinity: a number
        that is not finite goes as its name)."""
        value = float(self.value)
        return {"value": value if math.isfinite(value) else repr(value),
                "rule": self.rule, "limit": self.limit, "ok": self.ok}


def sample_rows(limits: Dict[str, Any]) -> int:
    return int(limits.get("sample_rows", SAMPLE))


def block_rows(limits: Dict[str, Any], n_rows: Optional[int] = None) -> int:
    """Rows of a split (``n_rows`` of them) the evaluate side's reference
    hands ``forward`` at a time: ``check.block_rows``, else 250 where that
    divides the split (one shape, one compile) and 64 where it does not.
    Of the inference check's sample (no ``n_rows``): ``check.block_rows``,
    else 64."""
    return int(limits.get("block_rows",
                          250 if n_rows and n_rows % 250 == 0 else 64))


def train_block_rows(limits: Dict[str, Any],
                     rows_independent: bool) -> Optional[int]:
    """Rows the training side's reference (the pool's scoring, the batch's
    gradient) hands ``forward`` at a time: ``check.train_block_rows``. None
    where the configuration names none: the whole pool and the whole batch
    in one forward each. Only a family that declares its rows independent
    (``rows_independent``) may name one."""
    rows = limits.get("train_block_rows")
    if rows is None:
        return None
    if not rows_independent:
        raise SystemExit(
            "perfbench: check.train_block_rows is set, and the "
            "configuration's family file does not declare ROWS_INDEPENDENT "
            "= True: a forward that couples the rows of a batch takes the "
            "whole pool and the whole batch at once")
    if int(rows) < 1:
        raise SystemExit(f"perfbench: check.train_block_rows is {rows!r}, "
                         "want 1 or more")
    return int(rows)


def sample_indices(seed: int, n_test: int, k: int = SAMPLE) -> np.ndarray:
    rng = np.random.default_rng([int(seed), 0xA])
    return np.sort(rng.choice(n_test, size=min(k, n_test), replace=False))


def logit_gap(system: np.ndarray, ref: np.ndarray) -> float:
    system, ref = np.asarray(system, np.float64), np.asarray(ref, np.float64)
    return float(np.sqrt(np.mean(np.square(system - ref))
                         / np.mean(np.square(ref))))


def eval_loss_gap(system: float, ref: float) -> float:
    return abs(system - ref) / ref


def update_rms(before, after, steps: int) -> float:
    """Root mean square, over every parameter, of its change from
    ``before`` to ``after``, per step."""
    import jax

    a, b = jax.tree.leaves(before), jax.tree.leaves(after)
    total = 0.0
    for x, y in zip(a, b):      # a leaf at a time, in one float64 array
        d = np.array(y, np.float64)
        d -= x
        total += float(np.sum(np.square(d, out=d)))
    return (total / sum(x.size for x in a)) ** 0.5 / max(steps, 1)


def failed_steps(losses: Sequence[float], log_every: int) -> int:
    """Steps that lie in a log interval whose record was not finite."""
    return log_every * sum(1 for v in losses if not math.isfinite(v))


def numbers(limits: Dict[str, Any], *, system_outputs, ref_outputs,
            eval_loss: Optional[float], ref_eval_loss: Optional[float],
            replay: Dict[str, float], window_update_rms: float,
            window_losses: List[float], steps_counted: int,
            steps_advanced: int, compiles: int) -> List[Number]:
    """Every number compared in a run, each beside its limit (``limits``:
    the configuration's ``check`` block)."""
    out = [Number(name, value, float(limits[f"{name}_limit"]))
           for name, value in replay.items()]
    out.append(Number("logit_gap", logit_gap(system_outputs, ref_outputs),
                      float(limits["logit_gap_limit"])))
    if eval_loss is not None and ref_eval_loss is not None:
        out.append(Number("eval_loss_gap",
                          eval_loss_gap(eval_loss, ref_eval_loss),
                          float(limits["eval_loss_gap_limit"])))
    finite = [v for v in window_losses if math.isfinite(v)]
    out += [
        Number("window_update_rms", window_update_rms,
               float(limits["window_update_rms_floor"]), ">="),
        Number("nonfinite_losses", len(window_losses) - len(finite), 0, "=="),
        Number("steps_advanced", steps_advanced, steps_counted, "=="),
        Number("compiles_in_window", compiles, 0, "=="),
    ]
    return out
