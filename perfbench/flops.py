"""Operations a training step requires and a step program does, from the
operations one example's forward pass requires.

That count is the model family's: each configuration file carries the
constant (``fwd_flops_per_example``), and the tests hold it to
``fwd_flops_per_example(config)`` of the configuration's family file
(``perfbench/references/``). A training step requires 3 x forward
(forward, and a backward of twice its cost); the scoring pass of importance
sampling is extra work the method chooses to do and does not count, as
recomputation does not.
"""

from __future__ import annotations

TRAIN_FLOPS_PER_FORWARD = 3.0


def train_flops_per_example(fwd_flops_per_example: float) -> float:
    return TRAIN_FLOPS_PER_FORWARD * fwd_flops_per_example


def step_program_flops(fwd_flops_per_example: float, fields) -> float:
    """What one worker's step program DOES, from the job's ``TrainConfig``
    fields: forward and backward over the batch, and under importance
    sampling one scoring forward over the candidate pool (every
    ``score_refresh_every``-th step). The numerator of
    ``step_roofline_share``. Counted from the shapes and not taken from
    XLA's cost analysis, which adds both branches of a ``cond`` (the
    pipelined step's priming branch, taken at step 0 only, would count a
    second scoring forward into every step)."""
    batch = float(fields["batch_size"])
    pool = 0.0
    if fields.get("use_importance_sampling", True):
        pool = (batch * float(fields["presample_batches"])
                / float(fields.get("score_refresh_every", 1)))
    return fwd_flops_per_example * (pool + TRAIN_FLOPS_PER_FORWARD * batch)
