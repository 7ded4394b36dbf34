"""The fp8 control of a cell whose replay does not fit the host twice: the
plain reference in the nearest precision below, put in the program's place
and held to the cell's own limits (``check.Number``, as a run's numbers
are), one side at a time. It has to come out ``correct: false``.

    python3 tests/perfbench/control_readings.py --workload <cell> \
        --seeds 1,2 --replay_seeds 1

``readings.py`` runs the cell's whole command and holds, beside the run's
own host copies, the replay's trees for the program, the reference and the
control at once; at 0.37 B parameters that passes the 40 GiB of a one-chip
machine (my chip run, PR 39: ended by the machine at its limit). The control
needs none of the run: it is the plain reference in fp8 (the nearest
precision below the bfloat16 the configurations state) put in the program's
place and compared with the reference in float32. So this script builds the
trainer, records the replay's steps through ``fit()`` as ``run.py`` does,
closes the trainer, and then follows the recorded batches with the reference
in each precision in turn (each side is its losses and the norm of each leaf
of its first gradient and of its parameters' change, all that
``replay.step_gaps`` reads: ``replay.reference_steps`` keeps no more since
PR 48, and ``perfbench/readings.py`` may fit again). The trainer then warms up as ``run.py`` warms
it (through the first log gate and one call more; ``--warm 0``: not at all),
and the inference and evaluate sides are taken at those weights: the ones a
run's ``logit_gap`` is read at. ``--replay_seeds`` seeds (the first of
``--seeds``) get the replayed steps' three gaps too, which cost three
host-side Adam updates a precision; every seed gets ``weight_gap``,
``logit_gap`` and ``eval_loss_gap``. Each number is printed beside the
cell's limit as a run prints it, and each seed ends in ``correct``: true
only if the control passed every limit it was read against. The sound
readings (the program against the reference) are those every run of the
cell prints. On the chip only; no test collects this file.
"""

import argparse
import gc
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import numpy as np  # noqa: E402

from perfbench import check, reference, replay, run  # noqa: E402
from perfbench.cell import Cell  # noqa: E402

LOWER = "fp8"


def worst_leaves(paths, p, r, top: int = 3) -> None:
    """Print the ``top`` leaves by ``replay.worst_leaf_gap``'s ratio, from
    the two sides' leaf norms."""
    ratio = np.abs(p - r) / np.maximum(r, np.median(r))
    for i in np.argsort(-ratio)[:top]:
        print(f"[control]   {paths[i]}: change {p[i]!r} reference {r[i]!r} "
              f"median leaf {np.median(r)!r} gap {ratio[i]!r}", flush=True)


def control_of(cell: Cell, seed: int, with_replay: bool, warm: bool):
    limits, arch = cell.config["check"], cell.config["reference"]
    train_block = check.train_block_rows(
        limits, reference.rows_independent(arch))
    fields = cell.train_config_fields(seed, False)
    trainer = run.build_trainer(fields, cell.data_seed(seed))
    try:
        with replay.Recorder(trainer) as recorder:
            trainer.fit(num_epochs=replay.STEPS + 1)
        if warm:
            trainer.fit(num_epochs=int(fields["log_every"])
                        - replay.STEPS - 1)
            trainer.fit(num_epochs=cell.steps_per_call)
        params = run._host_copy(trainer.state.params)
        ds = trainer.dataset
        x_test, y_test = np.asarray(ds.x_test), np.asarray(ds.y_test)
        train_split = (np.asarray(ds.x_train), np.asarray(ds.y_train),
                       np.asarray(ds.shard_indices))
    finally:
        trainer.close()
    del trainer
    gc.collect()
    steps = recorder.steps
    gaps = {}
    if with_replay:
        paths = [jax.tree_util.keystr(path) for path, _ in
                 jax.tree_util.tree_leaves_with_path(steps[0]["params"])]
        # the program's side first: it lets two trees go. The reference
        # consumes the start's moments: the lower precision, which follows
        # the steps first, gets a start of its own that shares them
        sound = replay.system_steps(steps, arch)
        sides = {LOWER: replay.reference_steps(
            [dict(steps[0])] + steps[1:], arch, fields, LOWER, train_block)}
        gc.collect()
        sides[None] = replay.reference_steps(steps, arch, fields, None,
                                             train_block)
        gaps.update(replay.step_gaps(sides[LOWER], sides[None]))
        # beside them, the program's own three gaps against the same
        # float32 side (what the run prints), with the leaves that decide
        # ``update_norm_gap``: its worst leaf is what a swing is traced to
        print(f"[control] seed {seed}: the program's replayed gaps "
              f"{replay.step_gaps(sound, sides[None])}", flush=True)
        for side in (sound, sides[LOWER]):
            worst_leaves(paths, side["change"], sides[None]["change"])
    scaled = {p: replay.reference_weights(steps, train_split, arch, fields,
                                          p, train_block)
              for p in (None, LOWER)}
    gaps["weight_gap"] = replay.weight_gap(scaled[LOWER], scaled[None])
    idx = check.sample_indices(seed, x_test.shape[0],
                               check.sample_rows(limits))
    outputs = {p: reference.outputs(params, None, x_test[idx], arch, p,
                                    block_rows=check.block_rows(limits))
               for p in (None, LOWER)}
    gaps["logit_gap"] = check.logit_gap(outputs[LOWER], outputs[None])
    del outputs
    losses = {p: reference.eval_loss(
        params, None, x_test, y_test, arch, p,
        block_rows=check.block_rows(limits, x_test.shape[0]))
        for p in (None, LOWER)}
    gaps["eval_loss_gap"] = check.eval_loss_gap(losses[LOWER], losses[None])
    return gaps


def held_to_the_limits(gaps, limits):
    """The control's numbers as a run's are held: each beside the cell's
    limit, ``correct`` if all pass."""
    numbers = [check.Number(name, value, float(limits[f"{name}_limit"]))
               for name, value in gaps.items()]
    for n in numbers:
        print(n.line().replace("check", "control", 1), flush=True)
    return all(n.ok for n in numbers)


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--replay_seeds", type=int, default=1)
    parser.add_argument("--warm", type=int, choices=(0, 1), default=1)
    args = parser.parse_args()
    if jax.devices()[0].platform != "tpu":
        print(f"control_readings: needs a TPU, jax found {jax.devices()[0]}",
              file=sys.stderr)
        return 1
    cell = Cell(args.workload)
    rows = []
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        rows.append(control_of(cell, seed, i < args.replay_seeds,
                               bool(args.warm)))
        correct = held_to_the_limits(rows[-1], cell.config["check"])
        print(json.dumps({"seed": seed, "correct": correct,
                          "control": rows[-1]}), flush=True)
        gc.collect()
    for name in sorted({n for row in rows for n in row}):
        read = [row[name] for row in rows if name in row]
        print(f"[control] {name}: min {min(read)!r} max {max(read)!r} over "
              f"{len(read)} seed(s); limit "
              f"{cell.config['check'].get(name + '_limit')!r}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
