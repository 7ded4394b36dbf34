"""Readings that a limit of the ``correct`` check is set from.

    python3 perfbench/readings.py --workload <cell> --seeds 1,2,3 --seconds 5

Runs the cell's whole command body once per seed in ONE process (set-up is
most of a short run's cost), each with the control beside it — the plain
reference in fp8, the nearest precision below the bfloat16 the
configurations state, put in the program's place — and prints, per seed,
every number compared, then the largest sound reading and the smallest
control reading of each. On the chip only, like ``run.py``; the
benchmark's own runs never call this.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys

import run as perfbench_run  # noqa: E402  (same directory)


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--seconds", type=float, default=5.0)
    args = parser.parse_args()
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        result = perfbench_run.run_cell(args.workload, seed, args.seconds,
                                        False, control=True)
        row = {"seed": seed, "correct": result["correct"],
               "control": result["control"], **result["numbers"]}
        row["examples_per_s"] = result["metrics"][
            "train_examples_per_s"]["value"]
        rows.append(row)
        print(json.dumps(row), flush=True)
        gc.collect()
    for name in rows[0]["control"]:
        sound = [r[name] for r in rows]
        control = [r["control"][name] for r in rows]
        print(f"[readings] {name}: sound max {max(sound)!r} (min "
              f"{min(sound)!r}) over {len(sound)} seeds; control min "
              f"{min(control)!r} (max {max(control)!r}); ratio "
              f"{min(control) / max(sound):.2f}")
    moved = [r["window_update_rms"] for r in rows]
    print(f"[readings] window_update_rms: min {min(moved)!r} max "
          f"{max(moved)!r}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
