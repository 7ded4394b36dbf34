"""A chip-only script that no test collects: the ops of a traced run's step
by the leaf ``reducers/model_leaf_share.py`` books them to, heaviest first,
so that what a partition calls ``unscoped`` (or any leaf) can be read by op
name, shape and path. Run it after a ``--trace 1`` run, in the same command
(the capture lies in ``perfbench/_trace`` until the next traced run):

    python3 perfbench/run.py --workload st21b-is-8k --seed 7 --seconds 10 \
        --trace 1 && python3 tests/perfbench/step_ops.py chiprun_out/ops.json

The JSON holds ``steps``, ``ms_per_step`` by leaf, ``ms_by_path`` (for each
scope the path metrics read, ``path_scope_share``'s way: the milliseconds of
the ops whose text holds it, by the leaf they went to, so that the two
readings can be reconciled), and for each leaf its ops as ``[ms a step, runs
a step, name, stats]`` (the ops of one name and path summed; an op's path
stands at the end of its stats), those under 0.01 ms a step left out.
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import trace_reduce  # noqa: E402
from perfbench.reducers import model_leaf_share  # noqa: E402
from perfbench.run import TRACE_DIR, load_capture  # noqa: E402

#: The step's module, as ``run.py`` names it (``jit_`` + the function).
STEP_MODULE = "jit_sharded"
#: The scopes that metrics over ``path_scope_share`` read.
PATH_SCOPES = ("mercury_attention", "mercury_mla", "mercury_mla_latent",
               "mercury_moe", "mercury_moe_route", "mercury_moe_shared",
               "mercury_lm_head")


def main(out: str, step_module: str = STEP_MODULE) -> int:
    events, source = load_capture(TRACE_DIR)
    capture = trace_reduce.Capture(events, step_module)
    steps = capture.step_count()
    plane = capture.planes[0]
    ops, by_path, recomputed = {}, {scope: {} for scope in PATH_SCOPES}, 0.0
    for event, us in capture._step_ops(plane):
        text = trace_reduce._searchable_text(event)
        stats = (event.get("args") or {}).get("stats", "")
        leaf = model_leaf_share.leaf_of(text)
        for scope in PATH_SCOPES:
            if scope in text:
                by_path[scope][leaf] = by_path[scope].get(leaf, 0.0) + us
        if "rematted_computation" in text and "mercury_train" in text:
            recomputed += us
        seen = ops.setdefault((leaf, str(event.get("name", "")), stats),
                              [0.0, 0])
        seen[0] += us
        seen[1] += 1
    by_leaf, totals = {}, {}
    for (leaf, name, stats), (us, runs) in ops.items():
        totals[leaf] = totals.get(leaf, 0.0) + us
        if us / steps >= 10.0:
            by_leaf.setdefault(leaf, []).append(
                [us / steps / 1e3, runs / steps, name[:160],
                 stats if len(stats) <= 500 else stats[:100] + " ... "
                 + stats[-400:]])
    for rows in by_leaf.values():
        rows.sort(key=lambda row: -row[0])
    result = {
        "source": source, "steps": steps,
        "ms_per_step": {leaf: us / steps / 1e3
                        for leaf, us in sorted(totals.items(),
                                               key=lambda kv: -kv[1])},
        "recomputed_ms_per_step": recomputed / steps / 1e3,
        "ms_by_path": {scope: {leaf: us / steps / 1e3
                               for leaf, us in leaves.items()}
                       for scope, leaves in by_path.items() if leaves},
        "ops": by_leaf}
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    with open(out, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result["ms_per_step"]))
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:]))
