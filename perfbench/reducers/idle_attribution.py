"""The device's idle time (first chip, between the first step program's
start and the last one's end: what ``device_idle_share`` is a share of) by
what the program was doing, read off the program's own spans on the
capture's host lane (``timeline.py``). ``part`` is ``eval``, ``dispatch``
or ``loop`` (ms per step), or ``unattributed`` (% of the idle time: under
no ``trainer/fit`` span at all, so the caller's code between two calls —
or a hole in the program's coverage)."""

from perfbench.reducers import timeline


def reduce(ctx, part):
    idle = timeline.idle_partition(ctx)
    if idle is None:
        return None
    if part == "unattributed":
        return 100.0 * idle[part] / idle["total"] if idle["total"] else 0.0
    return idle[part] / 1e3 / ctx["steps"] if ctx["steps"] else None
