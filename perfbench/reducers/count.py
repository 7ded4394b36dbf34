"""A counter the harness read, by name (``compiles_in_window``:
``CompileMonitor`` around the window)."""


def reduce(ctx, counter):
    value = ctx["counters"].get(counter)
    return None if value is None else float(value)
