"""Host time (ms per step) inside the program's ``SpanTracer`` spans of one
name, recorded during the traced call (``TrainConfig.trace`` is on in the
traced run alone)."""


def reduce(ctx, span):
    durs = [e["dur"] for e in ctx["spans"]
            if e.get("name") == span and e.get("ph") == "X"]
    if not durs or not ctx["steps"]:
        return None
    return sum(durs) / 1e3 / ctx["steps"]
