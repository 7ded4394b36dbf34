"""Self time (ms per step) of the program's ``SpanTracer`` spans of one
name, recorded during the traced calls: each span's duration minus that of
its child spans (those whose ``parent`` is its ``id``). For ``trainer/fit``
it is the host loop's own bookkeeping: what no span inside ``fit()``
covers. ``None`` for a program whose spans name no parent."""


def reduce(ctx, span):
    spans = [e for e in ctx["spans"] if e.get("ph") == "X"]
    roots = [e for e in spans if e.get("name") == span
             and "id" in (e.get("args") or {})]
    if not roots or not ctx["steps"]:
        return None
    ids = {e["args"]["id"] for e in roots}
    children = sum(e["dur"] for e in spans
                   if (e.get("args") or {}).get("parent") in ids)
    return (sum(e["dur"] for e in roots) - children) / 1e3 / ctx["steps"]
