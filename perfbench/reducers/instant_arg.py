"""One argument of the program's ``SpanTracer`` instants of one name,
recorded during the traced calls: the mean over those instants (a program
emits such an instant where it has the number anyway, e.g. at a log gate).
``None`` where the program emitted none."""


def reduce(ctx, instant, arg):
    values = [(e.get("args") or {}).get(arg) for e in ctx["spans"]
              if e.get("name") == instant]
    values = [float(v) for v in values if v is not None]
    return sum(values) / len(values) if values else None
