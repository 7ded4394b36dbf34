"""Share (%) of the train step's device time in the ops whose path names
one ``jax.named_scope``, wherever it nests: a model's own scopes lie inside
the step's (``mercury_score_forward``, ``mercury_train``), to which
``scope_share`` and ``leaf_scope_share`` would give the op first. The
scoring pass and the train pass count alike, forward and backward. ``None``
where no op of the step carries the scope (a program that does not emit
it)."""

from perfbench import trace_reduce


def named_us(capture, text):
    """Per device plane that ran the step: ``(self microseconds of the
    step's ops whose name or path holds ``text``, of all its ops)``."""
    out = []
    for plane in capture.planes:
        ops = [(text in trace_reduce._searchable_text(event), us)
               for event, us in capture._step_ops(plane)]
        total = sum(us for _, us in ops)
        if total:
            out.append((sum(us for hit, us in ops if hit), total))
    return out


def reduce(ctx, scope):
    planes = named_us(ctx["capture"], scope)
    if not any(hit for hit, _ in planes):
        return None
    return 100.0 * sum(hit / total for hit, total in planes) / len(planes)
