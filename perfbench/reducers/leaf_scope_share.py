"""Share (%) of the train step's device time under one of the step's leaf
scopes. Unlike ``scope_share`` (the first of a fixed list that an op's path
mentions, so ``mercury_scoring`` hides what is nested in it), an op belongs
to the INNERMOST of ``LEAVES`` on its path. ``backward`` splits a scope
that holds a ``value_and_grad``: jax marks the ops of the backward pass
``transpose(jvp(...))`` in their path. ``None`` where no op of the step
carries the scope (a program that does not emit it)."""

from perfbench import trace_reduce

#: The scopes that split the step by layer; none is nested in another.
LEAVES = ("mercury_pool_ingest", "mercury_score_forward",
          "mercury_score_loss", "mercury_draw", "mercury_train",
          "mercury_optimizer", "mercury_grad_sync")


def leaf_of(text):
    """The leaf scope an op's searchable text puts it under, or None."""
    at = {scope: text.rfind(scope) for scope in LEAVES}
    scope = max(at, key=at.get)
    return scope if at[scope] >= 0 else None


def reduce(ctx, scope, backward=None):
    capture = ctx["capture"]
    shares, seen = [], False
    for plane in capture.planes:
        total = hit = 0.0
        for event, us in capture._step_ops(plane):
            total += us
            text = trace_reduce._searchable_text(event)
            if leaf_of(text) != scope:
                continue
            seen = True
            if backward is None or ("transpose(" in text) == backward:
                hit += us
        if total:
            shares.append(hit / total)
    if not seen or not shares:
        return None
    return 100.0 * sum(shares) / len(shares)
