"""Share (%) of the train step's device time under one ``jax.named_scope``."""


def reduce(ctx, scope):
    share = ctx["capture"].scope_share(scope)
    return None if share is None else 100.0 * share
